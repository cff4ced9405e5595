"""Device plane (device.py ``DevicePayload.start_fetch``): microseconds the
call that STARTS a queued send's device-to-host copy takes
(``copy_to_host_async``, no wait; the ``fetch_start`` stage of
``perf.stage_snapshot()`` in the chip's process), on the poster's thread or
the engine's, mean over the window's staged sends.  None on a tree that
records no such stage.  Moves ``xfer_GBps``."""


def read(obs):
    stage = (obs.get("stages") or {}).get("fetch_start")
    if not stage or not stage.get("count"):
        return None
    return stage["seconds"] / stage["count"] * 1e6
