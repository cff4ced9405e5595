"""Device plane (device.py ``DeviceRecvSink.accept_device``): microseconds
enqueueing ONE chip-to-chip copy takes (``_copy_to_device``, no wait; the
``issue`` stage of ``perf.stage_snapshot()``), mean over the window's
handoffs; it runs on whoever delivers, under the receiving worker's lock.
None on a tree that records no such stage.  Moves ``xfer_GBps``."""


def read(obs):
    stage = (obs.get("stages") or {}).get("issue")
    if not stage or not stage.get("count"):
        return None
    return stage["seconds"] / stage["count"] * 1e6
