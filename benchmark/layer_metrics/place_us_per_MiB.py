"""Device plane (device.py): microseconds inside the ``place`` (host to
HBM) and ``stage`` (HBM to host) stages of ``perf.stage_snapshot()`` in the
chip's process, per MiB delivered in the window.  Moves ``xfer_GBps``."""


def read(obs):
    stages, nbytes = obs.get("stages"), obs.get("bytes")
    if not stages or not nbytes:
        return None
    sec = sum(stages.get(k, {}).get("seconds", 0.0) for k in ("place", "stage"))
    if not any(stages.get(k, {}).get("count") for k in ("place", "stage")):
        return None
    return sec / (nbytes / 2 ** 20) * 1e6
