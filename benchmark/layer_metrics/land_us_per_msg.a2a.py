"""Device plane (core/matching.py ``_land`` -> core/engine.py
``_run_land``): microseconds from a handoff's copy being issued to the
receiving worker's placer thread seeing it resident (the ``land`` stage of
``perf.stage_snapshot()``), queueing behind that thread's earlier waits
included, mean over the window's handoffs.  A lone 16 MiB copy is 906 us
(PERF.md section 6, PR 34).  None on a tree that records no such stage.
Moves ``xfer_GBps``."""


def read(obs):
    stage = (obs.get("stages") or {}).get("land")
    if not stage or not stage.get("count"):
        return None
    return stage["seconds"] / stage["count"] * 1e6
