"""Transport API (api.py): microseconds from a completion's ``done`` /
``fail`` being called on an engine's thread to its ``apply()`` running on
the event loop (the ``loop_hop`` stage of ``perf.stage_snapshot()``: the
trampoline's queue, the self-pipe write and the loop's wake-up), mean over
the window's completions that crossed; one that resolved on the loop's own
thread records nothing.  None on a tree that records no such stage.  Moves
``xfer_GBps``."""


def read(obs):
    stage = (obs.get("stages") or {}).get("loop_hop")
    if not stage or not stage.get("count"):
        return None
    return stage["seconds"] / stage["count"] * 1e6
