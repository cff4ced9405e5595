"""Transport API (api.py): microseconds a call of ``asend`` / ``arecv`` /
``aflush`` / ``aflush_ep`` takes from its entry to its return (the ``post``
stage of ``perf.stage_snapshot()``: future pair, device payload or sink,
submit to the worker, an inline match and a copy's enqueue included), mean
over the window's calls in the process that holds the chip.  None on a tree
that records no such stage.  Moves ``xfer_GBps``."""


def read(obs):
    stage = (obs.get("stages") or {}).get("post")
    if not stage or not stage.get("count"):
        return None
    return stage["seconds"] / stage["count"] * 1e6
