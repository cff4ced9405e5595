"""Engines (core/engine.py ``_hop`` and the ``"landed"`` op): microseconds
from the placer thread seeing a handoff resident to the receiving worker's
engine thread having run ``on_landed`` and fired what it released: the
receive, the send held with it, flushes held behind it (the ``settle``
stage of ``perf.stage_snapshot()``), mean over the window's handoffs.  None
on a tree that records no such stage.  Moves ``xfer_GBps``."""


def read(obs):
    stage = (obs.get("stages") or {}).get("settle")
    if not stage or not stage.get("count"):
        return None
    return stage["seconds"] / stage["count"] * 1e6
