"""Device plane (core/engine.py ``_place_beside`` -> ``_run_place``):
microseconds a wholly received message waits for the worker's ONE placer
thread before its placement begins (the ``place_queue`` stage of
``perf.stage_snapshot()`` in the chip's process), mean over the window's
staged receives.  Large beside ``place``: a second placer or a faster
placement pays.  None on a tree that records no such stage.  Moves
``xfer_GBps``."""


def read(obs):
    stage = (obs.get("stages") or {}).get("place_queue")
    if not stage or not stage.get("count"):
        return None
    return stage["seconds"] / stage["count"] * 1e6
