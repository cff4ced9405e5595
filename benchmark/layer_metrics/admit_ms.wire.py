"""Scheduler: wall milliseconds per admitted request inside
``SlotServer._admit`` in the chip's process of the wire cell (ends in the
admit program's result).  Mean over the window.  Moves ``ttft_p95_ms``."""


def read(obs):
    child = obs.get("child")
    if not child or not child.get("admits"):
        return None
    return child["admit_s"] / child["admits"] * 1e3
