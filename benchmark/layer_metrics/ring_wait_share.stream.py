"""Engines (core/conn.py ``kick_tx`` / ``_tx_write``): the share of the
transport's seconds in the window (``fw_seconds``) that the chip's process
spent as a producer BLOCKED on a full ``sm`` ring: from ``kick_tx`` leaving
on a starving doorbell to the next put that landed (the ``ring_wait`` stage
of ``perf.stage_snapshot()``; one sample a message that blocked, its
blocks' seconds summed; the chip's process only:
``obs["stages"]`` does not carry the peer's).  Large: a ring of 2-4
messages pays.  0 when the producer never blocked; None on a tree that
records no ``post`` stage either.  Moves ``xfer_GBps``."""


def read(obs):
    stages, seconds = obs.get("stages") or {}, obs.get("fw_seconds")
    if not seconds or "post" not in stages:
        return None
    return stages.get("ring_wait", {}).get("seconds", 0.0) / seconds * 100.0
