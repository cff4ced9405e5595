"""Engines (core/, native/): payload byte-copies on the hot path
(``hot_copies``, both ends' workers summed) per MiB delivered in the
window.  A count: it repeats exactly.  Moves ``xfer_GBps``."""


def read(obs):
    counters, nbytes = obs.get("counters"), obs.get("bytes")
    if not counters or not nbytes:
        return None
    return sum(c.get("hot_copies", 0) for c in counters) / (nbytes / 2 ** 20)
