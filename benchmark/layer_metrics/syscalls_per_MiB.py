"""Engines (core/, native/): I/O system calls on the hot path
(``io_syscalls``, both ends' workers summed) per MiB delivered in the
window.  A count.  Moves ``xfer_GBps``."""


def read(obs):
    counters, nbytes = obs.get("counters"), obs.get("bytes")
    if not counters or not nbytes:
        return None
    return sum(c.get("io_syscalls", 0) for c in counters) / (nbytes / 2 ** 20)
