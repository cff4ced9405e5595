"""Device plane (device.py, core/matching.py): of the in-process device
payloads whose copy onto ANOTHER chip was issued in the window
(``handoffs``, counted on the receiving worker), the share issued while an
earlier such copy into the same worker had not landed yet
(``handoffs_overlapped``).  0 means the copies went one at a time, each
waited for before the next was issued; with three arrivals a chip a round
all in flight together it reads 2/3.  A count.  None on a tree whose
workers have no such counters.  Moves ``xfer_GBps``."""


def read(obs):
    counters = obs.get("counters")
    if not isinstance(counters, list):
        return None
    issued = sum(c.get("handoffs", 0) for c in counters)
    if not issued:
        return None
    return sum(c.get("handoffs_overlapped", 0) for c in counters) / issued * 100.0
