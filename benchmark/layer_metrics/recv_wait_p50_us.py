"""Transport API (api.py): the p50 of swpulse's ``recv_wait_us`` histogram
over the window on the receiving side that holds the chip: the upper bound
of the base-2 log bucket the median falls in (a bucket bound, coarse by
construction, never a decider).  Moves ``xfer_GBps``."""

from benchmark.harness.stats import hist_percentile_bound


def read(obs):
    buckets = (obs.get("hists") or {}).get("recv_wait_us")
    return hist_percentile_bound(buckets, 50) if buckets else None
