"""The benchmark's latency and throughput arithmetic (stdlib only, so the
chip-less side can use it)."""

from __future__ import annotations

import math
import statistics


def percentile(values, q: float) -> float:
    """Linear-interpolated ``q``-th percentile (0..100) of ``values``."""
    v = sorted(values)
    if not v:
        raise ValueError("percentile of nothing")
    k = (len(v) - 1) * q / 100.0
    lo, hi = math.floor(k), math.ceil(k)
    return v[lo] + (v[hi] - v[lo]) * (k - lo)


def summary(values) -> dict:
    """Median, p95, max and count: printed on the lines before the result."""
    v = list(values)
    if not v:
        return {"n": 0}
    return {"n": len(v), "p50": statistics.median(v),
            "p95": percentile(v, 95), "max": max(v)}


def gbps(nbytes: float, seconds: float) -> float:
    """Bytes per second in GB/s, 1 GB = 1e9 B."""
    return nbytes / seconds / 1e9


def hist_percentile_bound(buckets, q: float):
    """Upper bound of the base-2 log bucket that holds the ``q``-th
    percentile of a swpulse histogram (bucket i counts values with
    bit_length i, so its bound is 2**i - 1).  A bucket bound, never a
    decider.  None when the histogram is empty."""
    total = sum(buckets)
    if not total:
        return None
    seen = 0
    for i, n in enumerate(buckets):
        seen += n
        if seen >= q / 100.0 * total:
            return float((1 << i) - 1 if i else 0)
    return float((1 << (len(buckets) - 1)) - 1)
