"""The one general traffic generator.  A traffic mix is a data file of
parameters (``benchmark/traffic/<mix>.json``); nothing here knows a mix by
name.  numpy only: the chip-less side uses it too.

Steadiness rule: every seed gets the SAME set of sizes and arrival gaps,
in another order.  The set is the distribution's own stratified quantiles,
so it depends on the mix's parameters alone; the run's seed permutes it and
draws the token ids.
"""

from __future__ import annotations

import statistics

import numpy as np

_NORMAL = statistics.NormalDist()


def _rng(*words) -> np.random.Generator:
    return np.random.default_rng([int(w) & 0xFFFFFFFFFFFFFFFF for w in words])


def quantile_set(dist: dict, n: int) -> np.ndarray:
    """``n`` whole numbers: the distribution's quantiles at (i + 0.5) / n,
    clipped to [min, max]."""
    u = (np.arange(n) + 0.5) / n
    kind = dist["dist"]
    if kind == "lognormal":
        z = np.array([_NORMAL.inv_cdf(float(p)) for p in u])
        x = dist["median"] * np.exp(dist["sigma"] * z)
    elif kind == "fixed":
        x = np.full(n, float(dist["value"]))
    elif kind == "uniform":
        x = dist["min"] + u * (dist["max"] - dist["min"])
    else:
        raise ValueError(f"traffic: unknown distribution {kind!r}")
    return np.clip(np.rint(x), dist["min"], dist["max"]).astype(np.int64)


def request_set(traffic: dict) -> list:
    """The mix's fixed set of (prompt tokens, output tokens) pairs.  Prompt
    and output lengths are paired by a permutation fixed in the mix
    (``pairing_seed``), not by the run's seed."""
    n = int(traffic["set_size"])
    prompts = quantile_set(traffic["prompt_tokens"], n)
    outputs = quantile_set(traffic["output_tokens"], n)
    order = _rng(traffic["pairing_seed"], 0x9A1).permutation(n)
    return [(int(p), int(o)) for p, o in zip(prompts, outputs[order])]


def request_lengths(traffic: dict, seed: int, count: int) -> list:
    """The first ``count`` requests of the run: the fixed set, epoch after
    epoch, each epoch in an order drawn from the seed."""
    pairs = request_set(traffic)
    rng = _rng(seed, 0x5E7)
    out = []
    while len(out) < count:
        out.extend(pairs[i] for i in rng.permutation(len(pairs)))
    return out[:count]


def prompt_tokens(seed: int, index: int, n: int, vocab: int) -> np.ndarray:
    """Token ids of request ``index`` (1 .. vocab-1), from the seed."""
    return _rng(seed, 0x70C, index).integers(1, vocab, n).astype(np.int32)


def arrival_times(traffic: dict, seed: int, horizon_s: float) -> list:
    """Due times in [0, horizon) of an open loop.  ``poisson``: the gaps
    are the exponential's stratified quantiles (the same multiset for
    every seed, mean 1 / rate), drawn epoch by epoch in seeded order."""
    rate = float(traffic["rate_per_s"])
    if traffic.get("arrivals", "poisson") != "poisson":
        raise ValueError(f"traffic: unknown arrivals {traffic['arrivals']!r}")
    n = int(traffic["set_size"])
    gaps = -np.log1p(-(np.arange(n) + 0.5) / n) / rate
    gaps *= (1.0 / rate) / gaps.mean()
    rng = _rng(seed, 0xA77)
    out, t = [], 0.0
    while True:
        for i in rng.permutation(n):
            t += float(gaps[i])
            if t >= horizon_s:
                return out
            out.append(t)


def transfer_round(traffic: dict, seed: int, round_no: int) -> list:
    """One round of a transfer mix: ``(src, dst, index)`` for every chunk,
    in the order the senders post them (drawn from the seed and the round).

    ``pair_duplex``: endpoints 0 and 1, ``chunks_per_round`` chunks each
    way.  ``all_to_all``: every one of ``workers`` endpoints sends
    ``chunks_per_pair`` chunks to every other."""
    topo = traffic["topology"]
    if topo == "pair_duplex":
        k = int(traffic["chunks_per_round"])
        moves = [(s, 1 - s, i) for s in (0, 1) for i in range(k)]
    elif topo == "all_to_all":
        w, k = int(traffic["workers"]), int(traffic.get("chunks_per_pair", 1))
        moves = [(s, d, i) for s in range(w) for d in range(w) if s != d
                 for i in range(k)]
    else:
        raise ValueError(f"traffic: unknown topology {topo!r}")
    order = _rng(seed, 0x7F3, round_no).permutation(len(moves))
    return [moves[i] for i in order]


def round_bytes(traffic: dict) -> int:
    """Payload bytes one round delivers, all directions summed."""
    return len(transfer_round(traffic, 0, 0)) * int(traffic["chunk_bytes"])


def describe(traffic: dict) -> dict:
    """What a run prints about its mix (sizes as generated, not as asked)."""
    if traffic["kind"] == "requests":
        pairs = request_set(traffic)
        p = sorted(x for x, _ in pairs)
        o = sorted(y for _, y in pairs)
        return {"set_size": len(pairs),
                "prompt_tokens": {"min": p[0], "median": p[len(p) // 2],
                                  "mean": sum(p) / len(p), "max": p[-1]},
                "output_tokens": {"min": o[0], "median": o[len(o) // 2],
                                  "mean": sum(o) / len(o), "max": o[-1]}}
    return {"round_bytes": round_bytes(traffic),
            "transfers_per_round": len(transfer_round(traffic, 0, 0)),
            "chunk_bytes": int(traffic["chunk_bytes"])}
