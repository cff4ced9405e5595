"""Replay a closed-loop serving cell's schedule on the host, with no chip:
which requests ``SlotServer.step`` admits when, what each admit program and
each decode step costs, and so the ``tok_s`` and ``tpot_p95_ms`` the
benchmark would read for a seed.  The device is deterministic; what differs
between seeds is the ORDER of the traffic's fixed set, and this answers how
far that alone spreads the two metrics, for any ``set_size``, before a
chip-minute is spent (PERF.md section 6, PR 30).

The costs are a cell's own, read from its traced runs on the chip and kept
in ``CELLS`` under its traffic's name: seconds of each admit bucket, and a
decode step as a constant plus its attention kernels by the cache positions
they sweep.  ``smallthinker-21b.longdoc_closed`` (PR 30): the replay gave 19
measured seeds' ``tok_s`` within 1% (12.7 +- 4.5 tokens/s high) and
``tpot_p95_ms`` within 0.5 ms.  ``kimi-linear.reason_closed`` (PR 32): a
step's 19.9 ms do not depend on the cursors (the state is constant in
length), the two latent layers' attention is 2.2 ms at 293k attended rows.
``phi4-mini-flash.cot_closed`` (PR 46, ``--traffic cot_closed_c120
--set-sizes 32``): one full layer's rows read eight times a step.
Another cell needs its own numbers here.

    python scripts/replay_serve_schedule.py --set-sizes 16 32 --seeds 240
"""

from __future__ import annotations

import argparse
import json
import random
import statistics
import sys
from pathlib import Path

ROOT = Path(__file__).resolve().parents[1]
sys.path.insert(0, str(ROOT))

from benchmark.harness import stats  # noqa: E402
from benchmark.harness import traffic as T  # noqa: E402

CHUNK = 8
HOST_S = 0.003            # a step's dispatch, fetch and harvest


def _longdoc_step_s(cursors) -> float:
    """One decode step: 10.3 ms that do not depend on the cursors, the two
    full layers' attention (3.7 ms at 362k attended positions) and the six
    rings' (6.0 ms at 186k)."""
    full = sum(p + 1 for p in cursors)
    ring = sum(min(p + 1, 4096) for p in cursors)
    return (10.3 + 0.3 + 3.43 * full / 362e3 + 0.5 + 5.53 * ring / 186e3) / 1e3


def _reason_step_s(cursors) -> float:
    """One decode step: 19.9 ms whatever the cursors (six layers' state in
    and out, weights, experts) and the two latent layers' attention (2.2 ms
    at 293k attended rows)."""
    return (19.9 + 2.2 * sum(p + 1 for p in cursors) / 293e3) / 1e3


def _cot_step_s(cursors) -> float:
    """One decode step (PR 46's traced run): 13.7 ms that do not depend on
    the cursors (weights, nine Mamba states, the head), the ONE full
    layer's rows read by eight layers (16.1 ms at 263k attended positions)
    and the eight rings (2.68 ms at 49k)."""
    full = sum(p + 1 for p in cursors)
    ring = sum(min(p + 1, 512) for p in cursors)
    return (13.7 + 16.1 * full / 263e3 + 2.68 * ring / 49152) / 1e3


# traffic name -> (slots, seconds of each admit bucket, a decode step)
CELLS = {
    "longdoc_closed_c72": (48, {
        2048: .0334, 4096: .0647, 6144: .0990, 8192: .1334, 11264: .1904,
        14336: .2473}, _longdoc_step_s),
    "reason_closed_c320": (256, {
        128: .0062, 256: .0096, 512: .0168, 1024: .0285, 2048: .0666,
        4096: .1401}, _reason_step_s),
    # Buckets 3,072 and 4,096 as traced (PR 46); the others in proportion.
    "cot_closed_c120": (96, {
        1024: .0345, 2048: .0680, 3072: .1010, 4096: .1355, 6144: .2050},
        _cot_step_s),
}


def replay(traffic: dict, seed: int, cell: tuple, seconds: float = 45.0) -> dict:
    """``serve.inproc_window`` over ``SlotServer.step``: admissions in the
    queue's order (of the first ``len(free)`` the one with most tokens to
    produce first), their first tokens when the last admit program has run,
    a chunk's tokens at its end, a finished request's client asking again
    at once."""
    n_slots, admit_cost, step_s = cell
    lengths = T.request_lengths(traffic, seed, 8192)
    pending, slots, rows, nxt = [], {}, {}, 0

    def submit():
        nonlocal nxt
        pending.append(nxt)
        rows[nxt] = {"first": None, "last": None, "n": 0, "done": False}
        nxt += 1

    for _ in range(int(traffic["clients"])):
        submit()
    t = admit_s = 0.0
    tokens = 0
    while t < seconds:
        free = [s for s in range(n_slots) if s not in slots]
        admitted = []
        while free and pending:
            at = max(range(min(len(free), len(pending))),
                     key=lambda i: (lengths[pending[i]][1], -i))
            idx = pending.pop(at)
            prompt, want = lengths[idx]
            cost = admit_cost[next(b for b in admit_cost if b >= prompt)] + 0.0005
            t, admit_s = t + cost, admit_s + cost
            slots[free.pop(0)] = {"idx": idx, "left": want - 1, "pos": prompt}
            admitted.append(idx)
        for idx in admitted:
            rows[idx].update(first=t, last=t, n=1)
        tokens += len(admitted)
        emitted = dict.fromkeys(slots, 0)
        for _ in range(CHUNK):
            t += step_s([v["pos"] for v in slots.values()])
            for s, v in slots.items():
                if v["left"] > 0:
                    v["left"] -= 1
                    v["pos"] += 1
                    emitted[s] += 1
        t += HOST_S
        for s, n in emitted.items():
            row = rows[slots[s]["idx"]]
            row["last"], row["n"] = t, row["n"] + n
            tokens += n
        for s in [s for s, v in slots.items() if v["left"] <= 0]:
            rows[slots.pop(s)["idx"]]["done"] = True
            submit()
    tpot = [(r["last"] - r["first"]) / (r["n"] - 1) * 1e3
            for r in rows.values() if r["done"] and r["n"] > 1]
    return {"tok_s": tokens / t, "tpot_p95_ms": stats.percentile(tpot, 95),
            "finished": sum(r["done"] for r in rows.values()),
            "admit_share": admit_s / t}


def spread(values) -> float:
    """The driver's: interquartile range over median, in percent."""
    q = statistics.quantiles(values, n=4)
    return (q[2] - q[0]) / statistics.median(values) * 100.0


def main(argv=None) -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--traffic", default="longdoc_closed_c72")
    ap.add_argument("--set-sizes", type=int, nargs="+", default=[16, 32])
    ap.add_argument("--seeds", type=int, default=120)
    ap.add_argument("--gates", type=float, nargs=2, default=[1.0, 4.0],
                    help="percent: tok_s, tpot_p95_ms")
    a = ap.parse_args(argv)
    with open(ROOT / "benchmark" / "traffic" / f"{a.traffic}.json") as f:
        traffic = json.load(f)
    rng = random.Random(5)
    seeds = [2 ** 31 + rng.randrange(2 ** 30) for _ in range(a.seeds)]
    for n in a.set_sizes:
        runs = [replay(dict(traffic, set_size=n), s, CELLS[a.traffic]) for s in seeds]
        tok = [r["tok_s"] for r in runs]
        tpot = [r["tpot_p95_ms"] for r in runs]
        sets = [rng.sample(range(len(runs)), 6) for _ in range(4000)]
        within = [spread([tok[i] for i in pick]) < a.gates[0]
                  and spread([tpot[i] for i in pick]) < a.gates[1]
                  for pick in sets]
        print(json.dumps({
            "set_size": n, "seeds": len(seeds),
            "tok_s_median": statistics.median(tok),
            "tok_s_sd_percent": statistics.pstdev(tok) / statistics.mean(tok) * 100,
            "tpot_p95_ms_median": statistics.median(tpot),
            "tpot_p95_ms_sd_percent":
                statistics.pstdev(tpot) / statistics.mean(tpot) * 100,
            "admit_share_sd": statistics.pstdev(r["admit_share"] for r in runs),
            "sets_of_six_within_gates": sum(within) / len(within)}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
