"""On the chip, at the cell's own size: the readings the limits of
``correct`` are set from (contract steps 3 to 5).  In ONE process, for each
seed: the cell's set-up and a short window at the cell's own load, the
served tokens' gaps under the float32 reference, and on the first
``--controls`` seeds the gaps of the lower-precision controls at the same
positions.  Prints one JSON line per seed and a summary.

    python benchmark/tests/calibrate_serve.py --workload mistral7b.chat_closed \\
        --seeds 12 --controls 4 --seconds 10
"""

from __future__ import annotations

import argparse
import json
import sys
import time
from pathlib import Path

ROOT = Path(__file__).resolve().parents[2]
sys.path.insert(0, str(ROOT))


def main(argv=None) -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--workload", default="mistral7b.chat_closed")
    ap.add_argument("--seeds", type=int, default=12)
    ap.add_argument("--first-seed", type=int, default=2147480000)
    ap.add_argument("--controls", type=int, default=4)
    ap.add_argument("--quants", default="int8,fp8")
    ap.add_argument("--seconds", type=float, default=10.0)
    ap.add_argument("--no-chip", action="store_true")
    a = ap.parse_args(argv)

    from benchmark import run as R
    from benchmark.harness import spec as S
    from benchmark.harness import traffic as T

    rows = []
    device = None
    for k in range(a.seeds):
        seed = a.first_seed + 7919 * k
        args = R.parse(["--workload", a.workload, "--seed", str(seed),
                        "--seconds", str(a.seconds), "--trace", "0"]
                       + (["--no-chip"] if a.no_chip else []))
        ctx = R.context(args)
        ctx["t_start"] = time.monotonic()
        if device is not None:
            ctx["device"] = device
        runner = S.load_runner(ctx["config"]["runner"])
        w = runner.inproc_window(ctx)
        device = ctx["device"]
        ref = S.load_reference(ctx["cell"]["config"])
        sv = ctx["config"]["serve"]
        out_to = max(o for _p, o in T.request_set(ctx["traffic"]))
        t0 = time.monotonic()
        row = {"seed": seed, "e2e": w["e2e"], "finished": len(w["rows"]),
               "faults": len(w["faults"]),
               "served": ref.served_gaps(ctx["config"], seed, w["sample"],
                                         sv["max_len"], out_to)}
        row["reference_seconds"] = time.monotonic() - t0
        if k < a.controls:
            for q in a.quants.split(","):
                t0 = time.monotonic()
                row[q] = ref.control_gaps(ctx["config"], seed, w["sample"],
                                          sv["max_len"], out_to, q)
                row[q]["seconds"] = time.monotonic() - t0
        print(json.dumps(row), flush=True)
        rows.append(row)
    summary = {"served_gap_max_largest": max(r["served"]["gap_max"] for r in rows),
               "served_gap_mean_largest": max(r["served"]["gap_mean"] for r in rows)}
    for q in a.quants.split(","):
        got = [r[q] for r in rows if q in r]
        if got:
            summary[f"{q}_gap_max_smallest"] = min(g["gap_max"] for g in got)
            summary[f"{q}_gap_mean_smallest"] = min(g["gap_mean"] for g in got)
    print(json.dumps({"summary": summary}), flush=True)
    out = ROOT / "chiprun_out" / "calibrate"
    out.mkdir(parents=True, exist_ok=True)
    (out / f"{a.workload}.jsonl").write_text(
        "\n".join(json.dumps(r) for r in rows + [{"summary": summary}]) + "\n")
    return 0


if __name__ == "__main__":
    sys.exit(main())
