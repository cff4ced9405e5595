"""``calibrate_serve.py`` for the ``serve_mla_moe`` runner kind, with one
more reading: on the chip, at the cell's own size and in ONE process, for
each seed the cell's set-up and a short window at the cell's own load, the
harness's own decision on the served tokens (``serve.decide_correct``: their
gaps under the float32 reference against the cell's limits), the share of
(position, routed layer) pairs whose top-k set a bfloat16 router flips
(``router_flips``), and on the first ``--controls`` seeds the SAME decision
on the int8 control at the same positions, which has to come out ``correct:
false``.  One JSON line a seed and a summary.

    python benchmark/tests/calibrate_mla_moe.py --seeds 20 --controls 4 --seconds 12
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
    ap.add_argument("--workload", default="kimi-k2.agent_closed")
    ap.add_argument("--seeds", type=int, default=20)
    ap.add_argument("--first-seed", type=int, default=2147480000)
    ap.add_argument("--controls", type=int, default=4)
    ap.add_argument("--flips", type=int, default=4)
    ap.add_argument("--seconds", type=float, default=12.0)
    a = ap.parse_args(argv)

    from benchmark import run as R
    from benchmark.harness import spec as S
    from benchmark.harness import traffic as T

    rows, device = [], None
    for k in range(a.seeds):
        seed = a.first_seed + 7919 * k
        ctx = R.context(R.parse(["--workload", a.workload, "--seed", str(seed),
                                 "--seconds", str(a.seconds), "--trace", "0"]))
        ctx["t_start"] = time.monotonic()
        if device is not None:
            ctx["device"] = device
        serve = S.load_runner(ctx["config"]["runner"]).serve
        w = serve.inproc_window(ctx)
        device = ctx["device"]
        ref = S.load_reference(ctx["cell"]["config"])
        decide = (w["sample"], w["faults"], len(w["rows"]))
        t0 = time.monotonic()
        row = {"seed": seed, "e2e": w["e2e"], "finished": len(w["rows"]),
               "faults": len(w["faults"]), "peak": w["peak"],
               "served": serve.decide_correct(ctx, *decide)}
        row["reference_seconds"] = time.monotonic() - t0
        if k < a.flips:
            row["flips"] = ref.router_flips(
                ctx["config"], seed, w["sample"],
                ctx["config"]["serve"]["max_len"],
                max(o for _p, o in T.request_set(ctx["traffic"])))
        if k < a.controls:
            ctx["config"]["correct"]["decide_control"] = True
            row["control"] = serve.decide_correct(ctx, *decide)
        print(json.dumps(row), flush=True)
        rows.append(row)

    def value(verdict, what):
        return next(c["value"] for c in verdict["compared"] if c["what"] == what)

    summary = {"served_correct": [r["served"]["correct"] for r in rows],
               "served_gap_max_largest": max(value(r["served"], "gap_max") for r in rows),
               "served_gap_mean_largest": max(value(r["served"], "gap_mean") for r in rows)}
    controls = [r["control"] for r in rows if "control" in r]
    if controls:
        summary["control_correct"] = [c["correct"] for c in controls]
        summary["control_gap_max_smallest"] = min(value(c, "gap_max") for c in controls)
        summary["control_gap_mean_smallest"] = min(value(c, "gap_mean") for c in controls)
    print(json.dumps({"summary": summary}), flush=True)
    out = ROOT / "chiprun_out" / "calibrate"
    out.mkdir(parents=True, exist_ok=True)
    (out / f"{a.workload}.jsonl").write_text(
        "\n".join(json.dumps(r) for r in rows + [{"summary": summary}]) + "\n")
    return 0


if __name__ == "__main__":
    sys.exit(main())
