"""``calibrate_mla_moe.py`` for the ``serve_window_moe_mtp`` runner kind,
whose ``correct`` compares log-probabilities: on the chip, at the cell's own
size and in ONE process, for each seed the cell's set-up and a short window
at the cell's own load, the runner's own decision on what the served path
said of its tokens and drafts (``decide_correct``: their gaps to the
float32 reference against the cell's limits), the acceptance the program
counted, and on the first ``--controls`` seeds the SAME decision with the
int8 reference's log-probabilities in the served ones' place, which has to
come out ``correct: false``.  One JSON line a seed and a summary.

    python benchmark/tests/calibrate_k_exaone.py --seeds 6 --controls 3 --seconds 20
"""

from __future__ import annotations

import argparse
import json
import sys
import time
from pathlib import Path

ROOT = Path(__file__).resolve().parents[2]
sys.path.insert(0, str(ROOT))

WHAT = ("logp_gap_mean", "logp_gap_max", "draft_logp_gap_mean")


def main(argv=None) -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--workload", default="k-exaone.think_closed")
    ap.add_argument("--seeds", type=int, default=6)
    ap.add_argument("--first-seed", type=int, default=2147480000)
    ap.add_argument("--controls", type=int, default=3)
    ap.add_argument("--seconds", type=float, default=20.0)
    a = ap.parse_args(argv)

    from benchmark import run as R
    from benchmark.harness import spec as S
    from benchmark.harness.window_moe_mtp_counts import spec_sums

    rows, device = [], None
    for k in range(a.seeds):
        seed = a.first_seed + 7919 * k
        ctx = R.context(R.parse(["--workload", a.workload, "--seed", str(seed),
                                 "--seconds", str(a.seconds), "--trace", "0"]))
        ctx["t_start"] = time.monotonic()
        if device is not None:
            ctx["device"] = device
        runner = S.load_runner(ctx["config"]["runner"])
        w = runner.serve.inproc_window(ctx)
        device = ctx["device"]
        decide = (runner.heard.samples(w["sample"]), w["faults"], len(w["rows"]))
        t0 = time.monotonic()
        row = {"seed": seed, "e2e": w["e2e"], "finished": len(w["rows"]),
               "faults": len(w["faults"]), "peak": w["peak"],
               "spec": spec_sums({"window": w["window"]}),
               "served": runner.decide_correct(ctx, *decide)}
        row["reference_seconds"] = time.monotonic() - t0
        if k < a.controls:
            ctx["config"]["correct"]["decide_control"] = True
            row["control"] = runner.decide_correct(ctx, *decide)
        print(json.dumps(row), flush=True)
        rows.append(row)

    def value(verdict, what):
        return next(c["value"] for c in verdict["compared"] if c["what"] == what)

    summary = {"served_correct": [r["served"]["correct"] for r in rows]}
    controls = [r["control"] for r in rows if "control" in r]
    for what in WHAT:
        summary["served_" + what] = sorted(value(r["served"], what) for r in rows)
        if controls:
            summary["control_" + what] = sorted(value(c, what) for c in controls)
    if controls:
        summary["control_correct"] = [c["correct"] for c in controls]
    print(json.dumps({"summary": summary}), flush=True)
    out = ROOT / "chiprun_out" / "calibrate"
    out.mkdir(parents=True, exist_ok=True)
    (out / f"{a.workload}.jsonl").write_text(
        "\n".join(json.dumps(r) for r in rows + [{"summary": summary}]) + "\n")
    return 0


if __name__ == "__main__":
    sys.exit(main())
