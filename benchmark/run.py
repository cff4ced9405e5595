#!/usr/bin/env python3
"""The benchmark's one command, as the driver calls it:

    python3 benchmark/run.py --workload <name> --seed <n> --seconds <s> --trace <0|1>

One new process per run.  It finds the cell in ``BENCHMARK.json`` and the
cell's configuration, traffic mix, runner and per-layer readers by NAME in
files under ``benchmark/`` (harness/spec.py); it holds no table of them.
The last line of standard output is the contract's one JSON object: with
``--trace 0`` the cell's end-to-end metrics, with ``--trace 1`` its
per-layer metrics, the device's busy seconds and the breakdown.

Without an accelerator, or with fewer chips than the cell asks for, it
exits non-zero and prints no result.  ``BENCH_RUN`` in the environment is
the driver's and is not read.
"""

from __future__ import annotations

import time

T_START = time.monotonic()   # set-up is counted from here

import argparse
import json
import sys
from pathlib import Path

ROOT = Path(__file__).resolve().parents[1]
sys.path.insert(0, str(ROOT))

from benchmark.harness import spec as S


def parse(argv):
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    # Not the driver's: a runner's own child processes, sweeps, rehearsals.
    ap.add_argument("--role", default=None,
                    help="internal: the part a runner's child process plays")
    ap.add_argument("--override", action="append", default=[],
                    metavar="KEY=JSON", help="sweeps only: replace one "
                    "top-level key of the traffic file for this run")
    ap.add_argument("--no-chip", action="store_true",
                    help="rehearsal on the CPU: control flow only; prints "
                    "no result line and no device metric")
    return ap.parse_args(argv)


def context(args) -> dict:
    spec = S.load_spec()
    cell = S.find_cell(spec, args.workload)
    config = S.load_config(spec, cell["config"])
    traffic = S.load_traffic(cell["traffic"])
    if args.no_chip:
        with open(S.BENCH / "tests" / "data" / "rehearsal.json") as f:
            small = json.load(f)
        config.update(small["configs"].get(cell["config"], {}))
        traffic.update(small["traffic"].get(cell["traffic"], {}))
    for kv in args.override:
        key, _, value = kv.partition("=")
        traffic[key] = json.loads(value)
    return {"args": args, "spec": spec, "cell": cell, "config": config,
            "traffic": traffic, "t_start": T_START, "chip": not args.no_chip}


def result_line(ctx: dict, out: dict) -> dict:
    """The contract's object from what the runner measured."""
    spec, cell, args = ctx["spec"], ctx["cell"]["name"], ctx["args"]
    device = dict(out["device"])
    if args.trace:
        metrics = S.read_layer_metrics(spec, cell, out["obs"])
        trace = out.get("trace") or {}
        device["busy_s"] = trace.get("busy_s")
        device["window_s"] = trace.get("window_s")
    else:
        metrics = {}
        for m in S.end_to_end_for(spec, cell):
            value = out["e2e"].get(m["name"])
            if value is None:
                raise SystemExit(f"benchmark: {cell} measured no {m['name']}")
            metrics[m["name"]] = {"value": float(value), "unit": m["unit"]}
    line = {"correct": bool(out["correct"]), "attempted": int(out["attempted"]),
            "failed": int(out["failed"]), "metrics": metrics, "device": device}
    if args.trace and out.get("trace"):
        line["breakdown"] = {"device_ops": out["trace"]["device_ops"],
                             "idle_gaps": out["trace"]["idle_gaps"]}
    return line


def main(argv=None) -> int:
    args = parse(argv)
    ctx = context(args)
    runner = S.load_runner(ctx["config"]["runner"])
    if args.role:
        return runner.run_role(args.role, ctx)
    out = runner.run(ctx)
    line = result_line(ctx, out)
    if not ctx["chip"]:
        print(json.dumps({"rehearsal": "no chip: no result line, no device "
                          "metric", "correct": line["correct"],
                          "attempted": line["attempted"],
                          "failed": line["failed"]}), flush=True)
        return 0
    print(json.dumps(line), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
