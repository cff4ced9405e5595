"""One benchmark run with the harness's own host spans added up.

``benchmark/run.py`` keeps the spans its runners record (``bench:post``,
``bench:await_transfers``, ``bench:flush``, ... on ``time.monotonic``) in
memory and prints only the device's idle gaps laid to them.  This runs the
same command in this process and then prints, for every span name, how
many there were and their seconds in all and each: where a transport
round's host time goes, which no device trace shows (chip-to-chip copies
are DMAs, not operations).  Arguments are ``benchmark/run.py``'s:

    chiprun --chips 4 -- python scripts/bench_host_spans.py \\
        --workload hbm_duplex.a2a_16m_x4 --seed 7 --seconds 45 --trace 1

Run from the root of the tree to be measured (it imports that tree's
``benchmark`` package): a parent's checkout is measured from its own root.
The spans hold warm-up rounds too; the last line says how many of each.

Beside them, for a ``--trace 1`` run, the window's own record of where its
messages waited: the row ``stages`` is ``obs["stages"]`` as the per-layer
readers got it (the delta of ``perf.stage_snapshot()`` over the window, the
message stages of DESIGN.md section 12 among them) with the window's
rounds and transport seconds, so ``post`` seconds a round stand beside
``bench:post``'s.  With ``STARWAY_TRACE=1`` in the environment the rings of
every worker of this process are dumped, the path printed in the row
``ring_dump``, for ``python -m starway_tpu.trace`` (one message's
``post`` -> ``issue`` -> ``land`` -> ``settle`` -> ``loop_hop`` under its tag).
"""

from __future__ import annotations

import json
import os
import sys


def main(argv) -> int:
    sys.path.insert(0, os.getcwd())
    import runpy

    from benchmark.harness import spans as harness_spans

    made: list = []
    init = harness_spans.Spans.__init__

    def recording_init(self, *args, **kwargs):
        init(self, *args, **kwargs)
        made.append(self)

    harness_spans.Spans.__init__ = recording_init

    from benchmark.harness import spec as harness_spec

    seen: dict = {}
    read = harness_spec.read_layer_metrics

    def recording_read(spec, cell, obs):
        seen["obs"] = obs
        return read(spec, cell, obs)

    harness_spec.read_layer_metrics = recording_read
    run = runpy.run_path(os.path.join("benchmark", "run.py"), run_name="bench_run")
    rc = run["main"](argv)
    obs = seen.get("obs")
    if obs is not None:
        counters = obs.get("counters") or []
        print(json.dumps({"row": "stages", "rounds": obs.get("rounds"),
                          "fw_seconds": obs.get("fw_seconds"),
                          "raw_seconds": obs.get("raw_seconds"),
                          "handoffs": sum(c.get("handoffs", 0) for c in counters),
                          "stages": {k: {"count": v.get("count"),
                                         "seconds": v.get("seconds")}
                                     for k, v in (obs.get("stages") or {}).items()}}),
              flush=True)
    from starway_tpu.core import swtrace

    if swtrace.active():
        os.makedirs("chiprun_out", exist_ok=True)
        path = swtrace.write_ring_dump(os.path.join(
            "chiprun_out", f"ring_dump_{os.getpid()}.json"))
        print(json.dumps({"row": "ring_dump", "path": str(path)}), flush=True)
    totals: dict = {}
    for sp in made:
        for name, t0, t1 in sp.rows:
            row = totals.setdefault(name, [0, 0.0])
            row[0] += 1
            row[1] += t1 - t0
    print(json.dumps({"row": "host_spans", "spans": {
        name: {"count": n, "seconds": s, "ms_each": 1e3 * s / n}
        for name, (n, s) in sorted(totals.items())}}), flush=True)
    return rc


if __name__ == "__main__":
    sys.exit(main(sys.argv[1:]))
