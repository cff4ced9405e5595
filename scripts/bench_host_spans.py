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
    run = runpy.run_path(os.path.join("benchmark", "run.py"), run_name="bench_run")
    rc = run["main"](argv)
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
