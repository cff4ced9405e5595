"""The control of the ``hbm_duplex`` cells, on the chip at the cell's own
size: a run in which one stated guarantee is broken (one byte of every
16th payload altered where it is sent, headers untouched) has to come out
``correct: false`` with ``byte_mismatches`` above its limit of 0, on every
seed; the same run unbroken stays at 0.

    python3 benchmark/tests/control_transport.py --workload hbm_duplex.stream_4m --seeds 3
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
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", type=int, default=3)
    ap.add_argument("--first-seed", type=int, default=2147470000)
    ap.add_argument("--seconds", type=float, default=4.0)
    ap.add_argument("--no-chip", action="store_true")
    a = ap.parse_args(argv)

    import numpy as np

    import starway_tpu as sw
    from benchmark import run as R
    from benchmark.harness import spec as S

    send, count = sw.Client.asend, [0]

    def altered(self, buffer, tag, *args, **kw):
        if getattr(buffer, "size", 0) >= 65536:       # payloads, not control words
            count[0] += 1
            if count[0] % 16 == 0:
                if isinstance(buffer, np.ndarray):
                    buffer = buffer.copy()
                    buffer[4096] ^= 1
                else:
                    buffer = buffer.at[4096].add(1)
        return send(self, buffer, tag, *args, **kw)

    verdicts = []
    for k in range(a.seeds):
        seed = a.first_seed + 104729 * k
        sw.Client.asend = altered
        try:
            args = R.parse(["--workload", a.workload, "--seed", str(seed),
                            "--seconds", str(a.seconds), "--trace", "0"]
                           + (["--no-chip"] if a.no_chip else []))
            ctx = R.context(args)
            ctx["t_start"] = time.monotonic()
            out = S.load_runner(ctx["config"]["runner"]).run(ctx)
        finally:
            sw.Client.asend = send
        verdicts.append(out["correct"])
        print(json.dumps({"control_seed": seed, "correct": out["correct"],
                          "attempted": out["attempted"]}), flush=True)
    ok = not any(verdicts)
    print(json.dumps({"control_fails_on_every_seed": ok}), flush=True)
    return 0 if ok else 1


if __name__ == "__main__":
    sys.exit(main())
