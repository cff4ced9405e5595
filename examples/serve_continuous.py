"""Continuous-batching demo: a request stream through a fixed slot set.

Requests arrive over time (here: submitted between decode chunks), cohabit
the slot batch, finish at different lengths, and free their slot for the
next arrival immediately — no waiting for the batch to drain.  Greedy
outputs are bit-identical to one-at-a-time ``generate()`` calls; this demo
cross-checks one request against that oracle.

Uses the tiny debug model so it runs anywhere (CPU included); swap in
converted HF weights (examples/serve_hf.py shows the conversion) to serve
a real checkpoint.

Usage:  python examples/serve_continuous.py [--requests 12] [--slots 3]
"""

import argparse
import os
import sys
import time

sys.path.insert(0, os.path.join(os.path.dirname(__file__), ".."))


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--requests", type=int, default=12)
    ap.add_argument("--slots", type=int, default=3)
    ap.add_argument("--chunk", type=int, default=8)
    ap.add_argument("--temperature", type=float, default=0.0)
    ap.add_argument("--device", action="store_true",
                    help="use the configured accelerator instead of CPU")
    args = ap.parse_args()

    import jax

    if not args.device:
        # Before first backend use: the demo runs anywhere by default.
        jax.config.update("jax_platforms", "cpu")

    import numpy as np

    from starway_tpu.models import LlamaConfig, SlotServer, init_params
    from starway_tpu.models.generate import generate

    cfg = LlamaConfig.preset("debug")
    params = init_params(jax.random.PRNGKey(0), cfg)
    srv = SlotServer(params, cfg, n_slots=args.slots, max_len=96,
                     chunk=args.chunk, temperature=args.temperature, seed=7)

    rng = np.random.default_rng(0)
    reqs = {}
    t0 = time.time()
    done = {}
    # Arrivals interleave with decode chunks — the continuous part.
    for i in range(args.requests):
        prompt = list(rng.integers(1, cfg.vocab_size,
                                   int(rng.integers(2, 16))))
        max_new = int(rng.integers(4, 12))
        reqs[srv.submit(prompt, max_new)] = (prompt, max_new)
        done.update(srv.step())
    done.update(srv.run())
    dt = time.time() - t0

    total = sum(len(v) for v in done.values())
    print(f"served {len(done)} requests / {total} tokens in {dt:.2f}s "
          f"({total / dt:.1f} tok/s wall) through {args.slots} slots")
    for rid in sorted(done)[:4]:
        print(f"  req {rid}: +{len(done[rid])} tokens {done[rid].tolist()}")

    if args.temperature == 0.0 and done:
        rid0 = sorted(done)[0]
        prompt, max_new = reqs[rid0]
        solo = generate(params, cfg,
                        jax.numpy.asarray([prompt], jax.numpy.int32), max_new)
        want = np.asarray(solo[0, len(prompt):])
        assert (done[rid0] == want).all(), "continuous != standalone greedy!"
        print(f"  req {rid0} cross-checked against standalone generate(): OK")

    # Prefix caching: a shared "system prompt" prefilled ONCE; requests
    # submit only their suffix and still generate exactly what
    # generate(prefix + suffix) would.
    if args.temperature == 0.0:
        system = list(rng.integers(1, cfg.vocab_size, 11))
        pid = srv.register_prefix(system)
        suffixes = [list(rng.integers(1, cfg.vocab_size, n))
                    for n in (3, 5, 2)]
        prids = [srv.submit(s, 6, prefix=pid) for s in suffixes]
        pdone = srv.run()
        for prid, suffix in zip(prids, suffixes):
            solo = generate(
                params, cfg,
                jax.numpy.asarray([system + suffix], jax.numpy.int32), 6)
            want = np.asarray(solo[0, len(system) + len(suffix):])
            assert (pdone[prid] == want).all(), "prefix != full-prompt!"
        print(f"  prefix caching: {len(prids)} suffix-only requests over "
              f"one {len(system)}-token cached prefix, all match "
              f"generate(prefix + suffix)")


if __name__ == "__main__":
    main()
