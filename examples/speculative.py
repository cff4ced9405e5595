"""Speculative decoding demo: draft-proposed tokens, target-verified.

A cheap draft model proposes ``gamma - 1`` tokens; the target model checks
the whole chunk in ONE forward and keeps the accepted prefix (plus one
corrected/bonus token) — the target's KV cache streams once per accepted
run instead of once per token, which is the whole speedup on a
bandwidth-bound decode.  Greedy output matches plain ``generate()``
token for token (up to bf16 argmax near-ties between the chunk and
stepwise forwards): the draft changes how fast tokens appear.

Uses the tiny debug model so it runs anywhere (CPU included).  With
random weights a shallow draft rarely agrees with the target, so the demo
also runs a self-draft (acceptance ~1) to show the mechanism at both ends;
a real deployment pairs a trained target with a distilled draft
(examples/serve_hf.py shows how checkpoints convert in).

Usage:  python examples/speculative.py [--gamma 4] [--max-new 24]
"""

import argparse
import os
import sys

sys.path.insert(0, os.path.join(os.path.dirname(__file__), ".."))


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--gamma", type=int, default=4)
    ap.add_argument("--max-new", type=int, default=24)
    ap.add_argument("--device", action="store_true",
                    help="run on the default (TPU) backend instead of CPU")
    args = ap.parse_args()

    import jax

    if not args.device:
        # Before first backend use: the demo runs anywhere by default.
        jax.config.update("jax_platforms", "cpu")
    import jax.numpy as jnp
    import numpy as np

    from starway_tpu.models import LlamaConfig, init_params
    from starway_tpu.models.generate import generate
    from starway_tpu.models.speculative import generate_speculative

    cfg = LlamaConfig.preset("debug")
    dcfg = LlamaConfig.preset("debug", n_layers=1)
    params = init_params(jax.random.PRNGKey(0), cfg)
    dparams = init_params(jax.random.PRNGKey(1), dcfg)
    prompt = jnp.asarray(np.random.default_rng(0).integers(
        1, cfg.vocab_size, (2, 8), dtype=np.int32))

    ref = generate(params, cfg, prompt, args.max_new)

    from starway_tpu.models.speculative import (draft_from_truncation,
                                                generate_lookup)

    def report(name, out, stats):
        same = bool((out == ref).all())
        steps = np.asarray(stats["macro_steps"], np.float64)
        acc = np.asarray(stats["accepted"], np.float64)
        rate = acc.sum() / max(steps.sum() * (args.gamma - 1), 1)
        amort = (acc.sum() + steps.sum()) / max(steps.sum(), 1)
        print(f"{name}: bit-identical to generate(): {same}; "
              f"acceptance {rate:.0%}, {amort:.2f} tokens/target-pass "
              f"(gamma={args.gamma})")
        assert same, "greedy speculative output diverged from generate()"

    # A FREE draft: the target's own first layer (no second checkpoint).
    tparams, tcfg = draft_from_truncation(params, cfg, 1)
    for name, dp, dc in (("shallow draft (1L, random)", dparams, dcfg),
                         ("truncation draft (target[:1])", tparams, tcfg),
                         ("self-draft (acceptance ~1)", params, cfg)):
        out, stats = generate_speculative(
            params, cfg, dp, dc, prompt, args.max_new, gamma=args.gamma,
            return_stats=True)
        report(name, out, stats)
    # Prompt-lookup: no draft model at all — proposals copy the latest
    # matching n-gram continuation from the sequence's own history.
    out, stats = generate_lookup(params, cfg, prompt, args.max_new,
                                 gamma=args.gamma, ngram=2,
                                 return_stats=True)
    report("prompt-lookup (ngram=2, draft-free)", out, stats)
    print("ok")


if __name__ == "__main__":
    main()
