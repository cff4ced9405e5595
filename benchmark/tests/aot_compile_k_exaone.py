"""``aot_compile_window_moe.py``'s sibling for the ``serve_window_moe_mtp``
runner kind: ask the chip's compiler, with no chip attached, whether the
configuration's serving programs fit one v5e chip at the cell's size.

    JAX_PLATFORMS=cpu python benchmark/tests/aot_compile_k_exaone.py k-exaone [bucket ...] [--slots N]

Compiles the decode-chunk program (a draft-and-verify step: rows, masked
rings and the MTP block's rows in its carry, the slots' draft state beside
the cursors, sampled as the cell samples, log-probabilities emitted) and
the admit programs (every prompt bucket the configuration names, or the
ones given) for a described ``v5e:2x2`` device and prints each program's
arguments and temporaries beside the bytes the weights and the cache kinds
hold, and the seconds each compile took.  Nothing runs.
"""

from __future__ import annotations

import os
import sys
import time
from pathlib import Path

ROOT = Path(__file__).resolve().parents[2]
sys.path.insert(0, str(ROOT))
os.environ.setdefault("TPU_LOG_DIR", "disabled")
os.environ.setdefault("JAX_PLATFORMS", "cpu")


def main(argv) -> int:
    import jax
    import jax.numpy as jnp
    from jax.experimental import topologies
    from jax.sharding import SingleDeviceSharding

    from benchmark.harness import spec as S
    from benchmark.harness import weights_k_exaone as W

    slots = None
    if "--slots" in argv:
        at = argv.index("--slots")
        slots, argv = int(argv[at + 1]), argv[:at] + argv[at + 2:]
    name = argv[0] if argv else "k-exaone"
    config = S.load_config(S.load_spec(), name)
    runner = S.load_runner(config["runner"])
    topo = topologies.get_topology_desc(platform="tpu", topology_name="v5e:2x2")
    one = SingleDeviceSharding(topo.devices[0])
    cfg = runner.model_config(config)
    sv = config["serve"]
    # The programs pick their TPU branches from jax.default_backend().
    jax.default_backend = lambda: "tpu"

    def on_chip(tree):
        return jax.tree_util.tree_map(
            lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype, sharding=one), tree)

    params = on_chip(jax.eval_shape(
        lambda: runner.program_tree(W.make_model(0, W.dims(config)))))
    from starway_tpu.models import init_cache
    from starway_tpu.models.serving import _compiled_admit, _compiled_chunk

    n, max_len, chunk = slots or sv["n_slots"], sv["max_len"], sv["chunk"]
    cache = on_chip(jax.eval_shape(lambda: init_cache(cfg, n, max_len)))
    gb = lambda t: sum(a.size * a.dtype.itemsize
                       for a in jax.tree_util.tree_leaves(t)) / 1e9
    kind = lambda end: gb({k: v for k, v in cache.items() if k.endswith(end)})
    print(f"{n} slots x {max_len}: weights {gb(params):.3f} GB (the MTP "
          f"block {gb(params['mtp']):.3f}), cache {gb(cache):.3f} GB = full "
          f"rows {gb(cache) - kind('_ring') - kind('_mtp'):.3f} + rings "
          f"{kind('_ring'):.3f} + the MTP block's rows {kind('_mtp'):.3f}",
          flush=True)
    vec = lambda dt: jax.ShapeDtypeStruct((n,), dt, sharding=one)
    scalar = jax.ShapeDtypeStruct((), jnp.int32, sharding=one)
    key = on_chip(jax.eval_shape(jax.random.PRNGKey, 0))

    def report(what, lowered):
        t0 = time.monotonic()
        m = lowered.compile().memory_analysis()
        print(f"{what}: arguments {m.argument_size_in_bytes / 1e9:.3f} GB, "
              f"temporaries {m.temp_size_in_bytes / 1e9:.3f} GB, outputs "
              f"{m.output_size_in_bytes / 1e9:.3f} GB (aliased "
              f"{m.alias_size_in_bytes / 1e9:.3f}), compiled in "
              f"{time.monotonic() - t0:.0f} s", flush=True)

    sampling = (float(sv["temperature"]), None, sv.get("top_p"))
    draft = (vec(jnp.int32), jax.ShapeDtypeStruct(
        (n, cfg.vocab_size if sampling[0] else 1), jnp.float32, sharding=one),
        vec(jnp.float32))
    run = _compiled_chunk(cfg, n, max_len, chunk, *sampling, None,
                          logprobs=True)
    report("decode chunk", run.lower(
        params, cache, vec(jnp.int32), vec(jnp.int32), vec(bool),
        vec(jnp.int32), key, draft))
    buckets = ([int(b) for b in argv[1:]] or sv.get("prompt_buckets")
               or runner.serve.default_buckets(max_len))
    for b in sorted(buckets, reverse=True):   # the largest first: it decides
        admit = _compiled_admit(cfg, b, *sampling)
        prompt = jax.ShapeDtypeStruct((1, b), jnp.int32, sharding=one)
        report(f"admit bucket {b}", admit.lower(
            params, cache, prompt, scalar, scalar, key))
    return 0


if __name__ == "__main__":
    sys.exit(main(sys.argv[1:]))
