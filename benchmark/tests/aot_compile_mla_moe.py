"""``aot_compile.py``'s sibling for the ``serve_mla_moe`` runner kind: ask
the chip's compiler, with no chip attached, whether the configuration's
serving programs fit one v5e chip at the cell's size.

    JAX_PLATFORMS=cpu python benchmark/tests/aot_compile_mla_moe.py kimi-k2 [bucket ...]

Compiles the decode-chunk program and the admit programs (every prompt
bucket the cell's traffic can reach, or the ones named) for a described
``v5e:2x2`` device and prints each program's memory analysis beside the
bytes the weights and the cache hold.  Nothing runs.
"""

from __future__ import annotations

import os
import sys
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
    from benchmark.harness import traffic as T
    from benchmark.harness import weights_mla_moe as W

    name = argv[0] if argv else "kimi-k2"
    spec = S.load_spec()
    config = S.load_config(spec, name)
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

    n, max_len, chunk = sv["n_slots"], sv["max_len"], sv["chunk"]
    cache = on_chip(jax.eval_shape(lambda: init_cache(cfg, n, max_len)))
    gb = lambda t: sum(a.size * a.dtype.itemsize
                       for a in jax.tree_util.tree_leaves(t)) / 1e9
    print(f"weights {gb(params):.3f} GB, cache {gb(cache):.3f} GB", flush=True)
    vec = lambda dt: jax.ShapeDtypeStruct((n,), dt, sharding=one)
    scalar = jax.ShapeDtypeStruct((), jnp.int32, sharding=one)
    key = on_chip(jax.eval_shape(jax.random.PRNGKey, 0))
    run = _compiled_chunk(cfg, n, max_len, chunk, 0.0, None, None, None)
    c = run.lower(params, cache, vec(jnp.int32), vec(jnp.int32), vec(bool),
                  vec(jnp.int32), key).compile()
    print("decode chunk:", c.memory_analysis(), flush=True)
    all_buckets = runner.serve.default_buckets(max_len)
    reach = set()
    for cell in spec["workloads"]:
        if cell["config"] == name:
            for p, _o in T.request_set(S.load_traffic(cell["traffic"])):
                reach.add(min(b for b in all_buckets if b >= p))
    buckets = [int(b) for b in argv[1:]] or sorted(reach) or all_buckets
    for b in buckets:
        admit = _compiled_admit(cfg, b, 0.0, None, None)
        prompt = jax.ShapeDtypeStruct((1, b), jnp.int32, sharding=one)
        c = admit.lower(params, cache, prompt, scalar, scalar, key).compile()
        print(f"admit bucket {b}:", c.memory_analysis(), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main(sys.argv[1:]))
