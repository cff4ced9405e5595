"""``aot_compile_gdn.py``'s sibling for the ``serve_ssm_yoco`` runner kind:
ask the chip's compiler, with no chip attached, whether the WHOLE model's
serving programs fit one v5e chip at the cell's size, whether any of them
holds a second copy of the Mamba state, the rings or the one full layer's
rows, how many layer bodies (``while`` loops: one a run of whole periods,
and the chunk's own scan over its steps) each program traces, and that
the 200,064-row table is there once.

    JAX_PLATFORMS=cpu python benchmark/tests/aot_compile_ssm_yoco.py phi4-mini-flash [bucket ...] [--slots N]

Compiles the decode-chunk program (state, rings and rows in its carry) and
the admit programs (every prompt bucket the configuration names, or the
ones given) for a described ``v5e:2x2`` device and prints each program's
arguments and temporaries beside the bytes the weights and each cache kind
hold, the ``copy`` instructions of the compiled program whose result has a
whole leaf's shape (none is what is wanted: the leaves are written in
place), and the seconds each compile took.  Nothing runs.
"""

from __future__ import annotations

import os
import re
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

    import importlib

    from benchmark.harness import spec as S

    slots = None
    if "--slots" in argv:
        at = argv.index("--slots")
        slots, argv = int(argv[at + 1]), argv[:at] + argv[at + 2:]
    name = argv[0] if argv else "phi4-mini-flash"
    config = S.load_config(S.load_spec(), name)
    runner = S.load_runner(config["runner"])
    W = importlib.import_module(
        "benchmark.harness.weights_" + config["runner"][len("serve_"):])
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
    print(f"{n} slots x {max_len}: weights {gb(params):.3f} GB, cache "
          f"{gb(cache):.3f} GB = "
          + " + ".join(f"{k} {gb(v):.3f}" for k, v in cache.items()), flush=True)
    types = {"float32": "f32", "bfloat16": "bf16"}
    leaves = {k: f"{types[str(v.dtype)]}[{','.join(map(str, v.shape))}]"
              for k, v in cache.items()}
    vec = lambda dt: jax.ShapeDtypeStruct((n,), dt, sharding=one)
    scalar = jax.ShapeDtypeStruct((), jnp.int32, sharding=one)
    key = on_chip(jax.eval_shape(jax.random.PRNGKey, 0))

    def report(what, lowered):
        t0 = time.monotonic()
        compiled = lowered.compile()
        m = compiled.memory_analysis()
        text = compiled.as_text()
        copies = {k: len(re.findall(
            r"= " + re.escape(shape) + r"[^ ]* copy\(", text))
            for k, shape in leaves.items()}
        table = params["embed"].shape
        tables = len(re.findall(
            rf"= bf16\[({table[0]},{table[1]}|{table[1]},{table[0]})\]"
            r"[^ ]* (copy|transpose)\(", text))
        print(f"{what}: while loops {len(re.findall(' while[(]', text))}, "
              f"copies or transposes of the table {tables}", flush=True)
        print(f"{what}: arguments {m.argument_size_in_bytes / 1e9:.3f} GB, "
              f"temporaries {m.temp_size_in_bytes / 1e9:.3f} GB, outputs "
              f"{m.output_size_in_bytes / 1e9:.3f} GB (aliased "
              f"{m.alias_size_in_bytes / 1e9:.3f}), whole-leaf copies "
              f"{copies}, compiled in {time.monotonic() - t0:.0f} s", flush=True)

    run = _compiled_chunk(cfg, n, max_len, chunk, 0.0, None, None, None)
    report("decode chunk", run.lower(
        params, cache, vec(jnp.int32), vec(jnp.int32), vec(bool),
        vec(jnp.int32), key))
    buckets = ([int(b) for b in argv[1:]] or sv.get("prompt_buckets")
               or runner.serve.default_buckets(max_len))
    for b in sorted(buckets, reverse=True):   # the largest first: it decides
        admit = _compiled_admit(cfg, b, 0.0, None, None)
        prompt = jax.ShapeDtypeStruct((1, b), jnp.int32, sharding=one)
        report(f"admit bucket {b}", admit.lower(
            params, cache, prompt, scalar, scalar, key))
    return 0


if __name__ == "__main__":
    sys.exit(main(sys.argv[1:]))
