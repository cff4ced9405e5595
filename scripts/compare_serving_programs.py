#!/usr/bin/env python3
"""Are the serving programs of two trees the same programs?

    JAX_PLATFORMS=cpu python scripts/compare_serving_programs.py <tree A> <tree B> [config ...]

For each serving configuration of the benchmark that BOTH trees can build
(default: ``kimi-k2`` and ``mistral7b``), each tree's decode chunk and its
admission programs (an admit bucket, or the mixed chunk of every width
where the server ingests) are compiled at the cell's own size for a
described ``v5e:2x2``, no chip attached, one child process a tree, and
compared: the compiled HLO with what names a source line taken out, and
each Pallas kernel's Mosaic module printed without debug locations.
"SAME" means the change left that cell's device programs as they were:
what a PR that touches shared model code quotes for a cell whose spread
sits at its gate.  A configuration only ONE tree can build (the one a
``model_config`` PR adds: ``phi4-mini-flash`` against its parent) is
printed for that tree alone, "ONLY IN", and decides nothing.

Beside each program, for both trees: the seconds its trace and lowering
took (what a warm start, its compile cache hit, still pays for every
program before it can hash and load it) and the printed size of each
distinct kernel module.  A kernel PR holds both within 1.25 times the
parent's before it spends chip time (PERF.md section 7: ``setup_s``
refused PR 41 for a body unrolled over the heads).  The seconds are this
host's, under whatever else runs on it: read them as a ratio, and run
twice where they decide.
"""

from __future__ import annotations

import base64
import hashlib
import json
import os
import re
import subprocess
import sys
import time

CHILD = "--dump"
# A runner's weights module where its name does not give it.
WEIGHTS = {"serve_window_moe_mtp": "weights_k_exaone"}


def _dump(root: str, configs: list) -> dict:
    """In a child, from the tree at ``root``: {program: [hlo text, [kernel
    module text, ...], seconds to trace and lower]}."""
    sys.path.insert(0, root)
    os.chdir(root)
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    os.environ["JAX_PLATFORMS"] = "cpu"
    import jax
    import jax.numpy as jnp
    from jax._src import tpu_custom_call  # noqa: F401  (registers dialects)
    from jax._src.lib import tpu
    from jax._src.lib.mlir import ir
    from jax.experimental import topologies
    from jax.sharding import SingleDeviceSharding

    from benchmark.harness import spec as S
    from starway_tpu.models import init_cache, serving

    one = SingleDeviceSharding(topologies.get_topology_desc(
        platform="tpu", topology_name="v5e:2x2").devices[0])
    jax.default_backend = lambda: "tpu"
    on_chip = lambda tree: jax.tree_util.tree_map(
        lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype, sharding=one), tree)

    def texts(lower):
        t0 = time.perf_counter()
        lowered = lower()
        seconds = time.perf_counter() - t0
        hlo = lowered.compile().as_text()
        kernels = []
        for m in re.finditer(r'\\?"body\\?": ?\\?"([A-Za-z0-9+/=]+)\\?"', hlo):
            ctx = ir.Context()
            tpu.register_dialect(ctx)
            ctx.allow_unregistered_dialects = True
            with ctx:
                kernels.append(ir.Module.parse(base64.b64decode(
                    m.group(1))).operation.get_asm(enable_debug_info=False))
        keep = []
        for line in hlo.splitlines():
            if re.match(r'^\d+ ("|\{)', line) or line.startswith((
                    "FileNames", "FunctionNames", "FileLocations", "StackFrames")):
                continue      # the tables of source files and lines
            line = re.sub(r', metadata=\{[^}]*\}', "", line)
            if "tpu_custom_call" in line:   # its body is compared above
                line = re.sub(r'backend_config=.*$', "", line)
            keep.append(line)
        return "\n".join(keep), kernels, seconds

    out, spec = {}, S.load_spec()
    for name in configs:
        try:
            config = S.load_config(spec, name)
            runner = S.load_runner(config["runner"])
            if config["runner"] != "serve":
                runner.model_config(config)
        except (SystemExit, Exception) as e:   # this tree cannot build it
            print(f"{root}: no {name}: {e!r}"[:300], file=sys.stderr)
            continue
        if config["runner"] == "serve":
            from benchmark.harness import weights as W
            cfg = runner.llama_config(config)
        else:
            import importlib
            W = importlib.import_module("benchmark.harness." + WEIGHTS.get(
                config["runner"],
                "weights_" + config["runner"][len("serve_"):]))
            cfg = runner.model_config(config)
        sv = config["serve"]
        n, max_len, chunk = sv["n_slots"], sv["max_len"], sv["chunk"]
        params = on_chip(jax.eval_shape(
            lambda: runner.program_tree(W.make_model(0, W.dims(config)))))
        cache = on_chip(jax.eval_shape(lambda: init_cache(cfg, n, max_len)))
        vec = lambda dt: jax.ShapeDtypeStruct((n,), dt, sharding=one)
        scalar = jax.ShapeDtypeStruct((), jnp.int32, sharding=one)
        key = on_chip(jax.eval_shape(jax.random.PRNGKey, 0))
        state = (vec(jnp.int32), vec(jnp.int32), vec(bool), vec(jnp.int32), key)
        if getattr(cfg, "mtp", None):   # drafts and verifies: as it samples
            run = serving._compiled_chunk(
                cfg, n, max_len, chunk, float(sv["temperature"]), None,
                sv.get("top_p"), None, logprobs=True)
            state += ((vec(jnp.int32), jax.ShapeDtypeStruct(
                (n, cfg.vocab_size), jnp.float32, sharding=one),
                vec(jnp.float32)),)
        else:
            run = serving._compiled_chunk(cfg, n, max_len, chunk, 0.0, None,
                                          None, None)
        out[f"{name}.chunk"] = texts(
            lambda: run.lower(params, cache, *state))
        if config["runner"] == "serve":    # a dense server ingests
            shaped = lambda *s: jax.ShapeDtypeStruct(s, jnp.int32, sharding=one)
            for width in serving.INGEST_WIDTHS:
                mixed = serving._compiled_ingest_chunk(
                    cfg, n, max_len, chunk, width, 0.0, None, None, None)
                out[f"{name}.ingest_{width}"] = texts(lambda: mixed.lower(
                    params, cache, *state,
                    shaped(chunk, len(serving.PIECE_FIELDS)),
                    shaped(chunk, width)))
        else:
            admit = serving._compiled_admit(cfg, 1024, 0.0, None, None)
            prompt = jax.ShapeDtypeStruct((1, 1024), jnp.int32, sharding=one)
            out[f"{name}.admit_1024"] = texts(lambda: admit.lower(
                params, cache, prompt, scalar, scalar, key))
    return out


def _sizes(kernels: list) -> dict:
    """{kernel name: the distinct sizes its printed modules have}."""
    by_name = {}
    for text in kernels:
        name = re.match(r"module @(\w+)", text)
        by_name.setdefault(name.group(1) if name else "?", set()).add(
            len(text))
    return {name: sorted(sizes) for name, sizes in sorted(by_name.items())}


def main(argv) -> int:
    if argv and argv[0] == CHILD:
        json.dump(_dump(argv[1], argv[2:]), sys.stdout)
        return 0
    if len(argv) < 2:
        print(__doc__)
        return 2
    trees = [os.path.abspath(t) for t in argv[:2]]
    configs = argv[2:] or ["kimi-k2", "mistral7b"]
    dumps = [json.loads(subprocess.run(
        [sys.executable, os.path.abspath(__file__), CHILD, tree, *configs],
        check=True, stdout=subprocess.PIPE, stderr=subprocess.DEVNULL).stdout)
        for tree in trees]
    digest = lambda text: hashlib.sha256(text.encode()).hexdigest()[:12]
    same = True
    for program in dict.fromkeys([*dumps[0], *dumps[1]]):
        if program not in dumps[0] or program not in dumps[1]:
            at = program in dumps[1]
            hlo, kernels, seconds = dumps[at][program]
            print(f"{program}: ONLY IN {trees[at]}: HLO {digest(hlo)}, "
                  f"{len(kernels)} kernels\n    trace and lower "
                  f"{seconds:.2f} s; kernel modules, chars: {_sizes(kernels)}"
                  f", all {sum(map(len, kernels))}")
            continue
        (hlo_a, ker_a, sec_a), (hlo_b, ker_b, sec_b) = (
            dumps[0][program], dumps[1][program])
        ok = hlo_a == hlo_b and ker_a == ker_b
        same &= ok
        print(f"{program}: {'SAME' if ok else 'DIFFERENT'}: HLO "
              f"{digest(hlo_a)} / {digest(hlo_b)}, {len(ker_a)} kernels "
              f"{[digest(k) for k in ker_a]} / {[digest(k) for k in ker_b]}")
        print(f"    trace and lower {sec_a:.2f} s / {sec_b:.2f} s "
              f"(x{sec_b / sec_a:.2f}); kernel modules, chars: "
              f"{_sizes(ker_a)} / {_sizes(ker_b)}, all {sum(map(len, ker_a))}"
              f" / {sum(map(len, ker_b))} "
              f"(x{sum(map(len, ker_b)) / max(sum(map(len, ker_a)), 1):.2f})")
    return 0 if same else 1


if __name__ == "__main__":
    sys.exit(main(sys.argv[1:]))
