"""Runner of the ``serve_ssm_yoco`` kind: a decoder-hybrid-decoder whose
self-decoder is Mamba state-space layers beside window differential
attention, whose one full attention layer's rows the cross-decoder's
attention layers read, with gated memory units between those, LayerNorm
with a bias and a tied head (SambaY with differential attention:
Phi-4-mini-flash-reasoning), behind the program's ``SlotServer``, WHOLE on
one chip.

Everything but the model is the ``serve`` runner's, used as it is: ``Book``,
the warm-up, the in-process driver and its window, the spans, the profile,
``decide_correct`` and the obs.  This file brings what is model-specific
there (the program's configuration from the file's keys, the benchmark's
weights in the program's layout, the prompt buckets the file names) and
adds to the traced run's obs the seconds and calls of each operation BY
NAME (``harness/trace_by_name.py``) and the decode chunk's seconds under
the program's scope ``sw_cross_decoder`` (``harness/trace_by_scope.py``),
from the same trace file; the counts the cell's readers rest on are
``harness/ssm_yoco_counts.py``'s, logged once a run as ``state_rows``.  A
``--no-chip`` rehearsal lays ``tests/data/rehearsal_ssm_yoco.json`` (tiny
sizes) over the real files: ``rehearsal.json`` is the accepted benchmark's.
"""

from __future__ import annotations

import json

from benchmark.harness import spec as S
from benchmark.harness import ssm_yoco_counts as C
from benchmark.harness.chipside import log

serve = S.load_runner("serve")


def model_config(config: dict):
    """The program's configuration from the file's (Hugging Face) keys.  A
    program without these kinds (the parent of the PR that added them)
    fails here: its models have no ``StateSpace``."""
    from benchmark.harness import weights_ssm_yoco as W
    from starway_tpu.models.llama import LayerKinds, LlamaConfig, StateSpace

    d = W.dims(config)
    return LlamaConfig(
        vocab_size=d["V"], d_model=d["D"], n_layers=d["L"], n_heads=d["H"],
        n_kv_heads=d["Hkv"], d_ff=d["F"], norm_eps=d["eps"], dtype=d["dtype"],
        kinds=LayerKinds.in_runs(d["runs"], window=d["window"]),
        ssm=StateSpace(d_inner=d["E"], d_state=d["N"], dt_rank=d["R"],
                       conv=d["taps"]),
        norm="layer", diff_attn=True, tied=True, attn_bias=True)


def program_tree(model: dict) -> dict:
    """The benchmark's weights in the layout ``SlotServer`` takes: a run of
    whole periods a tuple of stacked trees; ONE table, no ``lm_head``."""
    return {"embed": model["embed"], "layers": tuple(model["layers"]),
            "final_norm": model["final_norm"]}


def build_server(config: dict, seed: int, **kw):
    import jax

    from benchmark.harness import weights_ssm_yoco as W
    from starway_tpu.models import SlotServer

    sv, cfg = config["serve"], model_config(config)
    params = program_tree(W.make_model(seed, W.dims(config)))
    jax.block_until_ready(params)
    return SlotServer(params, cfg, n_slots=sv["n_slots"],
                      max_len=sv["max_len"], chunk=sv["chunk"],
                      temperature=sv.get("temperature", 0.0),
                      prompt_buckets=sv.get("prompt_buckets"), **kw)


serve.build_server = build_server   # the drivers build their server by name


def chunk_program_text(config: dict) -> "str | None":
    """The compiler's own text of the decode chunk at the cell's shapes
    (compiled again from shapes alone: the same instruction names as the
    one that ran), for ``trace_by_scope``.  None where it cannot be had."""
    try:
        import jax
        import jax.numpy as jnp

        from benchmark.harness import weights_ssm_yoco as W
        from starway_tpu.models import init_cache
        from starway_tpu.models.serving import _compiled_chunk

        sv, cfg = config["serve"], model_config(config)
        n = sv["n_slots"]
        vec = lambda dt: jax.ShapeDtypeStruct((n,), dt)
        run = _compiled_chunk(cfg, n, sv["max_len"], sv["chunk"],
                              float(sv.get("temperature", 0.0)), None, None,
                              None)
        return run.lower(
            jax.eval_shape(lambda: program_tree(W.make_model(0, W.dims(config)))),
            jax.eval_shape(lambda: init_cache(cfg, n, sv["max_len"])),
            vec(jnp.int32), vec(jnp.int32), vec(bool), vec(jnp.int32),
            jax.eval_shape(jax.random.PRNGKey, 0)).compile().as_text()
    except Exception as e:   # the metric is then left out, the run stands
        log(event="chunk_program_text", failed=repr(e)[:300])
        return None


def run_inproc(ctx: dict) -> dict:
    w = serve.inproc_window(ctx)
    verdict = serve.decide_correct(ctx, w["sample"], w["faults"], len(w["rows"]))
    by_name = by_scope = None
    if w["prof"].dir is not None and ctx["chip"]:
        from benchmark.harness.trace_by_name import reduce_by_name
        from benchmark.harness.trace_by_scope import reduce_by_scope

        by_name = reduce_by_name(w["prof"].dir)   # before reduce() removes it
        by_scope = reduce_by_scope(w["prof"].dir, C.SCOPES, C.CHUNK_PROGRAM,
                                   chunk_program_text(ctx["config"]))
        for program, rows in sorted((by_name or {"ops": {}})["ops"].items()):
            top = sorted(rows.items(), key=lambda kv: -kv[1][1])[:16]
            log(event="ops_by_name", program=program,
                seconds=sum(s for _c, s in rows.values()),
                top=[[n, round(c, 1), round(s, 5)] for n, (c, s) in top])
        log(event="ops_by_scope", program=C.CHUNK_PROGRAM, seconds=by_scope)
    trace = w["prof"].reduce()
    obs = serve.serving_obs(ctx, w["spans"], w["rows"], *w["window"], trace)
    obs["ops_by_name"], obs["ops_by_scope"] = by_name, by_scope
    means = C.step_means(obs)
    if means:   # the counters the new readers rest on
        cfg = ctx["config"]
        log(event="state_rows", **means,
            weights_MB=C.weight_bytes(cfg) / 1e6,
            state_rw_MB=C.state_rw_bytes(cfg, means["slots"]) / 1e6,
            full_rows_read_MB=C.full_read_bytes(
                cfg, means["rows_full"], means["readers"]) / 1e6,
            rings_read_MB=C.ring_read_bytes(cfg, means["rows_window"]) / 1e6)
    return {"correct": verdict["correct"], "attempted": len(w["rows"]),
            "failed": len(w["faults"]), "e2e": w["e2e"], "obs": obs,
            "device": dict(ctx["device"], memory_peak_bytes=w["peak"]),
            "trace": trace}


def run(ctx: dict) -> dict:
    if not ctx["chip"]:
        with open(S.BENCH / "tests" / "data" / "rehearsal_ssm_yoco.json") as f:
            small = json.load(f)
        ctx["config"].update(small["config"])
        ctx["traffic"].update(small["traffic"])
    # A program without these kinds (the parent of the PR that added them)
    # fails here, at once: before the chip is taken or a weight made.
    model_config(ctx["config"])
    if ctx["traffic"]["driver"] != "inproc":
        raise SystemExit("benchmark: the serve_ssm_yoco runner has the "
                         "inproc driver only")
    return run_inproc(ctx)


def run_role(role: str, ctx: dict) -> int:
    raise SystemExit(f"benchmark: the serve_ssm_yoco runner has no role {role!r}")
