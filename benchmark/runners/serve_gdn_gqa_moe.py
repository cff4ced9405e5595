"""Runner of the ``serve_gdn_gqa_moe`` kind: a decoder whose layers are
Gated DeltaNet linear attention (one log-decay a head, key heads fewer
than value heads: a request's state is a matrix a value head, not a row a
token) or gated grouped-query attention with partly rotated 256-wide heads,
on a period, every layer's FFN softmax-routed experts beside a shared
expert with a gate of its own (Qwen3-Next), behind the program's
``SlotServer``, as one holder of an expert-parallel deployment.

Everything but the model is the ``serve`` runner's, used as it is: ``Book``,
the warm-up, the in-process driver and its window, the spans, the profile,
``decide_correct`` and the obs.  This file brings what is model-specific
there (the program's configuration from the file's keys, the benchmark's
weights in the program's layout, the prompt buckets the file names) and
adds to the traced run's obs the seconds and calls of each operation BY
NAME, from the same trace file (``harness/trace_by_name.py``); the counts
the cell's readers rest on are ``harness/gdn_gqa_moe_counts.py``'s, logged
once a run as ``state_rows``.  A ``--no-chip`` rehearsal lays
``tests/data/rehearsal_gdn_gqa_moe.json`` (tiny sizes) over the real
files: ``rehearsal.json`` is the accepted benchmark's.
"""

from __future__ import annotations

import json

from benchmark.harness import gdn_gqa_moe_counts as C
from benchmark.harness import spec as S
from benchmark.harness.chipside import log

serve = S.load_runner("serve")


def model_config(config: dict):
    """The program's configuration from the file's (Hugging Face) keys.  A
    program without these kinds (the parent of the PR that added them)
    fails here: ``LinearAttn`` takes no ``n_k_heads``."""
    from benchmark.harness import weights_gdn_gqa_moe as W
    from starway_tpu.models.llama import (LayerKinds, LinearAttn, LlamaConfig,
                                          RoutedFFN)

    d = W.dims(config)
    period = d["linear"][:d["period"]]
    return LlamaConfig(
        vocab_size=d["V"], d_model=d["D"], n_layers=d["L"], n_heads=d["Hq"],
        n_kv_heads=d["Hkv"], d_ff=d["F"], rope_theta=d["theta"],
        norm_eps=d["eps"], dtype=d["dtype"], head_dim_override=d["hd"],
        rotary_dim=d["rot"], attn_gate=True, qk_norm=True,
        norm_zero_centred=True,
        linear=LinearAttn(n_heads=d["Hv"], head_dim=d["dl"], conv=d["taps"],
                          n_k_heads=d["Hk"], decay="head"),
        kinds=LayerKinds(windows=(None,) * len(period),
                         rope=tuple(not lin for lin in period), linear=period),
        routed=RoutedFFN(n_experts=d["E"], top_k=d["top_k"], d_expert=d["Fe"],
                         n_held=d["held"], first_held=d["first_held"],
                         n_shared=d["Fs"] // d["Fe"], score="softmax",
                         shared_gate=True))


def program_tree(model: dict) -> dict:
    """The benchmark's weights in the layout ``SlotServer`` takes: one
    stacked tree a run of layers of one kind."""
    return {"embed": model["embed"], "layers": tuple(model["layers"]),
            "final_norm": model["final_norm"], "lm_head": model["lm_head"]}


def build_server(config: dict, seed: int, **kw):
    import jax

    from benchmark.harness import weights_gdn_gqa_moe as W
    from starway_tpu.models import SlotServer

    sv, cfg = config["serve"], model_config(config)
    params = program_tree(W.make_model(seed, W.dims(config)))
    jax.block_until_ready(params)
    return SlotServer(params, cfg, n_slots=sv["n_slots"],
                      max_len=sv["max_len"], chunk=sv["chunk"],
                      temperature=sv.get("temperature", 0.0),
                      prompt_buckets=sv.get("prompt_buckets"), **kw)


serve.build_server = build_server   # the drivers build their server by name


def run_inproc(ctx: dict) -> dict:
    w = serve.inproc_window(ctx)
    verdict = serve.decide_correct(ctx, w["sample"], w["faults"], len(w["rows"]))
    by_name = None
    if w["prof"].dir is not None and ctx["chip"]:
        from benchmark.harness.trace_by_name import reduce_by_name

        by_name = reduce_by_name(w["prof"].dir)   # before reduce() removes it
        for program, rows in sorted((by_name or {"ops": {}})["ops"].items()):
            top = sorted(rows.items(), key=lambda kv: -kv[1][1])[:16]
            log(event="ops_by_name", program=program,
                seconds=sum(s for _c, s in rows.values()),
                top=[[n, round(c, 1), round(s, 5)] for n, (c, s) in top])
    trace = w["prof"].reduce()
    obs = serve.serving_obs(ctx, w["spans"], w["rows"], *w["window"], trace)
    obs["ops_by_name"] = by_name
    means = C.step_means(obs)
    if means:   # the counters the new readers rest on
        log(event="state_rows", **means,
            state_rw_MB=C.state_rw_bytes(ctx["config"], means["slots"]) / 1e6,
            kv_read_MB=C.kv_bytes(ctx["config"], means["rows"]) / 1e6)
    return {"correct": verdict["correct"], "attempted": len(w["rows"]),
            "failed": len(w["faults"]), "e2e": w["e2e"], "obs": obs,
            "device": dict(ctx["device"], memory_peak_bytes=w["peak"]),
            "trace": trace}


def run(ctx: dict) -> dict:
    if not ctx["chip"]:
        with open(S.BENCH / "tests" / "data" / "rehearsal_gdn_gqa_moe.json") as f:
            small = json.load(f)
        ctx["config"].update(small["config"])
        ctx["traffic"].update(small["traffic"])
    # A program without these kinds (the parent of the PR that added them)
    # fails here, at once: before the chip is taken or a weight made.
    model_config(ctx["config"])
    if ctx["traffic"]["driver"] != "inproc":
        raise SystemExit("benchmark: the serve_gdn_gqa_moe runner has the "
                         "inproc driver only")
    return run_inproc(ctx)


def run_role(role: str, ctx: dict) -> int:
    raise SystemExit(f"benchmark: the serve_gdn_gqa_moe runner has no role {role!r}")
