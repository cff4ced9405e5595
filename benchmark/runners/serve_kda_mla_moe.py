"""Runner of the ``serve_kda_mla_moe`` kind: a decoder whose layers are
gated delta-rule linear attention (KDA: a request's state is a matrix a
head, not a row a token) or NoPE latent attention by a published list, with
sigmoid-routed experts beside a shared one (Kimi-Linear), behind the
program's ``SlotServer``, as one holder of an expert-parallel deployment.

Everything but the model is the ``serve`` runner's, used as it is: ``Book``,
the warm-up, the in-process driver and its window, the spans, the profile,
``decide_correct`` and the obs.  This file brings what is model-specific
there (the program's configuration from the file's keys, the benchmark's
weights in the program's layout, the prompt buckets the file names) and
adds to the traced run's obs the seconds and calls of each operation BY
NAME, from the same trace file (``harness/trace_by_name.py``).  A
``--no-chip`` rehearsal lays ``tests/data/rehearsal_kda.json`` (tiny sizes)
over the real files: ``rehearsal.json`` is the accepted benchmark's.
"""

from __future__ import annotations

import json

from benchmark.harness import kda_mla_moe_counts as C
from benchmark.harness import spec as S
from benchmark.harness.chipside import log

serve = S.load_runner("serve")


def model_config(config: dict):
    """The program's configuration from the file's (Hugging Face) keys.  A
    program without the linear kind (the parent of the PR that added it)
    fails on the import."""
    from benchmark.harness import weights_kda_mla_moe as W
    from starway_tpu.models.llama import (LatentAttn, LayerKinds, LinearAttn,
                                          LlamaConfig, RoutedFFN)

    d = W.dims(config)
    linear = d["linear"]
    period = next(p for p in range(1, d["L"] + 1)
                  if all(linear[i] == linear[i % p] for i in range(d["L"])))
    return LlamaConfig(
        vocab_size=d["V"], d_model=d["D"], n_layers=d["L"], n_heads=d["H"],
        n_kv_heads=d["H"], d_ff=d["F"], rope_theta=float(config["rope_theta"]),
        norm_eps=d["eps"], dtype=d["dtype"],
        latent=LatentAttn(q_rank=None, kv_rank=d["kv_rank"], nope_dim=d["nope"],
                          rope_dim=d["rope"], v_dim=d["v"],
                          sm_scale=d["sm_scale"]),
        linear=LinearAttn(n_heads=d["Hl"], head_dim=d["dl"], conv=d["taps"]),
        kinds=LayerKinds(windows=(None,) * period, rope=(False,) * period,
                         linear=linear[:period]),
        routed=RoutedFFN(n_experts=d["E"], top_k=d["top_k"], d_expert=d["Fe"],
                         n_held=d["held"], first_held=d["first_held"],
                         n_shared=d["n_shared"], scale=d["route_scale"],
                         first_dense=d["first_dense"], score="sigmoid"))


def program_tree(model: dict) -> dict:
    """The benchmark's weights in the layout ``SlotServer`` takes: one
    stacked tree a run of layers of one kind."""
    return {"embed": model["embed"], "layers": tuple(model["layers"]),
            "final_norm": model["final_norm"], "lm_head": model["lm_head"]}


def build_server(config: dict, seed: int, **kw):
    import jax

    from benchmark.harness import weights_kda_mla_moe as W
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
            latent_read_MB=C.latent_bytes(ctx["config"], means["rows"]) / 1e6)
    return {"correct": verdict["correct"], "attempted": len(w["rows"]),
            "failed": len(w["faults"]), "e2e": w["e2e"], "obs": obs,
            "device": dict(ctx["device"], memory_peak_bytes=w["peak"]),
            "trace": trace}


def run(ctx: dict) -> dict:
    if not ctx["chip"]:
        with open(S.BENCH / "tests" / "data" / "rehearsal_kda.json") as f:
            small = json.load(f)
        ctx["config"].update(small["config"])
        ctx["traffic"].update(small["traffic"])
    # A program without these kinds (the parent of the PR that added them)
    # fails here, at once: before the chip is taken or a weight made.
    model_config(ctx["config"])
    if ctx["traffic"]["driver"] != "inproc":
        raise SystemExit("benchmark: the serve_kda_mla_moe runner has the "
                         "inproc driver only")
    return run_inproc(ctx)


def run_role(role: str, ctx: dict) -> int:
    raise SystemExit(f"benchmark: the serve_kda_mla_moe runner has no role {role!r}")
