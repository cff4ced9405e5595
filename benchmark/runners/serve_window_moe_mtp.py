"""Runner of the ``serve_window_moe_mtp`` kind: a decoder whose window RoPE
layers keep rings beside its full NoPE layers' rows, with head norms, a
leading dense layer and sigmoid-routed experts beside a shared one, and ONE
multi-token-prediction block that drafts inside the decode chunk of the
program's ``SlotServer`` (K-EXAONE): a step verifies the pending token and
the draft in one pass and yields one or two SAMPLED tokens a slot.

Everything but the model and the decision is the ``serve`` runner's, used
as it is, as ``serve_window_moe.py`` uses it: ``Book``, the warm-up, the
in-process driver and its window, the spans, the profile and the obs.  This
file brings the program's configuration from the file's keys, the
benchmark's weights in the program's layout, the sampling settings and the
prompt buckets the file names, and its OWN decision of ``correct``: served
tokens are sampled, so which token was served says nothing; the served
path hands out the log-probability it computed for each emitted token and
each draft (``SlotServer(on_logprobs=)``, passed through ``build_server``,
which ``serve.inproc_window`` calls by name) and the plain reference
recomputes both over the served sequence (``served_logps``).  A
``--no-chip`` rehearsal lays ``tests/data/rehearsal_window_moe_mtp.json``
(tiny sizes) over the real files.
"""

from __future__ import annotations

import json
import time

import numpy as np

from benchmark.harness import spec as S
from benchmark.harness import traffic as T
from benchmark.harness.chipside import log

serve = S.load_runner("serve")

RING_SLACK = 128   # a ring holds window + 128 positions: whole lane tiles


def model_config(config: dict):
    """The program's configuration from the file's (Hugging Face) keys."""
    from benchmark.harness import weights_k_exaone as W
    from starway_tpu.models.llama import LayerKinds, LlamaConfig, RoutedFFN

    d = W.dims(config)
    kinds = list(zip(d["windows"], d["rope"]))
    period = next(p for p in range(1, d["L"] + 1)
                  if all(kinds[i] == kinds[i % p] for i in range(d["L"])))
    return LlamaConfig(
        vocab_size=d["V"], d_model=d["D"], n_layers=d["L"], n_heads=d["Hq"],
        n_kv_heads=d["Hkv"], d_ff=d["F"], rope_theta=d["theta"],
        norm_eps=d["eps"], dtype=d["dtype"],
        head_dim_override=(d["hd"] if d["hd"] != d["D"] // d["Hq"] else None),
        qk_norm=True, mtp=config["num_nextn_predict_layers"],
        kinds=LayerKinds(windows=d["windows"][:period], rope=d["rope"][:period],
                         slack=config["serve"].get("ring_slack", RING_SLACK)),
        routed=RoutedFFN(n_experts=d["E"], top_k=d["top_k"], d_expert=d["Fe"],
                         n_held=d["held"], first_held=d["first_held"],
                         n_shared=d["n_shared"], scale=d["scale"],
                         first_dense=d["dense"], score="sigmoid", act="silu"))


def program_tree(model: dict) -> dict:
    """The benchmark's weights in the layout ``SlotServer`` takes: one
    stacked tree a run of layers of one kind, the MTP block a subtree."""
    return {"embed": model["embed"], "layers": tuple(model["layers"]),
            "final_norm": model["final_norm"], "lm_head": model["lm_head"],
            "mtp": model["mtp"]}


class Heard:
    """What the served path said of its own tokens, by request: each
    emitted token's log-probability and each draft ``(served index it stood
    for, token, log q, accepted)``, from ``on_logprobs``; the prompts, from
    ``submit``, so that a finished request is found again by what it
    sent."""

    def __init__(self):
        self.logp: dict = {}
        self.drafts: dict = {}
        self.prompts: dict = {}

    def on_logprobs(self, rid, logp, drafts) -> None:
        mine = self.logp.setdefault(rid, [])
        self.drafts.setdefault(rid, []).extend(
            (len(mine) + at, tok, lq, ok) for at, tok, lq, ok in drafts)
        mine.extend(logp)

    def samples(self, sample: list) -> list:
        """``serve``'s sample [(prompt ids, served ids)] as the reference's
        ``served_logps`` takes it."""
        by_prompt = {p: rid for rid, p in self.prompts.items()}
        out = []
        for prompt, tokens in sample:
            rid = by_prompt[np.asarray(prompt, np.int32).tobytes()]
            out.append({"prompt": prompt, "tokens": tokens,
                        "logp": self.logp.get(rid, []),
                        "drafts": [(j, t, lq) for j, t, lq, _ok
                                   in self.drafts.get(rid, [])],
                        "accepted": sum(ok for *_x, ok in self.drafts.get(rid, []))})
        return out


heard = Heard()


def build_server(config: dict, seed: int, **kw):
    import jax

    from benchmark.harness import weights_k_exaone as W
    from starway_tpu.models import SlotServer

    sv, cfg = config["serve"], model_config(config)
    params = program_tree(W.make_model(seed, W.dims(config)))
    jax.block_until_ready(params)
    srv = SlotServer(params, cfg, n_slots=sv["n_slots"],
                     max_len=sv["max_len"], chunk=sv["chunk"],
                     temperature=sv["temperature"], top_p=sv.get("top_p"),
                     prompt_buckets=sv.get("prompt_buckets"),
                     seed=seed & 0x7FFFFFFF, on_logprobs=heard.on_logprobs, **kw)
    submit = srv.submit

    def heard_submit(prompt, max_new_tokens, prefix=None):
        rid = submit(prompt, max_new_tokens, prefix)
        heard.prompts[rid] = np.asarray(prompt, np.int32).tobytes()
        return rid

    srv.submit = heard_submit
    return srv


serve.build_server = build_server   # the drivers build their server by name


def chunk_program_text(config: dict) -> "str | None":
    """The compiler's own text of the decode chunk at the cell's shapes
    (the program compiled again, from shapes alone: the same instruction
    names as the one that ran), for ``trace_by_scope``: a trace names an
    operation and not the scope it came from, this text has both.  None
    where it cannot be had."""
    try:
        import jax
        import jax.numpy as jnp

        from benchmark.harness import weights_k_exaone as W
        from starway_tpu.models import init_cache
        from starway_tpu.models.serving import _compiled_chunk

        sv, cfg = config["serve"], model_config(config)
        n = sv["n_slots"]
        vec = lambda dt: jax.ShapeDtypeStruct((n,), dt)
        run = _compiled_chunk(cfg, n, sv["max_len"], sv["chunk"],
                              float(sv["temperature"]), None, sv.get("top_p"),
                              None, logprobs=True)
        return run.lower(
            jax.eval_shape(lambda: program_tree(W.make_model(0, W.dims(config)))),
            jax.eval_shape(lambda: init_cache(cfg, n, sv["max_len"])),
            vec(jnp.int32), vec(jnp.int32), vec(bool), vec(jnp.int32),
            jax.eval_shape(jax.random.PRNGKey, 0),
            (vec(jnp.int32), jax.ShapeDtypeStruct(
                (n, cfg.vocab_size if sv["temperature"] else 1), jnp.float32),
             vec(jnp.float32))).compile().as_text()
    except Exception as e:   # the metric is then left out, the run stands
        log(event="chunk_program_text", failed=repr(e)[:300])
        return None


def decide_correct(ctx: dict, samples: list, faults: list, finished: int) -> dict:
    """The comparison with the plain reference, after the program's state
    is freed: the served path's own log-probabilities against the float32
    reference's over the served sequences, and the delivery guarantee with
    its exact count.  Every compared number is logged beside its limit."""
    config, limits = ctx["config"], ctx["config"]["correct"]
    ref = S.load_reference(ctx["cell"]["config"])
    out_to = max(o for _p, o in T.request_set(ctx["traffic"]))
    t0 = time.monotonic()
    complete = (bool(samples) and any(s["drafts"] for s in samples) and all(
        len(s["logp"]) == len(s["tokens"]) for s in samples))
    got = (ref.served_logps(config, ctx["args"].seed, samples,
                            config["serve"]["max_len"], out_to) if complete
           else {"logp_gap_max": float("inf"), "logp_gap_mean": float("inf"),
                 "draft_logp_gap_mean": float("inf"), "tokens": 0,
                 "drafts": 0, "sequences": 0, "finite": False})
    compared = [{"what": what, "value": got[what], "limit": limits.get(what + "_limit")}
                for what in ("logp_gap_mean", "logp_gap_max", "draft_logp_gap_mean")]
    compared.append({"what": "delivery_faults", "value": len(faults), "limit": 0})
    ok = bool(got["finite"] and finished > 0
              and all(c["limit"] is not None and c["value"] <= c["limit"]
                      for c in compared))
    log(event="correct", correct=ok, compared=compared,
        sample_sequences=got["sequences"], sample_tokens=got["tokens"],
        sample_drafts=got["drafts"],
        sample_accepted=sum(s["accepted"] for s in samples),
        logprobs_complete=complete, finished=finished,
        reference_seconds=time.monotonic() - t0,
        delivery_fault_requests=faults[:8])
    return {"correct": ok, "compared": compared}


def run_inproc(ctx: dict) -> dict:
    w = serve.inproc_window(ctx)
    verdict = decide_correct(ctx, heard.samples(w["sample"]), w["faults"],
                             len(w["rows"]))
    by_name = by_scope = None
    if w["prof"].dir is not None and ctx["chip"]:
        from benchmark.harness.trace_by_name import reduce_by_name
        from benchmark.harness.trace_by_scope import reduce_by_scope
        from benchmark.harness.window_moe_mtp_counts import (CHUNK_PROGRAM,
                                                             SCOPES)

        by_name = reduce_by_name(w["prof"].dir)   # before reduce() removes it
        by_scope = reduce_by_scope(w["prof"].dir, SCOPES, CHUNK_PROGRAM,
                                   chunk_program_text(ctx["config"]))
        for program, rows in sorted((by_name or {"ops": {}})["ops"].items()):
            top = sorted(rows.items(), key=lambda kv: -kv[1][1])[:16]
            log(event="ops_by_name", program=program,
                seconds=sum(s for _c, s in rows.values()),
                top=[[n, round(c, 1), round(s, 5)] for n, (c, s) in top])
        log(event="ops_by_scope", program=CHUNK_PROGRAM, seconds=by_scope)
    trace = w["prof"].reduce()
    obs = serve.serving_obs(ctx, w["spans"], w["rows"], *w["window"], trace)
    obs["ops_by_name"], obs["ops_by_scope"] = by_name, by_scope
    return {"correct": verdict["correct"], "attempted": len(w["rows"]),
            "failed": len(w["faults"]), "e2e": w["e2e"], "obs": obs,
            "device": dict(ctx["device"], memory_peak_bytes=w["peak"]),
            "trace": trace}


def run(ctx: dict) -> dict:
    if not ctx["chip"]:
        with open(S.BENCH / "tests" / "data" / "rehearsal_window_moe_mtp.json") as f:
            small = json.load(f)
        ctx["config"].update(small["config"])
        ctx["traffic"].update(small["traffic"])
    # A program without the MTP block (the parent of the PR that added it)
    # fails here, at once: before the chip is taken or a weight made.
    model_config(ctx["config"])
    if ctx["traffic"]["driver"] != "inproc":
        raise SystemExit("benchmark: the serve_window_moe_mtp runner has the "
                         "inproc driver only")
    return run_inproc(ctx)


def run_role(role: str, ctx: dict) -> int:
    raise SystemExit(f"benchmark: the serve_window_moe_mtp runner has no role {role!r}")
