"""Pipeline-parallel Llama training: embed + staged decoder pipeline + head.

End-to-end 1F1B over the ``pp`` mesh axis with ALL parameters receiving
gradients: token embedding (outside the pipeline, chained through the
input-cotangent the schedule emits), n_layers/n_stages decoder blocks per
stage (parallel/pipeline.py's collective 1F1B), and the head (final norm +
lm_head, differentiated inside the last stage's loss).  The decoder block
is the same :func:`~starway_tpu.models.llama.decoder_layer` the scan
forward uses — one source of truth for the math.

Layout: parameters live PRE-SPLIT in pipeline form (``pp_split_params``):

    {"embed": [V, D],                      # replicated
     "stages": {name: [n_stages, L/S, ...]},  # leading dim sharded over pp
     "head": {"final_norm": [D], "lm_head": [D, V]}}  # replicated

so optimizer state shards the same way and no reshuffling happens per step.
``pp_merge_params`` restores the flat layout (for generation/eval).

Reference hook: the reference's nearest analogue is the streaming-duplex
"model parallelism" traffic pattern (/root/reference/benchmark.md:91-99);
the schedule itself is the TPU build's own.
"""

from __future__ import annotations

from typing import Callable, Optional

import jax
import jax.numpy as jnp
from jax import lax
from jax.sharding import PartitionSpec as P

from .llama import (LlamaConfig, cfg_rope_tables, decoder_layer,
                    embed_tokens, head_logits, resolve_attn_fn, token_ce)
from ..parallel.pipeline import make_pipeline_train


def pp_split_params(params: dict, n_stages: int) -> dict:
    """Flat init_params tree -> pipeline layout (see module docstring)."""
    layers = params["layers"]
    lead = jax.tree_util.tree_leaves(layers)[0].shape[0]
    if lead % n_stages:
        raise ValueError(f"n_layers={lead} not divisible by {n_stages} stages")
    stages = jax.tree_util.tree_map(
        lambda a: a.reshape(n_stages, lead // n_stages, *a.shape[1:]), layers)
    return {
        "embed": params["embed"],
        "stages": stages,
        "head": {"final_norm": params["final_norm"],
                 "lm_head": params["lm_head"]},
    }


def pp_merge_params(pp_params: dict) -> dict:
    """Pipeline layout -> flat init_params tree."""
    stages = pp_params["stages"]
    lead = jax.tree_util.tree_leaves(stages)[0]
    n_layers = lead.shape[0] * lead.shape[1]
    return {
        "embed": pp_params["embed"],
        "layers": jax.tree_util.tree_map(
            lambda a: a.reshape(n_layers, *a.shape[2:]), stages),
        "final_norm": pp_params["head"]["final_norm"],
        "lm_head": pp_params["head"]["lm_head"],
    }


def _moe_stage_template(cfg: LlamaConfig) -> dict:
    """Shape-only skeleton of one MoE stage tree (keys mirror
    llama.py:init_params' layer dict for ``cfg``; leaf values are
    placeholders) — enough structure for :func:`_expert_leaf_spec` /
    :func:`pp_stage_specs` to build spec trees before any real params
    exist.  Must track init_params' key set exactly (tree_map over
    mismatched structures raises inside shard_map otherwise)."""
    t = {
        "wq": 0, "wk": 0, "wv": 0, "wo": 0,
        "attn_norm": 0, "mlp_norm": 0,
        "moe": {"router": 0, "w_in": 0, "w_out": 0},
    }
    if cfg.moe_swiglu:
        t["moe"]["w_gate"] = 0
    if cfg.attn_bias:
        t.update(bq=0, bk=0, bv=0)
    return t


def _expert_leaf_spec(stages: dict):
    """Bool pytree matching ``stages``: True on the expert-table leaves
    (``moe/w_in``, ``moe/w_out``, swiglu ``moe/w_gate``) whose rows are
    per-expert, False on everything else (including the
    replicated-per-device router)."""
    return jax.tree_util.tree_map_with_path(
        lambda path, _a: any(
            getattr(k, "key", None) == "moe" for k in path) and any(
            getattr(k, "key", None) in ("w_in", "w_out", "w_gate")
            for k in path),
        stages)


def pp_stage_specs(stages: dict, axis_name: str = "pp",
                   ep_axis: Optional[str] = None):
    """PartitionSpecs for the ``stages`` subtree: every leaf shards its
    leading (stage) dim over ``axis_name``; with ``ep_axis``, the expert
    tables ``[S, L/S, E, ...]`` additionally shard their expert dim."""
    if ep_axis is None:
        return jax.tree_util.tree_map(lambda _a: P(axis_name), stages)
    return jax.tree_util.tree_map(
        lambda is_exp: P(axis_name, None, ep_axis) if is_exp
        else P(axis_name),
        _expert_leaf_spec(stages))


def pp_param_specs(pp_params: dict, axis_name: str = "pp",
                   ep_axis: Optional[str] = None) -> dict:
    """Per-leaf PartitionSpec tree for the pipeline layout (same shape as
    ``pp_params``, consumable by :func:`~starway_tpu.parallel.shard_tree`):
    stage leaves shard their leading (stage) dim over ``axis_name``
    (expert tables additionally over ``ep_axis`` when given),
    embed/head replicate."""
    return {
        "embed": P(),
        "stages": pp_stage_specs(pp_params["stages"], axis_name, ep_axis),
        "head": jax.tree_util.tree_map(lambda _a: P(), pp_params["head"]),
    }


def shard_pp_params(pp_params: dict, mesh, axis_name: str = "pp",
                    ep_axis: Optional[str] = None) -> dict:
    from ..parallel.fsdp import shard_tree

    return shard_tree(pp_params, mesh,
                      pp_param_specs(pp_params, axis_name, ep_axis))


def ppv_split_params(params: dict, n_stages: int, n_chunks: int) -> dict:
    """Flat init_params tree -> INTERLEAVED pipeline layout: stages get a
    leading ``[V, S, L/(V*S), ...]`` shape where ``stages[c, d]`` holds
    virtual stage ``c*S + d``'s layers (parallel/interleaved.py's
    placement).  ``pp_split_params``'s [V*S]-leading layout reshapes
    straight in (virtual stage v = flat index v)."""
    flat = pp_split_params(params, n_stages * n_chunks)
    return {
        "embed": flat["embed"],
        "stages": jax.tree_util.tree_map(
            lambda a: a.reshape(n_chunks, n_stages, *a.shape[1:]),
            flat["stages"]),
        "head": flat["head"],
    }


def ppv_merge_params(ppv_params: dict) -> dict:
    stages = ppv_params["stages"]
    return pp_merge_params({
        "embed": ppv_params["embed"],
        "stages": jax.tree_util.tree_map(
            lambda a: a.reshape(a.shape[0] * a.shape[1], *a.shape[2:]),
            stages),
        "head": ppv_params["head"],
    })


def ppv_param_specs(ppv_params: dict, axis_name: str = "pp") -> dict:
    """Specs for the interleaved layout: stage leaves shard dim 1 (the
    device dim) over ``axis_name``; dim 0 (the chunk dim) is device-local
    and stays unsharded; embed/head replicate."""
    return {
        "embed": P(),
        "stages": jax.tree_util.tree_map(lambda _a: P(None, axis_name),
                                         ppv_params["stages"]),
        "head": jax.tree_util.tree_map(lambda _a: P(), ppv_params["head"]),
    }


def shard_ppv_params(ppv_params: dict, mesh, axis_name: str = "pp") -> dict:
    from ..parallel.fsdp import shard_tree

    return shard_tree(ppv_params, mesh, ppv_param_specs(ppv_params, axis_name))


def make_pp_llama_train(mesh, cfg: LlamaConfig, *, axis_name: str = "pp",
                        n_micro: int, attn_fn: Optional[Callable] = None,
                        n_chunks: int = 1, dp_axis: Optional[str] = None,
                        ep_axis: Optional[str] = None):
    """Build ``step(pp_params, batch) -> (loss, grads)``, jit-compiled.

    ``batch``: [B, S+1] token ids, B divisible by ``n_micro``.  ``grads``
    has the pipeline layout of ``pp_params`` — feed it straight to optax.

    MoE configs (``cfg.n_experts > 0``) pipeline too: each stage owns its
    layers' expert tables and routes per microbatch (capacity from the
    microbatch's token count), the per-stage balance aux chains through
    the schedule exactly like the main loss (pipeline.py ``with_aux``),
    and the step's loss matches the sequential
    ``mean_microbatch(CE + coef * aux / n_layers)`` semantics of
    llama.py's ``loss_fn``.  Without ``ep_axis`` the experts are
    stage-LOCAL (wholly resident on the stage's device — fine until the
    expert tables outgrow one chip).  With ``ep_axis`` (a pp x ep mesh),
    each stage's expert tables shard over the ep sub-axis, tokens shard
    over ep like a second dp axis, and the dispatch rides
    :func:`~starway_tpu.models.moe.sharded_switch_moe`'s explicit
    ``all_to_all`` — expert-table gradients get expert-aware reduction
    (no pmean across ep; the all-to-all transpose already summed).
    Interleaved MoE (``n_chunks > 1``) runs with stage-LOCAL experts
    (the virtual-chunk schedule chains aux the same way); ep sharding
    composes with the plain schedule only.

    ``n_chunks > 1``: the INTERLEAVED 1F1B schedule
    (parallel/interleaved.py) with that many virtual chunks per device;
    ``pp_params`` must then be in ``ppv_split_params`` layout
    (stages ``[V, S, L/(V*S), ...]``).  Worth it when stages are many and
    microbatches few — see interleaved.py's fill-cost accounting.

    ``dp_axis``: compose either schedule with data parallelism on a
    pp x dp mesh (parallel/pipeline.py:dp_compose): each microbatch's rows
    shard over dp (``B / n_micro`` must divide by the dp size), grads ride
    one dp pmean, and the embedding gradient chains from the 1/ndp-scaled
    input cotangents — same training math, smaller per-device batch.
    """
    n_stages = mesh.shape[axis_name]
    if cfg.n_layers % (n_stages * n_chunks):
        raise ValueError(f"n_layers={cfg.n_layers} not divisible by "
                         f"{n_stages} stages x {n_chunks} chunks")
    moe = cfg.n_experts > 0
    if moe and n_chunks > 1 and ep_axis is not None:
        raise NotImplementedError(
            "interleaved (n_chunks > 1) MoE is stage-local only; ep "
            "sharding composes with the plain 1F1B schedule")
    if ep_axis is not None and not moe:
        raise ValueError("ep_axis given but cfg.n_experts == 0")
    attn = resolve_attn_fn(cfg, attn_fn)

    if moe and ep_axis is not None:
        from .moe import sharded_switch_moe

        def moe_fn(x, router_w, w_in, w_out, w_gate=None):
            # Already inside the pipeline's shard_map: the ep axis is
            # live, w_in/w_out (and swiglu w_gate) leaves are the local
            # [E/ep, D, F] shard.
            return sharded_switch_moe(
                x, router_w, w_in, w_out, ep_axis, w_gate=w_gate,
                capacity_factor=cfg.moe_capacity_factor, k=cfg.moe_top_k)
    else:
        moe_fn = None  # decoder_layer defaults to stage-local switch_moe

    def run_layers(local, h):
        """Scan ``h`` through a [L_local, ...] slice of the layer tree.
        MoE: also return the slice's balance aux, scaled to llama.py
        loss_fn's semantics (coef * sum / n_layers) so stage aux terms
        sum to the sequential loss's term."""
        cos, sin = cfg_rope_tables(cfg, h.shape[1])

        def body(carry, lp):
            hh, aux = carry
            hh, a, _kv, _stats = decoder_layer(lp, hh, cfg, cos, sin,
                                               attn, moe_fn=moe_fn)
            return (hh, aux + a), None

        (h, aux), _ = lax.scan(body, (h, jnp.zeros((), jnp.float32)), local)
        if moe:
            return h, aux * (cfg.moe_aux_coef / cfg.n_layers)
        return h

    def stage_fn(stage_lp, h):
        # Inside shard_map the stage tree keeps a leading local dim of 1
        # ([1, L/S, ...]); peel it so the scan runs over this stage's L/S
        # layers (vjp through the indexing restores the dim on gradients).
        return run_layers(jax.tree_util.tree_map(lambda a: a[0], stage_lp), h)

    def chunk_fn(chunk_lp, h):
        # Interleaved path: the schedule's chunk_params already peeled the
        # leading dims -- chunk_lp leaves are [L/(V*S), ...].
        return run_layers(chunk_lp, h)

    def loss_fn(head, y, target):
        logits = head_logits(y, head["final_norm"], head["lm_head"],
                             cfg.norm_eps, cfg.norm_zero_centred)
        return token_ce(logits, target)

    if n_chunks > 1:
        from ..parallel.interleaved import make_interleaved_pipeline_train

        grad_step = make_interleaved_pipeline_train(
            mesh, chunk_fn, loss_fn, axis_name, n_chunks=n_chunks,
            n_micro=n_micro, with_head=True, return_dx=True,
            dp_axis=dp_axis, with_aux=moe)
    else:
        if moe:
            # Specs for leaves sharded beyond the stage dim (expert
            # tables over ep) ride through to shard_map; the expert mask
            # drives the ep-aware gradient reduction.  Built from a
            # shape-only template tree (leaf VALUES are ignored).
            template = _moe_stage_template(cfg)
            kw = {"with_aux": True}
            if ep_axis is not None:
                kw.update(
                    ep_axis=ep_axis,
                    expert_spec=_expert_leaf_spec(template),
                    param_specs=pp_stage_specs(template, axis_name, ep_axis))
        else:
            kw = {}
        grad_step = make_pipeline_train(mesh, stage_fn, loss_fn, axis_name,
                                        with_head=True, return_dx=True,
                                        dp_axis=dp_axis, **kw)

    def step(pp_params, batch):
        tokens, targets = batch[:, :-1], batch[:, 1:]
        B, S = tokens.shape
        if B % n_micro:
            raise ValueError(f"batch {B} not divisible by n_micro={n_micro}")
        mb = B // n_micro
        n_data = 1
        for a in (dp_axis, ep_axis):
            if a is not None:
                n_data *= mesh.shape[a]
        if mb % n_data:
            raise ValueError(
                f"microbatch rows ({mb} = {B}/{n_micro}) not divisible by "
                f"the data-sharding size {n_data} (dp x ep)")
        D = pp_params["embed"].shape[1]

        h0 = embed_tokens(pp_params, tokens, cfg).reshape(n_micro, mb, S, D)
        tgt = targets.reshape(n_micro, mb, S)
        loss, dstages, dhead, dh0 = grad_step(
            pp_params["stages"], pp_params["head"], h0, tgt)

        # Chain the input cotangent into the embedding table: scatter-add
        # d h0 over the token ids (B*S rows; reshape orders match h0's).
        # embed_tokens scales h0 by sqrt(D) on scaled_embed configs
        # (Gemma), so the chain rule carries the same factor back.
        dh0 = dh0.reshape(-1, D)
        if cfg.scaled_embed:
            dh0 = dh0 * (D ** 0.5)
        dembed = jnp.zeros(pp_params["embed"].shape, jnp.float32).at[
            tokens.reshape(-1)].add(dh0)

        grads = {"embed": dembed, "stages": dstages, "head": dhead}
        return loss, grads

    return jax.jit(step)
