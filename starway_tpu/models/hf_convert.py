"""HuggingFace Llama-family checkpoint -> starway-tpu parameter tree.

Bridges the ecosystem's weights into this framework — seven served
families: ``transformers.LlamaForCausalLM``, ``MistralForCausalLM``
(sliding-window attention -> ``LlamaConfig.sliding_window``),
``Qwen2ForCausalLM`` (q/k/v projection biases ->
``cfg.attn_bias``/``bq``/``bk``/``bv`` leaves), ``MixtralForCausalLM``
(SwiGLU top-2 MoE experts -> ``cfg.moe_swiglu``, dropless conversion
capacity), ``GemmaForCausalLM`` (GeGLU -> ``cfg.mlp_act``, the
(1 + w) RMSNorm convention folded into the converted weights,
sqrt(d_model)-scaled embeddings -> ``cfg.scaled_embed``),
``Phi3ForCausalLM`` (fused ``qkv_proj``/``gate_up_proj`` row-sliced into
separate projections at conversion), and the DeepSeek-V3 block of
``deepseek_v3`` / ``kimi_k2`` (latent attention -> ``cfg.latent``,
sigmoid-routed experts beside a shared one after leading dense layers ->
``cfg.routed`` and a tuple of layer segments; the published interleaved
rope columns permuted into split halves) — all into the
stacked-layer pytree ``models/llama.py`` trains and serves;
``config_from_hf`` derives the matching :class:`LlamaConfig`, including
modern variants with decoupled ``head_dim`` and linear/llama3
``rope_scaling``.

Convention notes (why this is transpose-and-stack, not surgery):

* HF's ``apply_rotary_pos_emb`` uses the rotate-half (NeoX / split-half)
  convention — the same one ``llama.apply_rope`` implements — so q/k
  projections carry over with NO column permutation.  (Meta's original
  release uses interleaved pairs; HF already permuted at import, and
  loading a Meta-native checkpoint still requires that permutation, as
  documented on ``apply_rope``.)
* HF ``nn.Linear`` stores ``[out, in]``; this tree stores ``[in, out]`` —
  every projection transposes.
* HF models may tie ``lm_head`` to the embedding; the converter follows
  ``get_output_embeddings``/falls back to the tied table.

Numerical parity with ``LlamaForCausalLM`` forward is pinned by
tests/test_hf_convert.py on a tiny random model.
"""

from __future__ import annotations

from typing import Any

import numpy as np

from .llama import LlamaConfig


def config_from_hf(hf_config: Any, **overrides) -> LlamaConfig:
    """LlamaConfig from a ``transformers.LlamaConfig``-shaped object.

    Refuses configs this model family cannot represent — silently dropping
    them would produce a numerically wrong model (the failure mode this
    module exists to prevent)."""
    if getattr(hf_config, "mlp_bias", False):
        raise NotImplementedError(
            "MLP biases are not represented in this parameter tree")
    model_type = getattr(hf_config, "model_type", "")
    if model_type in ("deepseek_v3", "kimi_k2"):
        return _latent_moe_config(hf_config, **overrides)
    if model_type in ("gemma2", "gemma3", "gemma3_text"):
        # Must precede the activation check, or these fall into the
        # generic hidden_act error with a misleading message.
        raise NotImplementedError(
            f"{model_type} adds logit soft-capping and pre/post "
            "feed-forward norms this tree does not represent; gemma (v1) "
            "converts")
    act = (getattr(hf_config, "hidden_activation", None)
           or getattr(hf_config, "hidden_act", "silu"))
    if act in ("silu", "swish"):
        mlp_act = "silu"
    elif act in ("gelu_pytorch_tanh", "gelu_tanh") and model_type == "gemma":
        mlp_act = "gelu_tanh"  # Gemma's GeGLU
    else:
        raise NotImplementedError(
            f"hidden_act={act!r} on model_type={model_type!r}; this family "
            "is gated-MLP with silu (Llama) or gelu_tanh (Gemma)")
    # Qwen2-family checkpoints attach q/k/v biases (cfg.attn_bias ->
    # bq/bk/bv leaves; Qwen2's o_proj carries NO bias, so the tree is
    # complete).  A generic attention_bias=True config is a DIFFERENT
    # shape: HF Llama then puts a bias on o_proj too, which this tree
    # does not represent — refuse rather than silently drop it.
    attn_bias = model_type == "qwen2"
    if getattr(hf_config, "attention_bias", False) and not attn_bias:
        raise NotImplementedError(
            "attention_bias=True on a non-Qwen2 config also biases o_proj, "
            "which this parameter tree does not represent; converting "
            "would silently drop it")
    # Qwen2 gates its sliding_window on use_sliding_window (default
    # False), and even then windows only the layers PAST
    # max_window_layers — a mixed pattern cfg.sliding_window (global)
    # cannot express.  Honour the gate; refuse the mixed case.
    sliding = getattr(hf_config, "sliding_window", None)
    if sliding is not None and hasattr(hf_config, "use_sliding_window"):
        mwl = getattr(hf_config, "max_window_layers", 0) or 0
        if not hf_config.use_sliding_window:
            sliding = None
        elif mwl >= hf_config.num_hidden_layers:
            sliding = None  # "first mwl layers full" covers every layer
        elif mwl > 0:
            raise NotImplementedError(
                f"use_sliding_window with max_window_layers={mwl} windows "
                f"only layers past it; this config represents a single "
                "global sliding_window")
    prf = getattr(hf_config, "partial_rotary_factor", None)
    if prf is not None and float(prf) != 1.0:
        raise NotImplementedError(
            f"partial_rotary_factor={prf} rotates only part of each head; "
            "this tree applies rope to the full head dim")
    # Newer HF configs may pin an explicit per-head dim decoupled from
    # hidden_size // num_attention_heads; llama.py keys every
    # projection/reshape off cfg.head_dim, so the override carries it.
    explicit_hd = getattr(hf_config, "head_dim", None)
    derived_hd = (hf_config.hidden_size // hf_config.num_attention_heads
                  if hf_config.hidden_size % hf_config.num_attention_heads == 0
                  else None)
    if explicit_hd is None and derived_hd is None:
        raise NotImplementedError(
            f"hidden_size={hf_config.hidden_size} is not divisible by "
            f"num_attention_heads={hf_config.num_attention_heads} and the "
            "config pins no explicit head_dim")
    kw = dict(
        vocab_size=hf_config.vocab_size,
        d_model=hf_config.hidden_size,
        n_layers=hf_config.num_hidden_layers,
        n_heads=hf_config.num_attention_heads,
        n_kv_heads=getattr(hf_config, "num_key_value_heads",
                           hf_config.num_attention_heads),
        d_ff=hf_config.intermediate_size,
        rope_theta=float(getattr(hf_config, "rope_theta", 10000.0)),
        norm_eps=float(getattr(hf_config, "rms_norm_eps", 1e-5)),
        # Mistral-family configs carry sliding_window; same architecture
        # otherwise, so the converter serves both families.
        sliding_window=sliding,
        attn_bias=attn_bias,
        head_dim_override=(explicit_hd if explicit_hd is not None
                           and explicit_hd != derived_hd else None),
        rope_scaling=_rope_scaling_from_hf(
            getattr(hf_config, "rope_scaling", None),
            getattr(hf_config, "max_position_embeddings", None),
            getattr(hf_config, "original_max_position_embeddings", None)),
        mlp_act=mlp_act,
        # Gemma scales the embedding OUTPUT by sqrt(d_model); the tied
        # lm_head reads the raw table, so it is a runtime flag, not a
        # weight fold.
        scaled_embed=model_type == "gemma",
    )
    if model_type == "mixtral":
        # Mixtral: SwiGLU experts, top-k routing with softmax-then-topk
        # renormalisation — exactly moe.py's _route.  HF routes dropless;
        # capacity_factor = n_experts makes our static capacity provably
        # dropless (capacity = T * k) so converted models match
        # transformers token for token.  Lower it for capacity-bound
        # training throughput at the cost of that guarantee.
        kw.update(
            n_experts=hf_config.num_local_experts,
            moe_top_k=hf_config.num_experts_per_tok,
            moe_swiglu=True,
            moe_capacity_factor=float(hf_config.num_local_experts),
            moe_aux_coef=float(getattr(hf_config, "router_aux_loss_coef",
                                       0.001)),
        )
    kw.update(overrides)
    return LlamaConfig(**kw)


def _latent_moe_config(hf_config: Any, held_experts=None,
                       **overrides) -> LlamaConfig:
    """The DeepSeek-V3 block (``deepseek_v3``, ``kimi_k2``): latent
    attention and sigmoid-routed experts beside shared ones after
    ``first_k_dense_replace`` dense layers.  ``held_experts = (first,
    count)``: the share of the routed experts this holder computes (all of
    them by default)."""
    import math

    from .llama import LatentAttn, RoutedFFN

    c = hf_config
    if getattr(c, "attention_bias", False):
        raise NotImplementedError("attention_bias on a latent-attention "
                                  "config is not represented in this tree")
    if getattr(c, "hidden_act", "silu") not in ("silu", "swish"):
        raise NotImplementedError(f"hidden_act={c.hidden_act!r}")
    if getattr(c, "scoring_func", "sigmoid") != "sigmoid" or (
            getattr(c, "n_group", 1) != 1 or getattr(c, "topk_group", 1) != 1):
        raise NotImplementedError(
            "the routed FFN scores by sigmoid and selects over all experts "
            "(n_group = topk_group = 1, Kimi-K2); softmax scoring and the "
            "group-limited selection of DeepSeek-V3 are not implemented")
    if not getattr(c, "norm_topk_prob", True) or getattr(
            c, "moe_layer_freq", 1) != 1 or not getattr(c, "q_lora_rank", None):
        raise NotImplementedError(
            "needs norm_topk_prob, moe_layer_freq 1 and a low-rank q")
    scaling = _rope_scaling_from_hf(
        getattr(c, "rope_scaling", None),
        getattr(c, "max_position_embeddings", None))
    rs = getattr(c, "rope_scaling", None) or {}
    mscale = 1.0
    if rs.get("mscale_all_dim"):  # the scores carry its square
        mscale = 0.1 * rs["mscale_all_dim"] * math.log(rs["factor"]) + 1.0
    first, count = held_experts or (0, c.n_routed_experts)
    kw = dict(
        vocab_size=c.vocab_size, d_model=c.hidden_size,
        n_layers=c.num_hidden_layers, n_heads=c.num_attention_heads,
        n_kv_heads=c.num_attention_heads, d_ff=c.intermediate_size,
        rope_theta=float(c.rope_theta), norm_eps=float(c.rms_norm_eps),
        rope_scaling=scaling,
        latent=LatentAttn(
            q_rank=c.q_lora_rank, kv_rank=c.kv_lora_rank,
            nope_dim=c.qk_nope_head_dim, rope_dim=c.qk_rope_head_dim,
            v_dim=c.v_head_dim,
            sm_scale=(c.qk_nope_head_dim + c.qk_rope_head_dim) ** -0.5
            * mscale * mscale),
        routed=RoutedFFN(
            n_experts=c.n_routed_experts, top_k=c.num_experts_per_tok,
            d_expert=c.moe_intermediate_size, n_held=count, first_held=first,
            n_shared=c.n_shared_experts,
            scale=float(c.routed_scaling_factor),
            first_dense=c.first_k_dense_replace))
    kw.update(overrides)
    return LlamaConfig(**kw)


def rope_rows_to_halves(w: np.ndarray, starts, width: int) -> np.ndarray:
    """Rows ``start .. start + width`` (for every start in ``starts``) of
    an HF ``[out, in]`` projection from the published INTERLEAVED rope
    order (pairs (0,1), (2,3), ...) into this package's split halves
    (evens, then odds): the permutation the published
    ``apply_rotary_pos_emb`` makes at run time.  One gather for all of a
    matrix's heads."""
    order = np.concatenate([np.arange(0, width, 2), np.arange(1, width, 2)])
    rows = np.arange(w.shape[0])
    for start in np.atleast_1d(starts):
        rows[start:start + width] = start + order
    return w[rows]


def _latent_moe_params(get, cfg: LlamaConfig, dt) -> tuple:
    """The segments of a DeepSeek-V3 / Kimi-K2 state dict: the leading
    dense layers, then the routed ones with the HELD experts stacked.  The
    checkpoints' block-quantised fp8 weights are not handled: dequantise
    first."""
    import jax.numpy as jnp

    la, r, H = cfg.latent, cfg.routed, cfg.n_heads
    qk = la.nope_dim + la.rope_dim

    def wq_b(i):
        return rope_rows_to_halves(
            _np(get(f"layers.{i}.self_attn.q_b_proj.weight")),
            np.arange(H) * qk + la.nope_dim, la.rope_dim).T

    def wkv_a(i):
        return rope_rows_to_halves(
            _np(get(f"layers.{i}.self_attn.kv_a_proj_with_mqa.weight")),
            la.kv_rank, la.rope_dim).T

    def mlp(name, i):
        return {ours: _t(get(f"layers.{i}.{name}.{theirs}.weight"))
                for ours, theirs in (("w_gate", "gate_proj"),
                                     ("w_up", "up_proj"),
                                     ("w_down", "down_proj"))}

    def layer(i, routed: bool) -> dict:
        at = f"layers.{i}.self_attn."
        out = {
            "attn_norm": _np(get(f"layers.{i}.input_layernorm.weight")),
            "mlp_norm": _np(get(f"layers.{i}.post_attention_layernorm.weight")),
            "wq_a": _t(get(at + "q_a_proj.weight")),
            "q_norm": _np(get(at + "q_a_layernorm.weight")),
            "wq_b": wq_b(i), "wkv_a": wkv_a(i),
            "kv_norm": _np(get(at + "kv_a_layernorm.weight")),
            "wkv_b": _t(get(at + "kv_b_proj.weight")),
            "wo": _t(get(at + "o_proj.weight")),
        }
        if not routed:
            out.update(mlp("mlp", i))
            return out
        held = [mlp(f"mlp.experts.{e}", i)
                for e in range(r.first_held, r.first_held + r.n_held)]
        out["routed"] = {
            "router": _t(get(f"layers.{i}.mlp.gate.weight")),
            "bias": _np(get(f"layers.{i}.mlp.gate.e_score_correction_bias")),
            **{n: np.stack([h[n] for h in held]) for n in held[0]},
            "shared": mlp("mlp.shared_experts", i)}
        return out

    def stacked(lo, hi, routed):
        import jax

        rows = [layer(i, routed) for i in range(lo, hi)]
        return jax.tree_util.tree_map(
            lambda *xs: jnp.asarray(np.stack(xs), dt), *rows)

    segs = [stacked(lo, hi, routed) for lo, hi, routed in (
        (0, r.first_dense, False), (r.first_dense, cfg.n_layers, True))
        if hi > lo]
    for seg in segs:  # the selection bias stays float32 (the router's type)
        if "routed" in seg:
            seg["routed"]["bias"] = seg["routed"]["bias"].astype(jnp.float32)
    return tuple(segs)


def _rope_scaling_from_hf(scaling, max_position_embeddings=None,
                          original_max_position_embeddings=None) -> "tuple | None":
    """HF ``rope_scaling`` dict -> LlamaConfig's hashable tuple.

    Implemented kinds: ``linear`` (position interpolation), ``llama3``
    (the Llama-3.1 banded scheme), ``yarn`` (NTK-by-parts,
    Qwen2.5-long / DeepSeek-family), and ``longrope`` (per-dim factor
    lists, Phi-3.5/128k line; see llama.py:rope_tables).  yarn's
    ``attention_factor`` is resolved HERE, HF-identically — explicit
    value wins, then the mscale/mscale_all_dim ratio (DeepSeek), then
    the paper default ``0.1*ln(factor)+1`` — so the config tuple carries
    one final float.  Anything else (dynamic, ...) still refuses —
    silently dropping a scaling scheme would change the rope
    frequencies vs transformers, the exact failure mode this module
    exists to prevent."""
    if not scaling:
        return None
    kind = scaling.get("rope_type", scaling.get("type"))
    if kind == "linear":
        return ("linear", float(scaling["factor"]))
    if kind == "llama3":
        return ("llama3", float(scaling["factor"]),
                float(scaling["low_freq_factor"]),
                float(scaling["high_freq_factor"]),
                float(scaling["original_max_position_embeddings"]))
    if kind == "yarn":
        import math

        factor = float(scaling["factor"])
        att = scaling.get("attention_factor")
        mscale = scaling.get("mscale")
        mscale_all_dim = scaling.get("mscale_all_dim")

        def get_mscale(scale, m=1.0):
            return 1.0 if scale <= 1 else 0.1 * m * math.log(scale) + 1.0

        if att is None:
            if mscale and mscale_all_dim:
                att = get_mscale(factor, mscale) / get_mscale(
                    factor, mscale_all_dim)
            else:
                att = get_mscale(factor)
        orig = (scaling.get("original_max_position_embeddings")
                or max_position_embeddings)
        if orig is None:
            raise ValueError(
                "yarn rope_scaling needs original_max_position_embeddings "
                "(in the scaling dict or the model config)")
        return ("yarn", factor, float(orig),
                float(scaling.get("beta_fast") or 32),
                float(scaling.get("beta_slow") or 1),
                float(att), bool(scaling.get("truncate", True)))
    if kind == "longrope":
        import math

        short = tuple(float(x) for x in scaling["short_factor"])
        long = tuple(float(x) for x in scaling["long_factor"])
        # HF: Phi3-style configs carry original_max_position_embeddings
        # at the CONFIG level and derive factor from the max/orig ratio;
        # otherwise the scaling dict's factor applies and orig = max.
        orig = original_max_position_embeddings
        if orig:
            factor = float(max_position_embeddings) / float(orig)
        else:
            orig = max_position_embeddings
            factor = scaling.get("factor")
        if orig is None or factor is None:
            raise ValueError(
                "longrope rope_scaling needs original_max_position_"
                "embeddings (config level) or an explicit factor")
        att = scaling.get("attention_factor")
        if att is None:
            att = (1.0 if factor <= 1.0
                   else math.sqrt(1.0 + math.log(factor) / math.log(orig)))
        # NOTE: the regime (short vs long factors) is chosen per rope
        # TABLE by its seq_len (llama.py:rope_tables).  A generation
        # whose horizon crosses orig uses one regime for the whole run;
        # HF switches per step on such runs and diverges there.
        return ("longrope", float(orig), float(att), short, long)
    if kind == "default":
        # transformers normalises "no scaling" configs to
        # {"rope_type": "default"} in some versions.
        return None
    raise NotImplementedError(
        f"rope_scaling={scaling!r} is not implemented here (linear, "
        "llama3, yarn, and longrope are); converting would silently "
        "change the rope frequencies vs transformers")


def _norm_w(w, plus_one: bool) -> np.ndarray:
    """RMSNorm weight, with Gemma's ``x̂ * (1 + w)`` convention folded to
    ``w' = 1 + w`` so the framework's plain ``x̂ * w`` is exact (the
    addition runs in f32 before the dtype cast, matching HF's f32 norm
    math)."""
    w = _np(w)
    return w + 1.0 if plus_one else w


def _t(w) -> np.ndarray:
    """torch/np tensor -> f32 numpy, transposed ([out, in] -> [in, out])."""
    return _np(w).T


def _np(w) -> np.ndarray:
    if hasattr(w, "detach"):
        w = w.detach().cpu().float().numpy()
    return np.asarray(w, dtype=np.float32)


def params_from_hf(model_or_state: Any, cfg: LlamaConfig, dtype=None, *,
                   quantize: str = "none",
                   norm_plus_one: "bool | None" = None) -> dict:
    """Convert a ``LlamaForCausalLM`` (or its ``state_dict()``) into this
    framework's stacked-layer parameter pytree, cast to ``dtype`` (default:
    ``cfg.compute_dtype``).

    Each leaf is cast and committed to jax AS it is converted, so peak host
    memory is the source checkpoint plus one stacked leaf's f32 scratch —
    not a second full-tree copy.

    ``quantize="int8"``: return the W8A16 serving tree
    (ops/quantize.py:quantize_params applied after conversion) — every
    matmul weight as per-output-channel int8 + scales, half the weight
    HBM, inference-only (see models/llama.py:matmul_w).

    ``norm_plus_one``: Gemma computes RMSNorm as ``x̂ * (1 + w)`` with
    zero-init weights; the fold ``w' = 1 + w`` at conversion makes the
    framework's plain ``x̂ * w`` norm exact with NO runtime flag.
    Defaults to ``cfg.scaled_embed`` (the Gemma marker config_from_hf
    sets), so Gemma state DICTS fold correctly too."""
    import jax.numpy as jnp

    if quantize not in ("none", "int8"):
        # Before the conversion work, not after.
        raise ValueError(f"quantize must be 'none' or 'int8', got {quantize!r}")
    if norm_plus_one is None:
        # cfg.scaled_embed is set by config_from_hf exactly for Gemma —
        # keying off the passed cfg (not model_or_state.config, absent on
        # raw state dicts) keeps dict conversions correct by default.
        norm_plus_one = cfg.scaled_embed
    if hasattr(model_or_state, "state_dict"):
        state = {k: v for k, v in model_or_state.state_dict().items()}
    else:
        state = dict(model_or_state)
    # Accept both bare-LlamaModel ("model.layers...") and ForCausalLM keys.
    prefix = "model." if any(k.startswith("model.") for k in state) else ""

    dt = jnp.dtype(dtype) if dtype is not None else cfg.compute_dtype

    def get(name):
        return state[prefix + name]

    if cfg.latent is not None:
        if quantize != "none":
            raise NotImplementedError("the W8A16 tree has no latent leaves")
        segs = _latent_moe_params(get, cfg, dt)
        return {"embed": jnp.asarray(_np(get("embed_tokens.weight")), dt),
                "layers": segs[0] if len(segs) == 1 else segs,
                "final_norm": jnp.asarray(_np(get("norm.weight")), dt),
                "lm_head": jnp.asarray(_t(state["lm_head.weight"]), dt)}
    L = cfg.n_layers
    stack = lambda fn: jnp.asarray(np.stack([fn(i) for i in range(L)]), dt)
    fused = prefix + "layers.0.self_attn.qkv_proj.weight" in state
    if fused:
        if (prefix + "layers.0.self_attn.qkv_proj.bias" in state
                or prefix + "layers.0.mlp.gate_up_proj.bias" in state):
            # Same loud-refusal contract as the split-projection bias
            # probes below: silently dropping a bias is a wrong model.
            raise NotImplementedError(
                "fused qkv_proj/gate_up_proj biases are not represented "
                "in this parameter tree; converting would silently drop "
                "them")
        # Phi-3 family: one fused qkv_proj [(Hq + 2*Hkv) * hd, D] — slice
        # the OUT rows (HF [out, in]) into q/k/v before the transpose.
        # Convert each fused tensor to f32 numpy ONCE and slice the cached
        # copy (three fresh .float().numpy() copies per layer would 3x the
        # conversion scratch the module docstring bounds).
        nq = cfg.n_heads * cfg.head_dim
        nkv = cfg.n_kv_heads * cfg.head_dim

        def qkv_split(i):
            w = _np(get(f"layers.{i}.self_attn.qkv_proj.weight"))
            # .copy(): a view would pin the whole fused matrix until the
            # final stack (L of them at once).
            return (w[0:nq].T.copy(), w[nq:nq + nkv].T.copy(),
                    w[nq + nkv:nq + 2 * nkv].T.copy())

        qkv = [qkv_split(i) for i in range(L)]
        wq = jnp.asarray(np.stack([q for q, _, _ in qkv]), dt)
        wk = jnp.asarray(np.stack([k for _, k, _ in qkv]), dt)
        wv = jnp.asarray(np.stack([v for _, _, v in qkv]), dt)
        del qkv
    else:
        wq = stack(lambda i: _t(get(f"layers.{i}.self_attn.q_proj.weight")))
        wk = stack(lambda i: _t(get(f"layers.{i}.self_attn.k_proj.weight")))
        wv = stack(lambda i: _t(get(f"layers.{i}.self_attn.v_proj.weight")))
    layers = {
        "wq": wq,
        "wk": wk,
        "wv": wv,
        "wo": stack(lambda i: _t(get(f"layers.{i}.self_attn.o_proj.weight"))),
        "attn_norm": stack(lambda i: _norm_w(
            get(f"layers.{i}.input_layernorm.weight"), norm_plus_one)),
        "mlp_norm": stack(lambda i: _norm_w(
            get(f"layers.{i}.post_attention_layernorm.weight"),
            norm_plus_one)),
    }
    if prefix + "layers.0.block_sparse_moe.gate.weight" in state:
        # Mixtral: gate -> router [D, E]; per-expert SwiGLU maps
        # w1 -> w_gate, w3 -> w_in, w2 -> w_out (all [out, in] -> [in, out]
        # transposes), stacked to [L, E, ...].
        E = cfg.n_experts

        def estack(which):
            return jnp.asarray(np.stack([
                np.stack([_t(get(f"layers.{i}.block_sparse_moe.experts."
                              f"{e}.{which}.weight")) for e in range(E)])
                for i in range(L)]), dt)

        layers["moe"] = {
            "router": stack(
                lambda i: _t(get(f"layers.{i}.block_sparse_moe.gate.weight"))),
            "w_gate": estack("w1"),
            "w_in": estack("w3"),
            "w_out": estack("w2"),
        }
    elif fused:
        # Phi-3's fused gate_up_proj [2F, D]: first F rows gate, last F up
        # (Phi3MLP chunks dim -1 after the matmul, gate first).  One f32
        # conversion per layer, sliced cached.
        F = cfg.d_ff

        def gu_split(i):
            w = _np(get(f"layers.{i}.mlp.gate_up_proj.weight"))
            return w[:F].T.copy(), w[F:2 * F].T.copy()

        gu = [gu_split(i) for i in range(L)]
        layers.update(
            w_gate=jnp.asarray(np.stack([g for g, _ in gu]), dt),
            w_up=jnp.asarray(np.stack([u for _, u in gu]), dt),
            w_down=stack(
                lambda i: _t(get(f"layers.{i}.mlp.down_proj.weight"))),
        )
        del gu
    else:
        layers.update(
            w_gate=stack(lambda i: _t(get(f"layers.{i}.mlp.gate_proj.weight"))),
            w_up=stack(lambda i: _t(get(f"layers.{i}.mlp.up_proj.weight"))),
            w_down=stack(
                lambda i: _t(get(f"layers.{i}.mlp.down_proj.weight"))),
        )
    if prefix + "layers.0.self_attn.o_proj.bias" in state:
        # config_from_hf refuses these configs; a raw state dict can still
        # reach here — same refusal, same reason.
        raise NotImplementedError(
            "o_proj carries a bias, which this parameter tree does not "
            "represent; converting would silently drop it")
    if prefix + "layers.0.self_attn.q_proj.bias" in state:
        # Qwen2 family: per-head projection biases (qkv_proj keys off the
        # leaves' presence; HF bias vectors are [out] — no transpose).
        layers.update(
            bq=stack(lambda i: _np(get(f"layers.{i}.self_attn.q_proj.bias"))),
            bk=stack(lambda i: _np(get(f"layers.{i}.self_attn.k_proj.bias"))),
            bv=stack(lambda i: _np(get(f"layers.{i}.self_attn.v_proj.bias"))),
        )
    embed = jnp.asarray(_np(get("embed_tokens.weight")), dt)
    if "lm_head.weight" in state:
        lm_head = jnp.asarray(_t(state["lm_head.weight"]), dt)
    else:  # tied embeddings
        lm_head = embed.T
    params = {
        "embed": embed,
        "layers": layers,
        "final_norm": jnp.asarray(
            _norm_w(get("norm.weight"), norm_plus_one), dt),
        "lm_head": lm_head,
    }
    if quantize == "int8":
        from ..ops.quantize import quantize_params

        return quantize_params(params)
    return params
