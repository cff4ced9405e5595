"""Seeded random weights of a decoder whose layers are gated delta-rule
linear attention (KDA) or NoPE latent attention by a published list, over a
leading dense layer and then sigmoid-routed experts beside a shared one
(Kimi-Linear), made ON THE DEVICE in the type they are served in.  After
``weights_mla_moe.py``, with the same rules: the benchmark makes the
weights, the served tree and the plain reference are both built from these
functions, one layer's weights depend on (seed, layer) alone and an
expert's on (seed, layer, EXPERT ID) alone, so any share of a layer's
experts holds exactly the numbers the whole layer would.

What the source does not fix is ASSUMED here and listed in the
configuration's file: ``a_log = log U(1, 16)`` a head and ``dt_bias`` the
inverse softplus of a step drawn log-uniform from [0.001, 0.1] a channel
(the selective-state-space convention the gate's names come from), the
convolutions' taps scaled normal without a bias.
"""

from __future__ import annotations

import math

import jax
import jax.numpy as jnp
from jax import lax

from benchmark.harness.weights import _norm_weight, _normal, base_key  # noqa: F401
from benchmark.harness.weights_mla_moe import (_mlp, expert_weights,  # noqa: F401
                                               outer_weights)


def dims(config: dict) -> dict:
    """Sizes from the configuration file's own (Hugging Face) keys.  Which
    layers are KDA and which latent is READ from ``linear_attn_config``
    (1-based lists, as published); the router keeps the PUBLISHED expert
    count, ``held`` of them live here."""
    lin = config["linear_attn_config"]
    n = config["num_hidden_layers"]
    kda, full = set(lin["kda_layers"]), set(lin["full_attn_layers"])
    if any((i in kda) == (i in full) for i in range(1, n + 1)):
        raise ValueError("linear_attn_config must name every layer once")
    nope, rope = config["qk_nope_head_dim"], config["qk_rope_head_dim"]
    held = config["num_experts"]
    if config["q_lora_rank"] is not None or config["rope_scaling"] is not None \
            or not config["mla_use_nope"]:
        raise ValueError("this kind has a direct q projection and NoPE "
                         "latent layers (q_lora_rank / rope_scaling null, "
                         "mla_use_nope true)")
    return {
        "D": config["hidden_size"], "H": config["num_attention_heads"],
        "kv_rank": config["kv_lora_rank"], "nope": nope, "rope": rope,
        "v": config["v_head_dim"], "sm_scale": (nope + rope) ** -0.5,
        "Hl": lin["num_heads"], "dl": lin["head_dim"],
        "taps": lin["short_conv_kernel_size"],
        "linear": tuple(i in kda for i in range(1, n + 1)),
        "F": config["intermediate_size"], "Fe": config["moe_intermediate_size"],
        "E": config.get("num_experts_published", held), "held": held,
        "first_held": config.get("expert_share", 0) * held,
        "top_k": config["num_experts_per_token"],
        "n_shared": config["num_shared_experts"],
        "route_scale": config["routed_scaling_factor"],
        "first_dense": config["first_k_dense_replace"],
        "V": config["vocab_size"], "L": n, "eps": config["rms_norm_eps"],
        "dtype": config.get("torch_dtype", "bfloat16"),
    }


def kda_weights(ks, d: dict) -> dict:
    """One KDA layer's own leaves (models/kda.py names them)."""
    dtype = jnp.dtype(d["dtype"])
    D, w, dl = d["D"], d["Hl"] * d["dl"], d["dl"]
    step = jnp.exp(jax.random.uniform(ks[6], (w,), jnp.float32,
                                      math.log(0.001), math.log(0.1)))
    return {
        # W_q | W_k | W_v side by side: the program's one matmul.
        "wqkv": _normal(ks[0], (D, 3 * w), D ** -0.5, dtype),
        "conv": _normal(ks[3], (d["taps"], 3 * w), d["taps"] ** -0.5, dtype),
        "w_fa": _normal(ks[4], (D, dl), D ** -0.5, dtype),
        "w_fb": _normal(ks[5], (dl, w), dl ** -0.5, dtype),
        "dt_bias": step + jnp.log(-jnp.expm1(-step)),     # softplus^-1(step)
        "a_log": jnp.log(jax.random.uniform(ks[7], (d["Hl"],), jnp.float32,
                                            1.0, 16.0)),
        "w_beta": _normal(ks[8], (D, d["Hl"]), D ** -0.5, dtype),
        "w_ga": _normal(ks[9], (D, dl), D ** -0.5, dtype),
        "w_gb": _normal(ks[10], (dl, w), dl ** -0.5, dtype),
        "o_norm": _norm_weight(ks[11], dl, dtype),
    }


def layer_weights(key, i, d: dict, linear: bool, routed: bool) -> dict:
    """Layer ``i`` (0-based): KDA or latent attention, and a dense MLP or
    (``routed``) the router over all ``E`` experts, its selection bias, the
    held experts stacked and the shared expert."""
    dtype = jnp.dtype(d["dtype"])
    ks = jax.random.split(jax.random.fold_in(key, i), 32)
    D, H = d["D"], d["H"]
    out = {"attn_norm": _norm_weight(ks[0], D, dtype),
           "mlp_norm": _norm_weight(ks[1], D, dtype)}
    if linear:
        w = d["Hl"] * d["dl"]
        out["kda"] = kda_weights(ks[16:28], d)
        out["wo"] = _normal(ks[8], (w, D), w ** -0.5, dtype)
    else:
        out.update(
            wq=_normal(ks[2], (D, H * (d["nope"] + d["rope"])), D ** -0.5, dtype),
            wkv_a=_normal(ks[5], (D, d["kv_rank"] + d["rope"]), D ** -0.5, dtype),
            kv_norm=_norm_weight(ks[6], d["kv_rank"], dtype),
            wkv_b=_normal(ks[7], (d["kv_rank"], H * (d["nope"] + d["v"])),
                          d["kv_rank"] ** -0.5, dtype),
            wo=_normal(ks[8], (H * d["v"], D), (H * d["v"]) ** -0.5, dtype))
    if not routed:
        out.update(_mlp(ks[9:12], (), D, d["F"], dtype))
        return out
    held = d["first_held"] + jnp.arange(d["held"])
    out["routed"] = {
        "router": _normal(ks[9], (D, d["E"]), D ** -0.5, dtype),
        # Small and not zero: weights_mla_moe.py says why.
        "bias": 0.005 * jax.random.normal(ks[10], (d["E"],), jnp.float32),
        **lax.map(lambda e: expert_weights(key, i, e, d), held),
        "shared": _mlp(ks[11:14], (), D, d["n_shared"] * d["Fe"], dtype),
    }
    return out


def segment_plan(d: dict) -> list:
    """``[(first layer, layers, linear, routed)]``: the runs of layers of
    one kind, cut behind the leading dense layers too: the stacked trees
    the program scans (``LlamaConfig.segment_plan``'s cuts)."""
    plan = []
    for i in range(d["L"]):
        kind = (d["linear"][i], i >= d["first_dense"])
        if plan and tuple(plan[-1][2:]) == kind:
            plan[-1][1] += 1
        else:
            plan.append([i, 1, *kind])
    return [tuple(p) for p in plan]


def make_model(seed: int, d: dict) -> dict:
    """The whole model: ``layers`` is a tuple of stacked segments, plus
    ``embed`` / ``final_norm`` / ``lm_head``.  One jitted call a segment,
    so that the float32 intermediates of one do not sit beside the next."""
    key = base_key(seed)
    segs = [jax.jit(lambda k, lo=lo, n=n, lin=lin, routed=routed: lax.map(
        lambda i: layer_weights(k, i, d, lin, routed),
        jnp.arange(lo, lo + n)))(key) for lo, n, lin, routed in segment_plan(d)]
    out = jax.jit(lambda k: outer_weights(k, d))(key)
    out["layers"] = tuple(segs)
    return out
