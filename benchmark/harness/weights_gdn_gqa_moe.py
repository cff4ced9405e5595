"""Seeded random weights of a decoder whose layers are Gated DeltaNet
linear attention (one log-decay a head, key heads fewer than value heads)
or gated grouped-query attention with partly rotated heads, on a period,
every layer's FFN softmax-routed experts beside a shared expert with a gate
of its own (Qwen3-Next), made ON THE DEVICE in the type they are served in.
After ``weights_kda_mla_moe.py``, with the same rules: the benchmark makes
the weights, the served tree and the plain reference are both built from
these functions, one layer's weights depend on (seed, layer) alone and an
expert's on (seed, layer, EXPERT ID) alone, so any share of a layer's
experts holds exactly the numbers the whole layer would.

What the source does not fix is ASSUMED here and listed in the
configuration's file: ``a_log = log U(1, 16)`` and ``dt_bias`` the inverse
softplus of a step drawn log-uniform from [0.001, 0.1], a VALUE HEAD each
(``kimi-linear.json``'s convention, a head where that has a channel), the
convolution's taps scaled normal without a bias, and the norms' form: every
RMSNorm but the DeltaNet's head-wise output norm multiplies by ``1 + w``,
so those gains are drawn around ZERO (0.1 N(0, 1)) and the output norm's
around one.  Inside ``w_qkvz`` the columns lie q | k | v | z, and inside
``wq`` a head's query columns beside its gate's: a checkpoint's loader
would permute the published matrices so, and nothing else changes.
"""

from __future__ import annotations

import math

import jax
import jax.numpy as jnp
from jax import lax

from benchmark.harness.weights import _norm_weight, _normal, base_key  # noqa: F401
from benchmark.harness.weights_mla_moe import _mlp, expert_weights  # noqa: F401


def dims(config: dict) -> dict:
    """Sizes from the configuration file's own (Hugging Face) keys.  Layer
    ``i`` (0-based) attends where ``(i + 1) % full_attention_interval ==
    0``; the router keeps the PUBLISHED expert count, ``held`` of them live
    here."""
    n, hd = config["num_hidden_layers"], config["head_dim"]
    held = config["num_experts"]
    if (config["decoder_sparse_step"] != 1 or config["mlp_only_layers"]
            or not config["norm_topk_prob"] or config["rope_scaling"] is not None
            or config["linear_key_head_dim"] != config["linear_value_head_dim"]
            or config["shared_expert_intermediate_size"]
            % config["moe_intermediate_size"]):
        raise ValueError(
            "this kind has a sparse FFN in every layer, gates renormalised "
            "over the chosen, unscaled rope, square DeltaNet states and a "
            "shared expert of whole expert widths")
    return {
        "D": config["hidden_size"], "Hq": config["num_attention_heads"],
        "Hkv": config["num_key_value_heads"], "hd": hd,
        "rot": int(hd * config["partial_rotary_factor"]),
        "Hk": config["linear_num_key_heads"],
        "Hv": config["linear_num_value_heads"],
        "dl": config["linear_key_head_dim"],
        "taps": config["linear_conv_kernel_dim"],
        "linear": tuple((i + 1) % config["full_attention_interval"] != 0
                        for i in range(n)),
        "period": config["full_attention_interval"],
        "F": config["intermediate_size"], "Fe": config["moe_intermediate_size"],
        "Fs": config["shared_expert_intermediate_size"],
        "E": config.get("num_experts_published", held), "held": held,
        "first_held": config.get("expert_share", 0) * held,
        "top_k": config["num_experts_per_tok"],
        "V": config["vocab_size"], "L": n, "eps": config["rms_norm_eps"],
        "theta": float(config["rope_theta"]),
        "dtype": config.get("torch_dtype", "bfloat16"),
    }


def _zero_norm_weight(key, n, dtype):
    """A zero-centred gain (the norm multiplies by 1 + w).  Not all zeros:
    a path that dropped the weight, or the 1, would pass otherwise."""
    return (0.1 * jax.random.normal(key, (n,), jnp.float32)).astype(dtype)


def gdn_weights(ks, d: dict) -> dict:
    """One Gated DeltaNet layer's own leaves (models/kda.py names them)."""
    dtype = jnp.dtype(d["dtype"])
    D, kw, vw = d["D"], d["Hk"] * d["dl"], d["Hv"] * d["dl"]
    step = jnp.exp(jax.random.uniform(ks[3], (d["Hv"],), jnp.float32,
                                      math.log(0.001), math.log(0.1)))
    return {
        # q | k | v | z side by side: the program's one wide matmul.
        "w_qkvz": _normal(ks[0], (D, 2 * kw + 2 * vw), D ** -0.5, dtype),
        "conv": _normal(ks[1], (d["taps"], 2 * kw + vw), d["taps"] ** -0.5,
                        dtype),
        "w_ba": _normal(ks[2], (D, 2 * d["Hv"]), D ** -0.5, dtype),   # b | a
        "dt_bias": step + jnp.log(-jnp.expm1(-step)),     # softplus^-1(step)
        "a_log": jnp.log(jax.random.uniform(ks[4], (d["Hv"],), jnp.float32,
                                            1.0, 16.0)),
        "o_norm": _norm_weight(ks[5], d["dl"], dtype),
    }


def layer_weights(key, i, d: dict, linear: bool) -> dict:
    """Layer ``i`` (0-based): Gated DeltaNet or gated attention, and the
    router over all ``E`` experts, the held experts stacked, the shared
    expert and its gate."""
    dtype = jnp.dtype(d["dtype"])
    ks = jax.random.split(jax.random.fold_in(key, i), 24)
    D, hd = d["D"], d["hd"]
    out = {"attn_norm": _zero_norm_weight(ks[0], D, dtype),
           "mlp_norm": _zero_norm_weight(ks[1], D, dtype)}
    if linear:
        vw = d["Hv"] * d["dl"]
        out["kda"] = gdn_weights(ks[16:22], d)
        out["wo"] = _normal(ks[8], (vw, D), vw ** -0.5, dtype)
    else:
        q, kv = d["Hq"] * hd, d["Hkv"] * hd
        out.update(
            # A head's columns: its hd query values, then its hd gate values.
            wq=_normal(ks[2], (D, 2 * q), D ** -0.5, dtype),
            wk=_normal(ks[3], (D, kv), D ** -0.5, dtype),
            wv=_normal(ks[4], (D, kv), D ** -0.5, dtype),
            q_head_norm=_zero_norm_weight(ks[5], hd, dtype),
            k_head_norm=_zero_norm_weight(ks[6], hd, dtype),
            wo=_normal(ks[8], (q, D), q ** -0.5, dtype))
    held = d["first_held"] + jnp.arange(d["held"])
    out["routed"] = {
        "router": _normal(ks[9], (D, d["E"]), D ** -0.5, dtype),
        **lax.map(lambda e: expert_weights(key, i, e, d), held),
        "shared": _mlp(ks[11:14], (), D, d["Fs"], dtype),
        "shared_gate": _normal(ks[14], (D, 1), D ** -0.5, dtype),
    }
    return out


def outer_weights(key, d: dict) -> dict:
    """Embedding table, final norm (zero-centred) and the untied output
    head, over the held slice of the vocabulary."""
    dtype = jnp.dtype(d["dtype"])
    ks = jax.random.split(jax.random.fold_in(key, 1 << 20), 3)
    return {
        "embed": _normal(ks[0], (d["V"], d["D"]), 0.02, dtype),
        "final_norm": _zero_norm_weight(ks[1], d["D"], dtype),
        "lm_head": _normal(ks[2], (d["D"], d["V"]), d["D"] ** -0.5, dtype),
    }


def segment_plan(d: dict) -> list:
    """``[(first layer, layers, linear)]``: the runs of layers of one kind,
    the stacked trees the program scans (``LlamaConfig.segment_plan``'s
    cuts: no leading dense layer here)."""
    plan = []
    for i, linear in enumerate(d["linear"]):
        if plan and plan[-1][2] == linear:
            plan[-1][1] += 1
        else:
            plan.append([i, 1, linear])
    return [tuple(p) for p in plan]


def make_model(seed: int, d: dict) -> dict:
    """The whole model: ``layers`` is a tuple of stacked segments, plus
    ``embed`` / ``final_norm`` / ``lm_head``.  One jitted call a segment,
    so that the float32 intermediates of one do not sit beside the next."""
    key = base_key(seed)
    segs = [jax.jit(lambda k, lo=lo, n=n, lin=lin: lax.map(
        lambda i: layer_weights(k, i, d, lin), jnp.arange(lo, lo + n)))(key)
        for lo, n, lin in segment_plan(d)]
    out = jax.jit(lambda k: outer_weights(k, d))(key)
    out["layers"] = tuple(segs)
    return out
