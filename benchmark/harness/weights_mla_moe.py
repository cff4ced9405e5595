"""Seeded random weights of a latent-attention, routed-expert decoder
(DeepSeek-V3 / Kimi-K2 block: low-rank q and kv projections with a shared
RoPE key head, leading dense layers, then sigmoid-routed experts beside a
shared one), made ON THE DEVICE in the type they are served in.  The
sibling of ``weights.py`` (dense GQA), with the same rules: the benchmark
makes the weights, the served tree and the plain reference are both built
from these functions, and one layer's weights depend on (seed, layer) alone.

An expert's weights depend on (seed, layer, EXPERT ID) alone, so any share
of a layer's experts (``first_held .. first_held + held - 1``) holds
exactly the numbers the whole layer would: the share test relies on it.
"""

from __future__ import annotations

import math

import jax
import jax.numpy as jnp
from jax import lax

from benchmark.harness.weights import _norm_weight, _normal, base_key  # noqa: F401


def dims(config: dict) -> dict:
    """Sizes from the configuration file's own (Hugging Face) keys.  The
    router keeps the PUBLISHED expert count; ``held`` of them live here."""
    rs = config["rope_scaling"]
    mscale = 0.1 * rs["mscale_all_dim"] * math.log(rs["factor"]) + 1.0
    nope, rope = config["qk_nope_head_dim"], config["qk_rope_head_dim"]
    held = config["n_routed_experts"]
    return {
        "D": config["hidden_size"], "H": config["num_attention_heads"],
        "q_rank": config["q_lora_rank"], "kv_rank": config["kv_lora_rank"],
        "nope": nope, "rope": rope, "v": config["v_head_dim"],
        "F": config["intermediate_size"], "Fe": config["moe_intermediate_size"],
        "E": config.get("n_routed_experts_published", held), "held": held,
        "first_held": config.get("expert_share", 0) * held,
        "top_k": config["num_experts_per_tok"],
        "n_shared": config["n_shared_experts"],
        "route_scale": config["routed_scaling_factor"],
        "first_dense": config["first_k_dense_replace"],
        "V": config["vocab_size"], "L": config["num_hidden_layers"],
        "eps": config["rms_norm_eps"], "theta": float(config["rope_theta"]),
        "yarn_factor": float(rs["factor"]),
        "yarn_orig": rs["original_max_position_embeddings"],
        "yarn_beta_fast": rs["beta_fast"], "yarn_beta_slow": rs["beta_slow"],
        # mscale / mscale_all_dim scale cos and sin (1.0 here); the scores'
        # multiplier carries mscale_all_dim's square.
        "rope_att": (0.1 * rs["mscale"] * math.log(rs["factor"]) + 1.0) / mscale,
        "sm_scale": (nope + rope) ** -0.5 * mscale * mscale,
        "dtype": config.get("torch_dtype", "bfloat16"),
    }


def _mlp(keys, lead, D, F, dtype):
    return {"w_gate": _normal(keys[0], (*lead, D, F), D ** -0.5, dtype),
            "w_up": _normal(keys[1], (*lead, D, F), D ** -0.5, dtype),
            "w_down": _normal(keys[2], (*lead, F, D), F ** -0.5, dtype)}


def expert_weights(key, i, e, d: dict) -> dict:
    """Routed expert ``e`` of layer ``i``."""
    ks = jax.random.split(jax.random.fold_in(
        jax.random.fold_in(key, i), (1 << 16) + e), 3)
    return _mlp(ks, (), d["D"], d["Fe"], jnp.dtype(d["dtype"]))


def layer_weights(key, i, d: dict, routed: bool) -> dict:
    """Layer ``i``: attention, and a dense MLP or (``routed``) the router
    over all ``E`` experts, its selection bias, the held experts stacked
    and the shared expert."""
    dtype = jnp.dtype(d["dtype"])
    ks = jax.random.split(jax.random.fold_in(key, i), 16)
    D, H = d["D"], d["H"]
    out = {
        "attn_norm": _norm_weight(ks[0], D, dtype),
        "mlp_norm": _norm_weight(ks[1], D, dtype),
        "wq_a": _normal(ks[2], (D, d["q_rank"]), D ** -0.5, dtype),
        "q_norm": _norm_weight(ks[3], d["q_rank"], dtype),
        "wq_b": _normal(ks[4], (d["q_rank"], H * (d["nope"] + d["rope"])),
                        d["q_rank"] ** -0.5, dtype),
        "wkv_a": _normal(ks[5], (D, d["kv_rank"] + d["rope"]), D ** -0.5, dtype),
        "kv_norm": _norm_weight(ks[6], d["kv_rank"], dtype),
        "wkv_b": _normal(ks[7], (d["kv_rank"], H * (d["nope"] + d["v"])),
                         d["kv_rank"] ** -0.5, dtype),
        "wo": _normal(ks[8], (H * d["v"], D), (H * d["v"]) ** -0.5, dtype),
    }
    if not routed:
        out.update(_mlp(ks[9:12], (), D, d["F"], dtype))
        return out
    held = d["first_held"] + jnp.arange(d["held"])
    out["routed"] = {
        "router": _normal(ks[9], (D, d["E"]), D ** -0.5, dtype),
        # Small and not zero, so the selection (scores + bias) is worked
        # and differs from the gates (scores alone).  Small against the
        # scores' spacing at the top-k threshold: there a score moves by
        # 0.1 a unit of logit, so 0.05 made an expert three times more or
        # four times less popular than its neighbour and the load on the
        # 12 held here a property of the seed (7.2-8.6 touched a step, a
        # decode step 3% longer or shorter: PERF.md section 6, PR 26).  A
        # trained router's bias is there to BALANCE the load.
        "bias": 0.005 * jax.random.normal(ks[10], (d["E"],), jnp.float32),
        **lax.map(lambda e: expert_weights(key, i, e, d), held),
        "shared": _mlp(ks[11:14], (), D, d["n_shared"] * d["Fe"], dtype),
    }
    return out


def outer_weights(key, d: dict) -> dict:
    """Embedding table, final norm and the (untied) output head, over the
    held slice of the vocabulary."""
    dtype = jnp.dtype(d["dtype"])
    ks = jax.random.split(jax.random.fold_in(key, 1 << 20), 3)
    return {
        "embed": _normal(ks[0], (d["V"], d["D"]), 0.02, dtype),
        "final_norm": _norm_weight(ks[1], d["D"], dtype),
        "lm_head": _normal(ks[2], (d["D"], d["V"]), d["D"] ** -0.5, dtype),
    }


def make_model(seed: int, d: dict) -> dict:
    """The whole model: ``layers`` is a tuple of stacked segments (the
    leading dense layers, then the routed ones), plus ``embed`` /
    ``final_norm`` / ``lm_head``.  One jitted call a segment, so that the
    float32 intermediates of one do not sit beside the other's."""
    key = base_key(seed)
    plan = [(0, d["first_dense"], False), (d["first_dense"], d["L"], True)]
    segs = []
    for lo, hi, routed in plan:
        if hi > lo:
            segs.append(jax.jit(lambda k, lo=lo, hi=hi, routed=routed: lax.map(
                lambda i: layer_weights(k, i, d, routed),
                jnp.arange(lo, hi)))(key))
    out = jax.jit(lambda k: outer_weights(k, d))(key)
    out["layers"] = tuple(segs)
    return out
