"""Seeded random weights of a decoder whose layers differ in attention kind
on a period (full NoPE layers beside window RoPE layers: SmallThinker) and
whose FFN is softmax-routed gated experts with no shared one, made ON THE
DEVICE in the type they are served in.  The sibling of ``weights.py``
(dense GQA) and ``weights_mla_moe.py``, with the same rules: the benchmark
makes the weights, the served tree and the plain reference are both built
from these functions, and one layer's weights depend on (seed, layer) alone.

An expert's weights depend on (seed, layer, EXPERT ID) alone, so any share
of a layer's experts (``first_held .. first_held + held - 1``) holds
exactly the numbers the whole layer would: the share test relies on it.
The router is scaled normal over normed inputs, so a token's 64 logits are
independent draws of one distribution and the experts stay about equally
popular (no selection bias: the published router has none).
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
from jax import lax

from benchmark.harness.weights import _norm_weight, _normal, base_key  # noqa: F401


def dims(config: dict) -> dict:
    """Sizes from the configuration file's own (Hugging Face) keys.  The
    two layouts are the published ones, whole; the model is their first
    ``num_hidden_layers`` entries.  ``held`` experts of the router's ``E``
    live here (all of them unless the file says otherwise)."""
    L = config["num_hidden_layers"]
    window = config["sliding_window_size"]
    E = config["moe_num_primary_experts"]
    held = config.get("experts_held", E)
    if config.get("rope_scaling") is not None:
        raise ValueError("window_moe: rope_scaling is not modelled")
    if not (config["moe_primary_router_apply_softmax"]
            and config["norm_topk_prob"]):
        raise ValueError("window_moe: the router is softmax over the chosen "
                         "logits, normalised")
    return {
        "D": config["hidden_size"], "Hq": config["num_attention_heads"],
        "Hkv": config["num_key_value_heads"], "hd": config["head_dim"],
        "Fe": config["moe_ffn_hidden_size"], "E": E, "held": held,
        "first_held": config.get("expert_share", 0) * held,
        "top_k": config["moe_num_active_primary_experts"],
        "V": config["vocab_size"], "L": L,
        "windows": tuple(window if w else None
                         for w in config["sliding_window_layout"][:L]),
        "rope": tuple(bool(r) for r in config["rope_layout"][:L]),
        "eps": config["rms_norm_eps"], "theta": float(config["rope_theta"]),
        "dtype": config.get("torch_dtype", "bfloat16"),
    }


def runs(d: dict) -> list:
    """``[(first layer, layers)]`` of the runs of layers of one attention
    kind: the segments the served tree is stacked in."""
    out = []
    for i, kind in enumerate(zip(d["windows"], d["rope"])):
        if out and kind == (d["windows"][out[-1][0]], d["rope"][out[-1][0]]):
            out[-1][1] += 1
        else:
            out.append([i, 1])
    return [tuple(r) for r in out]


def expert_weights(key, i, e, d: dict) -> dict:
    """Routed expert ``e`` of layer ``i``: gate, up, down."""
    ks = jax.random.split(jax.random.fold_in(
        jax.random.fold_in(key, i), (1 << 16) + e), 3)
    D, F, dtype = d["D"], d["Fe"], jnp.dtype(d["dtype"])
    return {"w_gate": _normal(ks[0], (D, F), D ** -0.5, dtype),
            "w_up": _normal(ks[1], (D, F), D ** -0.5, dtype),
            "w_down": _normal(ks[2], (F, D), F ** -0.5, dtype)}


def layer_weights(key, i, d: dict) -> dict:
    """Layer ``i``: grouped-query attention and the router over all ``E``
    experts with the held experts stacked."""
    dtype = jnp.dtype(d["dtype"])
    ks = jax.random.split(jax.random.fold_in(key, i), 8)
    D, q, kv = d["D"], d["Hq"] * d["hd"], d["Hkv"] * d["hd"]
    held = d["first_held"] + jnp.arange(d["held"])
    return {
        "attn_norm": _norm_weight(ks[0], D, dtype),
        "mlp_norm": _norm_weight(ks[1], D, dtype),
        "wq": _normal(ks[2], (D, q), D ** -0.5, dtype),
        "wk": _normal(ks[3], (D, kv), D ** -0.5, dtype),
        "wv": _normal(ks[4], (D, kv), D ** -0.5, dtype),
        "wo": _normal(ks[5], (q, D), q ** -0.5, dtype),
        "routed": {
            "router": _normal(ks[6], (D, d["E"]), D ** -0.5, dtype),
            **lax.map(lambda e: expert_weights(key, i, e, d), held)},
    }


def outer_weights(key, d: dict) -> dict:
    """Embedding table, final norm and the (untied) output head."""
    dtype = jnp.dtype(d["dtype"])
    ks = jax.random.split(jax.random.fold_in(key, 1 << 20), 3)
    return {
        "embed": _normal(ks[0], (d["V"], d["D"]), 0.02, dtype),
        "final_norm": _norm_weight(ks[1], d["D"], dtype),
        "lm_head": _normal(ks[2], (d["D"], d["V"]), d["D"] ** -0.5, dtype),
    }


def make_model(seed: int, d: dict) -> dict:
    """The whole model: ``layers`` is a tuple of stacked segments, one a
    run of layers of one attention kind, plus ``embed`` / ``final_norm`` /
    ``lm_head``.  One jitted call a segment, so that the float32
    intermediates of one do not sit beside the other's."""
    key = base_key(seed)
    segs = [jax.jit(lambda k, lo=lo, n=n: lax.map(
        lambda i: layer_weights(k, i, d), lo + jnp.arange(n)))(key)
        for lo, n in runs(d)]
    out = jax.jit(lambda k: outer_weights(k, d))(key)
    out["layers"] = tuple(segs)
    return out
