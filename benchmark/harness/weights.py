"""Seeded random weights of a dense pre-norm decoder (RMSNorm, RoPE, GQA,
SwiGLU), made ON THE DEVICE, in the type they are served in.

The benchmark makes the weights, not the program: the served tree and the
plain reference are both built from these functions and share nothing
else.  One layer's weights depend on (seed, layer index) alone, so the
reference can make them again layer by layer after the program's state is
freed, and the stacked tree (one ``lax.map`` over the layer index inside
one jitted call) holds exactly the same numbers.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
from jax import lax


def dims(config: dict) -> dict:
    """The sizes the weight shapes follow, from the configuration file's
    own (Hugging Face) keys."""
    d, hq = config["hidden_size"], config["num_attention_heads"]
    return {"D": d, "F": config["intermediate_size"], "Hq": hq,
            "Hkv": config["num_key_value_heads"],
            "hd": config.get("head_dim") or d // hq,
            "V": config["vocab_size"], "L": config["num_hidden_layers"],
            "eps": config["rms_norm_eps"], "theta": config["rope_theta"],
            "dtype": config.get("torch_dtype", "bfloat16")}


def base_key(seed: int):
    """A key from any whole number: the low 32 bits seed it, the rest is
    folded in (a PRNGKey alone would drop bits above 32)."""
    seed = int(seed)
    return jax.random.fold_in(jax.random.PRNGKey(seed & 0xFFFFFFFF),
                              (seed >> 32) & 0x7FFFFFFF)


def _normal(key, shape, scale, dtype):
    return (jax.random.normal(key, shape, jnp.float32) * scale).astype(dtype)


def _norm_weight(key, n, dtype):
    # Not all ones: a path that dropped a norm's weight would pass otherwise.
    return (1.0 + 0.1 * jax.random.normal(key, (n,), jnp.float32)).astype(dtype)


def layer_weights(key, i, d: dict) -> dict:
    """Layer ``i``: projections [in, out], scaled-normal; norms near one."""
    dtype = jnp.dtype(d["dtype"])
    ks = jax.random.split(jax.random.fold_in(key, i), 9)
    D, F, q, kv = d["D"], d["F"], d["Hq"] * d["hd"], d["Hkv"] * d["hd"]
    return {
        "wq": _normal(ks[0], (D, q), D ** -0.5, dtype),
        "wk": _normal(ks[1], (D, kv), D ** -0.5, dtype),
        "wv": _normal(ks[2], (D, kv), D ** -0.5, dtype),
        "wo": _normal(ks[3], (q, D), q ** -0.5, dtype),
        "w_gate": _normal(ks[4], (D, F), D ** -0.5, dtype),
        "w_up": _normal(ks[5], (D, F), D ** -0.5, dtype),
        "w_down": _normal(ks[6], (F, D), F ** -0.5, dtype),
        "attn_norm": _norm_weight(ks[7], D, dtype),
        "mlp_norm": _norm_weight(ks[8], D, dtype),
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
    """The whole model in one jitted call: ``layers`` stacked on a leading
    [L] axis, plus ``embed`` / ``final_norm`` / ``lm_head``."""

    @jax.jit
    def make(key):
        out = outer_weights(key, d)
        out["layers"] = lax.map(lambda i: layer_weights(key, i, d),
                                jnp.arange(d["L"]))
        return out

    return make(base_key(seed))
