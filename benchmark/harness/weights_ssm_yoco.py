"""Seeded random weights of a decoder-hybrid-decoder (SambaY with
differential attention: Phi-4-mini-flash-reasoning): a self-decoder of
Mamba state-space layers beside window attention, one full attention layer
whose k / v rows the cross-decoder's attention layers read, gated memory
units between those, LayerNorm with a bias, a tied head; made ON THE DEVICE
in the type they are served in.  After ``weights_gdn_gqa_moe.py``, with the
same rules: the benchmark makes the weights, the served tree and the plain
reference are both built from these functions, and one layer's weights
depend on (seed, layer) alone.

What the source does not fix is ASSUMED here and listed in the
configuration's file: the kinds by layer (``layout``), Mamba's sizes and
its own initialisation (``a_log = log(1 .. N)`` a channel, ``b_dt`` the
inverse softplus of a step drawn log-uniform from [0.001, 0.1], ``d_skip``
near one), the four ``lambda`` vectors a differential-attention layer (0.1
N(0, 1)), biases drawn 0.02 N(0, 1) and norm gains near one so that a path
which dropped one would not pass.  The columns inside ``wq`` / ``wk`` /
``wv`` lie head by head (a pair's two heads adjacent), inside ``w_in`` s
then z: a checkpoint's loader would permute the published matrices so.

The states of a channel lie along the second-last axis (``a_log [N, E]``):
the program's layout (starway_tpu/ops/pallas_ssm.py says why), and nothing
but a transpose of the published ``[E, N]``.
"""

from __future__ import annotations

import math

import jax
import jax.numpy as jnp
from jax import lax

from benchmark.harness.weights import _norm_weight, _normal, base_key  # noqa: F401
from benchmark.harness.weights_mla_moe import _mlp

KINDS = ("ssm", "window", "full", "gmu", "cross")


def dims(config: dict) -> dict:
    """Sizes from the configuration file's own (Hugging Face) keys and the
    sizes it lists as assumed."""
    D, H = config["hidden_size"], config["num_attention_heads"]
    runs = tuple((tuple(period), int(reps)) for period, reps in config["layout"])
    kinds = tuple(k for period, reps in runs for _ in range(reps) for k in period)
    if (len(kinds) != config["num_hidden_layers"] or set(kinds) - set(KINDS)
            or config["mlp_bias"] or config["lm_head_bias"]
            or not config["tie_word_embeddings"] or D % H
            or config["hidden_act"] != "silu"):
        raise ValueError(
            "this kind is a tied, SiLU-gated decoder with no bias in its "
            "MLP or head whose layout names every layer's kind")
    return {
        "D": D, "H": H, "Hkv": config["num_key_value_heads"], "hd": D // H,
        "F": config["intermediate_size"], "V": config["vocab_size"],
        "L": config["num_hidden_layers"], "eps": config["layer_norm_eps"],
        "window": config["sliding_window"],
        "E": config["mamba_expand"] * D, "N": config["mamba_d_state"],
        "R": config["mamba_dt_rank"], "taps": config["mamba_d_conv"],
        "runs": runs, "kinds": kinds,
        "dtype": config.get("torch_dtype", "bfloat16"),
    }


def _layernorm_weight(key, n, dtype):
    """``[2, n]``: a gain near one over a bias near zero."""
    kg, kb = jax.random.split(key)
    return jnp.stack([
        1.0 + 0.1 * jax.random.normal(kg, (n,), jnp.float32),
        0.02 * jax.random.normal(kb, (n,), jnp.float32)]).astype(dtype)


def ssm_weights(ks, d: dict) -> dict:
    """One Mamba layer's own leaves (models/ssm.py names them)."""
    dtype = jnp.dtype(d["dtype"])
    D, E, N, R = d["D"], d["E"], d["N"], d["R"]
    step = jnp.exp(jax.random.uniform(ks[5], (E,), jnp.float32,
                                      math.log(0.001), math.log(0.1)))
    return {
        "w_in": _normal(ks[0], (D, 2 * E), D ** -0.5, dtype),          # s | z
        "conv_w": _normal(ks[1], (d["taps"], E), d["taps"] ** -0.5, dtype),
        "conv_b": _normal(ks[2], (E,), 0.02, dtype),
        "w_x": _normal(ks[3], (E, R + 2 * N), E ** -0.5, dtype),   # r | B | C
        "w_dt": _normal(ks[4], (R, E), R ** -0.5, dtype),
        "b_dt": step + jnp.log(-jnp.expm1(-step)),        # softplus^-1(step)
        "a_log": jnp.broadcast_to(
            jnp.log(jnp.arange(1, N + 1, dtype=jnp.float32))[:, None], (N, E)),
        "d_skip": 1.0 + 0.1 * jax.random.normal(ks[6], (E,), jnp.float32),
    }


def lambda_init(i):
    """``lam0`` of layer ``i`` (0-based)."""
    return 0.8 - 0.6 * jnp.exp(-0.3 * jnp.asarray(i, jnp.float32))


def layer_weights(key, i, d: dict, kind: str) -> dict:
    """Layer ``i`` (0-based) of ``kind``: its mixer, the block's two
    LayerNorms and the gated MLP."""
    dtype = jnp.dtype(d["dtype"])
    ks = jax.random.split(jax.random.fold_in(key, i), 32)
    D, hd = d["D"], d["hd"]
    out = {"attn_norm": _layernorm_weight(ks[0], D, dtype),
           "mlp_norm": _layernorm_weight(ks[1], D, dtype),
           **_mlp(ks[2:5], (), D, d["F"], dtype)}
    if kind == "ssm":
        out["ssm"] = ssm_weights(ks[16:23], d)
        out["wo"] = _normal(ks[5], (d["E"], D), d["E"] ** -0.5, dtype)
    elif kind == "gmu":
        out["gmu_in"] = _normal(ks[6], (D, d["E"]), D ** -0.5, dtype)
        out["wo"] = _normal(ks[5], (d["E"], D), d["E"] ** -0.5, dtype)
    else:
        q, kv = d["H"] * hd, d["Hkv"] * hd
        out.update(wq=_normal(ks[6], (D, q), D ** -0.5, dtype),
                   bq=_normal(ks[7], (q,), 0.02, dtype),
                   wo=_normal(ks[5], (q, D), q ** -0.5, dtype),
                   bo=_normal(ks[8], (D,), 0.02, dtype),
                   sub_norm=_norm_weight(ks[9], 2 * hd, dtype),
                   lam0=lambda_init(i),
                   **{n: _normal(ks[10 + j], (hd,), 0.1, dtype) for j, n in
                      enumerate(("lam_q1", "lam_k1", "lam_q2", "lam_k2"))})
        if kind != "cross":
            out.update(wk=_normal(ks[14], (D, kv), D ** -0.5, dtype),
                       bk=_normal(ks[15], (kv,), 0.02, dtype),
                       wv=_normal(ks[24], (D, kv), D ** -0.5, dtype),
                       bv=_normal(ks[25], (kv,), 0.02, dtype))
    return out


def outer_weights(key, d: dict) -> dict:
    """The one table (embedding AND head) and the final LayerNorm."""
    dtype = jnp.dtype(d["dtype"])
    ks = jax.random.split(jax.random.fold_in(key, 1 << 20), 2)
    return {"embed": _normal(ks[0], (d["V"], d["D"]), 0.02, dtype),
            "final_norm": _layernorm_weight(ks[1], d["D"], dtype)}


def make_model(seed: int, d: dict) -> dict:
    """The whole model: ``layers`` is a tuple of runs, a run a tuple of
    stacked trees, one a layer of its period (the program's
    ``LayerKinds.runs`` layout), plus ``embed`` / ``final_norm``.  One
    jitted call a stacked tree, so that the float32 intermediates of one
    do not sit beside the next."""
    key = base_key(seed)
    runs, first = [], 0
    for period, reps in d["runs"]:
        p = len(period)
        runs.append(tuple(
            jax.jit(lambda k, kind=kind, lo=first + j, p=p, reps=reps: lax.map(
                lambda i: layer_weights(k, i, d, kind),
                lo + p * jnp.arange(reps)))(key)
            for j, kind in enumerate(period)))
        first += p * reps
    out = jax.jit(lambda k: outer_weights(k, d))(key)
    out["layers"] = tuple(runs)
    return out
