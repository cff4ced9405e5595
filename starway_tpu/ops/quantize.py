"""Symmetric int8 quantization for the KV cache.

Single-token decode streams the whole KV cache through the core once per
generated token — it is HBM-bandwidth-bound, so halving the cache's bytes
halves what the decode step must stream and doubles the context a chip
can serve.  The
scheme is the standard serving-stack one (per-token, per-head symmetric
int8): each cached [head_dim] vector x is stored as

    q = round(x / s),  s = max(|x|) / 127        (s in f32, q in int8)

Dequantization never materialises a wide cache in HBM or VMEM: the decode
kernel streams int8 blocks, folds ``k``'s scale into the score columns
(``(q . k_int8) * s_k``) and ``v``'s scale into the softmax weights before
the ``p @ v`` matmul (ops/pallas_decode.py) — the operands widen to the
compute dtype only inside the matmul itself, so the bandwidth-bound part
(the HBM/VMEM stream) stays at half width.  Accuracy: worst-case
per-element error is ``s/2 = amax/254`` (~0.4% of the vector's max); the
f32 softmax chain is unchanged.

No reference counterpart (/root/reference is a transport library); this is
the TPU build's own serving-stack extension, following the public KV-cache
quantization recipe used by mainstream inference engines.
"""

from __future__ import annotations

import jax.numpy as jnp

INT8_MAX = 127.0


def quantize_kv(x):
    """Quantize along the last axis: ``x [..., D]`` -> ``(q int8 [..., D],
    scale f32 [...])`` with ``x ~= q * scale[..., None]``.

    All-zero vectors (e.g. the cache's zero-initialised / padded slots) get
    scale 0 and quantize to zeros — dequantization returns exact zeros, so
    padding stays inert.
    """
    amax = jnp.max(jnp.abs(x.astype(jnp.float32)), axis=-1)
    scale = amax / INT8_MAX
    # Avoid 0/0 on all-zero vectors; where scale == 0 the numerator is 0 too.
    div = jnp.where(scale > 0.0, scale, 1.0)[..., None]
    q = jnp.clip(jnp.round(x.astype(jnp.float32) / div), -INT8_MAX, INT8_MAX)
    return q.astype(jnp.int8), scale


def dequantize_kv(q, scale, dtype=jnp.bfloat16):
    """Inverse of :func:`quantize_kv` (up to rounding): ``q int8 [..., D]``
    times ``scale [...]`` broadcast over the last axis."""
    return (q.astype(jnp.float32) * scale[..., None]).astype(dtype)


def quantize_weight(w):
    """Weight-only int8 (W8A16), symmetric per-OUTPUT-channel: ``w [...,
    D, F]`` -> ``{"q": int8 same shape, "s": f32 [..., F]}`` with
    ``w ~= q * s`` broadcast over rows.  Per-out-channel scales commute
    with the matmul (``(x @ q) * s == x @ (q * s)``), so dequantization
    folds into the PRODUCT — the weight stream stays int8 end to end
    (ops/pallas_gemv.py).  Leading axes (the stacked-layer dim) are
    batch dims of the scheme."""
    amax = jnp.max(jnp.abs(w.astype(jnp.float32)), axis=-2)
    scale = amax / INT8_MAX
    div = jnp.where(scale > 0.0, scale, 1.0)[..., None, :]
    q = jnp.clip(jnp.round(w.astype(jnp.float32) / div), -INT8_MAX, INT8_MAX)
    return {"q": q.astype(jnp.int8), "s": scale}


# The matmul weights of the Llama tree (models/llama.py:init_params):
# everything consumed as ``x @ w``.  embed stays wide (it is a GATHER,
# not a matmul — rows leave one at a time); norms are vectors.
_MATMUL_LEAVES = ("wq", "wk", "wv", "wo", "w_gate", "w_up", "w_down")


def quantize_params(params: dict):
    """Weight-only int8 serving tree: every matmul weight of a (dense)
    Llama parameter tree becomes a ``{"q", "s"}`` pair; embed, norms,
    and anything unrecognised stay untouched.  At batch-1 decode the
    weight stream is the dominant HBM bill (~2 bytes/param/token in
    bf16), so int8 weights are worth ~2x on the MLP-dominated share and
    halve weight memory.  The returned tree is INFERENCE-ONLY — it flows
    through forward/prefill/decode/serving/speculative via
    models/llama.py:matmul_w, but optimizers and the training step
    expect raw arrays.  MoE trees are refused (expert weights route
    through their own dispatch; not wired)."""
    layers = params["layers"]
    if "moe" in layers:
        raise NotImplementedError(
            "quantize_params covers dense models; MoE expert weights are "
            "not wired for weight-only int8 yet")
    new_layers = dict(layers)
    for name in _MATMUL_LEAVES:
        if name in new_layers:
            new_layers[name] = quantize_weight(new_layers[name])
    out = dict(params)
    out["layers"] = new_layers
    out["lm_head"] = quantize_weight(params["lm_head"])
    return out
