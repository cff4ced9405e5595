"""Kimi Delta Attention (KDA: the linear-attention layer of Kimi-Linear,
arXiv 2510.26692, parameterised as the model's ``KimiDeltaAttention``): the
projections, short convolutions and gates around the gated delta rule, in
the two forms a served model runs, and what a request's state holds.

A layer with ``kda`` in its tree is of this kind (leaf presence is the
marker, as everywhere in this package).  With ``H`` heads of ``d`` (keys
and values alike), per token ``x``:

    q~ | k~ | v~ = wqkv x                                H*d wide each
    q, k, v    = SiLU(conv(q~)), SiLU(conv(k~)), SiLU(conv(v~))
                 depthwise causal, ``conv`` taps, a channel its own taps
    q, k       = q / |q|, k / |k| a head;  q *= d ** -0.5
    g          = -exp(a_log[h]) softplus(w_fb w_fa x + dt_bias)   [H, d]
    beta       = sigmoid(w_beta x)                                [H]
    S, o       = the delta rule (ops/pallas_kda.py) on q, k, v, g, beta
    out        = RMSNorm_d(o; o_norm) * sigmoid(w_gb w_ga x)      -> wo

Tree of one layer's ``kda`` dict (stacked on a leading layer axis like
every other): ``wqkv [D, 3*H*d]`` (W_q, W_k and W_v side by side: one
matmul) and ``conv [taps, 3*H*d]`` (the taps of the same channels, the
LAST tap on the current token); ``w_fa``, ``w_ga [D, d]``; ``w_fb``, ``w_gb [d, H*d]``; ``dt_bias
[H*d]`` and ``a_log [H]`` float32; ``w_beta [D, H]``; ``o_norm [d]``.  The
output projection is the block's ``wo [H*d, D]``.

A request's state a layer: ``kda_state [H, d, d]`` float32 and ``kda_conv
[taps - 1, 3*H*d]``, the last inputs of the three convolutions BEFORE their
activation.  Neither has a position axis: prefill hands back the state
after a row's OWN last token (:func:`kda_prefill`), decode carries it
(:func:`kda_decode`).
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
from jax import lax

from ..ops import kda_chunk, kda_step

L2_EPS = 1e-6   # under the root of a head's q and k norms


def init_kda_params(key, n_layers: int, cfg) -> dict:
    """Seeded random leaves of ``n_layers`` stacked KDA layers."""
    la, dt, D = cfg.linear, cfg.compute_dtype, cfg.d_model
    w, d = la.width, la.head_dim
    ks = jax.random.split(key, 12)

    def norm(k, shape, scale, dtype=dt):
        return (jax.random.normal(k, (n_layers, *shape), jnp.float32)
                * scale).astype(dtype)

    return {
        "wqkv": norm(ks[0], (D, 3 * w), D**-0.5),
        "conv": norm(ks[3], (la.conv, 3 * w), la.conv**-0.5),
        "w_fa": norm(ks[4], (D, d), D**-0.5), "w_fb": norm(ks[5], (d, w), d**-0.5),
        "dt_bias": norm(ks[6], (w,), 1.0, jnp.float32),
        "a_log": jnp.log(jax.random.uniform(ks[7], (n_layers, la.n_heads),
                                            jnp.float32, 1.0, 16.0)),
        "w_beta": norm(ks[8], (D, la.n_heads), D**-0.5),
        "w_ga": norm(ks[9], (D, d), D**-0.5), "w_gb": norm(ks[10], (d, w), d**-0.5),
        "o_norm": jnp.ones((n_layers, d), dt),
    }


def _gates(x, kp, cfg):
    """x [B, S, D] -> (g [B, S, H, d] log-decay, beta [B, S, H], out gate
    [B, S, H, d]), float32."""
    from .llama import matmul_w

    la, f32 = cfg.linear, jnp.float32
    shape = x.shape[:2] + (la.n_heads, la.head_dim)
    f = matmul_w(matmul_w(x, kp["w_fa"]), kp["w_fb"]).astype(f32)
    g = -jnp.exp(kp["a_log"].astype(f32))[:, None] * jax.nn.softplus(
        (f + kp["dt_bias"]).reshape(shape))
    beta = jax.nn.sigmoid(matmul_w(x, kp["w_beta"]).astype(f32))
    gate = jax.nn.sigmoid(matmul_w(matmul_w(x, kp["w_ga"]), kp["w_gb"])
                          .astype(f32).reshape(shape))
    return g, beta, gate


def _qkv(conved, cfg):
    """The convolutions' outputs [B, S, 3*H*d] -> (q, k, v) [B, S, H, d]
    float32: SiLU, then q and k of unit length a head, q scaled."""
    la = cfg.linear
    y = jax.nn.silu(conved.astype(jnp.float32))
    q, k, v = (y[..., i * la.width:(i + 1) * la.width].reshape(
        y.shape[:2] + (la.n_heads, la.head_dim)) for i in range(3))

    def unit(a):
        return a * lax.rsqrt(jnp.sum(a * a, -1, keepdims=True) + L2_EPS)

    return unit(q) * la.head_dim**-0.5, unit(k), v


def _gated_out(o, gate, kp, cfg):
    """o, gate [B, S, H, d] float32 -> the head-wise normed, gated output in
    the compute type."""
    o = o * lax.rsqrt(jnp.mean(o * o, -1, keepdims=True) + cfg.norm_eps)
    return (o * kp["o_norm"].astype(jnp.float32) * gate).astype(
        cfg.compute_dtype)


def kda_prefill(x, kp, cfg, lengths=None):
    """The layer on whole rows x [B, S, D] (normed) from an empty state:
    ``(out [B, H, S, d]`` before ``wo``, ``{"kda_state": [B, H, d, d],
    "kda_conv": [B, taps - 1, 3*H*d]})``, the state after each row's first
    ``lengths[b]`` positions (default S).  The positions behind a row's
    length stand still (``g = 0``, ``beta = 0``) and stay out of the
    convolutions' tails; their outputs mean nothing."""
    from .llama import matmul_w

    la = cfg.linear
    b, s, _ = x.shape
    taps = la.conv
    lengths = (jnp.full((b,), s, jnp.int32) if lengths is None
               else jnp.asarray(lengths, jnp.int32))
    real = jnp.arange(s)[None, :] < lengths[:, None]            # [B, S]
    xin = matmul_w(x, kp["wqkv"])           # the convolutions' inputs
    padded = jnp.pad(xin, ((0, 0), (taps - 1, 0), (0, 0)))
    conved = sum(padded[:, j:j + s] * kp["conv"][j] for j in range(taps))
    # Row b's last taps - 1 real inputs: padded[b, lengths[b] ..].
    tails = jax.vmap(lambda row, n: lax.dynamic_slice_in_dim(
        row, n, taps - 1, 0))(padded, lengths)
    q, k, v = _qkv(conved, cfg)
    g, beta, gate = _gates(x, kp, cfg)
    g = jnp.where(real[..., None, None], g, 0.0)
    beta = jnp.where(real[..., None], beta, 0.0)
    heads = lambda a: jnp.moveaxis(a, 2, 1)                     # [B, H, S, ..]
    with jax.named_scope("sw_kda_chunk"):
        o, state = kda_chunk(heads(q), heads(k), heads(v), heads(g),
                             heads(beta))
    out = _gated_out(jnp.moveaxis(o, 1, 2), gate, kp, cfg)
    return jnp.moveaxis(out, 2, 1), {"kda_state": state, "kda_conv": tails}


def kda_decode(x, kp, cfg, cache: dict, layer):
    """One token a row, x [B, 1, D] (normed), through layer ``layer`` (its
    index among the linear layers) of the stacked state leaves
    ``cache["kda_state"] [L, B, H, d, d]`` / ``cache["kda_conv"] [L, B,
    taps - 1, 3*H*d]``: ``(out [B, H, 1, d]`` before ``wo``, the cache with
    that layer's state moved on)``."""
    from .llama import matmul_w

    tails = lax.dynamic_index_in_dim(cache["kda_conv"], layer, 0,
                                     keepdims=False)
    window = jnp.concatenate(
        [tails, matmul_w(x, kp["wqkv"]).astype(tails.dtype)], 1)
    conved = jnp.sum(window * kp["conv"], axis=1, keepdims=True)
    q, k, v = _qkv(conved, cfg)
    g, beta, gate = _gates(x, kp, cfg)
    o, state = kda_step(cache["kda_state"], q[:, 0], k[:, 0], v[:, 0],
                        g[:, 0], beta[:, 0], layer=layer)
    out = _gated_out(o[:, None], gate, kp, cfg)
    return jnp.moveaxis(out, 2, 1), {
        **cache, "kda_state": state,
        "kda_conv": lax.dynamic_update_index_in_dim(
            cache["kda_conv"], window[:, 1:], layer, 0)}
