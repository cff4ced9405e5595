"""The gated delta-rule linear-attention layer in its two published
parameterisations, the projections, short convolution and gates around
the recurrence (ops/pallas_kda.py), in the two forms a served model runs,
and what a request's state holds.

A layer with ``kda`` in its tree is of this kind (leaf presence is the
marker, as everywhere in this package), and the leaves under it say which
parameterisation (``cfg.linear.decay`` chose them at initialisation).  With
``H`` value heads of ``d`` (keys are as wide), per token ``x``:

Kimi Delta Attention (KDA: the linear layer of Kimi-Linear, arXiv
2510.26692, as the model's ``KimiDeltaAttention``; ``wqkv`` in the tree):

    q~ | k~ | v~ = wqkv x                                H*d wide each
    q, k, v    = SiLU(conv(q~ | k~ | v~))
                 depthwise causal, ``conv`` taps, a channel its own taps
    q, k       = q / |q|, k / |k| a head;  q *= d ** -0.5
    g          = -exp(a_log[h]) softplus(w_fb w_fa x + dt_bias)   [H, d]
    beta       = sigmoid(w_beta x)                                [H]
    S, o       = the delta rule (ops/pallas_kda.py) on q, k, v, g, beta
    out        = RMSNorm_d(o; o_norm) * sigmoid(w_gb w_ga x)      -> wo

Gated DeltaNet (the linear layer of Qwen3-Next, as the model's
``Qwen3NextGatedDeltaNet``; ``w_qkvz`` in the tree), ``Hk <= H`` key heads:

    q~ | k~ | v~ | z = w_qkvz x             Hk*d | Hk*d | H*d | H*d wide
    b | a      = w_ba x                                  H wide each
    q, k, v    = SiLU(conv(q~ | k~ | v~)), q and k of unit length a key
                 head, q scaled, each repeated to its H / Hk value heads
    g          = -exp(a_log[h]) softplus(a + dt_bias)    ONE number a head
    beta       = sigmoid(b)                                       [H]
    S, o       = the same delta rule, every channel of a head decaying alike
    out        = RMSNorm_d(o; o_norm) * SiLU(z)                   -> wo

Tree of one layer's ``kda`` dict (stacked on a leading layer axis like
every other).  Both: ``conv [taps, conv_width]`` (the taps of q, k and v's
channels side by side, the LAST tap on the current token), ``a_log [H]``
float32, ``o_norm [d]`` (multiplies as it is: not a zero-centred gain).
KDA: ``wqkv [D, 3*H*d]`` (one matmul), ``w_fa``, ``w_ga [D, d]``; ``w_fb``,
``w_gb [d, H*d]``; ``dt_bias [H*d]`` float32; ``w_beta [D, H]``.  Gated
DeltaNet: ``w_qkvz [D, 2*Hk*d + 2*H*d]``, ``w_ba [D, 2*H]``, ``dt_bias
[H]`` float32.  The output projection is the block's ``wo [H*d, D]``.

A request's state a layer: ``kda_state [H, d, d]`` float32 and ``kda_conv
[taps - 1, conv_width]``, the last inputs of the convolution BEFORE its
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
    """Seeded random leaves of ``n_layers`` stacked layers, in the
    parameterisation ``cfg.linear.decay`` names."""
    la, dt, D = cfg.linear, cfg.compute_dtype, cfg.d_model
    w, d = la.width, la.head_dim
    ks = jax.random.split(key, 12)

    def norm(k, shape, scale, dtype=dt):
        return (jax.random.normal(k, (n_layers, *shape), jnp.float32)
                * scale).astype(dtype)

    a_log = jnp.log(jax.random.uniform(ks[7], (n_layers, la.n_heads),
                                       jnp.float32, 1.0, 16.0))
    if la.decay == "head":
        return {
            "w_qkvz": norm(ks[0], (D, la.conv_width + w), D**-0.5),
            "conv": norm(ks[3], (la.conv, la.conv_width), la.conv**-0.5),
            "w_ba": norm(ks[8], (D, 2 * la.n_heads), D**-0.5),
            "dt_bias": norm(ks[6], (la.n_heads,), 1.0, jnp.float32),
            "a_log": a_log, "o_norm": jnp.ones((n_layers, d), dt),
        }
    return {
        "wqkv": norm(ks[0], (D, 3 * w), D**-0.5),
        "conv": norm(ks[3], (la.conv, 3 * w), la.conv**-0.5),
        "w_fa": norm(ks[4], (D, d), D**-0.5), "w_fb": norm(ks[5], (d, w), d**-0.5),
        "dt_bias": norm(ks[6], (w,), 1.0, jnp.float32),
        "a_log": a_log,
        "w_beta": norm(ks[8], (D, la.n_heads), D**-0.5),
        "w_ga": norm(ks[9], (D, d), D**-0.5), "w_gb": norm(ks[10], (d, w), d**-0.5),
        "o_norm": jnp.ones((n_layers, d), dt),
    }


def _project(x, kp, cfg):
    """x [B, S, D] -> (the convolution's inputs [B, S, conv_width], ``z``):
    the layer's one wide matmul; ``z [B, S, H*d]`` rides it in the Gated
    DeltaNet tree, None in KDA's."""
    from .llama import matmul_w

    if "w_qkvz" in kp:
        mixed, cw = matmul_w(x, kp["w_qkvz"]), cfg.linear.conv_width
        return mixed[..., :cw], mixed[..., cw:]
    return matmul_w(x, kp["wqkv"]), None


def _gates(x, z, kp, cfg):
    """x [B, S, D] -> (g the log-decay, ``[B, S, H, d]`` a channel or ``[B,
    S, H]`` a head, beta [B, S, H], the output gate [B, S, H, d]),
    float32: what the two parameterisations differ in."""
    from .llama import matmul_w

    la, f32 = cfg.linear, jnp.float32
    shape = x.shape[:2] + (la.n_heads, la.head_dim)
    if z is not None:     # Gated DeltaNet: b | a from one projection
        ba = matmul_w(x, kp["w_ba"]).astype(f32)
        g = -jnp.exp(kp["a_log"].astype(f32)) * jax.nn.softplus(
            ba[..., la.n_heads:] + kp["dt_bias"])
        return (g, jax.nn.sigmoid(ba[..., :la.n_heads]),
                jax.nn.silu(z.astype(f32)).reshape(shape))
    f = matmul_w(matmul_w(x, kp["w_fa"]), kp["w_fb"]).astype(f32)
    g = -jnp.exp(kp["a_log"].astype(f32))[:, None] * jax.nn.softplus(
        (f + kp["dt_bias"]).reshape(shape))
    beta = jax.nn.sigmoid(matmul_w(x, kp["w_beta"]).astype(f32))
    gate = jax.nn.sigmoid(matmul_w(matmul_w(x, kp["w_ga"]), kp["w_gb"])
                          .astype(f32).reshape(shape))
    return g, beta, gate


def _qkv(conved, cfg, repeat: bool = True):
    """The convolution's outputs [B, S, conv_width] -> (q, k, v) [B, S, H,
    d] float32: SiLU, then q and k of unit length a head, q scaled, and
    where the key heads are fewer each repeated to the value heads that
    read it (not with ``repeat`` off: ``ops.kda_chunk`` reads a key head
    for each of its value heads itself)."""
    la = cfg.linear
    y = jax.nn.silu(conved.astype(jnp.float32))
    kw = la.key_width
    q, k, v = (y[..., lo:hi].reshape(y.shape[:2] + (-1, la.head_dim))
               for lo, hi in ((0, kw), (kw, 2 * kw), (2 * kw, la.conv_width)))

    def unit(a):
        return a * lax.rsqrt(jnp.sum(a * a, -1, keepdims=True) + L2_EPS)

    q, k = unit(q) * la.head_dim**-0.5, unit(k)
    rep = la.n_heads // la.key_heads
    if repeat and rep > 1:
        q, k = jnp.repeat(q, rep, axis=2), jnp.repeat(k, rep, axis=2)
    return q, k, v


def _gated_out(o, gate, kp, cfg):
    """o, gate [B, S, H, d] float32 -> the head-wise normed, gated output in
    the compute type."""
    o = o * lax.rsqrt(jnp.mean(o * o, -1, keepdims=True) + cfg.norm_eps)
    return (o * kp["o_norm"].astype(jnp.float32) * gate).astype(
        cfg.compute_dtype)


def kda_prefill(x, kp, cfg, lengths=None):
    """The layer on whole rows x [B, S, D] (normed) from an empty state:
    ``(out [B, H, S, d]`` before ``wo``, ``{"kda_state": [B, H, d, d],
    "kda_conv": [B, taps - 1, conv_width]})``, the state after each row's
    first ``lengths[b]`` positions (default S).  The positions behind a row's
    length stand still (``g = 0``, ``beta = 0``) and stay out of the
    convolutions' tails; their outputs mean nothing."""
    la = cfg.linear
    b, s, _ = x.shape
    taps = la.conv
    lengths = (jnp.full((b,), s, jnp.int32) if lengths is None
               else jnp.asarray(lengths, jnp.int32))
    real = jnp.arange(s)[None, :] < lengths[:, None]            # [B, S]
    xin, z = _project(x, kp, cfg)
    padded = jnp.pad(xin, ((0, 0), (taps - 1, 0), (0, 0)))
    conved = sum(padded[:, j:j + s] * kp["conv"][j] for j in range(taps))
    # Row b's last taps - 1 real inputs: padded[b, lengths[b] ..].
    tails = jax.vmap(lambda row, n: lax.dynamic_slice_in_dim(
        row, n, taps - 1, 0))(padded, lengths)
    q, k, v = _qkv(conved, cfg, repeat=False)
    g, beta, gate = _gates(x, z, kp, cfg)
    g = jnp.where(real[..., None, None] if g.ndim == 4 else real[..., None],
                  g, 0.0)
    beta = jnp.where(real[..., None], beta, 0.0)
    with jax.named_scope("sw_kda_chunk"):
        o, state = kda_chunk(q, k, v, g, beta)                  # [B, S, H, d]
    out = _gated_out(o, gate, kp, cfg)
    return jnp.moveaxis(out, 2, 1), {"kda_state": state, "kda_conv": tails}


def kda_decode(x, kp, cfg, cache: dict, layer):
    """One token a row, x [B, 1, D] (normed), through layer ``layer`` (its
    index among the linear layers) of the stacked state leaves
    ``cache["kda_state"] [L, B, H, d, d]`` / ``cache["kda_conv"] [L, B,
    taps - 1, conv_width]``: ``(out [B, H, 1, d]`` before ``wo``, the cache
    with that layer's state moved on)``.  A decay a head goes to the one
    decode kernel as a decay a channel, every channel alike: the state's
    bytes are the call, the decays a hundredth of them."""
    tails = lax.dynamic_index_in_dim(cache["kda_conv"], layer, 0,
                                     keepdims=False)
    xin, z = _project(x, kp, cfg)
    window = jnp.concatenate([tails, xin.astype(tails.dtype)], 1)
    conved = jnp.sum(window * kp["conv"], axis=1, keepdims=True)
    q, k, v = _qkv(conved, cfg)
    g, beta, gate = _gates(x, z, kp, cfg)
    if g.ndim == 3:
        g = jnp.broadcast_to(g[..., None], q.shape)
    o, state = kda_step(cache["kda_state"], q[:, 0], k[:, 0], v[:, 0],
                        g[:, 0], beta[:, 0], layer=layer)
    out = _gated_out(o[:, None], gate, kp, cfg)
    return jnp.moveaxis(out, 2, 1), {
        **cache, "kda_state": state,
        "kda_conv": lax.dynamic_update_index_in_dim(
            cache["kda_conv"], window[:, 1:], layer, 0)}
