"""The selective state-space layer (Mamba-1, arXiv 2312.00752): the
projections, short convolution, step and gate around the recurrence
(ops/pallas_ssm.py), in the two forms a served model runs, and what a
request's state holds.

A layer with ``ssm`` in its tree is of this kind (leaf presence is the
marker, as everywhere in this package).  With ``E = cfg.ssm.d_inner``
channels of ``N = d_state`` states each and ``R = dt_rank``, per token
``u`` (the block's normed input):

    s | z      = w_in u                                   E wide each
    x          = SiLU(conv_b + conv(s))
                 depthwise causal, ``conv`` taps, a channel its own taps
    r | B | C  = w_x x                                    R | N | N
    dt         = softplus(w_dt r + b_dt)                  [E], float32
    S          = exp(dt A) * S + (dt x) B^T               A = -exp(a_log)
    y          = C . S + d_skip x                         [E]
    out        = y * SiLU(z)                              -> the block's wo

Tree of one layer's ``ssm`` dict (stacked on a leading layer axis like
every other): ``w_in [D, 2E]`` (s, then z), ``conv_w [taps, E]`` (the LAST
tap on the current token), ``conv_b [E]``, ``w_x [E, R + 2N]``, ``w_dt [R,
E]``, and in float32 ``b_dt [E]``, ``a_log [N, E]``, ``d_skip [E]``.  The
states lie along the second-last axis and the channels along the last
(ops/pallas_ssm.py says why).  The output projection is the block's ``wo
[E, D]``.

A request's state a layer: ``ssm_state [N, E]`` float32 and ``ssm_conv
[taps - 1, E]``, the last inputs of the convolution BEFORE its bias and
activation.  Neither has a position axis: prefill hands back the state
after a row's OWN last token (:func:`ssm_prefill`), decode carries it
(:func:`ssm_decode`).  Both also hand on ``y`` BEFORE the gate, the
layer's MEMORY: what the gated memory units of a later layer multiply
(``llama.gated_memory``).
"""

from __future__ import annotations

import math

import jax
import jax.numpy as jnp
from jax import lax

from ..ops import ssm_scan, ssm_step


def init_ssm_params(key, n_layers: int, cfg) -> dict:
    """Seeded random leaves of ``n_layers`` stacked layers: scaled normal
    projections, ``a_log = log(1 .. N)`` a channel and ``b_dt`` the inverse
    softplus of a step drawn log-uniform from [0.001, 0.1] (Mamba's own
    initialisation)."""
    sp, dt, D = cfg.ssm, cfg.compute_dtype, cfg.d_model
    E, N, R = sp.d_inner, sp.d_state, sp.dt_rank
    ks = jax.random.split(key, 8)

    def norm(k, shape, scale, dtype=dt):
        return (jax.random.normal(k, (n_layers, *shape), jnp.float32)
                * scale).astype(dtype)

    step = jnp.exp(jax.random.uniform(ks[5], (n_layers, E), jnp.float32,
                                      math.log(0.001), math.log(0.1)))
    return {
        "w_in": norm(ks[0], (D, 2 * E), D**-0.5),
        "conv_w": norm(ks[1], (sp.conv, E), sp.conv**-0.5),
        "conv_b": norm(ks[2], (E,), 0.02),
        "w_x": norm(ks[3], (E, R + 2 * N), E**-0.5),
        "w_dt": norm(ks[4], (R, E), R**-0.5),
        "b_dt": step + jnp.log(-jnp.expm1(-step)),        # softplus^-1(step)
        "a_log": jnp.broadcast_to(
            jnp.log(jnp.arange(1, N + 1, dtype=jnp.float32))[:, None],
            (n_layers, N, E)),
        "d_skip": jnp.ones((n_layers, E), jnp.float32),
    }


def _dot32(x, w):
    """``x @ w`` with a float32 result: the step and the transition go
    through an exponential, where a bfloat16 product's rounding shows."""
    return jnp.dot(x, w, preferred_element_type=jnp.float32)


def _step_terms(x, sp_params, cfg):
    """The convolution's activated output ``x [..., E]`` (compute type) ->
    ``(dt [..., E], B [..., N], C [..., N])`` float32."""
    sp = cfg.ssm
    rbc = _dot32(x, sp_params["w_x"])
    r, b, c = (rbc[..., :sp.dt_rank],
               rbc[..., sp.dt_rank:sp.dt_rank + sp.d_state],
               rbc[..., sp.dt_rank + sp.d_state:])
    dt = jax.nn.softplus(_dot32(r.astype(x.dtype), sp_params["w_dt"])
                         + sp_params["b_dt"])
    return dt, b, c


def _gated(y, z, cfg):
    """The read-out ``y`` (float32) times ``SiLU(z)``, in the compute
    type: what goes to ``wo``."""
    return (y * jax.nn.silu(z.astype(jnp.float32))).astype(cfg.compute_dtype)


def ssm_prefill(x, sp_params, cfg, lengths=None):
    """The layer on whole rows x [B, S, D] (normed) from an empty state:
    ``(out [B, S, E]`` before ``wo``, ``{"ssm_state": [B, N, E], "ssm_conv":
    [B, taps - 1, E], "mem": [B, S, E]})``: the state after each row's first
    ``lengths[b]`` positions (default S) and the scan's output at every
    position before the gate.  The positions behind a row's length stand
    still (``ops.ssm_scan``) and stay out of the convolution's tail; their
    outputs mean nothing."""
    from .llama import matmul_w

    sp, f32 = cfg.ssm, jnp.float32
    b, s, _ = x.shape
    taps, E = sp.conv, sp.d_inner
    lengths = (jnp.full((b,), s, jnp.int32) if lengths is None
               else jnp.asarray(lengths, jnp.int32))
    sz = matmul_w(x, sp_params["w_in"])
    xin, z = sz[..., :E], sz[..., E:]
    padded = jnp.pad(xin, ((0, 0), (taps - 1, 0), (0, 0)))
    conv_w = sp_params["conv_w"].astype(f32)
    conved = sum(padded[:, j:j + s].astype(f32) * conv_w[j]
                 for j in range(taps)) + sp_params["conv_b"].astype(f32)
    # Row b's last taps - 1 real inputs: padded[b, lengths[b] ..].
    tails = jax.vmap(lambda row, n: lax.dynamic_slice_in_dim(
        row, n, taps - 1, 0))(padded, lengths)
    act = jax.nn.silu(conved).astype(cfg.compute_dtype)
    dt, bm, cm = _step_terms(act, sp_params, cfg)
    with jax.named_scope("sw_ssm_scan"):
        y, state = ssm_scan(dt, act, bm, cm, -jnp.exp(sp_params["a_log"]),
                            sp_params["d_skip"], lengths)
    return _gated(y, z, cfg), {"ssm_state": state, "ssm_conv": tails,
                               "mem": y.astype(cfg.compute_dtype)}


def ssm_decode(x, sp_params, cfg, cache: dict, layer):
    """One token a row, x [B, 1, D] (normed), through layer ``layer`` (its
    index among the state-space layers) of the stacked state leaves
    ``cache["ssm_state"] [L, B, N, E]`` / ``cache["ssm_conv"] [L, B, taps -
    1, E]``: ``(out [B, 1, E]`` before ``wo``, the cache with that layer's
    state moved on, the read-out before the gate ``[B, 1, E]``)``."""
    from .llama import matmul_w

    E, f32 = cfg.ssm.d_inner, jnp.float32
    tails = lax.dynamic_index_in_dim(cache["ssm_conv"], layer, 0,
                                     keepdims=False)
    sz = matmul_w(x, sp_params["w_in"])
    xin, z = sz[..., :E], sz[..., E:]
    window = jnp.concatenate([tails, xin.astype(tails.dtype)], 1)
    conved = (jnp.sum(window.astype(f32) * sp_params["conv_w"].astype(f32),
                      axis=1) + sp_params["conv_b"].astype(f32))
    act = jax.nn.silu(conved).astype(cfg.compute_dtype)          # [B, E]
    dt, bm, cm = _step_terms(act, sp_params, cfg)
    with jax.named_scope("sw_ssm_step"):
        y, state = ssm_step(cache["ssm_state"], dt, act, bm, cm,
                            -jnp.exp(sp_params["a_log"]),
                            sp_params["d_skip"], layer=layer)
    y = y[:, None]
    return _gated(y, z, cfg), {
        **cache, "ssm_state": state,
        "ssm_conv": lax.dynamic_update_index_in_dim(
            cache["ssm_conv"], window[:, 1:], layer, 0)}, y.astype(
                cfg.compute_dtype)
