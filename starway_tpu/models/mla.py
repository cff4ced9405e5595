"""Multi-head latent attention (DeepSeek-V2/V3, Kimi-K2): the projections of
both of its forms, and what its cache holds.

A layer with ``wkv_a`` in its tree is latent (leaf presence is the marker,
as everywhere in this package).  Per token it caches ONE row for all heads:
``[c_kv | k_pe]``, the normed compressed key/value (``kv_rank`` values) and
the one shared RoPE key head (``rope_dim`` values) after rotation, then
zeros up to ``LatentAttn.cache_width`` (whole 128-lane tiles).  A layer
that does not rotate (``cos`` None: a NoPE layer of ``cfg.kinds``) caches
``k_pe`` as projected and leaves ``q_pe`` alone; both forms stay the same
bilinear form.

* **Expanded** (prefill, training): every head's ``k = [k_nope | k_pe]``
  and ``v`` are rebuilt from ``c_kv`` through ``wkv_b`` and attention runs
  at ``H`` heads of ``nope_dim + rope_dim`` over ``v_dim``-wide values.
* **Absorbed** (cached decode): ``W_UK`` (the k_nope half of ``wkv_b``) is
  applied to the QUERY, ``q_lat = q_nope W_UK^T``, so scores are taken
  against the cached rows as they lie, ``[q_lat | q_pe] . [c_kv | k_pe]``;
  the attention output ``P c_kv`` is expanded afterwards by ``W_UV``.  The
  two are the same bilinear form, regrouped (tests/test_mla.py pins it).

Tree of one layer (stacked on a leading layer axis like every other):
``wq_a [D, q_rank]``, ``q_norm [q_rank]``, ``wq_b [q_rank, H*(nope+rope)]``
(or, ``LatentAttn.q_rank`` None, the one direct ``wq [D, H*(nope+rope)]``),
``wkv_a [D, kv_rank+rope]``, ``kv_norm [kv_rank]``,
``wkv_b [kv_rank, H*(nope+v)]`` (per head ``[k_nope | v]``),
``wo [H*v, D]``.  RoPE columns are in this package's split-half order;
``hf_convert`` applies the published interleaved-to-halves permutation.
"""

from __future__ import annotations

import jax.numpy as jnp


def _queries(x, lp, cfg):
    """x [B, S, D] -> (q_nope [B, H, S, nope], q_pe [B, H, S, rope]),
    q_pe before its rotation."""
    from .llama import cfg_rmsnorm, matmul_w

    la = cfg.latent
    b, s = x.shape[:2]
    if "wq_a" in lp:
        cq = cfg_rmsnorm(matmul_w(x, lp["wq_a"]), lp["q_norm"], cfg)
        q = matmul_w(cq, lp["wq_b"])
    else:
        q = matmul_w(x, lp["wq"])
    q = q.reshape(
        b, s, cfg.n_heads, la.nope_dim + la.rope_dim).transpose(0, 2, 1, 3)
    return q[..., :la.nope_dim], q[..., la.nope_dim:]


def _rotate(x, cos, sin):
    """RoPE, or nothing in a layer that has none (``cos`` None)."""
    from .llama import apply_rope

    return x if cos is None else apply_rope(x, cos, sin)


def latent_rows(x, lp, cfg, cos, sin):
    """What the cache holds of x [B, S, D]: ``[B, 1, S, cache_width]``,
    c_kv after its norm beside k_pe after RoPE, zeros above."""
    from .llama import cfg_rmsnorm, matmul_w

    r = cfg.latent.kv_rank
    kv = matmul_w(x, lp["wkv_a"])[:, None]
    return _to_cache_width(jnp.concatenate(
        [cfg_rmsnorm(kv[..., :r], lp["kv_norm"], cfg),
         _rotate(kv[..., r:], cos, sin)], axis=-1), cfg)


def _to_cache_width(x, cfg):
    pad = cfg.latent.cache_width - x.shape[-1]
    return jnp.pad(x, ((0, 0),) * (x.ndim - 1) + ((0, pad),)) if pad else x


def _wkv_b(lp, cfg):
    la = cfg.latent
    return lp["wkv_b"].reshape(la.kv_rank, cfg.n_heads, la.nope_dim + la.v_dim)


def project_expanded(x, lp, cfg, cos, sin):
    """-> (q [B, H, S, nope+rope], k the same, v [B, H, S, v_dim],
    latent rows [B, 1, S, cache_width])."""
    la = cfg.latent
    q_nope, q_pe = _queries(x, lp, cfg)
    rows = latent_rows(x, lp, cfg, cos, sin)
    kv = jnp.einsum("bsr,rhe->bhse", rows[:, 0, :, :la.kv_rank],
                    _wkv_b(lp, cfg))
    k_pe = jnp.broadcast_to(
        rows[..., la.kv_rank:la.kv_rank + la.rope_dim], q_pe.shape)
    q = jnp.concatenate([q_nope, _rotate(q_pe, cos, sin)], axis=-1)
    k = jnp.concatenate([kv[..., :la.nope_dim], k_pe], axis=-1)
    return q, k, kv[..., la.nope_dim:], rows


def project_absorbed(x, lp, cfg, cos, sin):
    """-> (q [B, H, C, cache_width] = [q_nope W_UK^T | q_pe | 0], latent
    rows [B, 1, C, cache_width])."""
    la = cfg.latent
    q_nope, q_pe = _queries(x, lp, cfg)
    q_lat = jnp.einsum("bhcn,rhn->bhcr", q_nope,
                       _wkv_b(lp, cfg)[..., :la.nope_dim])
    q = jnp.concatenate([q_lat, _rotate(q_pe, cos, sin)], axis=-1)
    return _to_cache_width(q, cfg), latent_rows(x, lp, cfg, cos, sin)


def expand_values(o_lat, lp, cfg):
    """The absorbed attention's output ``P c_kv [B, H, C, kv_rank]``
    through ``W_UV`` -> ``[B, H, C, v_dim]``."""
    return jnp.einsum("bhcr,rhv->bhcv", o_lat,
                      _wkv_b(lp, cfg)[..., cfg.latent.nope_dim:])
