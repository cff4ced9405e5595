"""Attention building blocks: online-softmax partials for blockwise and ring
attention.

All functions are pure jax/lax (compiler-friendly static shapes, scan-based
control flow) so they run identically on the virtual CPU mesh and on TPU,
where XLA fuses the softmax chain and tiles the matmuls onto the MXU.  A
hand-tuned pallas kernel for the block partial lands behind the same
interface (ops/pallas_attention.py).

Layout convention: ``q, k, v: [batch, heads, seq, head_dim]``.

The decomposition is the standard flash/ring-attention algebra: a block
produces an *unnormalised* output ``o = exp(s - m) @ v`` with row statistics
``(m = rowmax(s), l = rowsum(exp(s - m)))``; partials merge associatively
with :func:`merge_partials`, which is what lets kv blocks arrive in any
order around the ICI ring (parallel/ring_attention.py).
"""

from __future__ import annotations

from typing import Optional

import jax
import jax.numpy as jnp

NEG_BIG = -0.9e30  # mask fill; avoids -inf NaN traps in exp/max chains


def repeat_kv(x, n_rep: int):
    """Expand grouped KV heads to match query heads (GQA)."""
    if n_rep == 1:
        return x
    b, h, t, d = x.shape
    return jnp.broadcast_to(x[:, :, None, :, :], (b, h, n_rep, t, d)).reshape(b, h * n_rep, t, d)


def partial_attention(
    q,
    k,
    v,
    *,
    q_offset=0,
    kv_offset=0,
    causal: bool = False,
    kv_limit: Optional[int] = None,
    sm_scale: Optional[float] = None,
    window: Optional[int] = None,
    kv_min: Optional[int] = None,
):
    """Attention of ``q`` against one kv block, in mergeable partial form.

    Returns ``(o, m, l)``: unnormalised output ``[B,H,Tq,D]``, row max
    ``[B,H,Tq]``, row sum ``[B,H,Tq]``.  ``q_offset``/``kv_offset`` are the
    global positions of the first query/key token -- the causal mask is
    computed in global coordinates so blocks can come from anywhere in the
    sequence (ring steps pass traced offsets).  ``kv_limit`` masks key
    positions at or beyond that global index (padding); ``kv_min`` masks
    positions below it (a cold rolling cache holds no keys before 0).
    ``window`` (requires ``causal``) keeps only the last ``window`` keys
    per query: ``kv_pos in (q_pos - window, q_pos]`` (Mistral-style
    sliding window).
    """
    if sm_scale is None:
        sm_scale = 1.0 / (q.shape[-1] ** 0.5)
    # Scores and row stats in f32 (MXU takes bf16 inputs, accumulates f32).
    s = jnp.einsum("bhqd,bhkd->bhqk", q, k, preferred_element_type=jnp.float32) * sm_scale
    kv_pos = kv_offset + jnp.arange(k.shape[2])
    mask = jnp.ones((q.shape[2], k.shape[2]), dtype=bool)
    if window is not None and window < 1:
        raise ValueError(f"window must be >= 1, got {window}")
    if causal:
        q_pos = q_offset + jnp.arange(q.shape[2])
        mask = mask & (q_pos[:, None] >= kv_pos[None, :])
        if window is not None:
            mask = mask & (kv_pos[None, :] > q_pos[:, None] - window)
    elif window is not None:
        raise ValueError("window requires causal attention")
    if kv_limit is not None:
        mask = mask & (kv_pos < kv_limit)[None, :]
    if kv_min is not None:
        mask = mask & (kv_pos >= kv_min)[None, :]
    s = jnp.where(mask[None, None, :, :], s, NEG_BIG)
    m = jnp.max(s, axis=-1)
    # Rows with no visible keys: exp(s - m) would be exp(0)=1; zero them.
    p = jnp.where(s > NEG_BIG / 2, jnp.exp(s - m[..., None]), 0.0)
    l = jnp.sum(p, axis=-1)
    o = jnp.einsum(
        "bhqk,bhkd->bhqd", p.astype(v.dtype), v, preferred_element_type=jnp.float32
    )
    return o, m, l


def merge_partials(a, b):
    """Associatively merge two attention partials over the same queries."""
    o_a, m_a, l_a = a
    o_b, m_b, l_b = b
    m = jnp.maximum(m_a, m_b)
    sa = jnp.exp(m_a - m)
    sb = jnp.exp(m_b - m)
    l = l_a * sa + l_b * sb
    o = o_a * sa[..., None].astype(o_a.dtype) + o_b * sb[..., None].astype(o_b.dtype)
    return o, m, l


def zero_partial(q):
    """Identity element for merge_partials over queries shaped like ``q``.
    Accumulators are f32 regardless of compute dtype."""
    b, h, tq, d = q.shape
    return (
        jnp.zeros((b, h, tq, d), dtype=jnp.float32),
        jnp.full((b, h, tq), NEG_BIG, dtype=jnp.float32),
        jnp.zeros((b, h, tq), dtype=jnp.float32),
    )


def finalize_partial(o, m, l, out_dtype=None):
    """Normalise a merged partial into the attention output."""
    out = o / jnp.maximum(l, 1e-30)[..., None]
    return out.astype(out_dtype) if out_dtype is not None else out


def blockwise_attention(
    q,
    k,
    v,
    *,
    causal: bool = False,
    block_k: int = 512,
    sm_scale: Optional[float] = None,
    window: Optional[int] = None,
):
    """Single-device flash-style attention: scan over kv blocks with the
    online-softmax merge, never materialising the full [Tq, Tkv] matrix.
    Grouped-query kv (fewer kv heads than q heads) is expanded here.
    ``window``: sliding-window causal (see :func:`partial_attention`)."""
    if k.shape[1] != q.shape[1]:
        n_rep = q.shape[1] // k.shape[1]
        k = repeat_kv(k, n_rep)
        v = repeat_kv(v, n_rep)
    b, h, tq, d = q.shape
    tkv = k.shape[2]
    block_k = min(block_k, tkv)
    nblocks = (tkv + block_k - 1) // block_k
    pad = nblocks * block_k - tkv
    if pad:
        k = jnp.pad(k, ((0, 0), (0, 0), (0, pad), (0, 0)))
        v = jnp.pad(v, ((0, 0), (0, 0), (0, pad), (0, 0)))
    kb = k.reshape(b, h, nblocks, block_k, d).transpose(2, 0, 1, 3, 4)
    vb = v.reshape(b, h, nblocks, block_k, v.shape[3]).transpose(2, 0, 1, 3, 4)
    offs = jnp.arange(nblocks) * block_k

    def step(carry, blk):
        k_i, v_i, off = blk
        part = partial_attention(
            q, k_i, v_i,
            q_offset=0, kv_offset=off,
            causal=causal, kv_limit=tkv if pad else None, sm_scale=sm_scale,
            window=window,
        )
        return merge_partials(carry, part), None

    # The accumulator follows the VALUES' width (latent attention's are
    # narrower than its keys).
    _, m0, l0 = zero_partial(q)
    o0 = jnp.zeros(q.shape[:3] + v.shape[3:], jnp.float32)
    (o, m, l), _ = jax.lax.scan(step, (o0, m0, l0), (kb, vb, offs))
    return finalize_partial(o, m, l, out_dtype=q.dtype)


def attention_reference(q, k, v, *, causal: bool = False,
                        sm_scale: Optional[float] = None,
                        window: Optional[int] = None):
    """Plain materialised-softmax attention (test oracle)."""
    if sm_scale is None:
        sm_scale = 1.0 / (q.shape[-1] ** 0.5)
    s = jnp.einsum("bhqd,bhkd->bhqk", q, k) * sm_scale
    if window is not None and window < 1:
        raise ValueError(f"window must be >= 1, got {window}")
    if causal:
        tq, tkv = q.shape[2], k.shape[2]
        qp = jnp.arange(tq)[:, None]
        kp = jnp.arange(tkv)[None, :]
        mask = qp >= kp
        if window is not None:
            mask = mask & (kp > qp - window)
        s = jnp.where(mask[None, None, :, :], s, NEG_BIG)
    elif window is not None:
        raise ValueError("window requires causal attention")
    p = jax.nn.softmax(s, axis=-1)
    return jnp.einsum("bhqk,bhkd->bhqd", p.astype(v.dtype), v)
