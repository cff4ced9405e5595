"""Pallas TPU flash-attention: forward + backward, differentiable end-to-end.

The hand-scheduled counterpart of ops/attention.py's lax implementation:
same online-softmax algebra, but tiled explicitly onto VMEM with f32
accumulator scratch that persists across the (sequential, innermost) kv-block
grid dimension, bf16 inputs feeding the MXU, and causal blocks that are
entirely masked skipped outright (their HBM DMA elided by repeating the
clamped block index).

Block sizes matter enormously on TPU: the per-grid-cell fixed cost (DMA
setup, softmax VPU work that cannot overlap the first matmul) is ~1 µs, so
128x128 cells leave the MXU >90% idle; hence the defaults (block_q=1024,
block_k=1024).  Their speed has not been measured this round (harness:
scripts/kernel_bench.py; the builder-reported pre-round figures are in
ROADMAP.md S2).

The backward runs as two passes in the same [block_q, block_k] score layout
as the forward; the transposed products (dK = dS^T Q, dV = P^T dO) are
expressed as dot_generals contracting dimension 0 of both operands, so no
in-kernel transposes are needed.  Per-q-row constants (lse, delta) are
carried as [BH, S, 8] arrays — lane dim 8 keeps the block shape legal while
column 0 broadcasts along lanes, the cheap direction:

  pass A (kv-stationary): grid (B*Hkv, kv blocks, rep*q blocks); accumulates
    dK/dV in f32 VMEM scratch across the q-block sweep, summing the grouped
    query heads of each kv head (GQA) in the same sweep.
  pass B (q-stationary): grid (B*Hq, q blocks, kv blocks); accumulates dQ.

Both recompute p = exp(s - lse) from the forward's saved log-sum-exp, the
standard flash trade (FLOPs for HBM).  `flash_attention` carries a
jax.custom_vjp, so consumers (:func:`self_attention` on TPU)
differentiate through the kernel on TPU and through interpret mode in CPU
tests.

Layouts: ``q [B, Hq, S, D]``, ``k/v [B, Hkv, S, D]`` (grouped kv accepted
directly — the kernel indexes the right kv head per q head, no repeat_kv
materialisation).

Reference hook: the reference (Clouder0/starway) has no kernels — this layer
is the TPU build's own; the lax oracle it must match is
ops/attention.py::blockwise_attention.
"""

from __future__ import annotations

import functools
from typing import NamedTuple, Optional

import jax
import jax.numpy as jnp
from jax.ad_checkpoint import checkpoint_name
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from . import dispatch
from .attention import (NEG_BIG, blockwise_attention, partial_attention,
                        repeat_kv)

DEFAULT_BLOCK_Q = 1024
DEFAULT_BLOCK_K = 1024
# The backward holds ~4 [block_q, block_k] f32 intermediates live per cell
# (s, p, dp, ds) on top of the kv-resident blocks; 1024x1024 exceeds v5e
# VMEM (the compile never converges), 512x1024 fits and measures ~97
# TFLOP/s effective fwd+bwd.
DEFAULT_BWD_BLOCK_Q = 512
DEFAULT_BWD_BLOCK_K = 1024


class _Cfg(NamedTuple):
    """Static kernel configuration (hashable: custom_vjp nondiff arg)."""

    causal: bool
    sm_scale: float
    block_q: int
    block_k: int
    bwd_block_q: int
    bwd_block_k: int
    interpret: bool
    window: Optional[int] = None  # sliding window (requires causal)


def _round_up(x: int, m: int) -> int:
    return ((x + m - 1) // m) * m


# ---------------------------------------------------------------------------
# forward
# ---------------------------------------------------------------------------


def _mask_scores(s, q_start, k_start, kv_len, kv_pad, causal,
                 k_start_local=None, window=None):
    """Apply causal/window/padding masking to a score block.

    ``q_start``/``k_start`` are GLOBAL sequence coordinates (they differ
    from the in-array block position when a ring step supplies offsets);
    ``k_start_local`` is the in-array key position the padding compare
    needs — it defaults to ``k_start`` for the offset-free path.
    ``window`` (with ``causal``) keeps ``k_pos in (q_pos - window, q_pos]``.

    The kv-padding compare is skipped at *trace* time when the sequence
    needs no padding (the common case); a scalar `lax.cond` around the
    whole thing was measured slower than unconditional masking — Mosaic
    fuses the iota/compare/select into the softmax chain, a vector branch
    does not.
    """
    if k_start_local is None:
        k_start_local = k_start
    mask = None
    if kv_pad != kv_len:  # Python-level: only traced when padding exists
        k_pos = k_start_local + jax.lax.broadcasted_iota(jnp.int32, s.shape, 1)
        mask = k_pos < kv_len
    if causal:
        q_pos = q_start + jax.lax.broadcasted_iota(jnp.int32, s.shape, 0)
        k_pos = k_start + jax.lax.broadcasted_iota(jnp.int32, s.shape, 1)
        c = q_pos >= k_pos
        if window is not None:
            c = c & (k_pos > q_pos - window)
        mask = c if mask is None else mask & c
    return s if mask is None else jnp.where(mask, s, NEG_BIG)


def _fwd_kernel(q_ref, k_ref, v_ref, o_ref, *rest, causal: bool,
                sm_scale: float, block_q: int, block_k: int, kv_len: int,
                kv_pad: int, save_lse: bool, window: "int | None" = None):
    if save_lse:
        lse_ref, m_scr, l_scr, acc_scr = rest
    else:
        m_scr, l_scr, acc_scr = rest
    qi = pl.program_id(1)
    ki = pl.program_id(2)
    n_k = pl.num_programs(2)

    @pl.when(ki == 0)
    def _init():
        m_scr[:] = jnp.full_like(m_scr, NEG_BIG)
        l_scr[:] = jnp.zeros_like(l_scr)
        acc_scr[:] = jnp.zeros_like(acc_scr)

    q_start = qi * block_q
    k_start = ki * block_k

    def _body():
        q = q_ref[0]  # [block_q, D]
        k = k_ref[0]  # [block_k, D]
        v = v_ref[0]
        s = jax.lax.dot_general(
            q, k, (((1,), (1,)), ((), ())), preferred_element_type=jnp.float32
        ) * sm_scale  # [block_q, block_k]
        s = _mask_scores(s, q_start, k_start, kv_len, kv_pad, causal,
                         window=window)

        # Row stats live in (block_q, 128) lanes (TPU tile granularity);
        # column 0 is authoritative.  Masked entries hold NEG_BIG, so
        # exp(s - m_new) underflows to exactly 0 — no select needed for
        # full causal (every row sees at least key 0 on its first live kv
        # block, so m_new is always finite).  With a WINDOW an entire row
        # of a live block can be masked (its window starts in a later
        # block); clamping only exp's argument keeps its p at exactly 0.
        m_prev = m_scr[:, :1]
        m_new = jnp.maximum(m_prev, jnp.max(s, axis=1, keepdims=True))
        if window is not None:
            p = jnp.exp(s - jnp.maximum(m_new, NEG_BIG / 2))
        else:
            p = jnp.exp(s - m_new)
        corr = jnp.exp(m_prev - m_new)
        l_new = l_scr[:, :1] * corr + jnp.sum(p, axis=1, keepdims=True)
        acc_scr[:] = acc_scr[:] * corr + jax.lax.dot_general(
            p.astype(v.dtype), v, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32,
        )
        m_scr[:] = jnp.broadcast_to(m_new, m_scr.shape)
        l_scr[:] = jnp.broadcast_to(l_new, l_scr.shape)

    if causal:
        # Live iff the block's first key position can be visible to the
        # block's last query position — and, with a window, its last key
        # position can still be inside the block's first query's window.
        live = k_start <= q_start + block_q - 1
        if window is not None:
            live = live & (k_start + block_k - 1 > q_start - window)
        pl.when(live)(_body)
    else:
        _body()

    @pl.when(ki == n_k - 1)
    def _finalize():
        l = jnp.maximum(l_scr[:, :1], 1e-30)
        o_ref[0] = (acc_scr[:] / l).astype(o_ref.dtype)
        if save_lse:
            lse = m_scr[:, :1] + jnp.log(l)  # [block_q, 1]
            lse_ref[0] = jnp.broadcast_to(lse, lse_ref.shape[1:])


def _fwd_impl(q, k, v, cfg: _Cfg, save_lse: bool):
    b, hq, s, d = q.shape
    hkv = k.shape[1]
    n_rep = hq // hkv
    kv_len = k.shape[2]
    dv = v.shape[3]  # the values may be narrower than q/k (latent attention)

    block_q = min(cfg.block_q, _round_up(s, 8))
    block_k = min(cfg.block_k, _round_up(kv_len, 8))
    s_pad = _round_up(s, block_q)
    kv_pad = _round_up(kv_len, block_k)
    if s_pad != s:
        q = jnp.pad(q, ((0, 0), (0, 0), (0, s_pad - s), (0, 0)))
    if kv_pad != kv_len:
        k = jnp.pad(k, ((0, 0), (0, 0), (0, kv_pad - kv_len), (0, 0)))
        v = jnp.pad(v, ((0, 0), (0, 0), (0, kv_pad - kv_len), (0, 0)))

    qf = q.reshape(b * hq, s_pad, d)
    kf = k.reshape(b * hkv, kv_pad, d)
    vf = v.reshape(b * hkv, kv_pad, dv)

    def kv_head(bh):  # q-head flat index -> kv-head flat index
        return (bh // hq) * hkv + (bh % hq) // n_rep

    def kv_index(bh, i, j):
        # Causal: clamp at the last block any query row of q-block i can
        # see.  The kernel skips those blocks' compute (pl.when); repeating
        # the block index makes the pipeline elide their HBM copies too, so
        # the upper triangle costs no bandwidth (~2x saving at long S).
        # A window adds the symmetric LOWER clamp: blocks entirely below
        # every row's window are elided the same way.
        if cfg.causal:
            j = jnp.minimum(j, (i * block_q + block_q - 1) // block_k)
            if cfg.window is not None:
                lo = jnp.maximum(i * block_q - (cfg.window - 1), 0) // block_k
                j = jnp.maximum(j, lo)
        return (kv_head(bh), j, 0)

    grid = (b * hq, s_pad // block_q, kv_pad // block_k)
    out_shapes = [jax.ShapeDtypeStruct((b * hq, s_pad, dv), q.dtype)]
    out_specs = [pl.BlockSpec((1, block_q, dv), lambda bh, i, j: (bh, i, 0))]
    if save_lse:
        # Lane dim 8 (not 1): keeps the block tiling legal; col 0 is the
        # value, the rest redundant broadcast (tiny: S*8 f32 per head).
        out_shapes.append(jax.ShapeDtypeStruct((b * hq, s_pad, 8), jnp.float32))
        out_specs.append(
            pl.BlockSpec((1, block_q, 8), lambda bh, i, j: (bh, i, 0)))
    out = pl.pallas_call(
        functools.partial(
            _fwd_kernel, causal=cfg.causal, sm_scale=cfg.sm_scale,
            block_q=block_q, block_k=block_k, kv_len=kv_len, kv_pad=kv_pad,
            save_lse=save_lse, window=cfg.window,
        ),
        grid=grid,
        in_specs=[
            pl.BlockSpec((1, block_q, d), lambda bh, i, j: (bh, i, 0)),
            pl.BlockSpec((1, block_k, d), kv_index),
            pl.BlockSpec((1, block_k, dv), kv_index),
        ],
        out_specs=out_specs,
        out_shape=out_shapes,
        scratch_shapes=[
            pltpu.VMEM((block_q, 128), jnp.float32),
            pltpu.VMEM((block_q, 128), jnp.float32),
            pltpu.VMEM((block_q, dv), jnp.float32),
        ],
        interpret=cfg.interpret,
        name="sw_flash_fwd",
    )(qf, kf, vf)
    if save_lse:
        o, lse = out
        return o.reshape(b, hq, s_pad, dv)[:, :, :s, :], lse[:, :s]
    return out[0].reshape(b, hq, s_pad, dv)[:, :, :s, :]


# ---------------------------------------------------------------------------
# backward + ring-step partials
# ---------------------------------------------------------------------------
#
# Same [block_q, block_k] score layout as the forward.  p is recomputed
# already *normalised* (p = exp(s - lse)), so no l bookkeeping:
#   dV  = P^T dO                      dP = dO V^T
#   dS  = P o (dP - delta)            delta = rowsum(dO o O)
#   dK  = sm_scale * dS^T Q           dQ = sm_scale * dS K
# The transposed products contract dim 0 of both operands (A^T B form) —
# the MXU takes them directly.  sm_scale on dK/dQ is applied once at
# emission, not per block element.
#
# Every kernel below takes a scalar-prefetch int32[2] = [q_offset, kv_offset]
# in GLOBAL sequence coordinates.  The plain flash_attention backward passes
# zeros; ring attention (parallel/ring_attention.py) passes the traced
# rotation offsets, which feed both the causal masking and the runtime
# DMA-elision clamps in the index maps — dead blocks cost neither MXU nor
# HBM bandwidth regardless of which ring step is executing.


def _bwd_block(q, do, k, v, lse, delta, *, causal, sm_scale, q_glob, k_glob,
               k_local, kv_len, kv_pad, window=None):
    """Shared recompute: returns (p, ds), both [block_q, block_k] f32.
    Masked entries get p = exp(NEG_BIG - lse) = 0 (lse is finite for every
    real row), so no all-masked-row handling is needed here even with a
    window."""
    s = jax.lax.dot_general(
        q, k, (((1,), (1,)), ((), ())), preferred_element_type=jnp.float32
    ) * sm_scale
    s = _mask_scores(s, q_glob, k_glob, kv_len, kv_pad, causal,
                     k_start_local=k_local, window=window)
    p = jnp.exp(s - lse)  # normalised probs; masked entries -> 0
    dp = jax.lax.dot_general(
        do, v, (((1,), (1,)), ((), ())), preferred_element_type=jnp.float32
    )
    ds = p * (dp - delta)
    return p, ds


def _bwd_dkv_kernel(offs_ref, q_ref, do_ref, k_ref, v_ref, lse_ref, delta_ref,
                    dk_ref, dv_ref, dk_scr, dv_scr, *, causal: bool,
                    sm_scale: float, block_q: int, block_k: int,
                    kv_len: int, kv_pad: int, n_q: int,
                    window: "int | None" = None):
    ki = pl.program_id(1)
    inner = pl.program_id(2)
    n_inner = pl.num_programs(2)
    qi = jax.lax.rem(inner, n_q)

    @pl.when(inner == 0)
    def _init():
        dk_scr[:] = jnp.zeros_like(dk_scr)
        dv_scr[:] = jnp.zeros_like(dv_scr)

    k_local = ki * block_k
    q_glob = offs_ref[0] + qi * block_q
    k_glob = offs_ref[1] + k_local

    def _body():
        q = q_ref[0]                 # [block_q, D]
        do = do_ref[0]
        p, ds = _bwd_block(
            q, do, k_ref[0], v_ref[0], lse_ref[0][:, :1], delta_ref[0][:, :1],
            causal=causal, sm_scale=sm_scale, q_glob=q_glob,
            k_glob=k_glob, k_local=k_local, kv_len=kv_len, kv_pad=kv_pad,
            window=window,
        )
        # P^T dO and dS^T Q: contract the shared block_q dim (dim 0 of both).
        dv_scr[:] += jax.lax.dot_general(
            p.astype(do.dtype), do, (((0,), (0,)), ((), ())),
            preferred_element_type=jnp.float32,
        )
        dk_scr[:] += jax.lax.dot_general(
            ds.astype(q.dtype), q, (((0,), (0,)), ((), ())),
            preferred_element_type=jnp.float32,
        )

    if causal:
        # Live iff this q block reaches at or below the kv block's first
        # row — and, with a window, starts before the block's last key
        # falls out of every query's window.
        live = q_glob + block_q - 1 >= k_glob
        if window is not None:
            live = live & (k_glob + block_k - 1 > q_glob - window)
        pl.when(live)(_body)
    else:
        _body()

    @pl.when(inner == n_inner - 1)
    def _emit():
        dk_ref[0] = (dk_scr[:] * sm_scale).astype(dk_ref.dtype)
        dv_ref[0] = dv_scr[:].astype(dv_ref.dtype)


def _bwd_dq_kernel(offs_ref, q_ref, do_ref, k_ref, v_ref, lse_ref, delta_ref,
                   dq_ref, dq_scr, *, causal: bool, sm_scale: float,
                   block_q: int, block_k: int, kv_len: int, kv_pad: int,
                   window: "int | None" = None):
    qi = pl.program_id(1)
    ki = pl.program_id(2)
    n_k = pl.num_programs(2)

    @pl.when(ki == 0)
    def _init():
        dq_scr[:] = jnp.zeros_like(dq_scr)

    k_local = ki * block_k
    q_glob = offs_ref[0] + qi * block_q
    k_glob = offs_ref[1] + k_local

    def _body():
        k = k_ref[0]
        _, ds = _bwd_block(
            q_ref[0], do_ref[0], k, v_ref[0], lse_ref[0][:, :1],
            delta_ref[0][:, :1], causal=causal, sm_scale=sm_scale,
            q_glob=q_glob, k_glob=k_glob, k_local=k_local, kv_len=kv_len,
            kv_pad=kv_pad, window=window,
        )
        dq_scr[:] += jax.lax.dot_general(
            ds.astype(k.dtype), k, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32,
        )

    if causal:
        live = k_glob <= q_glob + block_q - 1
        if window is not None:
            live = live & (k_glob + block_k - 1 > q_glob - window)
        pl.when(live)(_body)
    else:
        _body()

    @pl.when(ki == n_k - 1)
    def _emit():
        dq_ref[0] = (dq_scr[:] * sm_scale).astype(dq_ref.dtype)


def _run_bwd_passes(qf, dof, kf, vf, lse8, delta8, offs, *, b, hq, hkv,
                    s_pad, kv_pad, d, kv_len, block_q, block_k, causal,
                    sm_scale, interpret, dq_dtype, dkv_dtype, window=None):
    """Both backward passes over flattened [BH, S, D] operands.

    ``offs`` is the int32[2] global-offset vector (zeros for the plain
    path).  Returns (dq [b*hq, s_pad, d], dk, dv [b*hkv, kv_pad, d]).
    """
    n_rep = hq // hkv
    n_q = s_pad // block_q
    n_kv = kv_pad // block_k

    # ---- pass A: dK/dV (kv-stationary, sweeps rep x q blocks) ----
    def q_head(bkv, inner):
        r = inner // n_q
        return (bkv // hkv) * hq + (bkv % hkv) * n_rep + r

    def qi_eff(ki, inner, offs):
        qi = jax.lax.rem(inner, n_q)
        if causal:
            # Clamp dead (above-diagonal) q blocks onto the first live one:
            # their compute is skipped and their HBM DMA elided.  Global
            # coords: first live q row is kv_off + ki*bk - q_off.
            first = (offs[1] + ki * block_k - offs[0]) // block_q
            qi = jnp.maximum(qi, jnp.clip(first, 0, n_q - 1))
            if window is not None:
                # Window: q blocks past every key's window are dead too.
                last = (offs[1] + ki * block_k + block_k - 1 + window - 1
                        - offs[0]) // block_q
                qi = jnp.minimum(qi, jnp.clip(last, 0, n_q - 1))
        return qi

    qdo_spec = pl.BlockSpec(
        (1, block_q, d),
        lambda bkv, ki, inner, offs: (q_head(bkv, inner),
                                      qi_eff(ki, inner, offs), 0))
    row_spec = pl.BlockSpec(
        (1, block_q, 8),
        lambda bkv, ki, inner, offs: (q_head(bkv, inner),
                                      qi_eff(ki, inner, offs), 0))
    kv_spec = pl.BlockSpec(
        (1, block_k, d), lambda bkv, ki, inner, offs: (bkv, ki, 0))

    grid_a = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1,
        grid=(b * hkv, n_kv, n_rep * n_q),
        in_specs=[qdo_spec, qdo_spec, kv_spec, kv_spec, row_spec, row_spec],
        out_specs=[kv_spec, kv_spec],
        scratch_shapes=[
            pltpu.VMEM((block_k, d), jnp.float32),
            pltpu.VMEM((block_k, d), jnp.float32),
        ],
    )
    dk, dv = pl.pallas_call(
        functools.partial(
            _bwd_dkv_kernel, causal=causal, sm_scale=sm_scale,
            block_q=block_q, block_k=block_k, kv_len=kv_len, kv_pad=kv_pad,
            n_q=n_q, window=window,
        ),
        grid_spec=grid_a,
        out_shape=[
            jax.ShapeDtypeStruct((b * hkv, kv_pad, d), dkv_dtype),
            jax.ShapeDtypeStruct((b * hkv, kv_pad, d), dkv_dtype),
        ],
        interpret=interpret,
        name="sw_flash_bwd_dkv",
    )(offs, qf, dof, kf, vf, lse8, delta8)

    # ---- pass B: dQ (q-stationary, sweeps kv blocks) ----
    def kv_head(bh):
        return (bh // hq) * hkv + (bh % hq) // n_rep

    def ki_eff(i, j, offs):
        if causal:
            # Last kv block any row of q block i can see, in global coords.
            last = (offs[0] + i * block_q + block_q - 1 - offs[1]) // block_k
            j = jnp.minimum(j, jnp.clip(last, 0, n_kv - 1))
            if window is not None:
                first = (offs[0] + i * block_q - (window - 1)
                         - offs[1]) // block_k
                j = jnp.maximum(j, jnp.clip(first, 0, n_kv - 1))
        return j

    qdo_spec_b = pl.BlockSpec(
        (1, block_q, d), lambda bh, i, j, offs: (bh, i, 0))
    row_spec_b = pl.BlockSpec(
        (1, block_q, 8), lambda bh, i, j, offs: (bh, i, 0))
    kv_spec_b = pl.BlockSpec(
        (1, block_k, d), lambda bh, i, j, offs: (kv_head(bh),
                                                 ki_eff(i, j, offs), 0))

    grid_b = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1,
        grid=(b * hq, s_pad // block_q, n_kv),
        in_specs=[qdo_spec_b, qdo_spec_b, kv_spec_b, kv_spec_b, row_spec_b,
                  row_spec_b],
        out_specs=pl.BlockSpec(
            (1, block_q, d), lambda bh, i, j, offs: (bh, i, 0)),
        scratch_shapes=[pltpu.VMEM((block_q, d), jnp.float32)],
    )
    dq = pl.pallas_call(
        functools.partial(
            _bwd_dq_kernel, causal=causal, sm_scale=sm_scale,
            block_q=block_q, block_k=block_k, kv_len=kv_len, kv_pad=kv_pad,
            window=window,
        ),
        grid_spec=grid_b,
        out_shape=jax.ShapeDtypeStruct((b * hq, s_pad, d), dq_dtype),
        interpret=interpret,
        name="sw_flash_bwd_dq",
    )(offs, qf, dof, kf, vf, lse8, delta8)
    return dq, dk, dv


def _bwd_operands(q, do, k, v, lse8, delta, block_q, block_k):
    """Pad + flatten backward operands; returns dict of kernel inputs."""
    b, hq, s, d = q.shape
    hkv = k.shape[1]
    kv_len = k.shape[2]
    s_pad = _round_up(s, block_q)
    kv_pad = _round_up(kv_len, block_k)

    if s_pad != s:
        pad = ((0, 0), (0, 0), (0, s_pad - s), (0, 0))
        q = jnp.pad(q, pad)
        do = jnp.pad(do, pad)  # zero rows -> zero dk/dv/ds contributions
        delta = jnp.pad(delta, ((0, 0), (0, 0), (0, s_pad - s)))
        # Padded q rows contribute nothing (do = 0), but pad lse with +big
        # so p = exp(s - lse) underflows to 0 instead of risking inf*0.
        lse8 = jnp.pad(lse8, ((0, 0), (0, s_pad - s), (0, 0)),
                       constant_values=-NEG_BIG)
    if kv_pad != kv_len:
        pad = ((0, 0), (0, 0), (0, kv_pad - kv_len), (0, 0))
        k = jnp.pad(k, pad)
        v = jnp.pad(v, pad)

    return dict(
        qf=q.reshape(b * hq, s_pad, d),
        dof=do.reshape(b * hq, s_pad, d),
        kf=k.reshape(b * hkv, kv_pad, d),
        vf=v.reshape(b * hkv, kv_pad, d),
        lse8=lse8,
        delta8=jnp.broadcast_to(
            delta.reshape(b * hq, s_pad)[:, :, None], (b * hq, s_pad, 8)),
        b=b, hq=hq, hkv=hkv, s_pad=s_pad, kv_pad=kv_pad, d=d, kv_len=kv_len,
    )


def _bwd_impl(q, k, v, o, lse, do, cfg: _Cfg):
    b, hq, s, d = q.shape
    hkv = k.shape[1]
    kv_len = k.shape[2]
    block_q = min(cfg.bwd_block_q, _round_up(s, 8))
    block_k = min(cfg.bwd_block_k, _round_up(kv_len, 8))

    # delta = rowsum(dO o O): one cheap fused XLA pass, [B,Hq,S].
    delta = jnp.sum(do.astype(jnp.float32) * o.astype(jnp.float32), axis=-1)
    ops = _bwd_operands(q, do, k, v, lse, delta, block_q, block_k)
    dq, dk, dv = _run_bwd_passes(
        ops.pop("qf"), ops.pop("dof"), ops.pop("kf"), ops.pop("vf"),
        ops.pop("lse8"), ops.pop("delta8"), jnp.zeros((2,), jnp.int32),
        block_q=block_q, block_k=block_k, causal=cfg.causal,
        sm_scale=cfg.sm_scale, interpret=cfg.interpret,
        dq_dtype=q.dtype, dkv_dtype=k.dtype, window=cfg.window, **ops)

    dq = dq.reshape(b, hq, -1, d)[:, :, :s, :]
    dk = dk.reshape(b, hkv, -1, d)[:, :, :kv_len, :]
    dv = dv.reshape(b, hkv, -1, d)[:, :, :kv_len, :]
    return dq, dk, dv


# ---------------------------------------------------------------------------
# ring-step primitives: unnormalised partials at traced global offsets
# ---------------------------------------------------------------------------


def _partial_kernel(offs_ref, q_ref, k_ref, v_ref, o_ref, m_ref, l_ref,
                    m_scr, l_scr, acc_scr, *, causal: bool, sm_scale: float,
                    block_q: int, block_k: int, kv_len: int, kv_pad: int):
    qi = pl.program_id(1)
    ki = pl.program_id(2)
    n_k = pl.num_programs(2)

    @pl.when(ki == 0)
    def _init():
        m_scr[:] = jnp.full_like(m_scr, NEG_BIG)
        l_scr[:] = jnp.zeros_like(l_scr)
        acc_scr[:] = jnp.zeros_like(acc_scr)

    k_local = ki * block_k
    q_glob = offs_ref[0] + qi * block_q
    k_glob = offs_ref[1] + k_local

    def _body():
        q = q_ref[0]
        k = k_ref[0]
        v = v_ref[0]
        s = jax.lax.dot_general(
            q, k, (((1,), (1,)), ((), ())), preferred_element_type=jnp.float32
        ) * sm_scale
        s = _mask_scores(s, q_glob, k_glob, kv_len, kv_pad, causal,
                         k_start_local=k_local)
        m_prev = m_scr[:, :1]
        m_new = jnp.maximum(m_prev, jnp.max(s, axis=1, keepdims=True))
        # Rows with NO visible key in any block so far have m_new = NEG_BIG;
        # clamping only exp's argument (not the emitted m) keeps their p at
        # exactly 0, so the emitted partial is the true identity (o=0, l=0,
        # m=NEG_BIG) per partial_attention's mergeable contract.  Live rows
        # always have m_new > NEG_BIG/2, so this is a no-op for them.
        p = jnp.exp(s - jnp.maximum(m_new, NEG_BIG / 2))
        corr = jnp.exp(m_prev - m_new)
        l_new = l_scr[:, :1] * corr + jnp.sum(p, axis=1, keepdims=True)
        acc_scr[:] = acc_scr[:] * corr + jax.lax.dot_general(
            p.astype(v.dtype), v, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32,
        )
        m_scr[:] = jnp.broadcast_to(m_new, m_scr.shape)
        l_scr[:] = jnp.broadcast_to(l_new, l_scr.shape)

    if causal:
        pl.when(k_glob <= q_glob + block_q - 1)(_body)
    else:
        _body()

    @pl.when(ki == n_k - 1)
    def _emit():
        # Unnormalised partial: (acc, m, l) merge associatively across ring
        # steps (ops/attention.py::merge_partials).  Fully-masked rows --
        # whether from skipped blocks or from masking inside a live block --
        # emit the identity partial (acc=0, m=NEG_BIG, l=0; see the exp
        # clamp above).
        o_ref[0] = acc_scr[:].astype(o_ref.dtype)
        m_ref[0] = jnp.broadcast_to(m_scr[:, :1], m_ref.shape[1:])
        l_ref[0] = jnp.broadcast_to(l_scr[:, :1], l_ref.shape[1:])


def flash_partial(q, k, v, q_offset, kv_offset, *, causal: bool = True,
                  sm_scale: Optional[float] = None,
                  block_q: Optional[int] = None,
                  block_k: Optional[int] = None,
                  interpret: Optional[bool] = None):
    """One ring step's attention partial, Pallas-tiled.

    ``q [B,Hq,T,D]`` against one kv shard ``[B,Hkv,Tkv,D]`` (grouped heads
    accepted) whose global sequence positions start at ``kv_offset`` while
    the queries start at ``q_offset`` — both may be traced scalars (they
    ride a scalar-prefetch SMEM operand into the kernel and its index-map
    DMA clamps).  Returns ``(o, m, l)`` in the mergeable unnormalised form
    of ops/attention.py::partial_attention: o f32 ``[B,Hq,T,D]``, m/l f32
    ``[B,Hq,T]``.

    NOT differentiable — ring attention's custom_vjp (parallel/
    ring_attention.py) pairs it with :func:`flash_partial_bwd`.
    """
    if sm_scale is None:
        sm_scale = 1.0 / (q.shape[-1] ** 0.5)
    if interpret is None:
        interpret = dispatch.interpret()
    b, hq, s, d = q.shape
    hkv = k.shape[1]
    n_rep = hq // hkv
    kv_len = k.shape[2]

    block_q = min(block_q or DEFAULT_BLOCK_Q, _round_up(s, 8))
    block_k = min(block_k or DEFAULT_BLOCK_K, _round_up(kv_len, 8))
    s_pad = _round_up(s, block_q)
    kv_pad = _round_up(kv_len, block_k)
    if s_pad != s:
        q = jnp.pad(q, ((0, 0), (0, 0), (0, s_pad - s), (0, 0)))
    if kv_pad != kv_len:
        k = jnp.pad(k, ((0, 0), (0, 0), (0, kv_pad - kv_len), (0, 0)))
        v = jnp.pad(v, ((0, 0), (0, 0), (0, kv_pad - kv_len), (0, 0)))

    qf = q.reshape(b * hq, s_pad, d)
    kf = k.reshape(b * hkv, kv_pad, d)
    vf = v.reshape(b * hkv, kv_pad, d)
    offs = jnp.stack([jnp.asarray(q_offset, jnp.int32),
                      jnp.asarray(kv_offset, jnp.int32)])

    n_q = s_pad // block_q
    n_kv = kv_pad // block_k

    def kv_head(bh):
        return (bh // hq) * hkv + (bh % hq) // n_rep

    def kv_index(bh, i, j, offs):
        if causal:
            last = (offs[0] + i * block_q + block_q - 1 - offs[1]) // block_k
            j = jnp.minimum(j, jnp.clip(last, 0, n_kv - 1))
        return (kv_head(bh), j, 0)

    row8 = pl.BlockSpec((1, block_q, 8), lambda bh, i, j, offs: (bh, i, 0))
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1,
        grid=(b * hq, n_q, n_kv),
        in_specs=[
            pl.BlockSpec((1, block_q, d), lambda bh, i, j, offs: (bh, i, 0)),
            pl.BlockSpec((1, block_k, d), kv_index),
            pl.BlockSpec((1, block_k, d), kv_index),
        ],
        out_specs=[
            pl.BlockSpec((1, block_q, d), lambda bh, i, j, offs: (bh, i, 0)),
            row8,
            row8,
        ],
        scratch_shapes=[
            pltpu.VMEM((block_q, 128), jnp.float32),
            pltpu.VMEM((block_q, 128), jnp.float32),
            pltpu.VMEM((block_q, d), jnp.float32),
        ],
    )
    o, m8, l8 = pl.pallas_call(
        functools.partial(
            _partial_kernel, causal=causal, sm_scale=float(sm_scale),
            block_q=block_q, block_k=block_k, kv_len=kv_len, kv_pad=kv_pad,
        ),
        grid_spec=grid_spec,
        out_shape=[
            jax.ShapeDtypeStruct((b * hq, s_pad, d), jnp.float32),
            jax.ShapeDtypeStruct((b * hq, s_pad, 8), jnp.float32),
            jax.ShapeDtypeStruct((b * hq, s_pad, 8), jnp.float32),
        ],
        interpret=bool(interpret),
        name="sw_flash_partial",
    )(offs, qf, kf, vf)
    o = o.reshape(b, hq, s_pad, d)[:, :, :s, :]
    m = m8[:, :, 0].reshape(b, hq, s_pad)[:, :, :s]
    l = l8[:, :, 0].reshape(b, hq, s_pad)[:, :, :s]
    return o, m, l


def flash_partial_bwd(q, do, k, v, lse, delta, q_offset, kv_offset, *,
                      causal: bool = True, sm_scale: Optional[float] = None,
                      block_q: Optional[int] = None,
                      block_k: Optional[int] = None,
                      interpret: Optional[bool] = None):
    """Gradient contributions of one ring step.

    Inputs mirror :func:`flash_partial` plus the *globally merged* ``lse``
    and ``delta = rowsum(dO o O)`` (both ``[B,Hq,T]`` f32) — with global
    statistics, each step's contribution is exactly its slice of the full
    attention gradient, so contributions sum across ring steps.  Returns
    ``(dq, dk, dv)`` in f32 with dk/dv GROUPED ``[B,Hkv,Tkv,D]``.
    """
    if sm_scale is None:
        sm_scale = 1.0 / (q.shape[-1] ** 0.5)
    if interpret is None:
        interpret = dispatch.interpret()
    b, hq, s, d = q.shape
    hkv = k.shape[1]
    kv_len = k.shape[2]
    block_q = min(block_q or DEFAULT_BWD_BLOCK_Q, _round_up(s, 8))
    block_k = min(block_k or DEFAULT_BWD_BLOCK_K, _round_up(kv_len, 8))

    lse8 = jnp.broadcast_to(
        lse.astype(jnp.float32).reshape(b * hq, s)[:, :, None],
        (b * hq, s, 8))
    ops = _bwd_operands(q, do, k, v, lse8, delta.astype(jnp.float32),
                        block_q, block_k)
    offs = jnp.stack([jnp.asarray(q_offset, jnp.int32),
                      jnp.asarray(kv_offset, jnp.int32)])
    dq, dk, dv = _run_bwd_passes(
        ops.pop("qf"), ops.pop("dof"), ops.pop("kf"), ops.pop("vf"),
        ops.pop("lse8"), ops.pop("delta8"), offs,
        block_q=block_q, block_k=block_k, causal=causal,
        sm_scale=float(sm_scale), interpret=bool(interpret),
        dq_dtype=jnp.float32, dkv_dtype=jnp.float32, **ops)
    dq = dq.reshape(b, hq, -1, d)[:, :, :s, :]
    dk = dk.reshape(b, hkv, -1, d)[:, :, :kv_len, :]
    dv = dv.reshape(b, hkv, -1, d)[:, :, :kv_len, :]
    return dq, dk, dv


# ---------------------------------------------------------------------------
# custom_vjp plumbing + public entry point
# ---------------------------------------------------------------------------


@functools.partial(jax.custom_vjp, nondiff_argnums=(3,))
def _flash(q, k, v, cfg: _Cfg):
    # lse is a PRIMAL output (not just a vjp residual), tagged here so that
    # llama.py's "dots" remat policy (save attn_out + attn_lse) makes every
    # backward residual a subset of {inputs} ∪ {saved outputs} — the layer
    # backward then never re-runs this kernel.  With lse residual-only (the
    # pre-round-5 design), jax.checkpoint had to replay the forward kernel
    # inside every rematted layer just to regenerate lse, silently costing
    # a full extra flash forward per layer per step.  The extra [B*H, S, 8]
    # f32 store in inference paths is noise next to the O(S^2) compute.
    o, lse = _fwd_impl(q, k, v, cfg, save_lse=True)
    return checkpoint_name(o, "attn_out"), checkpoint_name(lse, "attn_lse")


def _flash_fwd(q, k, v, cfg: _Cfg):
    o, lse = _flash(q, k, v, cfg)
    return (o, lse), (q, k, v, o, lse)


def _flash_bwd(cfg: _Cfg, res, cts):
    q, k, v, o, lse = res
    do, _dlse = cts  # lse is an aux statistic; its cotangent is discarded
    return _bwd_impl(q, k, v, o, lse, do, cfg)


_flash.defvjp(_flash_fwd, _flash_bwd)


def flash_attention(
    q,
    k,
    v,
    *,
    causal: bool = False,
    sm_scale: Optional[float] = None,
    block_q: Optional[int] = None,
    block_k: Optional[int] = None,
    bwd_block_q: Optional[int] = None,
    bwd_block_k: Optional[int] = None,
    interpret: Optional[bool] = None,
    window: Optional[int] = None,
):
    """Flash attention, differentiable.  q: [B,Hq,S,D]; k/v: [B,Hkv,S,D]
    (grouped).

    Pads S to the block size internally; padded keys are masked, padded
    query rows are sliced off the output.  ``v`` may be narrower than q/k
    (latent attention's 192-wide keys over 128-wide values): the forward
    follows v's width; the backward kernels assume one width and are not
    reached by such a call's serving path.  ``window`` (requires
    ``causal``): sliding-window attention — kv blocks outside
    ``(q - window, q]`` are masked, compute-skipped, AND DMA-elided in
    both the forward and the two backward passes, so a windowed pass
    streams O(S·window) bytes, not O(S²).  Backward runs the hand-written
    two-pass Pallas kernel (see module docstring).  Explicit forward blocks
    are inherited by the backward only up to the safe backward defaults —
    the backward holds more live intermediates per cell, and oversized
    blocks there hang the Mosaic compile (see DEFAULT_BWD_* above); pass
    ``bwd_block_q``/``bwd_block_k`` to override deliberately.
    """
    if window is not None:
        if not causal:
            raise ValueError("window requires causal attention")
        if window < 1:
            raise ValueError(f"window must be >= 1, got {window}")
    if sm_scale is None:
        sm_scale = 1.0 / (q.shape[-1] ** 0.5)
    if interpret is None:
        interpret = dispatch.interpret()
    cfg = _Cfg(
        causal=causal,
        sm_scale=float(sm_scale),
        block_q=int(block_q) if block_q else DEFAULT_BLOCK_Q,
        block_k=int(block_k) if block_k else DEFAULT_BLOCK_K,
        bwd_block_q=int(bwd_block_q) if bwd_block_q else min(
            int(block_q) if block_q else DEFAULT_BWD_BLOCK_Q,
            DEFAULT_BWD_BLOCK_Q),
        bwd_block_k=int(bwd_block_k) if bwd_block_k else min(
            int(block_k) if block_k else DEFAULT_BWD_BLOCK_K,
            DEFAULT_BWD_BLOCK_K),
        interpret=bool(interpret),
        window=None if window is None else int(window),
    )
    o, _lse = _flash(q, k, v, cfg)
    return o


def self_attention(q, k, v, *, causal: bool = True,
                   window: Optional[int] = None,
                   sm_scale: Optional[float] = None):
    """Attention of a sequence over itself, the operation: the hand-tiled
    flash kernel on a TPU (per shard of the heads under a ``tp`` mesh),
    the lax blockwise scan elsewhere (bit-compatible algebra; both take
    grouped kv).  ``window``: sliding-window causal — the flash kernel
    masks, skips, and DMA-elides out-of-window blocks in forward AND
    backward.  ``sm_scale``: the scores' multiplier where it is not
    ``head_dim ** -0.5``."""
    if not dispatch.use_kernels():
        return blockwise_attention(q, k, v, causal=causal, window=window,
                                   sm_scale=sm_scale)
    return dispatch.per_head_shard(
        lambda q, k, v: flash_attention(q, k, v, causal=causal,
                                        window=window, sm_scale=sm_scale),
        (q, k, v))


# ---------------------------------------------------------------------------
# the ring step (parallel/ring_attention.py): kernel + lax pairs, same contract
# ---------------------------------------------------------------------------


def flash_partial_lax(q, k, v, q_offset, kv_offset, *, causal: bool = True,
                      sm_scale: Optional[float] = None,
                      window: Optional[int] = None):
    """:func:`flash_partial` in plain lax (grouped kv expanded), and the
    only form that carries a sliding-window band."""
    n_rep = q.shape[1] // k.shape[1]
    return partial_attention(
        q, repeat_kv(k, n_rep), repeat_kv(v, n_rep), q_offset=q_offset,
        kv_offset=kv_offset, causal=causal, sm_scale=sm_scale, window=window)


def flash_partial_bwd_lax(q, do, k, v, lse, delta, q_offset, kv_offset, *,
                          causal: bool = True, sm_scale: float,
                          window: Optional[int] = None):
    """:func:`flash_partial_bwd` in plain lax, window-aware."""
    b, hq, tq, d = q.shape
    hkv, tk = k.shape[1], k.shape[2]
    n_rep = hq // hkv
    ke = repeat_kv(k, n_rep).astype(jnp.float32)
    ve = repeat_kv(v, n_rep).astype(jnp.float32)
    qf = q.astype(jnp.float32)
    dof = do.astype(jnp.float32)
    s = jnp.einsum("bhqd,bhkd->bhqk", qf, ke) * sm_scale
    if causal:
        q_pos = q_offset + jnp.arange(tq)
        kv_pos = kv_offset + jnp.arange(tk)
        keep = q_pos[:, None] >= kv_pos[None, :]
        if window is not None:
            keep = keep & (kv_pos[None, :] > q_pos[:, None] - window)
        s = jnp.where(keep[None, None], s, NEG_BIG)
    p = jnp.exp(s - lse[..., None])  # normalised; masked entries -> 0
    dp = jnp.einsum("bhqd,bhkd->bhqk", dof, ve)
    ds = p * (dp - delta[..., None])
    dq = jnp.einsum("bhqk,bhkd->bhqd", ds, ke) * sm_scale
    dke = jnp.einsum("bhqk,bhqd->bhkd", ds, qf) * sm_scale
    dve = jnp.einsum("bhqk,bhqd->bhkd", p, dof)
    dk = dke.reshape(b, hkv, n_rep, tk, d).sum(2)
    dv = dve.reshape(b, hkv, n_rep, tk, d).sum(2)
    return dq, dk, dv


def ring_step(q, k, v, q_off, kv_off, causal, sm_scale, window=None):
    """One kv shard's unnormalised partial, the operation: ``(o, m, l)``,
    all f32.  The kernel on a TPU; a sliding-window band always takes the
    lax twin (the kernel carries none; windowed rings skip most pairs
    outright anyway)."""
    if dispatch.use_kernels() and window is None:
        return flash_partial(q, k, v, q_off, kv_off, causal=causal,
                             sm_scale=sm_scale)
    return flash_partial_lax(q, k, v, q_off, kv_off, causal=causal,
                             sm_scale=sm_scale, window=window)


def ring_step_bwd(q, do, k, v, lse, delta, q_off, kv_off, causal, sm_scale,
                  window=None):
    """One kv shard's gradient contributions, the operation: ``(dq, dk,
    dv)``, f32, dk/dv grouped.  lse/delta are the globally merged
    statistics.  Chosen as :func:`ring_step` chooses."""
    if dispatch.use_kernels() and window is None:
        return flash_partial_bwd(q, do, k, v, lse, delta, q_off, kv_off,
                                 causal=causal, sm_scale=sm_scale)
    return flash_partial_bwd_lax(q, do, k, v, lse, delta, q_off, kv_off,
                                 causal=causal, sm_scale=sm_scale,
                                 window=window)
