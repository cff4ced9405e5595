"""Pallas TPU decode-attention kernel for KV-cache inference.

Single-token decode is HBM-bandwidth bound: the whole KV cache streams
through the core once per generated token.  The lax path
(models/generate.py:_attend_cached) materialises ``repeat_kv`` — expanding
the grouped cache ``n_rep``× before the einsum — so a GQA model reads (and
first writes) n_rep times more HBM than the cache actually holds.  This
kernel keeps the cache narrow: the grid walks ``(batch*kv_head, kv_block)``,
loads each cache block exactly once, and attends all ``n_rep`` query heads
of the group against it as the rows of one MXU matmul.  Masking and the
online-softmax accumulation are fused; fully-masked blocks (beyond the
current position) are skipped via scalar-prefetched ``pos``.

Two variants share the same online-softmax block body:

* **stream** (default): one grid cell per (batch, kv head); the whole T
  sweep is a ``fori_loop`` with double-buffered manual DMA
  (``make_async_copy``) — compute on block i overlaps the HBM stream of
  block i+1, and the per-cell pipeline cost is paid b*hkv times total,
  independent of T.  Structural response to the r2 measurement below.
* **grid** (``stream=False``): one grid cell per kv block, Pallas-pipelined.
  Decode is bandwidth-bound with a ~0.4 µs fixed cost per grid cell, so
  small blocks drown in cell overhead (measured r2: block 128 at T=8192 =
  128 cells ≈ 51 µs of overhead on a 60.8 µs total — slower than the lax
  path); block 512 quarters the cell count.

``bench.py --kernels decode_tune`` sweeps both variants x block sizes on
real hardware; which is faster there has not been measured this round.

Same online-softmax algebra as ops/pallas_attention.py; layouts follow
models/generate.py: ``q [B, Hq, 1, D]``, caches ``[B, Hkv, T, D]``.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from .attention import NEG_BIG
from .pallas_attention import _round_up


def _softmax_block_update(q, k, v, k_start, pos, m_scr, l_scr, acc_scr, *,
                          sm_scale: float, window: "int | None",
                          k_scale=None, v_scale=None, row_off=None):
    """The one online-softmax block body both kernel variants share: score
    the group's query rows against one [block_k, D] cache block, mask by
    global position (and window), and fold into the m/l/acc scratches.

    ``row_off`` ([rows, 1] int32 — rank-2, Mosaic rejects rank-1 iota;
    multi-query decode): row r's query sits at global position
    ``pos + row_off[r, 0]`` — the speculative chunk verify packs C chunk
    positions x n_rep query heads as the matmul rows, so each row masks
    by its own cursor.  ``None`` = all rows at ``pos``.

    ``k_scale``/``v_scale`` ([1, block_k] f32 rows, int8 cache; see
    :func:`decode_attention` on the scale layout): dequantization is
    folded into the existing algebra instead of widening the operands —
    k's scale multiplies the score COLUMNS (``(q . k_int8[c]) * s_k[c]``)
    and v's scale folds into the softmax weights before the ``p @ v``
    matmul, so no dequantized [block_k, D] tile is ever materialised."""
    s = jax.lax.dot_general(
        q, k.astype(q.dtype), (((1,), (1,)), ((), ())),
        preferred_element_type=jnp.float32,
    )  # [rows, block_k]
    if k_scale is not None:
        s = s * (k_scale * sm_scale)
    else:
        s = s * sm_scale
    kv_pos = k_start + jax.lax.broadcasted_iota(jnp.int32, s.shape, 1)
    q_pos = pos if row_off is None else pos + row_off  # [rows, 1]
    keep = kv_pos <= q_pos
    if window is not None:
        keep = keep & (kv_pos > q_pos - window)
    s = jnp.where(keep, s, NEG_BIG)

    m_prev = m_scr[:, :1]
    m_new = jnp.maximum(m_prev, jnp.max(s, axis=1, keepdims=True))
    p = jnp.where(s > NEG_BIG / 2, jnp.exp(s - m_new), 0.0)
    corr = jnp.exp(m_prev - m_new)
    l_new = l_scr[:, :1] * corr + jnp.sum(p, axis=1, keepdims=True)
    pv_dtype = q.dtype
    if v_scale is not None:
        p = p * v_scale
    acc_scr[:] = acc_scr[:] * corr + jax.lax.dot_general(
        p.astype(pv_dtype), v.astype(pv_dtype), (((1,), (0,)), ((), ())),
        preferred_element_type=jnp.float32,
    )
    m_scr[:] = jnp.broadcast_to(m_new, m_scr.shape)
    l_scr[:] = jnp.broadcast_to(l_new, l_scr.shape)


def _row_offsets(rows: int, n_q: int):
    """Row r's query-position offset in the packed [n_rep, C] row layout
    (r = rep * C + ci -> offset ci), shaped [rows, 1] (rank-2: Mosaic
    rejects rank-1 iota); None when single-position."""
    if n_q == 1:
        return None
    return jax.lax.rem(
        jax.lax.broadcasted_iota(jnp.int32, (rows, 1), 0), n_q)


def _decode_kernel(pos_ref, q_ref, k_ref, v_ref, *refs, sm_scale: float,
                   block_k: int, hkv: int, window: "int | None",
                   quant: bool = False, n_q: int = 1):
    if quant:
        ks_ref, vs_ref, o_ref, m_scr, l_scr, acc_scr = refs
    else:
        ks_ref = vs_ref = None
        o_ref, m_scr, l_scr, acc_scr = refs
    ki = pl.program_id(1)
    n_k = pl.num_programs(1)

    @pl.when(ki == 0)
    def _init():
        m_scr[:] = jnp.full_like(m_scr, NEG_BIG)
        l_scr[:] = jnp.zeros_like(l_scr)
        acc_scr[:] = jnp.zeros_like(acc_scr)

    # Per-ROW positions (ragged batches): this grid cell serves batch row
    # bh // hkv, whose own cursor bounds both masking and the DMA clamp.
    # Multi-query (n_q > 1): queries span pos .. pos + n_q - 1.
    pos = pos_ref[pl.program_id(0) // hkv]
    k_start = ki * block_k

    live = k_start <= pos + (n_q - 1)
    if window is not None:
        # Sliding window: this block must overlap (pos - window,
        # pos + n_q - 1] (the union of every query's band).
        live = live & (k_start + block_k - 1 > pos - window)

    @pl.when(live)
    def _body():
        _softmax_block_update(
            q_ref[0], k_ref[0], v_ref[0], k_start, pos, m_scr, l_scr,
            acc_scr, sm_scale=sm_scale, window=window,
            k_scale=None if ks_ref is None else ks_ref[0],
            v_scale=None if vs_ref is None else vs_ref[0],
            row_off=_row_offsets(q_ref.shape[1], n_q))

    @pl.when(ki == n_k - 1)
    def _finalize():
        o_ref[0] = (acc_scr[:] / jnp.maximum(l_scr[:, :1], 1e-30)).astype(o_ref.dtype)


def _decode_stream_kernel(pos_ref, q_ref, k_hbm, v_hbm, *refs,
                          sm_scale: float, block_k: int, hkv: int,
                          window: "int | None", n_blocks: int,
                          quant: bool = False, n_q: int = 1):
    """One grid cell per (batch, kv head): the WHOLE cache sweep runs in a
    single cell as a fori_loop over kv blocks with double-buffered manual
    DMA (compute on block i overlaps the HBM stream of block i+1).

    Rationale: the grid kernel pays a fixed ~0.4 us pipeline cost per cell
    (measured r2: 64 cells at block 128 ~= 51 us of a 60.8 us total — slower
    than the lax path).  Here the cell count is b*hkv regardless of T, so
    the overhead term is gone and the kernel's time is the max of the DMA
    stream (~cache bytes / HBM bandwidth) and the (tiny) grouped-GQA
    matmuls.

    ``quant``: two extra HBM inputs (per-token f32 scales) and two extra
    scratch buffers ride the same double-buffered pipeline; the int8 cache
    blocks halve the DMA bytes (the scales add 1/(2*D) back).
    """
    if quant:
        (ks_hbm, vs_hbm, o_ref, k_buf, v_buf, ks_buf, vs_buf, sems, m_scr,
         l_scr, acc_scr) = refs
    else:
        ks_hbm = vs_hbm = ks_buf = vs_buf = None
        o_ref, k_buf, v_buf, sems, m_scr, l_scr, acc_scr = refs
    bh = pl.program_id(0)
    pos = pos_ref[bh // hkv]
    hi = (pos + n_q - 1) // block_k  # last live block (queries span n_q)
    if window is None:
        lo = jnp.int32(0)
    else:
        lo = jnp.maximum(pos - window + 1, 0) // block_k

    def copies(i, slot):
        cps = [
            pltpu.make_async_copy(
                k_hbm.at[bh, pl.ds(i * block_k, block_k)], k_buf.at[slot],
                sems.at[slot, 0]),
            pltpu.make_async_copy(
                v_hbm.at[bh, pl.ds(i * block_k, block_k)], v_buf.at[slot],
                sems.at[slot, 1]),
        ]
        if quant:
            cps.append(pltpu.make_async_copy(
                ks_hbm.at[bh, :, pl.ds(i * block_k, block_k)],
                ks_buf.at[slot],
                sems.at[slot, 2]))
            cps.append(pltpu.make_async_copy(
                vs_hbm.at[bh, :, pl.ds(i * block_k, block_k)],
                vs_buf.at[slot],
                sems.at[slot, 3]))
        return cps

    m_scr[:] = jnp.full_like(m_scr, NEG_BIG)
    l_scr[:] = jnp.zeros_like(l_scr)
    acc_scr[:] = jnp.zeros_like(acc_scr)
    for cp in copies(lo, 0):
        cp.start()
    q = q_ref[0]  # [rows, D] — the group's query heads (padded to tile)

    # STATIC trip count with liveness guards (not a dynamic-bound loop —
    # simpler Mosaic lowering): dead iterations run a few scalar ops; DMA,
    # waits, and compute all sit under pl.when, so only live blocks move
    # bytes — a windowed decode still streams ~window bytes however big T.
    def body(i, _):
        live = (i >= lo) & (i <= hi)

        @pl.when(live)
        def _live():
            slot = jax.lax.rem(i - lo, 2)

            @pl.when(i + 1 <= hi)
            def _prefetch():
                ns = jax.lax.rem(i + 1 - lo, 2)
                for cp in copies(i + 1, ns):
                    cp.start()

            for cp in copies(i, slot):
                cp.wait()
            _softmax_block_update(
                q, k_buf[slot], v_buf[slot], i * block_k, pos, m_scr, l_scr,
                acc_scr, sm_scale=sm_scale, window=window,
                k_scale=None if not quant else ks_buf[slot],
                v_scale=None if not quant else vs_buf[slot],
                row_off=_row_offsets(q.shape[0], n_q))

        return 0

    jax.lax.fori_loop(0, n_blocks, body, 0)
    o_ref[0] = (acc_scr[:] / jnp.maximum(l_scr[:, :1], 1e-30)).astype(o_ref.dtype)


def decode_attention(q, k_cache, v_cache, pos, *, sm_scale=None,
                     block_k: int = 512, interpret=None, window=None,
                     stream: "bool | None" = None, k_scale=None,
                     v_scale=None):
    """Cached decode attention (1..C query positions) without expanding
    the grouped cache.

    q: [B, Hq, C, D] — C consecutive query positions per row (C=1 is
    plain single-token decode; C>1 is the speculative chunk verify:
    models/speculative.py packs C positions x n_rep grouped heads as the
    rows of the SAME per-(batch, kv head) matmul, so the cache still
    streams exactly once, narrow and int8-capable).  k_cache/v_cache:
    [B, Hkv, T, D]; pos: scalar int or per-row [B] int (ragged batches)
    — row b's queries sit at ``pos[b] .. pos[b] + C - 1``, key positions
    above each query are masked, and row b's DMA stops at its last
    query's block.  Write-then-attend callers must have the C entries in
    the cache already.  ``window`` (static): sliding-window attention
    over the last ``window`` positions — blocks entirely below the
    window are DMA-elided too, so a windowed decode streams ~window
    bytes of cache regardless of T.  Returns [B, Hq, C, D].  Numerically
    matches models/generate.py:_attend_cached (softmax in f32).

    ``k_scale``/``v_scale`` ([B, Hkv, T] f32): int8-quantized caches
    (ops/quantize.py) — the kernel streams the int8 blocks (half the HBM
    bytes of bf16) and folds dequantization into the score/weight algebra;
    both or neither must be given, matching the caches' int8 dtype.  They
    reach the kernel as [B*Hkv, 1, T]: Mosaic blocks and DMAs the last
    two dims in (8, 128) tiles unless a block spans the whole dim, so one
    row OF a 2-D [B*Hkv, T] array is refused by the chip's compiler while
    a (1, block_k) slab of a dim of size 1 is not.

    ``stream`` (default True; ``STARWAY_DECODE_STREAM=0`` flips the
    default): the double-buffered single-cell kernel
    (:func:`_decode_stream_kernel`) — b*hkv grid cells total, per-cell
    pipeline overhead independent of T; it is what serves on the chip
    (chip_smoke.py phase c).  ``stream=False`` keeps the grid-pipelined
    kernel (one cell per kv block) until ROADMAP D4 deletes it;
    ``bench.py --kernels decode_tune`` sweeps both on-chip.
    """
    if stream is None:
        from ..config import decode_stream_enabled

        stream = decode_stream_enabled()
    if window is not None and window < 1:
        raise ValueError(f"window must be >= 1, got {window}")
    quant = k_scale is not None or v_scale is not None
    if quant and (k_scale is None or v_scale is None):
        raise ValueError("int8 caches need BOTH k_scale and v_scale")
    for name, c in (("k_cache", k_cache), ("v_cache", v_cache)):
        if quant != (c.dtype == jnp.int8):
            raise ValueError(
                f"{name} dtype {c.dtype} inconsistent with "
                f"{'present' if quant else 'absent'} scales (int8 caches "
                f"carry per-token scales; see ops/quantize.py)")
    b, hq, n_q, d = q.shape
    hkv, t = k_cache.shape[1], k_cache.shape[2]
    n_rep = hq // hkv
    if sm_scale is None:
        sm_scale = 1.0 / (d ** 0.5)
    if interpret is None:
        interpret = jax.default_backend() != "tpu"

    # Group query heads by their kv head: rows of the per-group matmul,
    # packed [n_rep, C] (row r = rep * C + ci — _row_offsets relies on
    # this layout).  repeat_kv maps q head h -> kv head h // n_rep, so the
    # reshape groups correctly (ops/attention.py:repeat_kv).
    n_rows = n_rep * n_q
    rows = _round_up(max(n_rows, 8), 8)  # TPU sublane tile
    qg = q.reshape(b, hkv, n_rows, d)
    if rows != n_rows:
        qg = jnp.pad(qg, ((0, 0), (0, 0), (0, rows - n_rows), (0, 0)))
    qf = qg.reshape(b * hkv, rows, d)

    block_k = min(block_k, _round_up(t, 128))
    t_pad = _round_up(t, block_k)
    kf = k_cache.reshape(b * hkv, t, d)
    vf = v_cache.reshape(b * hkv, t, d)
    if t_pad != t:
        kf = jnp.pad(kf, ((0, 0), (0, t_pad - t), (0, 0)))
        vf = jnp.pad(vf, ((0, 0), (0, t_pad - t), (0, 0)))
    scales = []
    if quant:
        for s in (k_scale, v_scale):
            # [b*hkv, 1, T], not [b*hkv, T]: one row's scales are then a
            # whole (1, block_k) slab of the last two dims, which Mosaic
            # can block and DMA; a single row OF a 2-D f32 array is a
            # slice below the (8, 128) tile and is refused.
            sf = s.astype(jnp.float32).reshape(b * hkv, 1, t)
            if t_pad != t:
                sf = jnp.pad(sf, ((0, 0), (0, 0), (0, t_pad - t)))
            scales.append(sf)

    pos_arr = jnp.broadcast_to(jnp.asarray(pos, jnp.int32).reshape(-1), (b,))

    if stream:
        any_spec = pl.BlockSpec(memory_space=pltpu.MemorySpace.ANY)
        quant_scratch = [
            pltpu.VMEM((2, 1, block_k), jnp.float32),
            pltpu.VMEM((2, 1, block_k), jnp.float32),
        ] if quant else []
        out = pl.pallas_call(
            functools.partial(
                _decode_stream_kernel, sm_scale=sm_scale, block_k=block_k,
                hkv=hkv, window=None if window is None else int(window),
                n_blocks=t_pad // block_k, quant=quant, n_q=n_q),
            grid_spec=pltpu.PrefetchScalarGridSpec(
                num_scalar_prefetch=1,
                grid=(b * hkv,),
                in_specs=[
                    pl.BlockSpec((1, rows, d), lambda bh, pos_ref: (bh, 0, 0)),
                    any_spec,
                    any_spec,
                ] + [any_spec] * (2 * quant),
                out_specs=pl.BlockSpec((1, rows, d),
                                       lambda bh, pos_ref: (bh, 0, 0)),
                scratch_shapes=[
                    pltpu.VMEM((2, block_k, d), kf.dtype),
                    pltpu.VMEM((2, block_k, d), vf.dtype),
                ] + quant_scratch + [
                    pltpu.SemaphoreType.DMA((2, 4 if quant else 2)),
                    pltpu.VMEM((rows, 128), jnp.float32),
                    pltpu.VMEM((rows, 128), jnp.float32),
                    pltpu.VMEM((rows, d), jnp.float32),
                ],
            ),
            out_shape=jax.ShapeDtypeStruct((b * hkv, rows, d), q.dtype),
            interpret=interpret,
            name="sw_decode_attn_stream",
        )(pos_arr, qf, kf, vf, *scales)
        return out.reshape(b, hkv, rows, d)[:, :, :n_rows, :].reshape(
            b, hq, n_q, d)

    grid = (b * hkv, t_pad // block_k)

    # Clamp the K/V block index into the live range: the kernel body is
    # skipped outside it (pl.when), and a repeated block index makes the
    # Pallas pipeline elide the HBM copy entirely -- so a decode at pos
    # streams only the blocks holding (pos - window, pos + n_q - 1], not
    # the whole padded cache.  (pl.when alone skips compute, not DMA.)
    def _kv_index(bh, ki, pos_ref):
        p = pos_ref[bh // hkv]
        hi = (p + n_q - 1) // block_k
        if window is None:
            return (bh, jnp.minimum(ki, hi), 0)
        lo = jnp.maximum(p - window + 1, 0) // block_k
        return (bh, jnp.clip(ki, lo, hi), 0)

    def _scale_index(bh, ki, pos_ref):
        bh_, ki_, _ = _kv_index(bh, ki, pos_ref)
        return (bh_, 0, ki_)

    out = pl.pallas_call(
        functools.partial(_decode_kernel, sm_scale=sm_scale, block_k=block_k,
                          hkv=hkv, window=None if window is None else int(window),
                          quant=quant, n_q=n_q),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1,
            grid=grid,
            in_specs=[
                pl.BlockSpec((1, rows, d), lambda bh, ki, pos_ref: (bh, 0, 0)),
                pl.BlockSpec((1, block_k, d), _kv_index),
                pl.BlockSpec((1, block_k, d), _kv_index),
            ] + [pl.BlockSpec((1, 1, block_k), _scale_index)] * (2 * quant),
            out_specs=pl.BlockSpec((1, rows, d), lambda bh, ki, pos_ref: (bh, 0, 0)),
            scratch_shapes=[
                pltpu.VMEM((rows, 128), jnp.float32),
                pltpu.VMEM((rows, 128), jnp.float32),
                pltpu.VMEM((rows, d), jnp.float32),
            ],
        ),
        out_shape=jax.ShapeDtypeStruct((b * hkv, rows, d), q.dtype),
        interpret=interpret,
        name="sw_decode_attn",
    )(pos_arr, qf, kf, vf, *scales)
    return out.reshape(b, hkv, rows, d)[:, :, :n_rows, :].reshape(
        b, hq, n_q, d)
