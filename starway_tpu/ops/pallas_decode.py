"""Pallas TPU decode-attention kernel for KV-cache inference.

Single-token decode is HBM-bandwidth bound: the whole KV cache streams
through the core once per generated token.  The lax twin
(:func:`decode_attention_lax`) materialises ``repeat_kv`` — expanding
the grouped cache ``n_rep``× before the einsum — so a GQA model reads (and
first writes) n_rep times more HBM than the cache actually holds.  This
kernel keeps the cache narrow: it loads each cache block exactly once and
attends all ``n_rep`` query heads of the group against it as the rows of
one MXU matmul.  Masking and the online-softmax accumulation are fused;
fully-masked blocks (beyond the current position) are skipped via
scalar-prefetched ``pos``.

The kernel (``sw_decode_attn_stream``) runs one grid cell per batch row:
the row's kv heads share the cell (all of them at decode; a group of them
where VMEM forbids more, :func:`_cell_shape`, decided from the shapes).
The whole T sweep is a ``fori_loop`` with double-buffered manual DMA
(``make_async_copy``): one descriptor a kv block for ALL the cell's heads,
their scores one matmul batched over the heads and their weighted sums
another, so compute on block i overlaps the HBM stream of block i+1 and
the body is the same whatever the heads, the query rows or T (a body
unrolled over the heads ran as fast and cost every warm start seconds of
tracing and lowering: PERF.md section 6, PRs 41 and 42).  The stream does
not stop at a cell's end: a cell's last block computes while the NEXT
cell's first block (its cursor and cache row are prefetched scalars) is
on its way into the other buffer, so a pipeline's cold start is paid once
a call and not b*hkv times.  That chain is why the grid axis is
sequential ("arbitrary") and must stay so.  (A form with one grid cell
per kv block paid about 0.4 us a cell; it lost its pair on the chip and
was deleted in PR 28.  One cell a (batch row, kv head), each starting
cold, paid about 1.1 us a cell until PR 42: 768 cells a call where 96
slots have 8 kv heads.)

The latent cache's kernel (``sw_mla_decode_attn``, :func:`mla_decode_attention`)
is the same kernel body and so the same chain (PR 48; until then it was a
body of its own, one cell a slot, each cell starting cold and waiting for
its whole first block): ONE operand streams, the slot's one row a position
that every head shares, and a block is used twice, whole as the keys and
its first ``rank`` columns as the values.

Same online-softmax algebra as ops/pallas_attention.py; layouts follow
models/generate.py: ``q [B, Hq, 1, D]``, caches ``[B, Hkv, T, D]`` — or the
whole scan-stacked cache ``[L, B, Hkv, T, D]`` with a traced ``layer``.

The stacked form is what serves.  A decode program that slices one layer
out of the stacked cache, updates the slice and stores it into a second
stacked array moves the whole cache four to six times a step (measured,
PR 23: half the device time of a 16-layer, 24 x 2048 serving step).  So
the kernel takes the layer as a second prefetched scalar and indexes HBM
by it, and :func:`kv_write` (``sw_kv_write``) puts the new entries into
the SAME buffer (``input_output_aliases``): the cache is only ever an
operand of these custom calls, never sliced, padded or scattered into by
XLA, so it can ride a ``lax.scan`` carry in place (models/generate.py).
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from . import dispatch
from .attention import NEG_BIG, repeat_kv
from .pallas_attention import _round_up
from .quantize import dequantize_kv


def _softmax_block_update(q, k, v, k_start, pos, m_scr, l_scr, acc_scr, *,
                          sm_scale: float, window: "int | None",
                          k_scale=None, v_scale=None, row_off=None,
                          ring: "tuple | None" = None):
    """The one online-softmax block body the decode kernels share: score
    the query rows against one cache block, mask by global position (and
    window), and fold into the m/l/acc scratches.  ``q [rows, D]`` against
    ``k``, ``v`` ``[block_k, D]``, or every head of a grid cell at once:
    ``q [heads, rows, D]`` against ``[heads, block_k, D]``, both matmuls
    batched over the heads and the softmax state ``[heads, rows, ...]``.

    ``row_off`` ([rows, 1] int32 — rank-2, Mosaic rejects rank-1 iota;
    multi-query decode): row r's query sits at global position
    ``pos + row_off[r, 0]`` — the speculative chunk verify packs C chunk
    positions x n_rep query heads as the matmul rows, so each row masks
    by its own cursor.  ``None`` = all rows at ``pos``.

    ``k_scale``/``v_scale`` (f32 rows ``[(heads,) 1, block_k]``, int8
    cache; see :func:`decode_attention` on the scale layout):
    dequantization is folded into the existing algebra instead of
    widening the operands — k's scale multiplies the score COLUMNS
    (``(q . k_int8[c]) * s_k[c]``) and v's scale folds into the softmax
    weights before the ``p @ v`` matmul, so no dequantized [block_k, D]
    tile is ever materialised.

    ``ring = (T, top)`` (a ring longer than its window, written at ``p %
    T``): slot ``s`` holds position ``top - (top - s) % T``, ``top`` being
    the last position written; a slot no position reached yet reads a
    negative one and is masked."""
    heads = tuple(range(q.ndim - 2))  # the batch dims of both matmuls
    last = q.ndim - 1
    s = jax.lax.dot_general(
        q, k.astype(q.dtype), (((last,), (last,)), (heads, heads)),
        preferred_element_type=jnp.float32,
    )  # [(heads,) rows, block_k]
    if k_scale is not None:
        s = s * (k_scale * sm_scale)
    else:
        s = s * sm_scale
    kv_pos = k_start + jax.lax.broadcasted_iota(jnp.int32, s.shape, last)
    q_pos = pos if row_off is None else pos + row_off  # [rows, 1]
    if ring is not None:
        t, top = ring
        kv_pos = top - jax.lax.rem(top - kv_pos + t, t)  # 0 <= slot < t
    keep = kv_pos <= q_pos
    if ring is not None:
        keep = keep & (kv_pos >= 0)
    if window is not None:
        keep = keep & (kv_pos > q_pos - window)
    s = jnp.where(keep, s, NEG_BIG)

    col = (slice(None),) * last + (slice(0, 1),)  # the state's first lane
    m_prev = m_scr[col]
    m_new = jnp.maximum(m_prev, jnp.max(s, axis=last, keepdims=True))
    p = jnp.where(s > NEG_BIG / 2, jnp.exp(s - m_new), 0.0)
    corr = jnp.exp(m_prev - m_new)
    l_new = l_scr[col] * corr + jnp.sum(p, axis=last, keepdims=True)
    pv_dtype = q.dtype
    if v_scale is not None:
        p = p * v_scale
    acc_scr[...] = acc_scr[...] * corr + jax.lax.dot_general(
        p.astype(pv_dtype), v.astype(pv_dtype),
        (((last,), (last - 1,)), (heads, heads)),
        preferred_element_type=jnp.float32,
    )
    m_scr[...] = jnp.broadcast_to(m_new, m_scr.shape)
    l_scr[...] = jnp.broadcast_to(l_new, l_scr.shape)


def _row_offsets(rows: int, n_q: int):
    """Row r's query-position offset in the packed [n_rep, C] row layout
    (r = rep * C + ci -> offset ci), shaped [rows, 1] (rank-2: Mosaic
    rejects rank-1 iota); None when single-position."""
    if n_q == 1:
        return None
    return jax.lax.rem(
        jax.lax.broadcasted_iota(jnp.int32, (rows, 1), 0), n_q)


def _head_rows(scales, first, heads: int):
    """Rows ``first .. first + heads - 1`` of a ``[Hkv, block_k]`` f32 scale
    block as ``[heads, 1, block_k]``, one row under each head's scores (a
    masked sublane sum: rows OF a tile are neither a slice Mosaic takes at
    a traced index nor a leading dim it reshapes to, and the block is a
    few KiB)."""
    pick = (first + jax.lax.broadcasted_iota(jnp.int32, (heads, 1, 1), 0)
            == jax.lax.broadcasted_iota(
                jnp.int32, (1, scales.shape[0], 1), 1))
    return jnp.sum(jnp.where(pick, scales[None], 0.0), axis=1, keepdims=True)


def _decode_stream_kernel(pos_ref, layer_ref, *refs,
                          sm_scale: float, block_k: int, heads: int,
                          n_groups: int, window: "int | None", n_blocks: int,
                          quant: bool = False, n_q: int = 1,
                          by_row: bool = False, ring: bool = False,
                          rank: "int | None" = None):
    """One grid cell per (batch row, group of ``heads`` kv heads; a row has
    ``n_groups`` of them, one where VMEM lets every head in): the WHOLE
    cache sweep of all the cell's heads runs in a single cell as a
    fori_loop over kv blocks with double-buffered manual DMA (compute on
    block i overlaps the HBM stream of block i+1), one descriptor a block
    for all the heads, one batched matmul for their scores and one for
    their weighted sums.  The body does not grow with the heads, the query
    rows or the blocks: no Python loop over any of them.

    The stream runs ACROSS cells: while a cell computes its last block the
    first block of the next cell (its cursor and cache row are prefetched
    scalars) is already on its way into the other buffer, so only the
    call's first cell starts cold.  Which of the two buffers a cell starts
    in is carried from cell to cell in SMEM (``par_ref``); the grid axis
    must stay sequential ("arbitrary").

    What streams (``src``: k and v) are the whole stacked caches ``[L, B,
    Hkv, T, D]`` left in HBM; ``layer_ref`` (second prefetched scalar)
    picks the layer in the DMA's source address, so no layer is ever
    sliced out.

    ``quant``: two extra HBM inputs (per-token f32 scales ``[L, B, Hkv,
    T]``) and two extra scratch buffers ride the same double-buffered
    pipeline; the int8 cache blocks halve the DMA bytes.  A cell fetches
    its batch row's ``[Hkv, block_k]`` scale block whole (a few heads' rows
    of it are a slice below the (8, 128) tile) and keeps its own rows.

    ``by_row``: a third prefetched scalar array names the CACHE row each
    batch row reads (:func:`slot_attention`: the pieces of one prompt, all
    on their request's slot); without it batch row b reads cache row b.

    ``ring`` (with ``window``): the cache is a ring of ``T > window``
    positions written at ``p % T`` and ``pos`` is absolute: every warm
    block is streamed and each slot masked by the position it holds.

    ``rank`` (the latent cache, :func:`mla_decode_attention`): ONE operand
    ``[L, B, 1, T, W]`` streams, as ``k`` does and with no ``v`` beside it;
    a cell's "head" is the row all the heads share, their absorbed queries
    are the rows of its matmuls, and a block's first ``rank`` columns are
    its values.
    """
    if by_row:
        row_ref, *refs = refs
    q_ref, *refs = refs
    # What streams: k and v, then their scales; the latent rows alone.
    n_src = 1 if rank is not None else 4 if quant else 2
    src, o_ref, bufs = refs[:n_src], refs[n_src], refs[n_src + 1:2 * n_src + 1]
    sems, par_ref, m_scr, l_scr, acc_scr = refs[2 * n_src + 1:]
    cell = pl.program_id(0)
    layer = layer_ref[0]

    def span(c):
        """Cell ``c``'s cache row, first head, cursor and its first and
        last live kv block.  Whatever the cursor, ``0 <= lo <= hi <
        n_blocks``: every cell has a block to wait for, and the cell
        before it one to fetch (``lax.div`` is a fraction of the scalar
        code ``//`` lowers to, and rounds a negative cursor to block 0)."""
        b = c if n_groups == 1 else jax.lax.div(c, n_groups)
        h0 = 0 if n_groups == 1 else jax.lax.rem(c, n_groups) * heads
        pos = pos_ref[b]
        # The last live block: the queries span n_q positions.  (A ring is
        # streamed whole; a prompt's last piece is padded, and its pad
        # queries may lie past T.)
        hi = jnp.minimum(jax.lax.div(pos + n_q - 1, block_k), n_blocks - 1)
        if window is None or ring:
            lo = 0
        else:
            lo = jnp.minimum(
                jax.lax.div(jnp.maximum(pos - window + 1, 0), block_k), hi)
        return (row_ref[b] if by_row else b), h0, pos, lo, hi

    def copies(row, h0, i, slot):
        blk = pl.ds(i * block_k, block_k)
        hs = pl.ds(h0, heads)  # of k and v; a scale block comes whole
        return [
            pltpu.make_async_copy(
                hbm.at[layer, row, hs if a < 2 else slice(None), blk],
                buf.at[slot], sems.at[slot, a])
            for a, (hbm, buf) in enumerate(zip(src, bufs))]

    row, h0, pos, lo, hi = span(cell)
    # What follows this cell's last block in the stream: the next cell's
    # first (the call's last cell names itself and fetches nothing).
    last_cell = pl.num_programs(0) - 1
    nrow, nh0, _, nlo, _ = span(jnp.minimum(cell + 1, last_cell))

    @pl.when(cell == 0)
    def _cold():
        par_ref[0] = 0
        for cp in copies(row, h0, lo, 0):
            cp.start()

    first = par_ref[0]  # the buffer this cell's first block is (put) in
    m_scr[...] = jnp.full_like(m_scr, NEG_BIG)
    l_scr[...] = jnp.zeros_like(l_scr)
    acc_scr[...] = jnp.zeros_like(acc_scr)
    q = q_ref[0]  # [heads, rows, D]: each group's query heads (padded to tile)

    # STATIC trip count with liveness guards (not a dynamic-bound loop —
    # simpler Mosaic lowering): dead iterations run a few scalar ops; DMA,
    # waits, and compute all sit under pl.when, so only live blocks move
    # bytes — a windowed decode still streams ~window bytes however big T.
    def body(i, _):
        @pl.when((i >= lo) & (i <= hi))
        def _live():
            slot = jax.lax.rem(first + i - lo, 2)
            more = i < hi  # of this cell; else the stream moves on

            @pl.when(more | (cell < last_cell))
            def _prefetch():
                for cp in copies(
                        jnp.where(more, row, nrow),
                        h0 if n_groups == 1 else jnp.where(more, h0, nh0),
                        jnp.where(more, i + 1, nlo), 1 - slot):
                    cp.start()

            for cp in copies(row, h0, i, slot):
                cp.wait()
            k = bufs[0][slot]
            _softmax_block_update(
                q, k, k[..., :rank] if rank is not None else bufs[1][slot],
                i * block_k, pos, m_scr, l_scr,
                acc_scr, sm_scale=sm_scale, window=window,
                k_scale=(None if not quant
                         else _head_rows(bufs[2][slot], h0, heads)),
                v_scale=(None if not quant
                         else _head_rows(bufs[3][slot], h0, heads)),
                row_off=_row_offsets(q.shape[1], n_q),
                ring=(n_blocks * block_k, pos + n_q - 1) if ring else None)

        return 0

    jax.lax.fori_loop(0, n_blocks, body, 0)
    par_ref[0] = jax.lax.rem(first + hi - lo + 1, 2)
    o_ref[0] = (acc_scr[...] / jnp.maximum(l_scr[:, :, :1], 1e-30)).astype(
        o_ref.dtype)


def _pick_block(t: int, block_k: int, quant: bool) -> "int | None":
    """A kv block that DIVIDES the cache length, so that the cache is
    streamed where it lies: the largest multiple of 128 up to ``block_k``
    that divides ``t``, else (bf16 only: the scales' positions lie on the
    128 lanes) a moderate length of whole sublane tiles as one block.
    None: this length cannot be tiled."""
    if t % 128 == 0:
        return max(c for c in range(128, max(block_k, 128) + 1, 128)
                   if t % c == 0)
    if not quant and t % 8 == 0 and t <= 4096:
        return t
    return None


# What a grid cell of :func:`decode_attention` may hold: the query rows of
# all its heads (one kv block's scores are [heads, rows, block_k] float32,
# 1 MiB at 512 x 512) and the bytes of one of its four k/v buffers.
_CELL_ROWS = 512
_CELL_BLOCK_BYTES = 1 << 20


def _cell_shape(hkv: int, rows_q: int, t: int, pos_bytes: int, block_k: int,
                quant: bool) -> "tuple[int, int]":
    """The kv heads a grid cell takes and its kv block, from the shapes
    alone: as many of the row's heads as keep the cell's query rows within
    ``_CELL_ROWS`` (all of them at decode; one where a prompt's piece
    brings 512 rows a head) and with them the largest block
    (:func:`_pick_block`) of at most ``_CELL_BLOCK_BYTES`` (``pos_bytes``
    one head's position of k), fewer heads where even the smallest block
    of all of them is larger (a length that is one block)."""
    for heads in range(hkv, 0, -1):
        if hkv % heads or (heads > 1 and heads * rows_q > _CELL_ROWS):
            continue
        block = _pick_block(
            t, min(block_k, _CELL_BLOCK_BYTES // (heads * pos_bytes)), quant)
        if heads == 1 or heads * block * pos_bytes <= _CELL_BLOCK_BYTES:
            return heads, block


def decode_attention(q, k_cache, v_cache, pos, *, layer=None, sm_scale=None,
                     block_k: int = 512, interpret=None, window=None,
                     k_scale=None, v_scale=None, rows=None,
                     kernel_name: str = "sw_decode_attn_stream",
                     ring: bool = False):
    """Cached decode attention (1..C query positions) without expanding
    the grouped cache.

    q: [B, Hq, C, D] — C consecutive query positions per row (C=1 is
    plain single-token decode; C>1 is the speculative chunk verify:
    models/speculative.py packs C positions x n_rep grouped heads as the
    rows of the SAME per-kv-head matmul, so the cache still streams
    exactly once, narrow and int8-capable).  k_cache/v_cache:
    the scan-stacked caches ``[L, B, Hkv, T, D]`` with ``layer`` a scalar
    int (traced inside the layer scan: it reaches the kernel as a
    prefetched scalar and indexes HBM, so no layer is sliced out and the
    stacked arrays are never copied), or one layer's ``[B, Hkv, T, D]``
    with ``layer=None`` (a stack of one).  pos: scalar int or per-row [B]
    int (ragged batches) — row b's queries sit at ``pos[b] .. pos[b] + C
    - 1``, key positions above each query are masked, and row b's DMA
    stops at its last query's block.  Write-then-attend callers must have
    the C entries in the cache already (:func:`kv_write`).  ``window``
    (static): sliding-window attention over the last ``window`` positions
    — blocks entirely below the window are DMA-elided too, so a windowed
    decode streams ~window bytes of cache regardless of T.  Returns [B,
    Hq, C, D].  Numerically matches :func:`decode_attention_lax`
    (softmax in f32).

    ``k_scale``/``v_scale`` ([L, B, Hkv, T] f32, or [B, Hkv, T] with
    ``layer=None``): int8-quantized caches (ops/quantize.py) — the kernel
    streams the int8 blocks (half the HBM bytes of bf16) and folds
    dequantization into the score/weight algebra; both or neither must be
    given, matching the caches' int8 dtype.  They stay in that layout: a
    cell reads its batch row's ``[Hkv, block_k]`` block (Mosaic blocks
    and DMAs the last two dims in (8, 128) tiles unless a block spans the
    whole dim, so a few heads' rows of it are refused by the chip's
    compiler) and keeps its heads' rows.

    A grid cell is a batch row's kv heads, all of them or a group
    (:func:`_cell_shape`: what the shapes let into VMEM), and the kv block
    is chosen to divide T (:func:`_pick_block`), so nothing
    is padded.  A length no block divides (not a multiple of 128; for
    bf16 up to 4096, of 8) costs what every length cost before: that
    layer is sliced out and padded, a copy of it a call.  Allocate
    multiples of 128.

    ``rows`` ([B] ints; :func:`slot_attention`): the cache row each batch
    row reads, a third prefetched scalar array; queries may then lie past
    the cache's end (they see every position).  ``kernel_name``: what a
    trace calls the kernel.

    ``ring`` (with ``window``; T a multiple of 128): the cache is a ring
    of ``T > window`` positions written at ``p % T``, ``pos`` stays
    absolute and every slot is masked by the position it holds
    (:func:`_softmax_block_update`): what a step that writes several
    positions a row before it knows which of them stay reads its window
    layers through.
    """
    if ring and (window is None or k_cache.shape[-2] % 128):
        raise ValueError("a masked ring needs its window and a length of "
                         "whole 128-lane tiles")
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
    scales = ([s.astype(jnp.float32) for s in (k_scale, v_scale)]
              if quant else [])
    if layer is None:
        k_cache, v_cache = k_cache[None], v_cache[None]
        scales = [s[None] for s in scales]
        layer = 0
    b, hq, n_q, d = q.shape
    hkv, t = k_cache.shape[2], k_cache.shape[3]
    n_rep = hq // hkv
    if sm_scale is None:
        sm_scale = 1.0 / (d ** 0.5)
    if interpret is None:
        interpret = dispatch.interpret()

    # Group query heads by their kv head: rows of the per-group matmul,
    # packed [n_rep, C] (row r = rep * C + ci — _row_offsets relies on
    # this layout).  repeat_kv maps q head h -> kv head h // n_rep, so the
    # reshape groups correctly (ops/attention.py:repeat_kv).
    n_rows = n_rep * n_q
    rows_q = _round_up(max(n_rows, 8), 8)  # TPU sublane tile
    qg = q.reshape(b, hkv, n_rows, d)
    if rows_q != n_rows:
        qg = jnp.pad(qg, ((0, 0), (0, 0), (0, rows_q - n_rows), (0, 0)))

    if _pick_block(t, block_k, quant) is None:
        def one_padded(a):
            a = jax.lax.dynamic_index_in_dim(a, layer, 0)
            grow = [(0, 0)] * a.ndim
            grow[3] = (0, _round_up(t, 128) - t)
            return jnp.pad(a, grow)

        k_cache, v_cache = one_padded(k_cache), one_padded(v_cache)
        scales = [one_padded(s) for s in scales]
        layer, t = 0, k_cache.shape[3]
    heads, block_k = _cell_shape(hkv, rows_q, t, d * k_cache.dtype.itemsize,
                                 block_k, quant)
    n_groups = hkv // heads
    pos_arr = jnp.broadcast_to(jnp.asarray(pos, jnp.int32).reshape(-1), (b,))
    layer_arr = jnp.asarray(layer, jnp.int32).reshape(1)
    by_row = () if rows is None else (jnp.asarray(rows, jnp.int32),)
    q_spec = pl.BlockSpec(
        (1, heads, rows_q, d),
        lambda c, *_: (jax.lax.div(c, n_groups), jax.lax.rem(c, n_groups),
                       0, 0))
    any_spec = pl.BlockSpec(memory_space=pltpu.MemorySpace.ANY)
    quant_scratch = [pltpu.VMEM((2, hkv, block_k), jnp.float32)] * (
        2 * quant)
    out = pl.pallas_call(
        functools.partial(
            _decode_stream_kernel, sm_scale=sm_scale, block_k=block_k,
            heads=heads, n_groups=n_groups,
            window=None if window is None else int(window),
            n_blocks=t // block_k, quant=quant, n_q=n_q,
            by_row=bool(by_row), ring=ring),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=2 + len(by_row),
            grid=(b * n_groups,),
            in_specs=[q_spec] + [any_spec] * (2 + 2 * quant),
            out_specs=q_spec,
            scratch_shapes=[
                pltpu.VMEM((2, heads, block_k, d), k_cache.dtype),
                pltpu.VMEM((2, heads, block_k, d), v_cache.dtype),
            ] + quant_scratch + [
                pltpu.SemaphoreType.DMA((2, 4 if quant else 2)),
                pltpu.SMEM((1,), jnp.int32),
                pltpu.VMEM((heads, rows_q, 128), jnp.float32),
                pltpu.VMEM((heads, rows_q, 128), jnp.float32),
                pltpu.VMEM((heads, rows_q, d), jnp.float32),
            ],
        ),
        out_shape=jax.ShapeDtypeStruct((b, hkv, rows_q, d), q.dtype),
        # Each cell starts the next one's stream: one core, in order.
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",)),
        interpret=interpret,
        name=kernel_name,
    )(pos_arr, layer_arr, *by_row, qg, k_cache, v_cache, *scales)
    return out[:, :, :n_rows, :].reshape(b, hq, n_q, d)


def decode_attention_lax(q, k_cache, v_cache, pos, *, layer=None,
                         window=None, k_scale=None, v_scale=None,
                         ring: bool = False, sm_scale=None):
    """:func:`decode_attention` in plain lax (softmax in f32): what runs
    where Pallas does not, and what the kernel is tested against.  It
    slices the layer out, dequantizes an int8 cache up front and expands
    the grouped heads (``repeat_kv``): n_rep times the cache's bytes."""
    if layer is not None:
        k_cache, v_cache, k_scale, v_scale = (
            None if a is None
            else jax.lax.dynamic_index_in_dim(a, layer, 0, keepdims=False)
            for a in (k_cache, v_cache, k_scale, v_scale))
    if k_scale is not None:
        k_cache = dequantize_kv(k_cache, k_scale, q.dtype)
        v_cache = dequantize_kv(v_cache, v_scale, q.dtype)
    n_rep = q.shape[1] // k_cache.shape[1]
    k = repeat_kv(k_cache, n_rep)
    v = repeat_kv(v_cache, n_rep)
    s = jnp.einsum("bhqd,bhkd->bhqk", q, k, preferred_element_type=jnp.float32)
    s = s / (q.shape[-1] ** 0.5) if sm_scale is None else s * sm_scale
    kv_pos = jnp.arange(k.shape[2])[None, None, None, :]
    qp = (jnp.asarray(pos).reshape(-1)[:, None, None, None]
          + jnp.arange(q.shape[2])[None, None, :, None])
    if ring:  # slot s holds the latest position p <= top with p % T == s
        top = qp[:, :, -1:, :]
        kv_pos = top - (top - kv_pos) % k.shape[2]
    keep = kv_pos <= qp
    if ring:
        keep = keep & (kv_pos >= 0)
    if window is not None:
        keep = keep & (kv_pos > qp - window)
    s = jnp.where(keep, s, NEG_BIG)
    p = jax.nn.softmax(s, axis=-1)
    return jnp.einsum("bhqk,bhkd->bhqd", p.astype(v.dtype), v)


def cached_attention(q, k_cache, v_cache, pos, *, layer=None, window=None,
                     k_scale=None, v_scale=None, ring: bool = False,
                     sm_scale=None):
    """Decode attention over a grouped k/v cache, the operation: the
    arguments of :func:`decode_attention`.  On a TPU the kernel streams
    the grouped cache once where it lies (an ``n_rep``-fold saving of HBM
    bandwidth on the bandwidth-bound decode step, and only ~window bytes
    of it under a sliding window), per shard of the heads under a ``tp``
    mesh; elsewhere :func:`decode_attention_lax`.

    ``ring``: the cache is a circular window of its own length ``T``,
    written at ``pos % T`` (models/generate.py): its warm slots ARE the
    window, so the query at ``pos`` sees every slot up to ``min(pos, T -
    1)`` with no window re-mask (keys carry their absolute RoPE, and
    attention does not depend on the order of its keys).  With
    ``window=`` the ring is LONGER than that window (``T > window``,
    ``LayerKinds.slack``): ``pos`` stays absolute, every slot is read
    under the mask of the position it holds (:func:`decode_attention`'s
    ``ring``), and ``C`` query positions may have been written.  The
    same kernel either way, which a trace then calls
    ``sw_decode_attn_ring``.  ``sm_scale``: the scores' multiplier where
    it is not ``D ** -0.5``."""
    masked = ring and window is not None
    if masked and not window < k_cache.shape[-2]:
        raise ValueError(f"a masked ring is longer than its window, got "
                         f"{k_cache.shape[-2]} for window {window}")
    if ring and not masked:
        if q.shape[2] != 1:
            raise ValueError(
                "a ring of exactly one window takes one position a step: a "
                "chunk written into it overwrites entries its own earlier "
                "positions attend (LayerKinds.slack lengthens it)")
        pos = jnp.minimum(jnp.asarray(pos, jnp.int32), k_cache.shape[-2] - 1)
    if not dispatch.use_kernels():
        return decode_attention_lax(q, k_cache, v_cache, pos, layer=layer,
                                    window=window, k_scale=k_scale,
                                    v_scale=v_scale, ring=masked,
                                    sm_scale=sm_scale)
    if layer is None:  # one layer's caches: a stack of one
        k_cache, v_cache, k_scale, v_scale = (
            None if a is None else a[None]
            for a in (k_cache, v_cache, k_scale, v_scale))
        layer = 0
    scales = () if k_scale is None else (k_scale, v_scale)
    name = "sw_decode_attn_ring" if ring else "sw_decode_attn_stream"

    def kernel(q, k, v, *rest):  # rest = (*scales, pos, layer)
        ks, vs = rest[:-2] or (None, None)
        return decode_attention(q, k, v, rest[-2], layer=rest[-1],
                                window=window, k_scale=ks, v_scale=vs,
                                kernel_name=name, ring=masked,
                                sm_scale=sm_scale)

    # Heads (dim 1 of q, dim 2 of the stacked caches and scales) shard
    # alike; pos (a scalar, or one cursor per batch row) and the layer
    # index are the same on every shard.
    return dispatch.per_head_shard(
        kernel, (q, k_cache, v_cache, *scales),
        (jnp.asarray(pos, jnp.int32), jnp.asarray(layer, jnp.int32)),
        head_dims=(1,) + (2,) * (2 + len(scales)))


# ------------------------------------------- a prompt's piece on its slot

# Query positions a grid cell of :func:`slot_attention` takes: with the
# group's n_rep heads they are the rows of one matmul a kv block (512 at
# Mistral's 4), and a cell sweeps the cache only as far as its own last
# query.
_SLOT_BLOCK_Q = 128


def slot_attention(q, k_cache, v_cache, pos, rows, *, layer, k_scale=None,
                   v_scale=None, interpret=None):
    """``C`` consecutive queries a batch row against ONE row of the stacked
    cache, named by an index: ``q [B, Hq, C, D]`` at positions ``pos[b] ..
    pos[b] + C - 1`` attends ``cache[layer, rows[b]]`` (write-then-attend:
    the entries are in the cache already, :func:`kv_write`).  This is how a
    prompt's piece attends inside a serving step (models/generate.py::
    ingest_decode_step): its request's slot is where the piece was just
    written, beside the decode rows' own.  Returns ``[B, Hq, C, D]``.

    The kernel is :func:`decode_attention`'s, under the name
    ``sw_ingest_attn``: the queries are cut into tiles of
    ``_SLOT_BLOCK_Q`` positions, each tile a batch row of that kernel at
    its own cursor, all reading the cache row their request owns through a
    prefetched row index beside the layer index.  Nothing is sliced out of
    the stacked cache.  Queries past the cache's end (a last piece's pads)
    are computed and mean nothing."""
    b, hq, c, d = q.shape
    tq = _SLOT_BLOCK_Q if c % _SLOT_BLOCK_Q == 0 else c
    n = c // tq
    tiles = q.reshape(b, hq, n, tq, d).transpose(0, 2, 1, 3, 4)
    at = (jnp.asarray(pos, jnp.int32).reshape(-1, 1)
          + tq * jnp.arange(n, dtype=jnp.int32)[None, :])
    out = decode_attention(
        tiles.reshape(b * n, hq, tq, d), k_cache, v_cache, at.reshape(-1),
        layer=layer, k_scale=k_scale, v_scale=v_scale, interpret=interpret,
        rows=jnp.repeat(jnp.asarray(rows, jnp.int32).reshape(-1), n),
        kernel_name="sw_ingest_attn")
    return out.reshape(b, n, hq, tq, d).transpose(0, 2, 1, 3, 4).reshape(
        b, hq, c, d)


def slot_attention_lax(q, k_cache, v_cache, pos, rows, *, layer,
                       k_scale=None, v_scale=None):
    """:func:`slot_attention` in plain lax: the layer and the rows are
    sliced out (copies), then :func:`decode_attention_lax`."""
    rows = jnp.asarray(rows, jnp.int32).reshape(-1)
    k, v, ks, vs = (
        None if a is None else jnp.take(
            jax.lax.dynamic_index_in_dim(a, layer, 0, keepdims=False),
            rows, axis=0)
        for a in (k_cache, v_cache, k_scale, v_scale))
    return decode_attention_lax(q, k, v, pos, k_scale=ks, v_scale=vs)


def ingest_attention(q, k_cache, v_cache, pos, rows, *, layer, k_scale=None,
                     v_scale=None):
    """A prompt piece's attention over its request's cache row, the
    operation: the arguments of :func:`slot_attention`.  On a TPU the
    kernel, per shard of the heads under a ``tp`` mesh; elsewhere
    :func:`slot_attention_lax`."""
    if not dispatch.use_kernels():
        return slot_attention_lax(q, k_cache, v_cache, pos, rows,
                                  layer=layer, k_scale=k_scale,
                                  v_scale=v_scale)
    scales = () if k_scale is None else (k_scale, v_scale)

    def kernel(q, k, v, *rest):  # rest = (*scales, pos, rows, layer)
        ks, vs = rest[:-3] or (None, None)
        return slot_attention(q, k, v, rest[-3], rest[-2], layer=rest[-1],
                              k_scale=ks, v_scale=vs)

    return dispatch.per_head_shard(
        kernel, (q, k_cache, v_cache, *scales),
        (jnp.asarray(pos, jnp.int32), jnp.asarray(rows, jnp.int32),
         jnp.asarray(layer, jnp.int32)),
        head_dims=(1,) + (2,) * (2 + len(scales)))


# ------------------------------------------------- latent (MLA) attention


def mla_decode_attention_lax(q, latent, pos, *, rank: int, sm_scale: float,
                             layer=0):
    """:func:`mla_decode_attention` in plain lax (softmax in f32): what runs
    where Pallas does not, and what the kernel is tested against."""
    c = jax.lax.dynamic_index_in_dim(latent, layer, 0, keepdims=False)[:, 0]
    s = jnp.einsum("bhcw,btw->bhct", q, c,
                   preferred_element_type=jnp.float32) * sm_scale
    qp = (jnp.asarray(pos, jnp.int32).reshape(-1)[:, None, None, None]
          + jnp.arange(q.shape[2])[None, None, :, None])
    s = jnp.where(jnp.arange(c.shape[1])[None, None, None, :] <= qp, s, NEG_BIG)
    p = jax.nn.softmax(s, axis=-1)
    return jnp.einsum("bhct,btr->bhcr", p.astype(c.dtype), c[..., :rank])


def mla_decode_attention(q, latent, pos, *, rank: int, sm_scale: float,
                         layer=0, block_k: int = 512, interpret=None):
    """Cached decode attention over a LATENT cache (multi-head latent
    attention, absorbed form): every head shares one cached row a
    position, ``[c_kv (rank) | k_pe]``, whose first ``rank`` values are
    also the value.  q: ``[B, H, C, W]`` absorbed queries ``[q_nope W_UK |
    q_pe]`` at positions ``pos[b] .. pos[b] + C - 1``; latent: the stacked
    cache ``[L, B, 1, T, W]`` with ``layer`` a (traced) scalar, never
    sliced; pos: scalar or ``[B]``.  Returns ``[B, H, C, rank]`` (the
    caller applies ``W_UV``).  The H x C queries of a row are the rows of
    one matmul against each block, so the cache is read once for all heads
    and once for both uses.  T must be a multiple of 128
    (:func:`_pick_block`); other lengths take the lax form.

    The kernel is :func:`_decode_stream_kernel` over one operand, one grid
    cell a slot, and its stream is that kernel's chain: a slot's last
    block computes while the NEXT slot's first is on its way into the
    other buffer, the buffer a slot starts in rides from cell to cell in
    SMEM, and only the call's first cell starts cold (before PR 48 every
    cell did, and waited for its whole first block: 1.1 us of the 4 to 5
    a cell took at the served cursors, PERF.md section 6).  Hence the sequential ("arbitrary") grid axis: a cell
    waits for a copy the cell before it started.  And hence the clamp of a
    slot's last block to the cache (``span``): every cell must have a
    block the one before it can fetch, also a frozen slot whose cursor
    stands at ``T``."""
    b, h, n_q, w = q.shape
    t = latent.shape[3]
    if interpret is None:
        interpret = dispatch.interpret()
    block_k = _pick_block(t, block_k, True)
    if block_k is None:
        return mla_decode_attention_lax(q, latent, pos, rank=rank,
                                        sm_scale=sm_scale, layer=layer)
    rows = h * n_q  # row r = head * C + ci (_row_offsets' layout)
    pos_arr = jnp.broadcast_to(jnp.asarray(pos, jnp.int32).reshape(-1), (b,))
    out = pl.pallas_call(
        functools.partial(
            _decode_stream_kernel, sm_scale=float(sm_scale), block_k=block_k,
            heads=1, n_groups=1, window=None, n_blocks=t // block_k, n_q=n_q,
            rank=rank),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=2,
            grid=(b,),
            in_specs=[pl.BlockSpec((1, 1, rows, w), lambda i, *_: (i, 0, 0, 0)),
                      pl.BlockSpec(memory_space=pltpu.MemorySpace.ANY)],
            out_specs=pl.BlockSpec((1, 1, rows, rank),
                                   lambda i, *_: (i, 0, 0, 0)),
            scratch_shapes=[
                pltpu.VMEM((2, 1, block_k, w), latent.dtype),
                pltpu.SemaphoreType.DMA((2, 1)),
                pltpu.SMEM((1,), jnp.int32),
                pltpu.VMEM((1, rows, 128), jnp.float32),
                pltpu.VMEM((1, rows, 128), jnp.float32),
                pltpu.VMEM((1, rows, rank), jnp.float32),
            ],
        ),
        out_shape=jax.ShapeDtypeStruct((b, 1, rows, rank), q.dtype),
        # Each cell starts the next one's stream: one core, in order.
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",)),
        interpret=interpret,
        name="sw_mla_decode_attn",
    )(pos_arr, jnp.asarray(layer, jnp.int32).reshape(1),
      q.reshape(b, 1, rows, w), latent)
    return out.reshape(b, h, n_q, rank)


def latent_attention(q, latent, pos, *, rank: int, sm_scale: float, layer=0):
    """Decode attention over a latent cache, the operation: the kernel
    (:func:`mla_decode_attention`) on a TPU, its lax twin elsewhere.  One
    row a position serves every head, so there is no head to shard by."""
    fn = (mla_decode_attention if dispatch.use_kernels()
          else mla_decode_attention_lax)
    return fn(q, latent, pos, rank=rank, sm_scale=sm_scale, layer=layer)


# ------------------------------------------------------ the in-place write

# VMEM one call's read-modify-write buffers may take; the update blocks
# ride the Pallas pipeline double-buffered on top (C > 1), all inside the
# compiler's default scoped limit of 16 MiB.
_WRITE_VMEM_BYTES = 3 << 20


def _kv_write_kernel(layer_ref, row_ref, start_ref, lo_ref, hi_ref, *refs,
                     n_arr: int, group: int, width: int, align: int):
    """One grid cell per GROUP of rows.  For each row and each array: DMA
    the tile-aligned window ``[Hkv, width(, D)]`` that holds the row's new
    positions out of HBM, replace positions ``lo .. hi - 1`` of it by the
    update (a select), DMA it back to where it came from.  All of a
    group's reads are in flight together, then all of its writes: a
    decode step's 24 rows cost two DMA latencies, not 48.

    The rows are walked by loops, not unrolled in Python: unrolled, the
    indexing of a group's windows was most of the seconds a serving
    program takes to trace (24 rows: half a second on the chip's host,
    once a program, PR 29; 128 rows in a latent cache's write)."""
    updates = refs[:n_arr]
    src = refs[n_arr:2 * n_arr]        # the caches, in HBM
    dst = refs[2 * n_arr:3 * n_arr]    # the same buffers (aliased outputs)
    bufs = refs[3 * n_arr:4 * n_arr]   # VMEM [group, Hkv, width(, D)]
    sems = refs[4 * n_arr]
    layer = layer_ref[0]
    base = pl.program_id(0) * group

    def copy(a, j, back):
        """The DMA of row ``base + j``'s window of array ``a``: out of HBM,
        or ``back`` to where it came from."""
        ref, i = (dst if back else src)[a], base + j
        window = ref.at[(layer, row_ref[i], slice(None),
                         pl.ds(pl.multiple_of(start_ref[i], align), width))
                        + (slice(None),) * (len(ref.shape) - 4)]
        ends = (bufs[a].at[j], window) if back else (window, bufs[a].at[j])
        return pltpu.make_async_copy(*ends, sems.at[a, j])

    def each_row(body):
        jax.lax.fori_loop(0, group, lambda j, _: body(j) or 0, 0)

    def each_copy(back, act):
        each_row(lambda j: [getattr(copy(a, j, back), act)()
                            for a in range(n_arr)] and None)

    def select(j):
        at = jax.lax.broadcasted_iota(jnp.int32, bufs[0].shape[1:], 1)
        new = (at >= lo_ref[base + j]) & (at < hi_ref[base + j])
        for a in range(n_arr):
            bufs[a][j] = jnp.where(new, updates[a][j], bufs[a][j])

    each_copy(False, "start")
    each_copy(False, "wait")
    each_row(select)
    each_copy(True, "start")
    each_copy(True, "wait")


def kv_write_lax(caches, updates, layer, rows, pos, count=None):
    """:func:`kv_write` in plain lax (one scatter an array): what runs
    where Pallas does not (the CPU), where a cache length has no whole
    tiles, and what the kernel is tested against.  Same clamp."""
    c = updates[0].shape[2]
    t = caches[0].shape[3]
    pos = jnp.asarray(pos, jnp.int32)
    if count is None:
        pos = jnp.clip(pos, 0, t - c)
    at = pos[:, None] + jnp.arange(c)[None, :]                  # [N, C]
    if count is not None:  # the others go past the end, and are dropped
        at = jnp.where(jnp.arange(c)[None, :]
                       < jnp.asarray(count, jnp.int32)[:, None], at, t)
    rows = jnp.asarray(rows, jnp.int32)[:, None]
    # Advanced indices (row, position) lead the update: [N, C, Hkv(, D)].
    return tuple(
        x.at[layer, rows, :, at].set(jnp.moveaxis(u, 2, 1), mode="drop")
        for x, u in zip(caches, updates))


def kv_write(caches, updates, layer, rows, pos, *, count=None,
             interpret=None):
    """``cache[layer, rows[n], :, pos[n] + c] = update[n, :, c]`` for every
    ``(cache, update)`` pair, IN PLACE: each output aliases its cache
    operand, only the new entries' tiles move, and the stacked array's
    layout is left alone.  This is the write half of write-then-attend
    (:func:`decode_attention` is the read half); the XLA forms of it
    (scatter, ``dynamic_update_slice`` per row) make the chip's compiler
    re-lay the scan's carry and copy the whole cache every layer.

    caches: same-shaped stacked arrays ``[L, R, Hkv, T, D]`` (k and v) or
    ``[L, R, Hkv, T]`` (their int8 scales); updates: ``[N, Hkv, C, D]`` /
    ``[N, Hkv, C]``, the C new consecutive positions of N rows; layer:
    scalar; rows, pos: ``[N]`` ints — the dense cache has ``rows =
    arange(B)``, the paged pool ``rows = page ids, pos = offsets``.  As
    with ``lax.dynamic_update_slice`` a start above ``T - C`` is clamped.
    The N windows of one call must not overlap (two rows in one tile
    would race, each writing back what it read).  ``count`` ([N] ints):
    only the first ``count[n]`` of row n's C positions are written and
    nothing is clamped; the caller keeps ``pos + count <= T`` (a prompt's
    last piece, padded to the piece's width, may reach past the cache's
    end with its pads).

    DMAs move whole tiles, so a row's window is the aligned span of
    ``width`` positions around ``pos .. pos + C - 1`` (at C = 1: 16 for
    bf16, 32 for int8, 128 lanes for the scales).  A cache length with no
    whole tiles takes :func:`kv_write_lax`.  Returns the updated caches,
    as a tuple."""
    c0, u0 = caches[0], updates[0]
    hkv, t = c0.shape[2:4]
    n, c = u0.shape[0], u0.shape[2]
    if interpret is None:
        interpret = dispatch.interpret()
    # T is the second-minor (sublane) dim of k/v and the minor (lane) dim
    # of the scales.
    align = 128 if c0.ndim == 4 else 32 // c0.dtype.itemsize
    if t % align:
        return kv_write_lax(caches, updates, layer, rows, pos, count)
    tail = c0.shape[4:]
    row_bytes = sum(x.dtype.itemsize for x in caches) * hkv * (
        tail[0] if tail else 1)  # of one position, all arrays
    c_max = max(_WRITE_VMEM_BYTES // row_bytes - align, align)
    pos = jnp.asarray(pos, jnp.int32)
    if count is None:
        pos = jnp.clip(pos, 0, t - c)
    else:
        pos = jnp.clip(pos, 0, t)
        count = jnp.asarray(count, jnp.int32)
    if c > c_max:  # a long chunk (a prefix admit's suffix): in pieces
        for at in range(0, c, c_max):
            caches = kv_write(
                caches, [u[:, :, at:at + c_max] for u in updates], layer,
                rows, pos + at, interpret=interpret,
                count=None if count is None else jnp.clip(count - at, 0,
                                                          c_max))
        return tuple(caches)

    width = min(_round_up(align - 1 + c, align), t)
    start = jnp.minimum(pos // align * align, t - width)
    lo = pos - start
    hi = lo + c if count is None else jnp.minimum(lo + count, width)
    if c > 1:
        # The update, placed in its window by XLA (a gather over a few
        # KiB): in the kernel a shift by a traced count is not a select.
        src = jnp.clip(jnp.arange(width)[None, :] - lo[:, None], 0, c - 1)
        updates = [jnp.take_along_axis(
            u, src.reshape((n, 1, width) + (1,) * (u.ndim - 3)), axis=2)
            for u in updates]
    group = max(g for g in range(1, n + 1)
                if n % g == 0 and (g == 1 or g * width * row_bytes
                                   <= _WRITE_VMEM_BYTES))
    n_arr = len(caches)
    ublock = (group, hkv, updates[0].shape[2]) + tail
    any_spec = pl.BlockSpec(memory_space=pltpu.MemorySpace.ANY)
    out = pl.pallas_call(
        functools.partial(_kv_write_kernel, n_arr=n_arr, group=group,
                          width=width, align=align),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=5,
            grid=(n // group,),
            in_specs=[pl.BlockSpec(ublock, lambda g, *_: (g,) + (0,) * (
                len(ublock) - 1))] * n_arr + [any_spec] * n_arr,
            out_specs=[any_spec] * n_arr,
            scratch_shapes=[
                pltpu.VMEM((group, hkv, width) + tail, x.dtype)
                for x in caches
            ] + [pltpu.SemaphoreType.DMA((n_arr, group))],
        ),
        out_shape=[jax.ShapeDtypeStruct(x.shape, x.dtype) for x in caches],
        input_output_aliases={5 + n_arr + a: a for a in range(n_arr)},
        interpret=interpret,
        name="sw_kv_write",
    )(jnp.asarray(layer, jnp.int32).reshape(1),
      jnp.asarray(rows, jnp.int32), start, lo, hi, *updates, *caches)
    return tuple(out)


def cache_write(caches, updates, layer, rows, pos, count=None):
    """The in-place cache write, the operation: the arguments of
    :func:`kv_write`.  On a TPU the kernel, per shard of the heads under a
    ``tp`` mesh (dim 2 of the caches, dim 1 of the updates); elsewhere
    :func:`kv_write_lax`.  A caller whose rows share a tile (one row a
    token of a page: models/paged.py's prefix admit) must take
    :func:`kv_write_lax` by name: the kernel's rows would race."""
    if not dispatch.use_kernels():
        return kv_write_lax(caches, updates, layer, rows, pos, count)
    n = len(caches)
    return dispatch.per_head_shard(
        lambda *a: kv_write(a[:n], a[n:2 * n], *a[2 * n:2 * n + 3],
                            count=a[2 * n + 3] if count is not None else None),
        tuple(caches) + tuple(updates),
        (layer, rows, pos) + (() if count is None else (count,)),
        head_dims=(2,) * n + (1,) * n, out_head_dims=(2,) * n)
