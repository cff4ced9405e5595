"""Paged KV-cache decode attention (vLLM-style block tables, TPU-first).

The dense serving cache ``[L, n_slots, Hkv, max_len, D]`` reserves
``max_len`` positions per slot whether a request uses them or not; real
workloads mix short and long requests, so most of that HBM is dead.
Paging shares one POOL of fixed-size pages across all slots:

* pool:  ``k/v [n_pages, Hkv, page, D]`` — the only large allocation;
  sized by expected TOTAL live tokens, not slots x max_len;
* table: ``[n_slots, max_pages] int32`` page ids per slot (host-managed
  free list, models/paged.py);
* decode reads the pages through the table with NO materialisation of a
  dense view — the indirection lives in the kernel's DMA stream.

The kernel is the stream decode kernel's structure (pallas_decode.py:
one grid cell per (slot, kv head), whole-cache sweep as a fori_loop with
double-buffered manual ``make_async_copy``) with one change: block i's
DMA source is ``pool.at[table[slot, i], head]`` instead of a contiguous
``cache.at[slot*hkv+head, i*block]`` slice.  Page id and cursor ride the
scalar-prefetch operand (SMEM), so the address is known when the copy
starts — the pipeline still overlaps compute on page i with the stream
of page i+1, and pages past the cursor are never fetched.  Bandwidth per
decoded token is identical to the dense stream kernel: the pool pages
the slot actually owns, once, narrow (grouped heads, no repeat_kv).

Same online-softmax block body as the dense kernels
(``_softmax_block_update``); numerics pinned against the dense oracle in
tests/test_paged.py.  int8 pools are not wired yet (the dense kernel's
quant path shows the shape; refused loudly below).
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from . import dispatch
from .attention import NEG_BIG
from .pallas_attention import _round_up
from .pallas_decode import _row_offsets, _softmax_block_update


def _paged_stream_kernel(meta_ref, layer_ref, q_ref, k_pool, v_pool, o_ref,
                         k_buf, v_buf, sems, m_scr, l_scr, acc_scr, *,
                         sm_scale: float, page: int, hkv: int,
                         max_pages: int, n_q: int):
    """One grid cell per (slot, kv head); fori_loop over the slot's pages
    with double-buffered DMA through the block table.

    ``meta_ref`` (scalar prefetch, SMEM): ``[n_slots, 1 + max_pages]`` —
    column 0 is the slot's cursor, columns 1.. its page ids.
    ``layer_ref``: the layer of the stacked pools ``[L, n_pages, Hkv,
    page, D]`` to read, in the DMA's source address."""
    bh = pl.program_id(0)
    b = bh // hkv
    h = jax.lax.rem(bh, hkv)
    layer = layer_ref[0]
    pos = meta_ref[b, 0]
    hi = (pos + n_q - 1) // page  # last live page (queries span n_q)

    def copies(i, slot):
        pid = meta_ref[b, 1 + i]
        return [
            pltpu.make_async_copy(
                k_pool.at[layer, pid, h], k_buf.at[slot], sems.at[slot, 0]),
            pltpu.make_async_copy(
                v_pool.at[layer, pid, h], v_buf.at[slot], sems.at[slot, 1]),
        ]

    m_scr[:] = jnp.full_like(m_scr, NEG_BIG)
    l_scr[:] = jnp.zeros_like(l_scr)
    acc_scr[:] = jnp.zeros_like(acc_scr)
    for cp in copies(0, 0):
        cp.start()
    q = q_ref[0]  # [rows, D]

    def body(i, _):
        live = i <= hi

        @pl.when(live)
        def _live():
            slot = jax.lax.rem(i, 2)

            @pl.when(i + 1 <= hi)
            def _prefetch():
                for cp in copies(i + 1, jax.lax.rem(i + 1, 2)):
                    cp.start()

            for cp in copies(i, slot):
                cp.wait()
            _softmax_block_update(
                q, k_buf[slot], v_buf[slot], i * page, pos, m_scr, l_scr,
                acc_scr, sm_scale=sm_scale, window=None,
                row_off=_row_offsets(q.shape[0], n_q))

        return 0

    jax.lax.fori_loop(0, max_pages, body, 0)
    o_ref[0] = (acc_scr[:] / jnp.maximum(l_scr[:, :1], 1e-30)).astype(o_ref.dtype)


def paged_decode_attention(q, k_pool, v_pool, table, pos, *, layer=None,
                           sm_scale=None, interpret=None):
    """Decode attention over a paged KV pool.

    q: ``[B, Hq, C, D]`` (C consecutive query positions per slot, like
    the dense kernel — C=1 is plain decode).  k_pool/v_pool: the stacked
    pools ``[L, n_pages, Hkv, page, D]`` with ``layer`` a (traced) scalar
    that indexes HBM in the kernel, so no layer is sliced out of the scan's
    carry; or one layer's ``[n_pages, Hkv, page, D]`` with
    ``layer=None``.  table: ``[B, max_pages] int32`` (page i
    of slot b holds positions ``i*page .. (i+1)*page - 1``; ids past the
    cursor may be anything — they are never fetched); pos: scalar or
    ``[B]`` cursors.  Returns ``[B, Hq, C, D]``, numerically matching
    the dense :func:`~starway_tpu.ops.pallas_decode.decode_attention`
    over the gathered logical cache (tests/test_paged.py).
    """
    if k_pool.dtype == jnp.int8 or v_pool.dtype == jnp.int8:
        raise NotImplementedError(
            "int8 paged pools are not wired yet; serve int8 caches "
            "through the dense kernel (ops/pallas_decode.py)")
    if layer is None:
        k_pool, v_pool, layer = k_pool[None], v_pool[None], 0
    b, hq, n_q, d = q.shape
    hkv, page = k_pool.shape[2:4]
    max_pages = table.shape[1]
    n_rep = hq // hkv
    if sm_scale is None:
        sm_scale = 1.0 / (d ** 0.5)
    if interpret is None:
        interpret = dispatch.interpret()

    n_rows = n_rep * n_q
    rows = _round_up(max(n_rows, 8), 8)
    qg = q.reshape(b, hkv, n_rows, d)
    if rows != n_rows:
        qg = jnp.pad(qg, ((0, 0), (0, 0), (0, rows - n_rows), (0, 0)))
    qf = qg.reshape(b * hkv, rows, d)

    pos_arr = jnp.broadcast_to(jnp.asarray(pos, jnp.int32).reshape(-1), (b,))
    meta = jnp.concatenate([pos_arr[:, None], table.astype(jnp.int32)],
                           axis=1)

    any_spec = pl.BlockSpec(memory_space=pltpu.MemorySpace.ANY)
    out = pl.pallas_call(
        functools.partial(
            _paged_stream_kernel, sm_scale=sm_scale, page=page, hkv=hkv,
            max_pages=max_pages, n_q=n_q),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=2,
            grid=(b * hkv,),
            in_specs=[
                pl.BlockSpec((1, rows, d), lambda bh, *_: (bh, 0, 0)),
                any_spec,
                any_spec,
            ],
            out_specs=pl.BlockSpec((1, rows, d), lambda bh, *_: (bh, 0, 0)),
            scratch_shapes=[
                pltpu.VMEM((2, page, d), k_pool.dtype),
                pltpu.VMEM((2, page, d), v_pool.dtype),
                pltpu.SemaphoreType.DMA((2, 2)),
                pltpu.VMEM((rows, 128), jnp.float32),
                pltpu.VMEM((rows, 128), jnp.float32),
                pltpu.VMEM((rows, d), jnp.float32),
            ],
        ),
        out_shape=jax.ShapeDtypeStruct((b * hkv, rows, d), q.dtype),
        interpret=interpret,
        name="sw_paged_decode_attn",
    )(meta, jnp.asarray(layer, jnp.int32).reshape(1), qf, k_pool, v_pool)
    return out.reshape(b, hkv, rows, d)[:, :, :n_rows, :].reshape(
        b, hq, n_q, d)


def paged_attention(q, k_pool, v_pool, table, pos, *, layer=None):
    """Decode attention over the paged pool, the operation: the arguments
    of :func:`paged_decode_attention` (pools ``[L, n_pages, Hkv, page, D]``
    with ``layer``: heads at dim 2), per shard of the heads under a ``tp``
    mesh.  It has ONE implementation, interpreted off the chip: no lax
    twin is written for it (tests use :func:`gather_logical` and the dense
    twin as the oracle)."""
    if layer is None:
        k_pool, v_pool, layer = k_pool[None], v_pool[None], 0
    return dispatch.per_head_shard(
        lambda q, k, v, table, pos, layer: paged_decode_attention(
            q, k, v, table, pos, layer=layer),
        (q, k_pool, v_pool),
        (table, pos, jnp.asarray(layer, jnp.int32)), head_dims=(1, 2, 2))


def gather_logical(pool, table):
    """Dense view of each slot's logical cache (TEST/ORACLE use only —
    materialising this is exactly what the kernel avoids): pool
    ``[n_pages, Hkv, page, D]`` + table ``[B, max_pages]`` ->
    ``[B, Hkv, max_pages*page, D]``."""
    g = pool[table]  # [B, max_pages, Hkv, page, D]
    b, mp, hkv, page, d = g.shape
    return g.transpose(0, 2, 1, 3, 4).reshape(b, hkv, mp * page, d)
