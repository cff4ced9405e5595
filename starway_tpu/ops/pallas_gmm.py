"""Pallas TPU grouped matmul for a dropless mixture-of-experts FFN.

The rows of ``x [M, K]`` are (token, choice) pairs laid out expert by
expert, each expert's rows padded up to whole row tiles, so that a row
tile belongs to exactly ONE expert (models/moe.py::group_rows builds the
layout).  A row tile's expert reaches the kernel as a prefetched scalar and
picks the weight block in the DMA's source address, so only the experts
that got a token are read and nothing is padded to a capacity.

The kernel walks a RUN at a time: the live row tiles of one expert, which
lie together.  Inside a run the column block is the outer index and the
run's tiles the inner one (:func:`tile_walk` lays the walk out; a grid
step reads its expert, tile and column block as prefetched scalars), so
the weight block ``(expert, column block)`` keeps its index over the run's
tiles and the pipeline, which copies a block only where its index changed,
fetches it ONCE a call however many tiles the expert fills.  (Row tile
outer, a 3 MB weight block was fetched again for each 16-row tile of its
expert wherever a matrix has several column blocks: PERF.md, PR 47.)  A
run of several tiles pays with its ``x`` tiles, fetched once a column
block: a fifteenth of the weight block they ride under.  A run of one tile
holds its ``x`` tile over its columns, and with one column block the walk
is the tiles' own order.  Steps past the last live tile repeat the last
live step's block indices and skip their body: they move no bytes and do
no work, so a step's cost follows the pairs that really landed here, not
the worst case the static shape allows.

Two forms under one name (``sw_moe_gmm``): ``x @ w[e]`` and, with a second
weight, the gated pair ``act(x @ w[e]) * (x @ w2[e])`` of a gated expert's
first half (``act``: SiLU for SwiGLU, ReLU for ReGLU), which reads the row
tile once for both.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from . import dispatch

# The double-buffered weight blocks of the gated form at K = 7168 are more
# than the compiler's default scoped limit (16 MiB) leaves room for.
_VMEM_LIMIT_BYTES = 64 << 20
_BLOCK_BYTES = 4 << 20   # one weight block [K, tn]

# The gate activations of the gated form, by the name a configuration gives.
GATE_ACTS = {"silu": jax.nn.silu, "relu": jax.nn.relu}


def _gmm_kernel(step_expert_ref, step_tile_ref, step_col_ref, n_live_ref,
                layer_ref, x_ref, *refs, gated: bool, act: str, n_col: int):
    if gated:
        w_ref, w2_ref, o_ref = refs
    else:
        w_ref, o_ref = refs

    @pl.when(pl.program_id(0) < n_live_ref[0] * n_col)
    def _body():
        x = x_ref[...]
        y = jnp.dot(x, w_ref[...], preferred_element_type=jnp.float32)
        if gated:
            y = GATE_ACTS[act](y) * jnp.dot(
                x, w2_ref[...], preferred_element_type=jnp.float32)
        o_ref[...] = y.astype(o_ref.dtype)


def column_block(k: int, n: int, itemsize: int) -> int:
    """Columns of one weight block: the widest multiple of 128 that divides
    ``n`` and keeps ``[k, tn]`` under ``_BLOCK_BYTES`` (``n`` itself where
    it has no such divisor: small test shapes)."""
    fits = [c for c in range(128, n + 1, 128)
            if n % c == 0 and k * c * itemsize <= _BLOCK_BYTES]
    return max(fits) if fits else n


def tile_walk(tile_expert, n_live, n_col: int):
    """The kernel's walk, ``(step_expert, step_tile, step_col)``, each
    ``[tiles * n_col]`` int32: the expert, row tile and column block whose
    blocks a grid step holds.  A RUN is the live tiles of one expert
    (``tile_expert`` does not decrease over them, so they lie together);
    its ``length * n_col`` steps start where its first tile's would, tile
    by tile, and go column block by column block over all its tiles.  A
    step past the last live tile is the last live step."""
    tiles = jnp.arange(tile_expert.shape[0], dtype=jnp.int32)
    live = tiles < n_live
    same = live[None, :] & (tile_expert[None, :] == tile_expert[:, None])
    # A tile's run (a dead tile is alone), said at each of its n_col steps.
    first, length, expert = (jnp.repeat(a.astype(jnp.int32), n_col) for a in (
        jnp.where(live, jnp.argmax(same, axis=1), tiles),
        jnp.where(live, jnp.sum(same, axis=1), 1), tile_expert))
    steps = jnp.arange(first.shape[0], dtype=jnp.int32)
    at = steps - first * n_col
    on = steps < n_live * n_col
    last = jnp.maximum(n_live - 1, 0)
    return (jnp.where(on, expert, tile_expert[last]),
            jnp.where(on, first + at % length, last),
            jnp.where(on, at // length, n_col - 1))


def gmm_lax(x, w, tile_expert, n_live, tile_m: int, w2=None, layer=None,
            act: str = "silu"):
    """:func:`gmm` in plain lax: what runs where Pallas does not, and what
    the kernel is tested against.  Rows of dead tiles come out zero."""
    if layer is not None:
        w, w2 = (None if a is None else jax.lax.dynamic_index_in_dim(
            a, layer, 0, keepdims=False) for a in (w, w2))
    sizes = jnp.zeros((w.shape[0],), jnp.int32).at[tile_expert].add(
        jnp.where(jnp.arange(tile_expert.shape[0]) < n_live, tile_m, 0))
    y = jax.lax.ragged_dot(x, w, sizes, preferred_element_type=jnp.float32)
    if w2 is not None:
        y = GATE_ACTS[act](y) * jax.lax.ragged_dot(
            x, w2, sizes, preferred_element_type=jnp.float32)
    return y.astype(x.dtype)


def gmm(x, w, tile_expert, n_live, *, tile_m: int, w2=None, layer=None,
        act: str = "silu", interpret=None):
    """``out[r] = x[r] @ w[tile_expert[r // tile_m]]`` for the rows of the
    first ``n_live`` row tiles; rows of later tiles are NOT written (the
    caller never reads them).  With ``w2``: ``act(x @ w[e]) * (x @ w2[e])``,
    ``act`` a name of ``GATE_ACTS``.

    x: ``[M, K]``, M a multiple of ``tile_m``, rows grouped by expert in
    whole tiles; w (and w2): ``[G, K, N]``, or every layer's stacked ``[L,
    G, K, N]`` with ``layer`` a (traced) scalar: it reaches the kernel as a
    prefetched scalar and indexes HBM, so a layer scan never slices its
    experts out (352 MB a matrix a layer at Kimi-K2's widths, copied every
    decode step otherwise: PERF.md, PR 26); tile_expert: ``[M // tile_m]``
    int32, non-decreasing over the live tiles; n_live: scalar int32.
    Returns ``[M, N]`` in x's dtype (f32 accumulation inside)."""
    if layer is None:
        w, w2, layer = w[None], None if w2 is None else w2[None], 0
    m, k = x.shape
    n = w.shape[3]
    if m % tile_m:
        raise ValueError(f"rows {m} are not whole tiles of {tile_m}")
    if interpret is None:
        interpret = dispatch.interpret()
    tn = column_block(k, n, w.dtype.itemsize)
    n_col = n // tn
    n_live = jnp.asarray(n_live, jnp.int32).reshape(())

    def x_index(s, expert, tile, col, nl, la):
        return (tile[s], 0)

    def w_index(s, expert, tile, col, nl, la):
        return (la[0], expert[s], 0, col[s])

    def o_index(s, expert, tile, col, nl, la):
        return (tile[s], col[s])

    w_spec = pl.BlockSpec((None, None, k, tn), w_index)
    weights = (w,) if w2 is None else (w, w2)
    return pl.pallas_call(
        functools.partial(_gmm_kernel, gated=w2 is not None, act=act,
                          n_col=n_col),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=5,
            grid=(m // tile_m * n_col,),
            in_specs=[pl.BlockSpec((tile_m, k), x_index)]
            + [w_spec] * len(weights),
            out_specs=pl.BlockSpec((tile_m, tn), o_index),
        ),
        out_shape=jax.ShapeDtypeStruct((m, n), x.dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",),
            vmem_limit_bytes=_VMEM_LIMIT_BYTES),
        interpret=interpret,
        name="sw_moe_gmm",
    )(*tile_walk(jnp.asarray(tile_expert, jnp.int32), n_live, n_col),
      n_live.reshape(1), jnp.asarray(layer, jnp.int32).reshape(1), x, *weights)


def grouped_matmul(x, w, tile_expert, n_live, *, tile_m: int, w2=None,
                   layer=None, act: str = "silu"):
    """The grouped matmul, the operation: the arguments of :func:`gmm`,
    which runs on a TPU; :func:`gmm_lax` elsewhere.  The experts a chip
    holds are whole, so there is no head to shard by."""
    fn = gmm if dispatch.use_kernels() else gmm_lax
    return fn(x, w, tile_expert, n_live, tile_m=tile_m, w2=w2, layer=layer,
              act=act)
