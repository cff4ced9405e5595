"""The selective state-space recurrence (Mamba, arXiv 2312.00752) in the two
forms a served model runs.  A layer's state a request is a matrix ``S [N,
E]`` in float32, ``N`` states (16) of each of ``E`` channels (5,120), and
one token moves it by

    S = exp(dt_t A) * S + (dt_t x_t) B_t^T     dt_t, x_t [E]; B_t [N]; A [N, E] < 0
    y_t = C_t . S + D x_t                      C_t [N]; the read-out [E]

a diagonal transition that the token chooses: no delta update, nothing
solved.  The channels lie along the lanes and the states along the
sublanes (``[N, E]``, not the papers' ``[E, N]``): sixteen values are an
eighth of a lane tile, and the chip would pad each to a whole one.

* :func:`ssm_step` (decode): one token a row.  The kernel ``sw_ssm_step``
  reads every row's state of one layer out of the STACKED leaf ``[L, B, N,
  E]``, moves it on and writes it back where it came from (the output
  aliases the leaf; the layer is a prefetched scalar, the leaf is never
  sliced), the read-out in the same pass.  :func:`ssm_step_lax` is its
  twin.
* :func:`ssm_scan` (prefill): a whole prompt from a zero state, in chunks
  of ``CHUNK`` positions along the sequence.  The kernel ``sw_ssm_scan``
  walks a (row, block of channels)'s chunks in order with the state in
  VMEM scratch (a loop carry of twenty registers inside a chunk):
  ``[S, N, E]`` never exists in HBM, and only the last state is written.
  ``B`` and ``C`` arrive transposed, ``[N, S]``, and a token's column is
  picked by its lane.  A position with ``dt = 0`` leaves the state as it
  was: that is how ``lengths`` tells a padded bucket's pads to stand
  still.  :func:`ssm_scan_lax` is the twin, the recurrence token by token.

Neither kernel body holds a Python loop: rows and tokens are
``lax.fori_loop``s, so a program's text does not grow with either.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax import lax
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from . import dispatch

CHUNK = 128        # positions a grid cell of the scan walks
ROWS = 8           # rows (slots) a grid cell of the step moves on
_BLOCKS = (2560, 1280, 1024, 640, 512, 384, 256, 128)


def _channel_block(e: int, most: int) -> "int | None":
    """Channels a grid cell takes: the widest whole-lane-tile block of at
    most ``most`` that divides ``e`` (None: ``e`` is not whole tiles)."""
    return next((b for b in _BLOCKS if b <= most and e % b == 0), None)


# ----------------------------------------------------------------- one step


def ssm_step_lax(state, dt, x, b, c, a, d, *, layer):
    """:func:`ssm_step` in plain lax: what runs where Pallas does not, and
    what the kernel is tested against."""
    s = lax.dynamic_index_in_dim(state, layer, 0, keepdims=False)
    s = jnp.exp(dt[:, None, :] * a) * s + (dt * x)[:, None, :] * b[:, :, None]
    y = jnp.sum(c[:, :, None] * s, axis=1) + d * x
    return y, lax.dynamic_update_index_in_dim(state, s, layer, 0)


def _ssm_step_kernel(layer_ref, dt_ref, x_ref, b_ref, c_ref, a_ref, d_ref,
                     s_ref, y_ref, s_out, *, rows: int):
    """One grid cell a (block of rows, block of channels).  ``b`` and ``c``
    arrive as columns ``[rows, N, 1]``: a row's states vary along the
    sublanes of its ``[N, channels]`` state and broadcast along its
    lanes; ``dt``, ``x`` and the read-out are rows."""
    del layer_ref
    a, d = a_ref[...], d_ref[...]

    def row(i, carry):
        dt = dt_ref[pl.ds(i, 1), :]                       # [1, channels]
        x = x_ref[pl.ds(i, 1), :]
        s = jnp.exp(dt * a) * s_ref[0, i] + (dt * x) * b_ref[i]
        y_ref[pl.ds(i, 1), :] = (jnp.sum(c_ref[i] * s, axis=0, keepdims=True)
                                 + d * x)
        s_out[0, i] = s
        return carry

    lax.fori_loop(0, rows, row, 0)


def _step_tiles(b: int, e: int) -> "tuple | None":
    """(rows, channels) of a step's grid cell, or None where the chip
    cannot tile the call: rows in whole sublane tiles (or all of a small
    batch), channels in whole lane tiles."""
    rows = ROWS if b % ROWS == 0 else b if b < ROWS else None
    block = _channel_block(e, 2560)
    return None if rows is None or block is None else (rows, block)


def ssm_step_kernel(state, dt, x, b, c, a, d, *, layer, interpret=None):
    """:func:`ssm_step` as the Pallas kernel ``sw_ssm_step``."""
    _layers, bsz, n, e = state.shape
    if interpret is None:
        interpret = dispatch.interpret()
    rows, block = _step_tiles(bsz, e)
    f32 = jnp.float32
    vec = pl.BlockSpec((rows, block), lambda i, j, *_: (i, j))
    col = pl.BlockSpec((rows, n, 1), lambda i, j, *_: (i, 0, 0))
    s_spec = pl.BlockSpec((1, rows, n, block),
                          lambda i, j, layer: (layer[0], i, 0, j))
    y, state = pl.pallas_call(
        functools.partial(_ssm_step_kernel, rows=rows),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1,
            grid=(bsz // rows, e // block),
            in_specs=[vec, vec, col, col,
                      pl.BlockSpec((n, block), lambda i, j, *_: (0, j)),
                      pl.BlockSpec((1, block), lambda i, j, *_: (0, j)),
                      s_spec],
            out_specs=[vec, s_spec],
        ),
        out_shape=[jax.ShapeDtypeStruct((bsz, e), f32),
                   jax.ShapeDtypeStruct(state.shape, state.dtype)],
        input_output_aliases={7: 1},   # the state: read and written in place
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel")),
        interpret=interpret,
        name="sw_ssm_step",
    )(jnp.asarray(layer, jnp.int32).reshape(1), dt.astype(f32), x.astype(f32),
      b.astype(f32)[..., None], c.astype(f32)[..., None], a.astype(f32),
      d.astype(f32).reshape(1, e), state)
    return y, state


def ssm_step(state, dt, x, b, c, a, d, *, layer):
    """One token of every row through the recurrence, the operation.
    state: the stacked leaf ``[L, B, N, E]`` float32, ``layer`` a (traced)
    scalar; ``dt`` (the step, after its softplus) and ``x`` ``[B, E]``, ``b``
    and ``c`` ``[B, N]``, ``a [N, E]`` (negative), ``d [E]``.  Returns ``(y
    [B, E] float32, state)``, the state of ``layer`` replaced.  On a TPU
    the kernel (in place), elsewhere and at sizes the chip cannot tile the
    lax twin."""
    _l, bsz, n, e = state.shape
    if (dispatch.use_kernels() and n % 8 == 0
            and _step_tiles(bsz, e) is not None):
        return ssm_step_kernel(state, dt, x, b, c, a, d, layer=layer)
    f32 = jnp.float32
    return ssm_step_lax(state, dt.astype(f32), x.astype(f32), b.astype(f32),
                        c.astype(f32), a.astype(f32), d.astype(f32),
                        layer=layer)


# ------------------------------------------------------------ a whole prompt


def ssm_scan_lax(dt, x, b, c, a, d):
    """:func:`ssm_scan` (``lengths`` applied already) in plain lax, the
    recurrence token by token: what runs where Pallas does not, and what
    the kernel is tested against."""
    def token(s, xs):
        dt, x, b, c = xs                       # [B, E], [B, E], [B, N], [B, N]
        s = jnp.exp(dt[:, None, :] * a) * s + (dt * x)[:, None, :] * b[:, :, None]
        return s, jnp.sum(c[:, :, None] * s, axis=1) + d * x

    zero = jnp.zeros((dt.shape[0],) + a.shape, jnp.float32)
    s, y = lax.scan(token, zero, tuple(jnp.swapaxes(t, 0, 1)
                                       for t in (dt, x, b, c)))
    return jnp.swapaxes(y, 0, 1), s


def _ssm_scan_kernel(dt_ref, x_ref, bt_ref, ct_ref, a_ref, d_ref, y_ref,
                     st_ref, s_scr, *, chunk: int):
    """One grid cell a (row, block of channels, chunk), the chunks in
    order.  ``bt`` / ``ct`` hold the chunk's ``B`` and ``C`` transposed,
    ``[N, chunk]``: a token's column is what its lane holds."""
    n = pl.program_id(2)

    @pl.when(n == 0)
    def _start():
        s_scr[...] = jnp.zeros_like(s_scr)

    a, d = a_ref[...], d_ref[...]
    bt, ct = bt_ref[0], ct_ref[0]
    lane = lax.broadcasted_iota(jnp.int32, bt.shape, 1)

    def token(t, s):
        dt = dt_ref[0, pl.ds(t, 1), :]                    # [1, channels]
        x = x_ref[0, pl.ds(t, 1), :]
        mine = lane == t
        b = jnp.sum(jnp.where(mine, bt, 0.0), axis=1, keepdims=True)  # [N, 1]
        c = jnp.sum(jnp.where(mine, ct, 0.0), axis=1, keepdims=True)
        s = jnp.exp(dt * a) * s + (dt * x) * b
        y_ref[0, pl.ds(t, 1), :] = jnp.sum(c * s, axis=0, keepdims=True) + d * x
        return s

    s = lax.fori_loop(0, chunk, token, s_scr[...])
    s_scr[...] = s

    @pl.when(n == pl.num_programs(2) - 1)
    def _end():
        st_ref[0] = s


def ssm_scan_kernel(dt, x, b, c, a, d, *, interpret=None):
    """:func:`ssm_scan` (``lengths`` applied already) as the Pallas kernel
    ``sw_ssm_scan``, S a whole number of chunks."""
    bsz, s, e = dt.shape
    n = a.shape[0]
    if interpret is None:
        interpret = dispatch.interpret()
    block = _channel_block(e, 1280)
    f32 = jnp.float32
    seq = pl.BlockSpec((1, CHUNK, block), lambda i, j, m: (i, m, j))
    cols = pl.BlockSpec((1, n, CHUNK), lambda i, j, m: (i, 0, m))
    y, state = pl.pallas_call(
        functools.partial(_ssm_scan_kernel, chunk=CHUNK),
        grid=(bsz, e // block, s // CHUNK),
        in_specs=[seq, seq, cols, cols,
                  pl.BlockSpec((n, block), lambda i, j, m: (0, j)),
                  pl.BlockSpec((1, block), lambda i, j, m: (0, j))],
        out_specs=[seq, pl.BlockSpec((1, n, block), lambda i, j, m: (i, 0, j))],
        out_shape=[jax.ShapeDtypeStruct((bsz, s, e), f32),
                   jax.ShapeDtypeStruct((bsz, n, e), f32)],
        scratch_shapes=[pltpu.VMEM((n, block), f32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary")),
        interpret=interpret,
        name="sw_ssm_scan",
    )(dt.astype(f32), x.astype(f32), jnp.swapaxes(b.astype(f32), 1, 2),
      jnp.swapaxes(c.astype(f32), 1, 2), a.astype(f32),
      d.astype(f32).reshape(1, e))
    return y, state


def ssm_scan(dt, x, b, c, a, d, lengths=None):
    """A whole prompt through the recurrence from a zero state, the
    operation.  ``dt`` (the step, after its softplus) and ``x`` ``[B, S,
    E]``, ``b`` and ``c`` ``[B, S, N]``, ``a [N, E]`` (negative), ``d [E]``;
    ``lengths`` ([B] ints, default all S): the positions behind a row's
    length do not move its state (their ``dt`` is set to 0) and their
    read-outs mean nothing.  Returns ``(y [B, S, E] float32, state [B, N,
    E] float32)``: every position's read-out and the state after each
    row's last REAL token.  S is padded to whole chunks with such standing
    positions.  On a TPU the kernel, elsewhere and at shapes it does not
    tile the lax twin."""
    s, f32 = dt.shape[1], jnp.float32
    dt = dt.astype(f32)
    if lengths is not None:
        real = jnp.arange(s)[None, :] < jnp.asarray(lengths)[:, None]
        dt = jnp.where(real[..., None], dt, 0.0)
    if not (dispatch.use_kernels() and a.shape[0] % 8 == 0
            and _channel_block(dt.shape[-1], 1280) is not None):
        return ssm_scan_lax(dt, x.astype(f32), b.astype(f32), c.astype(f32),
                            a.astype(f32), d.astype(f32))
    pad = -s % CHUNK
    if pad:
        dt, x, b, c = (jnp.pad(t, ((0, 0), (0, pad), (0, 0)))
                       for t in (dt, x, b, c))
    y, state = ssm_scan_kernel(dt, x, b, c, a, d)
    return y[:, :s], state
