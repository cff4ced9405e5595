"""Pallas TPU int8-weight matmul: the W8A16 serving hot path.

Single-token decode at small batch is WEIGHT-bandwidth bound: every
generated token streams every matmul weight of the model through the core
once (~2 bytes/param in bf16).  This kernel streams the weights as int8 —
half the bytes — and folds the per-output-channel dequantisation scale
into the product after the MXU matmul (``(x @ q) * s == x @ (q * s)``,
ops/quantize.py:quantize_weight), so no wide weight tile ever exists in
VMEM or HBM.

Left operand ``x [M, D]`` is small (M = batch x chunk rows) and rides
whole; the grid walks output-channel blocks, and Pallas's pipeline
double-buffers the int8 weight DMA exactly like any blocked matmul — the
structural point is only that the streamed operand is int8 while the MXU
consumes the activation dtype.

No reference counterpart (/root/reference is a transport library); this is
the TPU build's serving-stack extension implementing standard weight-only
quantization.
"""

from __future__ import annotations


import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from . import dispatch
from .pallas_attention import _round_up


def _gemv_kernel(x_ref, w_ref, s_ref, o_ref):
    x = x_ref[...]
    w = w_ref[...].astype(x.dtype)  # widen in-register, post-DMA
    acc = jax.lax.dot_general(
        x, w, (((1,), (0,)), ((), ())), preferred_element_type=jnp.float32)
    o_ref[...] = (acc * s_ref[0][None, :]).astype(o_ref.dtype)


def int8_matmul(x, wq, scale, *, block_f: "int | None" = None,
                interpret=None, out_dtype=None):
    """``x [M, D] @ (wq int8 [D, F] * scale f32 [F]) -> [M, F]``.

    Matches ``(x @ wq.astype(f32)) * scale`` up to float rounding (f32
    accumulate on the MXU).  ``block_f`` tunes the output-channel block
    (default sized so a double-buffered int8 [D, block_f] tile stays
    within a few MB of VMEM).  M is padded to the 8-sublane tile, F to
    the block; both paddings are sliced off.
    """
    m, d = x.shape
    d2, f = wq.shape
    assert d == d2 and scale.shape == (f,)
    if out_dtype is None:
        out_dtype = x.dtype
    if interpret is None:
        interpret = dispatch.interpret()
    f128 = _round_up(f, 128)
    if block_f is None:
        # ~4 MB of int8 weight block per buffer, lane-aligned.
        block_f = max(128, min(512, ((4 << 20) // max(d, 1)) // 128 * 128))
    # The block must DIVIDE the padded width: padding to a 512-multiple
    # would copy the whole weight inside the traced hot path whenever f
    # is merely 128-aligned (e.g. a 128256 vocab head) — fall down the
    # lane-multiple ladder instead, so the pad stays <= 127 columns.
    block_f = min(block_f, f128)
    while f128 % block_f:
        block_f -= 128
    m_pad = _round_up(max(m, 8), 8)
    if m_pad != m:
        x = jnp.pad(x, ((0, m_pad - m), (0, 0)))
    f_pad = f128
    if f_pad != f:
        wq = jnp.pad(wq, ((0, 0), (0, f_pad - f)))
        scale = jnp.pad(scale, (0, f_pad - f))
    scale2 = scale.reshape(1, f_pad)  # rank-2 for the TPU lane layout

    out = pl.pallas_call(
        _gemv_kernel,
        grid=(f_pad // block_f,),
        in_specs=[
            pl.BlockSpec((m_pad, d), lambda i: (0, 0)),
            pl.BlockSpec((d, block_f), lambda i: (0, i)),
            pl.BlockSpec((1, block_f), lambda i: (0, i)),
        ],
        out_specs=pl.BlockSpec((m_pad, block_f), lambda i: (0, i)),
        out_shape=jax.ShapeDtypeStruct((m_pad, f_pad), out_dtype),
        interpret=interpret,
        name="sw_gemv_int8",
    )(x, wq, scale2)
    return out[:m, :f]


def int8_matmul_lax(x, wq, scale):
    """:func:`int8_matmul` in plain lax: dequantise, then matmul in f32.
    What runs where Pallas does not, and what the kernel is tested
    against."""
    return (x.astype(jnp.float32)
            @ (wq.astype(jnp.float32) * scale[None, :])).astype(x.dtype)


def quantized_matmul(x, wq, scale):
    """``x [M, D] @ (wq int8 [D, F] * scale [F])``, the operation: on a TPU
    the kernel streams the weights at half width with the scale folded
    into the product; elsewhere dequantise-then-matmul."""
    fn = int8_matmul if dispatch.use_kernels() else int8_matmul_lax
    return fn(x, wq, scale)
