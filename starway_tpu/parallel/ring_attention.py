"""Ring attention: sequence-parallel exact attention over an ICI ring.

Long-context substrate (the capability SURVEY.md section 5 calls out as the
point of the tagged-P2P primitives: "ring attention = asend/arecv to ring
neighbors + overlap, i.e. CollectivePermute").  Implemented TPU-native: each
device owns a sequence shard of q/k/v; kv shards rotate around the mesh axis
with ``lax.ppermute`` while every device accumulates online-softmax partials
against its resident queries.  XLA overlaps the ppermute DMA with the next
block's matmuls, so the ring rides ICI concurrently with MXU compute.

Exactness comes from the associative merge in ops/attention.py -- blocks may
arrive in any rotation order, which is also what makes the accumulation
robust to mesh axis ordering.

Both ring variants carry a ``jax.custom_vjp``:

* forward: per-step partials come from ``ops.ring_step``: the Pallas
  kernel (ops/pallas_attention.py::flash_partial, ~7x the lax step rate
  on TPU) or its lax twin elsewhere, chosen there at trace time.
* backward: a second ring pass.  Each device keeps its q/do/lse/delta
  resident and accumulates dq locally, while dk/dv accumulators *rotate
  with their kv shard* -- after the full rotation each shard's gradient
  arrives back at its home device having summed every device's
  contribution.  Per-step math uses the globally merged lse/delta, so each
  step's contribution is exactly its slice of the full attention gradient
  (``ops.ring_step_bwd``).
"""

from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax
from jax.sharding import PartitionSpec as P

from ..ops import ring_step, ring_step_bwd
from ..ops.attention import finalize_partial, merge_partials, zero_partial
from ..ops.collectives import ring_shift
from .sharding import shard_map_fn


def _lse_of(acc):
    """Merged partial -> log-sum-exp (f32), the backward's row statistic."""
    o, m, l = acc
    return m + jnp.log(jnp.maximum(l, 1e-30))


def _rotate(xs, axis_name):
    return tuple(ring_shift(x, axis_name, 1) for x in xs)


# ---------------------------------------------------------------------------
# plain ring (natural layout)
# ---------------------------------------------------------------------------


def _band_live(q_off, kv_off, tq, tk, causal, window):
    """Does the (q block, kv block) pair contribute anything under the
    causal/window band?  False -> the whole tile is masked and its ring
    step can skip compute outright (the windowed-ring win: at
    window << S only ~window/t_local + 1 of the n steps are live)."""
    live = jnp.asarray(True)
    if causal:
        live = q_off + tq - 1 >= kv_off          # some key is in the past
    if window is not None:
        live = live & (q_off - (kv_off + tk - 1) < window)  # ...and close
    return live


def _ring_steps(n: int, t_local: int, window) -> int:
    """How many ring steps can EVER be live under the band: device my
    attends shard my - i only while i * t_local reaches back < window
    (plus its own diagonal).  Static — window and shard sizes are
    trace-time constants — so both loops AND rotations stop after the
    band: communication scales with the window, not the sequence."""
    if window is None:
        return n
    return min(n, (window - 2 + t_local) // t_local + 1)


def _ring_fwd_impl(q, k, v, axis_name, causal, sm_scale, window=None):
    n = lax.axis_size(axis_name)
    my = lax.axis_index(axis_name)
    t_local = q.shape[2]
    q_off = my * t_local
    steps = _ring_steps(n, t_local, window)

    def compute(i, acc, k_cur, v_cur):
        src = (my - i) % n  # owner of the kv shard currently resident here
        kv_off = src * t_local

        def live_part(_):
            return ring_step(q, k_cur, v_cur, q_off, kv_off, causal,
                             sm_scale, window)

        if window is None:
            part = live_part(None)
        else:
            # merge with the identity partial (m=-inf, l=0) when skipped.
            part = lax.cond(
                _band_live(q_off, kv_off, t_local, t_local, causal, window),
                live_part, lambda _: zero_partial(q), None)
        return merge_partials(acc, part)

    def body(i, carry):
        acc, k_cur, v_cur = carry
        acc = compute(i, acc, k_cur, v_cur)
        # Rotate kv to the next device; XLA overlaps this ppermute with the
        # next iteration's compute.
        k_cur, v_cur = _rotate((k_cur, v_cur), axis_name)
        return acc, k_cur, v_cur

    acc, k_last, v_last = lax.fori_loop(0, steps - 1, body,
                                        (zero_partial(q), k, v))
    acc = compute(steps - 1, acc, k_last, v_last)
    out = finalize_partial(*acc, out_dtype=q.dtype)
    return out, _lse_of(acc)


def _ring_bwd_impl(q, k, v, out, lse, do, axis_name, causal, sm_scale,
                   window=None):
    n = lax.axis_size(axis_name)
    my = lax.axis_index(axis_name)
    t_local = q.shape[2]
    q_off = my * t_local
    steps = _ring_steps(n, t_local, window)
    delta = jnp.sum(do.astype(jnp.float32) * out.astype(jnp.float32), axis=-1)

    def step(i, carry):
        dq, k_cur, v_cur, dk_cur, dv_cur = carry
        src = (my - i) % n
        kv_off = src * t_local

        def live_grads(_):
            return ring_step_bwd(q, do, k_cur, v_cur, lse, delta, q_off,
                                 kv_off, causal, sm_scale, window)

        if window is None:
            dq_c, dk_c, dv_c = live_grads(None)
        else:
            dq_c, dk_c, dv_c = lax.cond(
                _band_live(q_off, kv_off, t_local, t_local, causal, window),
                live_grads,
                lambda _: (jnp.zeros(q.shape, jnp.float32),
                           jnp.zeros(k.shape, jnp.float32),
                           jnp.zeros(v.shape, jnp.float32)), None)
        return dq + dq_c, k_cur, v_cur, dk_cur + dk_c, dv_cur + dv_c

    def body(i, carry):
        carry = step(i, carry)
        dq, k_cur, v_cur, dk_cur, dv_cur = carry
        # dk/dv accumulators rotate WITH their kv shard, so each shard's
        # gradient keeps collecting contributions device by device.
        k_cur, v_cur, dk_cur, dv_cur = _rotate(
            (k_cur, v_cur, dk_cur, dv_cur), axis_name)
        return dq, k_cur, v_cur, dk_cur, dv_cur

    init = (jnp.zeros(q.shape, jnp.float32), k, v,
            jnp.zeros(k.shape, jnp.float32), jnp.zeros(v.shape, jnp.float32))
    carry = lax.fori_loop(0, steps - 1, body, init)
    dq, _, _, dk, dv = step(steps - 1, carry)
    # Send each kv shard's gradient home: after steps-1 in-loop rotations
    # a shard's grad sits steps-1 hops from its owner, so one ppermute of
    # the REMAINING distance closes the ring (shift 1 in the full-ring
    # case; identity skipped when the band never moved the shards).
    home = (n - (steps - 1)) % n
    if home:
        dk = ring_shift(dk, axis_name, home)
        dv = ring_shift(dv, axis_name, home)
    return dq.astype(q.dtype), dk.astype(k.dtype), dv.astype(v.dtype)


@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4, 5, 6))
def _ring(q, k, v, axis_name, causal, sm_scale, window):
    out, _ = _ring_fwd_impl(q, k, v, axis_name, causal, sm_scale, window)
    return out


def _ring_vjp_fwd(q, k, v, axis_name, causal, sm_scale, window):
    out, lse = _ring_fwd_impl(q, k, v, axis_name, causal, sm_scale, window)
    return out, (q, k, v, out, lse)


def _ring_vjp_bwd(axis_name, causal, sm_scale, window, res, do):
    q, k, v, out, lse = res
    return _ring_bwd_impl(q, k, v, out, lse, do, axis_name, causal, sm_scale,
                          window)


_ring.defvjp(_ring_vjp_fwd, _ring_vjp_bwd)


def ring_attention(q, k, v, axis_name: str, *, causal: bool = True,
                   sm_scale: Optional[float] = None,
                   window: Optional[int] = None):
    """Per-device body (call inside shard_map): q/k/v are local sequence
    shards ``[B, H, T_local, D]``; returns the local output shard.

    Grouped-query kv is accepted unexpanded (``k/v`` with fewer heads): the
    ring rotates the *narrow* kv shards and the per-step compute expands (or
    the Pallas kernel indexes) per head group, so ICI moves 1/n_rep of the
    naive traffic.  Rotation schedule: after step ``i`` the device holds kv
    shard ``(my_index - i) mod n``; global offsets feed the causal mask so
    no cross-shard attention is wrongly masked or admitted.  The last
    compute step skips the rotation (n-1 ppermutes for n shards).

    Differentiable: gradients run the backward ring (module docstring).

    ``window`` (requires ``causal``): Mistral-style sliding-window band.
    Ring steps whose (q shard, kv shard) pair lies wholly outside the
    band cond-skip their compute — at ``window << S`` only about
    ``window / t_local + 1`` of the ``n`` steps are live, so wall-clock
    scales with the band, not the sequence (the banded analogue of the
    zigzag causal win).  In-band steps run the lax masked path (the
    flash_partial kernel carries no band; the skipped steps dominate the
    savings).
    """
    if sm_scale is None:
        sm_scale = 1.0 / (q.shape[-1] ** 0.5)
    if window is not None:
        if not causal:
            raise ValueError("window requires causal attention")
        if window < 1:
            raise ValueError(f"window must be >= 1, got {window}")
    return _ring(q, k, v, axis_name, bool(causal), float(sm_scale),
                 None if window is None else int(window))


# ---------------------------------------------------------------------------
# zigzag (load-balanced causal) ring
# ---------------------------------------------------------------------------


def zigzag_indices(s: int, n: int) -> np.ndarray:
    """Global sequence permutation for the zigzag causal layout.

    The plain ring layout is causally imbalanced: device ``d`` has useful
    (unmasked) work on only ``d+1`` of ``n`` ring steps, and SPMD lockstep
    makes every step as slow as the busiest device -- so half the ring's
    MXU time is spent computing fully-masked scores.  Zigzag (the "striped"
    fix, cf. Brandon et al., Striped Attention, arXiv:2311.09431) gives
    each device one block from the front of the sequence and its mirror
    from the back: blocks ``d`` and ``2n-1-d``.  Every device then has
    exactly one fully-live pair plus one conditionally-live pair per step
    -- uniform work, ~2x causal wall-clock at scale.

    Returns the gather indices (length ``s``, requires ``2n | s``) mapping
    the natural sequence into zigzag order; invert with ``np.argsort``.
    """
    if s % (2 * n):
        raise ValueError(f"zigzag needs sequence length divisible by 2n={2*n}, got {s}")
    sb = s // (2 * n)
    blocks = []
    for d in range(n):
        blocks.append(d)
        blocks.append(2 * n - 1 - d)
    return np.concatenate([np.arange(b * sb, (b + 1) * sb) for b in blocks])


def _zz_offsets(my, src, n, sb):
    """Global offsets of the four half-blocks in play at one zigzag step."""
    return dict(
        off_lo=my * sb,                   # our front block
        off_hi=(2 * n - 1 - my) * sb,     # our mirrored back block
        src_lo=src * sb,                  # visiting front block
        src_hi=(2 * n - 1 - src) * sb,    # visiting back block
    )


def _zz_fwd_impl(q, k, v, axis_name, sm_scale):
    n = lax.axis_size(axis_name)
    my = lax.axis_index(axis_name)
    sb = q.shape[2] // 2

    q_lo, q_hi = q[:, :, :sb], q[:, :, sb:]

    def compute(src, acc_lo, acc_hi, k_cur, v_cur):
        o = _zz_offsets(my, src, n, sb)
        k_lo, k_hi = k_cur[:, :, :sb], k_cur[:, :, sb:]
        v_lo, v_hi = v_cur[:, :, :sb], v_cur[:, :, sb:]

        # Back blocks start at >= n*sb while front blocks end at <= n*sb:
        # this pair's causal mask is provably all-ones, so skip the mask.
        acc_hi = merge_partials(
            acc_hi,
            ring_step(q_hi, k_lo, v_lo, o["off_hi"], o["src_lo"], False,
                      sm_scale),
        )
        acc_lo = lax.cond(
            my >= src,
            lambda a: merge_partials(
                a, ring_step(q_lo, k_lo, v_lo, o["off_lo"], o["src_lo"],
                             True, sm_scale)),
            lambda a: a,
            acc_lo,
        )
        acc_hi = lax.cond(
            my <= src,
            lambda a: merge_partials(
                a, ring_step(q_hi, k_hi, v_hi, o["off_hi"], o["src_hi"],
                             True, sm_scale)),
            lambda a: a,
            acc_hi,
        )
        return acc_lo, acc_hi

    def body(i, carry):
        acc_lo, acc_hi, k_cur, v_cur = carry
        acc_lo, acc_hi = compute((my - i) % n, acc_lo, acc_hi, k_cur, v_cur)
        k_cur, v_cur = _rotate((k_cur, v_cur), axis_name)
        return acc_lo, acc_hi, k_cur, v_cur

    acc_lo, acc_hi, k_last, v_last = lax.fori_loop(
        0, n - 1, body, (zero_partial(q_lo), zero_partial(q_hi), k, v)
    )
    acc_lo, acc_hi = compute((my - (n - 1)) % n, acc_lo, acc_hi, k_last,
                             v_last)
    out = jnp.concatenate(
        [finalize_partial(*acc_lo, out_dtype=q.dtype),
         finalize_partial(*acc_hi, out_dtype=q.dtype)], axis=2)
    lse = jnp.concatenate([_lse_of(acc_lo), _lse_of(acc_hi)], axis=2)
    return out, lse


def _zz_bwd_impl(q, k, v, out, lse, do, axis_name, sm_scale):
    n = lax.axis_size(axis_name)
    my = lax.axis_index(axis_name)
    sb = q.shape[2] // 2
    delta = jnp.sum(do.astype(jnp.float32) * out.astype(jnp.float32), axis=-1)

    q_lo, q_hi = q[:, :, :sb], q[:, :, sb:]
    do_lo, do_hi = do[:, :, :sb], do[:, :, sb:]
    lse_lo, lse_hi = lse[:, :, :sb], lse[:, :, sb:]
    d_lo, d_hi = delta[:, :, :sb], delta[:, :, sb:]

    kv_zero = jnp.zeros(k.shape[:2] + (sb,) + k.shape[3:], jnp.float32)

    def step(i, carry):
        dq, k_cur, v_cur, dk_cur, dv_cur = carry
        src = (my - i) % n
        o = _zz_offsets(my, src, n, sb)
        k_lo, k_hi = k_cur[:, :, :sb], k_cur[:, :, sb:]
        v_lo, v_hi = v_cur[:, :, :sb], v_cur[:, :, sb:]

        # Pair hi-lo: always live, mask-free.
        dqh, dkl, dvl = ring_step_bwd(q_hi, do_hi, k_lo, v_lo, lse_hi, d_hi,
                                      o["off_hi"], o["src_lo"], False,
                                      sm_scale)
        # Pair lo-lo: live iff my >= src (diagonal at equality).
        z3 = (jnp.zeros(q_lo.shape, jnp.float32), kv_zero, kv_zero)
        dql, dkl2, dvl2 = lax.cond(
            my >= src,
            lambda: ring_step_bwd(q_lo, do_lo, k_lo, v_lo, lse_lo, d_lo,
                                  o["off_lo"], o["src_lo"], True, sm_scale),
            lambda: z3,
        )
        # Pair hi-hi: live iff my <= src.
        dqh2, dkh, dvh = lax.cond(
            my <= src,
            lambda: ring_step_bwd(q_hi, do_hi, k_hi, v_hi, lse_hi, d_hi,
                                  o["off_hi"], o["src_hi"], True, sm_scale),
            lambda: z3,
        )
        dq = dq + jnp.concatenate([dql, dqh + dqh2], axis=2)
        dk_cur = dk_cur + jnp.concatenate([dkl + dkl2, dkh], axis=2)
        dv_cur = dv_cur + jnp.concatenate([dvl + dvl2, dvh], axis=2)
        return dq, k_cur, v_cur, dk_cur, dv_cur

    def body(i, carry):
        carry = step(i, carry)
        dq, k_cur, v_cur, dk_cur, dv_cur = carry
        k_cur, v_cur, dk_cur, dv_cur = _rotate(
            (k_cur, v_cur, dk_cur, dv_cur), axis_name)
        return dq, k_cur, v_cur, dk_cur, dv_cur

    init = (jnp.zeros(q.shape, jnp.float32), k, v,
            jnp.zeros(k.shape, jnp.float32), jnp.zeros(v.shape, jnp.float32))
    carry = lax.fori_loop(0, n - 1, body, init)
    dq, _, _, dk, dv = step(n - 1, carry)
    dk, dv = _rotate((dk, dv), axis_name)
    return dq.astype(q.dtype), dk.astype(k.dtype), dv.astype(v.dtype)


@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4))
def _zigzag(q, k, v, axis_name, sm_scale):
    out, _ = _zz_fwd_impl(q, k, v, axis_name, sm_scale)
    return out


def _zz_vjp_fwd(q, k, v, axis_name, sm_scale):
    out, lse = _zz_fwd_impl(q, k, v, axis_name, sm_scale)
    return out, (q, k, v, out, lse)


def _zz_vjp_bwd(axis_name, sm_scale, res, do):
    q, k, v, out, lse = res
    return _zz_bwd_impl(q, k, v, out, lse, do, axis_name, sm_scale)


_zigzag.defvjp(_zz_vjp_fwd, _zz_vjp_bwd)


def zigzag_ring_attention(q, k, v, axis_name: str, *,
                          sm_scale: Optional[float] = None):
    """Per-device body (call inside shard_map) for causal zigzag ring
    attention.  Local shards are in zigzag layout (see :func:`zigzag_indices`):
    the first half of the local sequence is original block ``my`` (global
    offset ``my*sb``), the second half is block ``2n-1-my``.

    Per ring step the four (q-half, kv-half) pairs are either fully live,
    diagonal, or fully in the future; the future pairs are skipped with
    ``lax.cond`` so no MXU time is spent on all-masked scores:

    * ``q_hi  vs kv_lo`` -- always live (back blocks see all front blocks)
    * ``q_lo  vs kv_lo`` -- live iff ``my >= src`` (diagonal at ``my == src``)
    * ``q_hi  vs kv_hi`` -- live iff ``my <= src``
    * ``q_lo  vs kv_hi`` -- never live (front blocks never see back blocks)

    Exactness comes from the same associative merge as :func:`ring_attention`;
    skipped pairs contribute nothing by construction.  Differentiable via
    the backward ring (module docstring); the backward mirrors the same
    pair liveness so skipped pairs cost nothing there either.
    """
    if q.shape[2] % 2:
        raise ValueError(
            f"zigzag local sequence must be even (two half-blocks), got {q.shape[2]}"
        )
    if sm_scale is None:
        sm_scale = 1.0 / (q.shape[-1] ** 0.5)
    return _zigzag(q, k, v, axis_name, float(sm_scale))


def zigzag_wrap(inner, n: int):
    """Wrap a zigzag-layout attention callable (global view, natural-order
    in/out): permutes q/k/v into zigzag order, runs ``inner``, inverts the
    permutation on the output.  Persistent-layout users skip this and call
    :func:`zigzag_ring_attention` directly inside their own shard_map,
    keeping activations zigzagged across layers and paying the shuffle
    once."""

    def fn(q, k, v):
        perm = zigzag_indices(q.shape[2], n)
        inv = np.argsort(perm)
        qz = jnp.take(q, perm, axis=2)
        kz = jnp.take(k, perm, axis=2)
        vz = jnp.take(v, perm, axis=2)
        return jnp.take(inner(qz, kz, vz), inv, axis=2)

    return fn


def make_zigzag_ring_attention(mesh, axis_name: str = "sp", *,
                               sm_scale: Optional[float] = None):
    """Jitted global-view causal ring attention in the load-balanced zigzag
    layout: q/k/v are natural-order global arrays ``[B, H, S, D]`` sharded
    on the sequence dimension; the permutation into and out of zigzag order
    is applied at the jit boundary."""
    spec = P(None, None, axis_name, None)

    def local(q, k, v):
        return zigzag_ring_attention(q, k, v, axis_name, sm_scale=sm_scale)

    inner = shard_map_fn(mesh, local, in_specs=(spec, spec, spec), out_specs=spec)
    return jax.jit(zigzag_wrap(inner, mesh.shape[axis_name]))


def make_ring_attention(mesh, axis_name: str = "sp", *, causal: bool = True,
                        sm_scale: Optional[float] = None,
                        window: Optional[int] = None):
    """Jitted global-view ring attention: q/k/v are global arrays sharded on
    the sequence dimension over ``axis_name`` ([B, H, S, D], S sharded).
    ``window``: sliding-window band (see :func:`ring_attention`)."""
    spec = P(None, None, axis_name, None)

    def local(q, k, v):
        return ring_attention(q, k, v, axis_name, causal=causal,
                              sm_scale=sm_scale, window=window)

    return jax.jit(shard_map_fn(mesh, local, in_specs=(spec, spec, spec), out_specs=spec))
