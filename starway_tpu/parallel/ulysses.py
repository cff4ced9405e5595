"""Ulysses-style sequence parallelism: all-to-all head/sequence re-sharding.

The second long-context strategy next to ring attention (SURVEY.md section 5
names both: "Ulysses = all-to-all composed from P2P").  Where the ring keeps
queries resident and rotates kv, Ulysses re-shards: an all-to-all over the
sequence axis converts [heads: full, seq: sharded] into [heads: sharded,
seq: full], attention runs locally over the whole sequence, and a reverse
all-to-all restores the layout.  Two collectives total per attention call --
cheaper than a ring when n_heads >= mesh axis size and sequence length
dominates; the ring wins for GQA models with few kv heads.

Requires ``n_heads % axis_size == 0``.  Grouped kv stays narrow across the
all-to-all whenever ``n_kv_heads % axis_size == 0`` -- the collectives move
``1/n_rep`` of the expanded traffic and the expansion happens locally after
re-sharding (block-aligned head ranges keep the q-head -> kv-head mapping
exact); otherwise kv is pre-expanded so head shards align.
"""

from __future__ import annotations

from typing import Optional

import jax
from jax import lax
from jax.sharding import PartitionSpec as P

from ..ops import self_attention
from ..ops.attention import repeat_kv
from .sharding import shard_map_fn


def ulysses_attention(q, k, v, axis_name: str, *, causal: bool = True,
                      sm_scale: Optional[float] = None,
                      window: Optional[int] = None):
    """Per-device body (call inside shard_map): q/k/v are sequence shards
    ``[B, H, T_local, D]`` with the FULL head dimension; returns the local
    sequence shard of the output.

    ``window``: sliding-window band — after the re-shard each device holds
    the FULL sequence for its heads, so the band is just the local
    blockwise mask (no cross-shard bookkeeping, unlike the ring)."""
    if window is not None and not causal:
        raise ValueError("window requires causal attention")
    n = lax.axis_size(axis_name)
    n_rep = q.shape[1] // k.shape[1]
    if n_rep > 1 and k.shape[1] % n != 0:
        # Narrow heads don't split evenly over the axis: pre-expand.
        k = repeat_kv(k, n_rep)
        v = repeat_kv(v, n_rep)
        n_rep = 1
    # [B, H, T/n, D] -> [B, H/n, T, D]: scatter heads, gather sequence.
    # Grouped kv rides the all-to-all narrow (1/n_rep of the bytes): device
    # d ends up with q heads [d*H/n, (d+1)*H/n) and kv heads
    # [d*Hkv/n, (d+1)*Hkv/n), which are exactly each other's GQA partners
    # (q head h uses kv head h // n_rep), so the local repeat_kv below
    # reproduces the global mapping.
    q2 = lax.all_to_all(q, axis_name, split_axis=1, concat_axis=2, tiled=True)
    k2 = lax.all_to_all(k, axis_name, split_axis=1, concat_axis=2, tiled=True)
    v2 = lax.all_to_all(v, axis_name, split_axis=1, concat_axis=2, tiled=True)
    # Grouped (narrow) kv goes in as it is: the flash kernel indexes it
    # and, with a window, DMA-elides out-of-band tiles, so windowed
    # Ulysses wall-clock scales with the band, matching the ring path;
    # the lax twin expands it.
    o2 = self_attention(q2, k2, v2, causal=causal, sm_scale=sm_scale,
                        window=window)
    # Restore: [B, H/n, T, D] -> [B, H, T/n, D].
    return lax.all_to_all(o2, axis_name, split_axis=2, concat_axis=1, tiled=True)


def make_ulysses_attention(mesh, axis_name: str = "sp", *, causal: bool = True,
                           sm_scale: Optional[float] = None,
                           window: Optional[int] = None):
    """Jitted global-view Ulysses attention over sequence-sharded q/k/v.
    ``window``: sliding-window band (see :func:`ulysses_attention`)."""
    if window is not None and not causal:
        # Fail at build, not first-call trace (matches make_sharded_attn).
        raise ValueError("window requires causal attention")
    spec = P(None, None, axis_name, None)

    def local(q, k, v):
        return ulysses_attention(q, k, v, axis_name, causal=causal,
                                 sm_scale=sm_scale, window=window)

    jitted = jax.jit(shard_map_fn(mesh, local, in_specs=(spec, spec, spec),
                                  out_specs=spec))
    if window is None:
        return jitted  # keep the PjitFunction surface (.lower, caching)

    def fn(q, k, v):
        return jitted(q, k, v)

    # resolve_attn_fn's windowed-config contract (models/llama.py);
    # attributes cannot be set on the jit object itself.
    fn.handles_window = True
    fn.window = window
    return fn
