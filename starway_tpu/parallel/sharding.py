"""Mesh + sharding helpers.

The recipe (scaling-book style): pick a mesh, annotate shardings, let XLA
insert the collectives.  These helpers keep mesh construction and
NamedSharding spelling in one place for the rest of the framework.
"""

from __future__ import annotations

from typing import Optional, Sequence

import jax
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P


def make_mesh(axes: dict[str, int], devices: Optional[Sequence] = None) -> Mesh:
    """Build a Mesh from ``{"dp": 2, "tp": 2, "sp": 2}``-style axis sizes.

    Axis order follows dict order; sizes must multiply to the device count
    used.  On TPU hardware the trailing axes map to the fastest ICI
    neighborhoods, so put the most communication-heavy axis (tp/sp) last.
    """
    devs = list(devices) if devices is not None else jax.devices()
    shape = tuple(axes.values())
    n = int(np.prod(shape))
    if n > len(devs):
        raise ValueError(f"mesh needs {n} devices, only {len(devs)} available")
    grid = np.array(devs[:n]).reshape(shape)
    return Mesh(grid, tuple(axes.keys()))


def mesh_sharding(mesh: Mesh, *spec) -> NamedSharding:
    """NamedSharding shorthand: mesh_sharding(mesh, 'dp', None, 'tp')."""
    return NamedSharding(mesh, P(*spec))


def shard_array(mesh: Mesh, x, *spec):
    return jax.device_put(x, mesh_sharding(mesh, *spec))


def shard_map_fn(mesh: Mesh, fn, in_specs, out_specs):
    """``jax.shard_map`` over ``mesh`` (per-device SPMD view), unchecked."""
    return jax.shard_map(fn, mesh=mesh, in_specs=in_specs,
                         out_specs=out_specs, check_vma=False)


def per_head_shard(kernel, sharded, replicated=(), *, axis: str = "tp",
                   head_dims=None, out_head_dims=None):
    """``kernel(*sharded, *replicated)``, run per shard of the head
    dimension when the ambient mesh (``jax.set_mesh``) has ``axis`` with
    more than one device; called directly otherwise.

    ``head_dims``: which dim of each ``sharded`` operand counts heads —
    1 for ``[B, H, ...]`` activations and per-layer caches (the default
    for all), 2 for the scan-stacked caches ``[L, B, Hkv, T, D]``.
    ``out_head_dims``: the same for the result — an int, or a tuple when
    the kernel returns a tuple; default the first operand's.

    A Mosaic kernel cannot be partitioned by the compiler: traced under a
    tensor-parallel GSPMD program it is refused ("Mosaic kernels cannot be
    automatically partitioned. Please wrap the call in a shard_map").
    Heads are independent in attention and ``param_specs`` already shards
    the q/k/v projections over ``axis`` by head, so each device runs the
    kernel on the heads it holds and nothing moves.  Grouped-query
    pairing survives the split while ``axis`` divides the kv heads (q
    head h reads kv head h // n_rep)."""
    mesh = jax.sharding.get_abstract_mesh()
    if mesh.empty or mesh.shape.get(axis, 1) == 1:
        return kernel(*sharded, *replicated)
    if head_dims is None:
        head_dims = (1,) * len(sharded)
    if out_head_dims is None:
        out_head_dims = head_dims[0]

    def heads(dim):
        return P(*([None] * dim), axis)

    return jax.shard_map(
        kernel,
        in_specs=(*(heads(d) for d in head_dims), *(P() for _ in replicated)),
        out_specs=(heads(out_head_dims) if isinstance(out_head_dims, int)
                   else tuple(heads(d) for d in out_head_dims)),
        check_vma=False,
    )(*sharded, *replicated)
