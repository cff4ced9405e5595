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
