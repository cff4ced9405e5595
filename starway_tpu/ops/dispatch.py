"""Where an implementation is chosen: the ONE decision, and the wrapper a
Mosaic kernel needs under a tensor-parallel mesh.

Every operation with a Pallas kernel has one public function in ``ops/``
(exported from the package) that alone decides what runs: the kernel where
:func:`use_kernels` says so, under :func:`per_head_shard` where the
operation shards by head; the ``*_lax`` twin beside it elsewhere.  Callers
in ``models/`` and ``parallel/`` name the operation, never a backend.  A
test that needs a whole program on one side substitutes
:func:`use_kernels` (the dispatchers look it up through this module at
trace time); a test of one side calls the kernel (``interpret=True``) or
the twin by name.
"""

from __future__ import annotations

import jax
from jax.sharding import PartitionSpec as P


def use_kernels() -> bool:
    """The Pallas kernels run on a TPU; everything else takes the lax
    twins (interpret mode is for tests, a thousand times slower)."""
    return jax.default_backend() == "tpu"


def interpret() -> bool:
    """A Pallas kernel's default ``interpret=``: off the chip a kernel
    that IS called runs interpreted.  Not :func:`use_kernels`' question:
    a test that forces the kernels on off the chip (``force_kernels``)
    substitutes that decision and still gets them interpreted, by this
    one."""
    return jax.default_backend() != "tpu"


def per_head_shard(kernel, sharded, replicated=(), *, axis: str = "tp",
                   head_dims=None, out_head_dims=None):
    """``kernel(*sharded, *replicated)``, run per shard of the head
    dimension when the ambient mesh (``jax.set_mesh``) has ``axis`` with
    more than one device; called directly otherwise (and inside a
    ``shard_map`` that already holds ``axis``: the operands are local).

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
    if (mesh.empty or mesh.shape.get(axis, 1) == 1
            or axis in mesh.manual_axes):
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
