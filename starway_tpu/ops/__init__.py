"""Jitted device-plane building blocks: collectives and attention kernels.

This is the SPMD-native layer of the framework: where the host runtime
(core/) moves opaque tagged buffers between workers, these ops move sharded
``jax.Array`` data across a ``jax.sharding.Mesh`` with XLA collectives over
ICI -- the idiomatic TPU equivalent of composing transfers from the
reference's P2P primitives (SURVEY.md section 5 "Long-context / sequence
parallelism": "ring attention = asend/arecv to ring neighbors + overlap,
i.e. CollectivePermute; Ulysses = all-to-all composed from P2P").


Every operation that has a Pallas kernel is ONE function here, and that
function alone decides what runs (ops/dispatch.py): ``self_attention``,
``cached_attention``, ``ingest_attention``, ``latent_attention``,
``cache_write``, ``paged_attention``, ``grouped_matmul``,
``quantized_matmul``, the linear-attention recurrence ``kda_step`` /
``kda_chunk``, the state-space recurrence ``ssm_step`` / ``ssm_scan`` and
the ring step ``ring_step`` / ``ring_step_bwd``.  Each lives in the file that holds
its kernel, beside its ``*_lax`` twin.
"""

from .collectives import (
    all_gather,
    all_to_all,
    psum,
    reduce_scatter,
    ring_shift,
)
from .dispatch import per_head_shard
from .pallas_attention import ring_step, ring_step_bwd, self_attention
from .pallas_decode import (cache_write, cached_attention, ingest_attention,
                            latent_attention)
from .pallas_gemv import quantized_matmul
from .pallas_gmm import GATE_ACTS, grouped_matmul
from .pallas_kda import kda_chunk, kda_step
from .pallas_paged import paged_attention
from .pallas_ssm import ssm_scan, ssm_step
from .quantize import quantize_params

__all__ = ["ring_shift", "all_to_all", "all_gather", "psum",
           "reduce_scatter", "quantize_params", "per_head_shard",
           "self_attention", "cached_attention", "ingest_attention",
           "latent_attention",
           "cache_write", "paged_attention", "grouped_matmul", "GATE_ACTS",
           "quantized_matmul", "kda_step", "kda_chunk", "ssm_step",
           "ssm_scan", "ring_step",
           "ring_step_bwd"]
