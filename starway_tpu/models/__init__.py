"""Model families built on the framework's device plane.

The reference moves opaque buffers; the configs in BASELINE.json ground them
in real workloads ("Llama-3 8B activation/grad transfer between TPU hosts").
This package provides the flagship Llama family used by the benchmarks, the
DP-exchange demos, and the graft entry's multichip training step.
"""

from .llama import (
    LlamaConfig,
    forward,
    init_params,
    loss_fn,
    make_train_step,
    param_specs,
)
from .hf_convert import config_from_hf, params_from_hf
from .pp_llama import (
    make_pp_llama_train,
    pp_merge_params,
    pp_param_specs,
    pp_split_params,
    ppv_merge_params,
    ppv_split_params,
    shard_pp_params,
    shard_ppv_params,
)
from .beam import generate_beam
from .cache import init_cache, init_rolling_cache
from .generate import generate, prefill, prefill_rolling
from .paged import PagedSlotServer, init_paged_pool, paged_decode_step
from .remote_serving import RemoteGenerateSession, RemoteSlotServer
from .serving import SlotServer
from .trainer import Trainer
from .speculative import (chunk_decode_step, draft_from_truncation,
                          generate_lookup, generate_speculative)

__all__ = [
    "LlamaConfig",
    "init_params",
    "forward",
    "loss_fn",
    "make_train_step",
    "param_specs",
    "config_from_hf",
    "params_from_hf",
    "make_pp_llama_train",
    "pp_split_params",
    "pp_merge_params",
    "pp_param_specs",
    "shard_pp_params",
    "ppv_split_params",
    "ppv_merge_params",
    "shard_ppv_params",
    "PagedSlotServer",
    "RemoteGenerateSession",
    "RemoteSlotServer",
    "SlotServer",
    "Trainer",
    "generate",
    "init_cache",
    "init_rolling_cache",
    "prefill",
    "prefill_rolling",
    "chunk_decode_step",
    "draft_from_truncation",
    "generate_beam",
    "generate_lookup",
    "generate_speculative",
]
