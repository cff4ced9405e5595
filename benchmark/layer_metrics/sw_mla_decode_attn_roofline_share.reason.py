"""Kernels (ops/pallas_decode.py): the absorbed latent decode attention
against its roofline, as ``sw_mla_decode_attn_roofline_share`` at this
model's shapes (32 heads, 256 slots, NoPE: no rotation changes no byte).
A call (one latent layer of one step) must read the live latent rows once
(512 + 64 values each) and score every head's query against them, whole
and over their first 512 values (harness/mla_moe_counts.py;
``kv_rows_latent`` of the program's ``step_log()``, mean over the window);
the larger of bytes over the HBM's peak and operations over the bf16 peak,
times the calls traced, over the seconds of ``sw_mla_decode_attn*`` inside
``jit_serve_decode_chunk`` in the device trace.  Cannot pass 100%.  Moves
``tpot_p95_ms``."""

from benchmark.harness import kda_mla_moe_counts as C
from benchmark.harness.peaks import peaks
from benchmark.harness.trace_by_name import kernel


def read(obs):
    ran = kernel(obs.get("ops_by_name"), "sw_mla_decode_attn", C.CHUNK_PROGRAM)
    means = C.step_means(obs)
    if not ran or not means or not ran[1]:
        return None
    calls, seconds = ran
    config = obs["config"]
    floor = C.roofline_s(
        C.mla_decode_flops(config, means["rows"]),
        C.mla_decode_bytes(config, config["serve"]["n_slots"], means["rows"]),
        peaks(obs["device"]["kind"]))
    return floor * calls / seconds * 100.0
