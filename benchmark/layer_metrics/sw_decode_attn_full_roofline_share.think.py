"""Kernels (ops/pallas_decode.py): the decode attention of a
draft-and-verify step over the full rows of ``max_len``
(``sw_decode_attn_stream``: the model's two full layers and the MTP
block's one) against its roofline.  A call (one layer of one step, TWO
query rows a slot) must read the cached positions its live slots attend
once (``kv_rows_full`` of the program's ``step_log()``: ``pos + 2`` a slot,
mean over the window's chunks; k and v of 8 heads of 128:
harness/window_moe_mtp_counts.py) and score every query head of both rows
against them; the larger of bytes over the HBM's peak and operations over
the bf16 peak, times the calls traced, over the kernel's seconds inside
``jit_serve_decode_chunk`` in the device trace.  Cannot pass 100%.  Moves
``tpot_p95_ms``."""

from benchmark.harness import window_moe_mtp_counts as C
from benchmark.harness.peaks import peaks
from benchmark.harness.trace_by_name import kernel


def read(obs):
    ran = kernel(obs.get("ops_by_name"), C.FULL_KERNEL, C.CHUNK_PROGRAM)
    means = C.step_means(obs)
    if not ran or not means or not ran[1]:
        return None
    calls, seconds = ran
    config = obs["config"]
    floor = C.roofline_s(
        C.attn_flops(config, means["rows_full"]),
        C.attn_bytes(config, config["serve"]["n_slots"], means["rows_full"]),
        peaks(obs["device"]["kind"]))
    return floor * calls / seconds * 100.0
