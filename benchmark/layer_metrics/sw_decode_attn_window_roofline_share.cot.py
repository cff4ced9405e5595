"""Kernels (ops/pallas_decode.py): the decode attention over the window
layers' rings (``sw_decode_attn_ring``: the same kernel over a ring of one
window of 512, the cursor clamped to it) against its roofline, at 128-wide
pairs of 64-wide heads.  A call (one layer of one step) must read the
cached positions its live slots attend once (``kv_rows_window`` of the
program's ``step_log()``: ``min(pos + 1, 512)`` a slot, mean over the
window's chunks; 5,120 B a position: harness/ssm_yoco_counts.py) and score
every query head against them; the larger of bytes over the HBM's peak and
operations over the bf16 peak, times the calls traced, over the kernel's
seconds inside ``jit_serve_decode_chunk`` in the device trace.  Cannot pass
100%.  Moves ``tpot_p95_ms``."""

from benchmark.harness import ssm_yoco_counts as C
from benchmark.harness.peaks import peaks
from benchmark.harness.trace_by_name import kernel


def read(obs):
    ran = kernel(obs.get("ops_by_name"), C.RING_KERNEL, C.CHUNK_PROGRAM)
    means = C.step_means(obs)
    if not ran or not means or not ran[1]:
        return None
    calls, seconds = ran
    config = obs["config"]
    floor = C.roofline_s(
        C.attn_flops(config, means["rows_window"]),
        C.attn_bytes(config, config["serve"]["n_slots"], means["rows_window"]),
        peaks(obs["device"]["kind"]))
    return floor * calls / seconds * 100.0
