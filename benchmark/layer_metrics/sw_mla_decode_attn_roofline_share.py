"""Kernels (ops/pallas_decode.py): the absorbed latent decode attention
against its roofline.  A call (one layer of one step) must read the live
latent rows once (512 + 64 values each) and score every head's query
against them, whole and over their first 512 values
(harness/mla_moe_counts.py; live rows from the program's request and step
logs, mean over the window); the larger of bytes over the HBM's peak and
operations over the bf16 peak, times the calls traced, over the seconds of
``sw_mla_decode_attn*`` in the device trace.  Cannot pass 100%.  Moves
``tpot_p95_ms``."""

from benchmark.harness import mla_moe_counts as C
from benchmark.harness.mla_moe_obs import CHUNK_PROGRAM, live_rows
from benchmark.harness.peaks import peaks
from benchmark.harness.trace_by_name import kernel


def read(obs):
    ran = kernel(obs.get("ops_by_name"), "sw_mla_decode_attn", CHUNK_PROGRAM)
    rows = live_rows(obs)
    if not ran or not rows or not ran[1]:
        return None
    calls, seconds = ran
    config = obs["config"]
    floor = C.roofline_s(
        C.mla_decode_flops(config, rows),
        C.mla_decode_bytes(config, config["serve"]["n_slots"], rows),
        peaks(obs["device"]["kind"]))
    return floor * calls / seconds * 100.0
