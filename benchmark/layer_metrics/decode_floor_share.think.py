"""Kernels + model: the least time the chip could take for one
DRAFT-AND-VERIFY step of the K-EXAONE block (the larger of the bytes it
must read over the HBM's peak and its operations over the bf16 peak: every
weight once, the ``moe_touched`` experts that got a row, the head once,
the MTP block, and the k/v of three full rows at ``kv_rows_full`` and six
rings at ``kv_rows_window``; harness/window_moe_mtp_counts.py), as a share
of ``decode_step_ms``, which here is such a step.  Cannot pass 100%.  Moves
``tpot_p95_ms``."""

from benchmark.harness import window_moe_mtp_counts as C
from benchmark.harness.peaks import peaks
from benchmark.harness.spec import load_reader


def read(obs):
    step_ms = load_reader("decode_step_ms").read(obs)
    means = C.step_means(obs)
    if not step_ms or not means:
        return None
    config = obs["config"]
    floor = C.step_floor_s(config, peaks(obs["device"]["kind"]),
                           config["serve"]["n_slots"], means["rows_full"],
                           means["rows_window"], means["touched"],
                           means["pairs"])
    return floor / (step_ms / 1e3) * 100.0
