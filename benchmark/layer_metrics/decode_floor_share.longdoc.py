"""Kernels + model: the least time the chip could take for one decode step
of the window-and-full, routed-expert model (the larger of the bytes it
must read over the HBM's peak and its operations over the bf16 peak:
attention and router weights, the ``moe_touched`` experts that got a token,
the head, and the k/v of ``kv_rows_full`` / ``kv_rows_window``;
harness/window_moe_counts.py), as a share of ``decode_step_ms``.  The
cell's share of the whole step; cannot pass 100%.  Moves ``tpot_p95_ms``."""

from benchmark.harness import window_moe_counts as C
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
