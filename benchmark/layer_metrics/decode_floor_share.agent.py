"""Kernels + model: the least time the chip could take for one decode step
of the latent-attention, routed-expert model (the larger of the bytes it
must read over the HBM's peak and its operations over the bf16 peak:
every weight it multiplies by, of the held experts only the
``moe_touched`` that got a token, plus the live latent rows;
harness/mla_moe_counts.py), as a share of ``decode_step_ms``.  Cannot pass
100%.  Moves ``tpot_p95_ms``."""

from benchmark.harness import mla_moe_counts as C
from benchmark.harness.mla_moe_obs import live_rows, moe_means
from benchmark.harness.peaks import peaks
from benchmark.harness.spec import load_reader


def read(obs):
    step_ms = load_reader("decode_step_ms").read(obs)
    means, rows = moe_means(obs), live_rows(obs)
    if not step_ms or not means or not rows:
        return None
    config = obs["config"]
    floor = C.step_floor_s(config, peaks(obs["device"]["kind"]),
                           config["serve"]["n_slots"], rows, means["touched"],
                           means["pairs"])
    return floor / (step_ms / 1e3) * 100.0
