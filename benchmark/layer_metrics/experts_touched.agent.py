"""Model (models/moe.py): held experts that got at least one (token,
choice) pair in a decode step, mean over routed layers, steps and the
window's chunks (``moe_touched`` of the program's ``step_log()``): how many
experts' weights a step reads.  Moves ``tpot_p95_ms``."""

from benchmark.harness.mla_moe_obs import moe_means


def read(obs):
    means = moe_means(obs)
    return means["touched"] if means else None
