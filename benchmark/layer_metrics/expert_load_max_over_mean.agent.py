"""Model (models/moe.py): the most pairs one held expert got in one layer
of one step of a chunk (``moe_max`` of ``step_log()``) over the mean pairs
an expert a layer a step (``moe_assign`` over steps, layers and held
experts), mean over the window's chunks: the imbalance the grouped
matmul's longest expert sees.  Moves ``tpot_p95_ms``."""

from benchmark.harness.mla_moe_obs import moe_means


def read(obs):
    means = moe_means(obs)
    if not means or not means["pairs"]:
        return None
    return means["max"] / (means["pairs"] / obs["config"]["n_routed_experts"])
