"""Model (models/kda.py, generate.py, serving.py): megabytes of
linear-attention state one decode step must read AND write: the slots that
decode (``state_slots`` of the program's ``step_log()``, mean over the
window's chunks) times the DeltaNet layers times twice a slot's state (32
value heads x 128 x 128 float32 and the convolution's tails of 8,192
channels; harness/gdn_gqa_moe_counts.py).  Constant in the requests'
lengths: what a cache of keys and values would make grow with every token.
Moves ``tpot_p95_ms``."""

from benchmark.harness import gdn_gqa_moe_counts as C


def read(obs):
    means = C.step_means(obs)
    if not means:
        return None
    return C.state_rw_bytes(obs["config"], means["slots"]) / 1e6
