"""Model (models/ssm.py, generate.py, serving.py): megabytes of state-space
state one decode step must read AND write: the slots that decode
(``state_slots`` of the program's ``step_log()``, mean over the window's
chunks) times the 9 Mamba layers times twice a slot's state (16 states of
5,120 channels in float32 and the convolution's tails of 3 x 5,120 bf16;
harness/ssm_yoco_counts.py).  Constant in the requests' lengths: what a
cache of keys and values would make grow with every token.  Moves
``tpot_p95_ms``."""

from benchmark.harness import ssm_yoco_counts as C


def read(obs):
    means = C.step_means(obs)
    if not means:
        return None
    return C.state_rw_bytes(obs["config"], means["slots"]) / 1e6
