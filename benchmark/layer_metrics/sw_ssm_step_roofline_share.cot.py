"""Kernels (ops/pallas_ssm.py): the state-space recurrence's decode step
(``sw_ssm_step``) against its roofline.  A call (one Mamba layer of one
step) must read AND write the state of every slot (16 states of 5,120
channels in float32) and move the step's dt, x, B, C and read-out
(harness/ssm_yoco_counts.py); the larger of those bytes over the HBM's peak
and its operations over the bf16 peak (the vector unit runs them and the
peaks' table has no figure for it: the bytes decide), times the calls
traced, over the seconds of ``sw_ssm_step*`` inside
``jit_serve_decode_chunk`` in the device trace.  Cannot pass 100%.  Moves
``tpot_p95_ms``."""

from benchmark.harness import ssm_yoco_counts as C
from benchmark.harness.peaks import peaks
from benchmark.harness.trace_by_name import kernel


def read(obs):
    ran = kernel(obs.get("ops_by_name"), C.STEP_KERNEL, C.CHUNK_PROGRAM)
    if not ran or not ran[1] or not C.step_means(obs):
        return None
    calls, seconds = ran
    config = obs["config"]
    # The kernel runs every slot's row, decoding or not.
    slots = config["serve"]["n_slots"]
    floor = C.roofline_s(C.ssm_step_flops(config, slots),
                         C.ssm_step_bytes(config, slots),
                         peaks(obs["device"]["kind"]))
    return floor * calls / seconds * 100.0
