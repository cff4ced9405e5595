"""Kernels (ops/pallas_kda.py): the gated delta rule's decode step against
its roofline.  A call (one KDA layer of one step) must read AND write the
state of every slot that decodes (32 heads x 128 x 128 float32 each) and
move the step's q, k, decay, v, beta and read-out
(harness/kda_mla_moe_counts.py; ``state_slots`` of the program's
``step_log()``, mean over the window); the larger of those bytes over the
HBM's peak and its operations over the bf16 peak, times the calls traced,
over the seconds of ``sw_kda_step*`` inside ``jit_serve_decode_chunk`` in
the device trace.  Cannot pass 100%.  Moves ``tpot_p95_ms``."""

from benchmark.harness import kda_mla_moe_counts as C
from benchmark.harness.peaks import peaks
from benchmark.harness.trace_by_name import kernel


def read(obs):
    ran = kernel(obs.get("ops_by_name"), C.STEP_KERNEL, C.CHUNK_PROGRAM)
    means = C.step_means(obs)
    if not ran or not means or not ran[1]:
        return None
    calls, seconds = ran
    config = obs["config"]
    # The kernel runs every slot's row, decoding or not.
    slots = config["serve"]["n_slots"]
    floor = C.roofline_s(C.kda_step_flops(config, slots),
                         C.kda_step_bytes(config, slots),
                         peaks(obs["device"]["kind"]))
    return floor * calls / seconds * 100.0
