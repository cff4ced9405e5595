"""Kernels (ops/pallas_kda.py): the gated delta rule's decode step against
its roofline, where a Gated DeltaNet layer runs it (one log-decay a value
head, fed to ``sw_kda_step`` as a decay a channel; key heads repeated to
their value heads before the call).  A call (one DeltaNet layer of one
step) must read AND write the state of every slot (32 value heads x 128 x
128 float32 each) and move the step's q, k, decay, v, beta and read-out
(harness/gdn_gqa_moe_counts.py); the larger of those bytes over the HBM's
peak and its operations over the bf16 peak, times the calls traced, over
the seconds of ``sw_kda_step*`` inside ``jit_serve_decode_chunk`` in the
device trace.  Cannot pass 100%.  Moves ``tpot_p95_ms``."""

from benchmark.harness import gdn_gqa_moe_counts as C
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
    floor = C.roofline_s(C.gdn_step_flops(config, slots),
                         C.gdn_step_bytes(config, slots),
                         peaks(obs["device"]["kind"]))
    return floor * calls / seconds * 100.0
