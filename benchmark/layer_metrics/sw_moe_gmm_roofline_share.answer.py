"""Kernels (ops/pallas_gmm.py): the routed experts' grouped matmul in the
decode chunk against its roofline, at this model's shapes (128 of 512
experts held, width 512, 10 a token: a held expert sees 3.75 rows a step).
Per decode step and layer the kernel must read the touched experts' weights
once and multiply the pairs that landed on them (``moe_touched`` /
``moe_assign`` of the program's ``step_log()``, means over the window;
counts: harness/gdn_gqa_moe_counts.py); the larger of those bytes over the
HBM's peak and operations over the bf16 peak, times the layer-steps traced
(two calls each: gate with up, down), over the seconds of ``sw_moe_gmm*``
inside ``jit_serve_decode_chunk`` in the device trace.  Cannot pass 100%.
Moves ``tpot_p95_ms``."""

from benchmark.harness import gdn_gqa_moe_counts as C
from benchmark.harness.peaks import peaks
from benchmark.harness.trace_by_name import kernel


def read(obs):
    ran = kernel(obs.get("ops_by_name"), C.GMM_KERNEL, C.CHUNK_PROGRAM)
    means = C.step_means(obs)
    if not ran or not means or not ran[1]:
        return None
    calls, seconds = ran
    floor = C.roofline_s(
        C.moe_layer_flops(obs["config"], means["pairs"]),
        C.moe_layer_bytes(obs["config"], means["touched"], means["pairs"]),
        peaks(obs["device"]["kind"]))
    return floor * (calls / 2) / seconds * 100.0
