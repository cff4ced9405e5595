"""Kernels (ops/pallas_ssm.py): the state-space recurrence over a whole
prompt (``sw_ssm_scan``: chunks of 128 positions, the state in VMEM)
against its roofline.  A call (one Mamba layer of one admission) walks its
bucket's positions: dt and x in and the read-out out at every position in
float32, B and C, the state out once (harness/ssm_yoco_counts.py); the
larger of those bytes over the HBM's peak and its operations over the bf16
peak (the vector unit runs them token by token and the peaks' table has no
figure for it: the bytes decide, so this share says how far the
recurrence's latency, not the HBM, bounds the kernel), summed over the
calls traced in each ``jit_serve_admit_<bucket>`` program, over the seconds
of ``sw_ssm_scan*`` there.  Cannot pass 100%.  Moves ``tpot_p95_ms`` (an
admission stalls every lane)."""

from benchmark.harness import ssm_yoco_counts as C
from benchmark.harness.peaks import peaks


def read(obs):
    floor = seconds = 0.0
    for program, rows in ((obs.get("ops_by_name") or {}).get("ops") or {}).items():
        bucket = program[len(C.ADMIT_PROGRAM):]
        if not program.startswith(C.ADMIT_PROGRAM) or not bucket.isdigit():
            continue
        for name, (calls, secs) in rows.items():
            if name == C.SCAN_KERNEL or name.startswith(C.SCAN_KERNEL + "."):
                floor += calls * C.roofline_s(
                    C.ssm_scan_flops(obs["config"], int(bucket)),
                    C.ssm_scan_bytes(obs["config"], int(bucket)),
                    peaks(obs["device"]["kind"]))
                seconds += secs
    return floor / seconds * 100.0 if seconds else None
