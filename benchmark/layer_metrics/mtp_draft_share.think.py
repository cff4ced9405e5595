"""Model (models/serving.py, mtp.py): what speculation costs beside the
verify it rides on: device seconds of the operations under the program's
scopes ``sw_mtp_draft`` (the MTP block's forward at both positions, its
head and the next draft's sampling) and ``sw_mtp_accept`` (the accept rule,
the resample, the log-probabilities) as a share of the decode chunk
program's device seconds, in the profiler's trace
(harness/trace_by_scope.py; the rest is ``sw_mtp_verify`` and the scan's
own bookkeeping).  Moves ``tpot_p95_ms``."""

from benchmark.harness.window_moe_mtp_counts import SCOPES


def read(obs):
    by_scope = obs.get("ops_by_scope")
    if not by_scope:
        return None
    total = sum(by_scope.values())
    spec = sum(by_scope.get(s, 0.0) for s in SCOPES if s != "sw_mtp_verify")
    return spec / total * 100.0 if total else None
