"""Model (models/generate.py): device seconds of the operations under the
program's scope ``sw_cross_decoder`` (layers 18-31: the seven gated memory
units and the seven cross-attention layers, which read layer 17's rows and
keep nothing, their MLPs included) as a share of the decode chunk program's
device seconds, in the profiler's trace (harness/trace_by_scope.py).  The
rest is the self-decoder (layers 0-17), the head and the scan's own
bookkeeping.  Moves ``tpot_p95_ms``."""

SCOPE = "sw_cross_decoder"


def read(obs):
    by_scope = obs.get("ops_by_scope")
    if not by_scope:
        return None
    total = sum(by_scope.values())
    return by_scope.get(SCOPE, 0.0) / total * 100.0 if total else None
