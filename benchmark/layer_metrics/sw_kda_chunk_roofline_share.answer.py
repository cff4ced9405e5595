"""Kernels (ops/pallas_kda.py): the chunked prefill form of the gated delta
rule, the part that carries the state from chunk to chunk, against its
roofline, where a Gated DeltaNet layer runs it (the pairwise decays around
it are one ``[64, 64]`` matrix a head here, XLA's own matmuls under the
scope ``sw_kda_chunk``).  A call (one DeltaNet layer of one admission)
walks its bucket's chunks of 64 positions for 32 value heads: a chunk a
head is three [64, 128] x [128, 128] products and one [64, 64] x [64, 128]
and moves 180 KB of float32 operands (harness/gdn_gqa_moe_counts.py); the
larger of operations over the bf16 peak and bytes over the HBM's peak,
summed over the calls traced in each ``jit_serve_admit_<bucket>`` program,
over the seconds of ``sw_kda_chunk*`` there.  Cannot pass 100%.  Moves
``tpot_p95_ms`` (an admission stalls every lane)."""

from benchmark.harness import gdn_gqa_moe_counts as C
from benchmark.harness.peaks import peaks


def read(obs):
    floor = seconds = 0.0
    for program, rows in ((obs.get("ops_by_name") or {}).get("ops") or {}).items():
        bucket = program[len(C.ADMIT_PROGRAM):]
        if not program.startswith(C.ADMIT_PROGRAM) or not bucket.isdigit():
            continue
        for name, (calls, secs) in rows.items():
            if name == C.CHUNK_KERNEL or name.startswith(C.CHUNK_KERNEL + "."):
                floor += calls * C.roofline_s(
                    C.gdn_chunk_flops(obs["config"], int(bucket)),
                    C.gdn_chunk_bytes(obs["config"], int(bucket)),
                    peaks(obs["device"]["kind"]))
                seconds += secs
    return floor / seconds * 100.0 if seconds else None
