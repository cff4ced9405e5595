"""Kernels + model: the time the chip's HBM needs, at its published peak,
for the bytes one decode step MUST read (every weight but the embedding
table, plus the live cache rows at the chunk's start, averaged over the
window's chunks: benchmark/harness/decode_bytes.py), as a share of
``decode_step_ms``.  Cannot pass 100%.  Moves ``tpot_p95_ms``."""

from benchmark.harness.decode_bytes import decode_step_bytes
from benchmark.harness.peaks import peaks
from benchmark.harness.spec import load_reader


def read(obs):
    step_ms = load_reader("decode_step_ms").read(obs)
    counts = (obs.get("child") or
              (obs["spans"].counts if obs.get("spans") else None))
    if not step_ms or not counts or not counts.get("chunks"):
        return None
    rows = counts["live_rows"] / counts["chunks"]
    floor_s = (decode_step_bytes(obs["config"], rows)
               / peaks(obs["device"]["kind"])["hbm_bytes_per_s"])
    return floor_s / (step_ms / 1e3) * 100.0
