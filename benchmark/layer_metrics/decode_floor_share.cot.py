"""Kernels + model: the least time the chip could take for one decode step
of the decoder-hybrid-decoder (the larger of the bytes it must move over
the HBM's peak and its operations over the bf16 peak: every weight and the
tied table once, the ``kv_rows_full`` positions of the one full layer's
rows times their ``kv_full_readers``, the ``kv_rows_window`` positions of
every ring, the state of ``state_slots`` read AND written in every Mamba
layer; harness/ssm_yoco_counts.py), as a share of the device seconds of a
step of ``jit_serve_decode_chunk`` itself (its ``XLA Modules`` seconds over
its executions and the chunk's steps; ``decode_step_ms`` where the trace
has no such line).  The cell's share of the whole step; cannot pass 100%.
Moves ``tpot_p95_ms``."""

from benchmark.harness import ssm_yoco_counts as C
from benchmark.harness.peaks import peaks
from benchmark.harness.spec import load_reader


def read(obs):
    means = C.step_means(obs)
    step_s = C.chunk_step_s(obs)
    if step_s is None:
        step_ms = load_reader("decode_step_ms").read(obs)
        step_s = step_ms / 1e3 if step_ms else None
    if not step_s or not means:
        return None
    config = obs["config"]
    floor = C.step_floor_s(config, peaks(obs["device"]["kind"]),
                           config["serve"]["n_slots"], means["slots"],
                           means["rows_full"], means["readers"],
                           means["rows_window"])
    return floor / step_s * 100.0
