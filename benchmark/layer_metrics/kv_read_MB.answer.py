"""Model (models/generate.py, serving.py): megabytes of k/v one decode step
must read: ``kv_rows_full`` positions (``pos + 1`` a decoding slot, of the
program's ``step_log()``, mean over the window's chunks, plus half a chunk
a slot) in each of the attention layers, which are one in four, times
2,048 B a position a layer (2 kv heads of 256, k and v, bf16;
harness/gdn_gqa_moe_counts.py).  Beside ``state_rw_MB.answer``: what the
layers that keep rows cost against the layers that keep a state.  Moves
``tpot_p95_ms``."""

from benchmark.harness import gdn_gqa_moe_counts as C


def read(obs):
    means = C.step_means(obs)
    if not means:
        return None
    return C.kv_bytes(obs["config"], means["rows"]) / 1e6
