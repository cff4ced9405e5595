"""Model (models/generate.py, cache.py, serving.py): megabytes of k / v one
decode step must read: the ONE full layer's rows, ``kv_rows_full`` positions
(``pos + 1`` a decoding slot, of the program's ``step_log()``, mean over
the window's chunks, plus half a chunk a slot) ONCE FOR EACH OF ITS
``kv_full_readers`` (8: the layer itself and the seven cross-attention
layers, which keep nothing and read its rows), and ``kv_rows_window``
positions in each of the 8 window layers' rings, at 5,120 B a position a
layer (10 pairs of 64-wide kv heads, k and v, bf16;
harness/ssm_yoco_counts.py).  The run's log gives the two parts apart
(``state_rows``: ``full_rows_read_MB`` / ``rings_read_MB``).  Beside
``state_rw_MB.cot``: what the layers that keep rows cost against the layers
that keep a state.  Moves ``tpot_p95_ms``."""

from benchmark.harness import ssm_yoco_counts as C


def read(obs):
    means = C.step_means(obs)
    if not means:
        return None
    return C.kv_bytes(obs["config"], means["rows_full"], means["readers"],
                      means["rows_window"]) / 1e6
