"""Model (models/generate.py, serving.py): megabytes of k/v one decode step
must read, both cache kinds: ``kv_rows_full`` positions in each full layer
and ``kv_rows_window`` (``min(pos + 1, window)`` a slot) in each window
layer, of the program's ``step_log()``, mean over the window's chunks,
times 2,048 B a position a layer (harness/window_moe_counts.py).  The
number that says whether the rings bound the window layers' reads: with
whole-length window caches it would be ``kv_rows_full`` in every layer.
Moves ``tpot_p95_ms``."""

from benchmark.harness import window_moe_counts as C


def read(obs):
    means = C.step_means(obs)
    if not means:
        return None
    return C.kv_bytes(obs["config"], means["rows_full"],
                      means["rows_window"]) / 1e6
