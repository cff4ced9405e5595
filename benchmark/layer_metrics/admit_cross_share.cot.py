"""Scheduler + model (models/serving.py, generate.py): of the rows of the
prompts' buckets that the window's admit programs put through the layers
which keep something (``admit_rows_self`` of the program's ``step_log()``:
layers 0-17), the share the layers behind them, which keep nothing, saw
(``admit_rows_cross``: layers 18-31).  An admission that exits early runs
those for the prompt's last row alone (one row a prompt: a few hundredths
of a percent); one that runs every layer over the bucket reads 100.  Moves
``tok_s`` (an admission stalls every lane)."""

from benchmark.harness import ssm_yoco_counts as C


def read(obs):
    means = C.step_means(obs)
    if not means or not means["admit_rows_self"]:
        return None
    return means["admit_rows_cross"] / means["admit_rows_self"] * 100.0
