"""Scheduler (models/serving.py): occupied slots when the decode chunk is
dispatched, as a share of ``n_slots``; mean over the ``step()`` calls of the
window.  Source: the program's own ``serving.step_log()`` (``live`` /
``n_slots``).  Moves ``tok_s``."""

from benchmark.harness.serve_logs import window_steps


def read(obs):
    steps = window_steps(obs)
    if not steps:
        return None
    return sum(r["live"] / r["n_slots"] for r in steps) / len(steps) * 100.0
