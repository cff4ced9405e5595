"""Scheduler (models/serving.py): seconds inside admissions (each ends in
its prefill program's first token on the host, all lanes stalled meanwhile)
as a share of the window's seconds.  Source: the program's own
``serving.step_log()`` (``admit_s``).  Moves ``tok_s``."""

from benchmark.harness.serve_logs import window_steps


def read(obs):
    steps = window_steps(obs)
    t0, t1 = obs["window"]
    if not steps or t1 <= t0:
        return None
    return sum(r["admit_s"] for r in steps) / (t1 - t0) * 100.0
