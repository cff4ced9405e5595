"""Scheduler (models/serving.py): real prompt tokens the window's decode
chunks ingested, as a share of the rows they spent on prompt pieces (the
piece width times the steps that carried a piece; a prompt's last piece is
padded to the width).  Source: the program's own ``serving.step_log()``
(``ingest_tokens`` / ``ingest_rows``).  None where no step ingested, or
from a program without the fields (its prompts come in by admit programs).
Moves ``tok_s``."""

from benchmark.harness.serve_logs import window_steps


def read(obs):
    steps = window_steps(obs)
    rows = sum(r.get("ingest_rows", 0) for r in steps)
    if not rows:
        return None
    return sum(r.get("ingest_tokens", 0) for r in steps) / rows * 100.0
