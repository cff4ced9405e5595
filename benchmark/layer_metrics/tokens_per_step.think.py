"""Scheduler (models/serving.py): tokens a live slot yields in one
draft-and-verify step (1: the draft was rejected, or the budget ended; 2:
accepted, with its bonus token), over the window's chunks.  Source: the
program's own ``serving.step_log()`` (``spec_emitted`` / ``spec_drafted``).
Moves ``tok_s``."""

from benchmark.harness.window_moe_mtp_counts import spec_sums


def read(obs):
    sums = spec_sums(obs)
    return sums["emitted"] / sums["drafted"] if sums else None
