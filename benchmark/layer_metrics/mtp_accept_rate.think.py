"""Model (models/mtp.py, speculative.accept_rule): drafts of the MTP block
the main model's verify accepted, as a share of the drafts verified (one a
live slot a step), over the window's chunks.  Source: the program's own
``serving.step_log()`` (``spec_accepted`` / ``spec_drafted``).  With random
weights the draft and the target are unrelated near-flat distributions and
this reads what their overlap gives (PERF.md section 6), not what a trained
block would.  Moves ``tok_s``."""

from benchmark.harness.window_moe_mtp_counts import spec_sums


def read(obs):
    sums = spec_sums(obs)
    return sums["accepted"] / sums["drafted"] * 100.0 if sums else None
