"""Model (models/moe.py): the live row tiles the grouped matmul walks for
one held expert that got a pair, in one routed layer's call of a decode
step (``moe_tiles`` over ``moe_touched`` of the program's ``step_log()``,
each a mean over layers and steps, summed over the window's chunks): how
many row tiles share one read of an expert's weights; where the kernel
walks row tile outer over several column blocks, how many times those
weights were read.  A program whose rows carry no ``moe_tiles`` gives
nothing to read.  Moves ``tpot_p95_ms``."""

from benchmark.harness.serve_logs import window_steps


def read(obs):
    rows = [r for r in window_steps(obs) if "moe_tiles" in r]
    touched = sum(r["moe_touched"] for r in rows)
    return sum(r["moe_tiles"] for r in rows) / touched if touched else None
