"""What the ``kimi-k2`` readers share: from the program's own
``step_log()`` / ``request_log()`` (harness/serve_logs.py), the means a
routed, latent-cache model's per-layer metrics are read for."""

from __future__ import annotations

import bisect

from benchmark.harness.serve_logs import _log, window_steps

CHUNK_PROGRAM = "jit_serve_decode_chunk"


def moe_means(obs) -> "dict | None":
    """Per decode step and routed layer, over the window's chunks:
    ``touched`` held experts, ``pairs`` on held experts, ``max`` pairs on
    one expert (the chunk's largest), and the ``chunks`` they rest on."""
    rows = [r for r in window_steps(obs) if "moe_assign" in r]
    if not rows:
        return None
    config = obs["config"]
    routed = config["num_hidden_layers"] - config["first_k_dense_replace"]
    per_chunk = config["serve"]["chunk"] * routed
    return {"touched": sum(r["moe_touched"] for r in rows) / len(rows),
            "pairs": sum(r["moe_assign"] for r in rows) / len(rows) / per_chunk,
            "max": sum(r["moe_max"] for r in rows) / len(rows),
            "chunks": len(rows)}


def live_rows(obs) -> "float | None":
    """Cached positions a decode step attends, all slots summed, mean over
    the window's steps.  From the server's request rows: a request in a
    slot at a chunk's start holds its prompt and ``chunk`` tokens for every
    chunk since its first token (at most its output), and grows by one a
    step inside the chunk."""
    steps = [r for r in window_steps(obs) if r["live"]]
    if not steps:
        return None
    server = steps[0]["server"]
    chunk = obs["config"]["serve"]["chunk"]
    starts = sorted(r["t0"] for r in _log("step_log")
                    if r["server"] == server and r["live"])
    reqs = [r for r in _log("request_log")
            if r.get("side") == "server" and r["server"] == server
            and r["t_first"] is not None]
    total = 0.0
    for s in steps:
        t = s["t0"] + s["admit_s"]     # the chunk is dispatched after the admits
        for r in reqs:
            if r["t_first"] <= t and (r["t_done"] is None or r["t_done"] > t):
                chunks = bisect.bisect_left(starts, s["t0"]) - bisect.bisect_left(
                    starts, r["t_first"])
                out = 1 + chunk * max(chunks, 0)
                if r["status"] == "done":
                    out = min(out, r["n_out"])
                total += r["n_prompt"] + out + (chunk - 1) / 2
    return total / len(steps)
