"""The same profiler trace ``trace_reduce.reduce_trace`` reads, reduced BY
PROGRAM AND OPERATION NAME: self seconds and calls of every device
operation inside the traced window, keyed by the program (``XLA Modules``
event) it ran inside, so that a per-layer reader finds a kernel by the
name its ``pl.pallas_call(name=)`` gave it (``sw_moe_gmm.3`` and
``sw_moe_gmm.4`` are two call sites of one kernel) in the programs it means
(the decode chunk, not the admits, which run the same kernel on a prompt).
``reduce_trace`` keeps ten operations of all programs together; a kernel's
seconds are not among them as a rule.
"""

from __future__ import annotations

import bisect

from benchmark.harness.trace_reduce import (DEVICE_PLANE, SPAN_PREFIX, _events,
                                            find_trace, self_times, short_name)


def program_name(module: str) -> str:
    """``jit_serve_decode_chunk(1234...)`` -> ``jit_serve_decode_chunk``."""
    return module.split("(", 1)[0]


def reduce_by_name(trace_dir, window_span: str = "traced") -> "dict | None":
    """``{"ops": {program: {operation: [calls, self seconds]}}, "chips":
    n}``, per chip averaged; operations outside any program go under
    ``""``.  None where the trace holds no device operation."""
    from jax.profiler import ProfileData

    data = ProfileData.from_file(str(find_trace(trace_dir)))
    devices, marks = [], []
    for plane in data.planes:
        if DEVICE_PLANE.match(plane.name):
            lines = {line.name: _events(line) for line in plane.lines}
            if lines.get("XLA Ops"):
                devices.append((lines["XLA Ops"], lines.get("XLA Modules", [])))
        elif plane.name == "/host:CPU":
            marks += [(s, s + d) for line in plane.lines
                      for n, s, d in _events(line)
                      if n == SPAN_PREFIX + window_span]
    if not devices:
        return None
    lo, hi = (marks[0][0], marks[-1][1]) if marks else (0.0, float("inf"))
    out: dict = {}
    for ops, mods in devices:
        mods = sorted((s, s + d, program_name(n)) for n, s, d in mods)
        starts = [m[0] for m in mods]
        by_program: dict = {}
        for n, s, d in ops:
            if lo <= s < hi:
                i = bisect.bisect_right(starts, s) - 1
                inside = i >= 0 and s < mods[i][1]
                by_program.setdefault(mods[i][2] if inside else "", []).append(
                    (n, s, d))
        for program, events in by_program.items():
            rows = out.setdefault(program, {})
            for name, sec in self_times(events).items():
                rows.setdefault(name, [0, 0.0])[1] += sec / len(devices)
            for n, _s, _d in events:
                rows.setdefault(short_name(n), [0, 0.0])[0] += 1 / len(devices)
    return {"ops": out, "chips": len(devices)}


def kernel(by_name: "dict | None", prefix: str,
           program: str = "") -> "tuple | None":
    """(calls, seconds) of every operation named ``prefix`` or
    ``prefix.<n>`` in the programs whose name starts with ``program``;
    None where none ran."""
    hits = [v for prog, rows in ((by_name or {}).get("ops") or {}).items()
            if prog.startswith(program) for k, v in rows.items()
            if k == prefix or k.startswith(prefix + ".")]
    if not hits:
        return None
    return sum(c for c, _s in hits), sum(s for _c, s in hits)
