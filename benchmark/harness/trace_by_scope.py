"""The same profiler trace ``trace_by_name.reduce_by_name`` reads, reduced
BY THE PROGRAM'S OWN SCOPES: self seconds of the device operations of one
program whose source scope (``jax.named_scope``) is one of the names asked
for.  An operation's name in the trace is the compiler's (``fusion.12``)
and a v5e trace's events carry nothing of where an operation came from
(looked at by hand, PR 37: an event's text is the instruction without its
metadata, its stats are times); the compiler's own text of the program
does: every instruction there carries ``metadata={op_name="jit(...)/
.../sw_mtp_draft/..."}``, the scopes it was traced under.  So the caller
hands in that text (the program compiled again for the same shapes: the
same instruction names) and an event is filed under the first scope its
instruction's ``op_name`` holds.  A fusion the compiler made across two
scopes is filed under its root's.  Where there is no text, or no operation
of the program names a scope, the result is None and the reader returns
nothing.
"""

from __future__ import annotations

import bisect
import re

from benchmark.harness.trace_by_name import program_name
from benchmark.harness.trace_reduce import (DEVICE_PLANE, SPAN_PREFIX,
                                            find_trace, self_times, short_name)

_INSTRUCTION = re.compile(r'^\s*(?:ROOT )?%?([\w.\-]+) = .*op_name="([^"]*)"')


def scopes_by_instruction(hlo_text: str, scopes) -> dict:
    """``{instruction name: scope}`` for the instructions of a compiled
    program's text whose ``op_name`` holds one of ``scopes`` (the first it
    holds, reading from the left)."""
    out = {}
    for line in hlo_text.splitlines():
        m = _INSTRUCTION.match(line)
        if m:
            found = [(m.group(2).find(s), s) for s in scopes if s in m.group(2)]
            if found:
                out[m.group(1)] = min(found)[1]
    return out


def reduce_by_scope(trace_dir, scopes, program: str, hlo_text,
                    window_span: str = "traced") -> "dict | None":
    """``{scope: self seconds, "": the program's other operations}`` inside
    the traced window, per chip averaged, of the operations that ran
    inside executions of ``program``, whose compiled text is ``hlo_text``.
    None where nothing is found."""
    by_name = scopes_by_instruction(hlo_text or "", scopes)
    if not by_name:
        return None
    try:
        from jax.profiler import ProfileData

        data = ProfileData.from_file(str(find_trace(trace_dir)))
    except (Exception, SystemExit):
        return None
    devices, marks = [], []
    for plane in data.planes:
        if DEVICE_PLANE.match(plane.name):
            lines = {line.name: line for line in plane.lines}
            if "XLA Ops" in lines and "XLA Modules" in lines:
                devices.append((lines["XLA Ops"], lines["XLA Modules"]))
        elif plane.name == "/host:CPU":
            marks += [(float(e.start_ns), float(e.start_ns + e.duration_ns))
                      for line in plane.lines for e in line.events
                      if e.name == SPAN_PREFIX + window_span]
    if not devices:
        return None
    lo, hi = (marks[0][0], marks[-1][1]) if marks else (0.0, float("inf"))
    out = {s: 0.0 for s in (*scopes, "")}
    for ops, mods in devices:
        runs = sorted((float(e.start_ns), float(e.start_ns + e.duration_ns))
                      for e in mods.events
                      if program_name(e.name).startswith(program))
        starts = [a for a, _b in runs]
        events = []
        for e in ops.events:
            s, d = float(e.start_ns), float(e.duration_ns)
            i = bisect.bisect_right(starts, s) - 1
            if lo <= s < hi and i >= 0 and s < runs[i][1]:
                events.append((by_name.get(short_name(e.name), ""), s, d))
        # Self times, filed by scope: a ``while`` event holds its body's.
        for scope, seconds in self_times(events).items():
            out[scope] += seconds / len(devices)
    return out if any(out[s] for s in scopes) else None
