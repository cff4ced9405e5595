"""Reduction of a JAX profiler trace (``*.xplane.pb``) to what the result
line carries: device busy seconds in the traced window, the device
operations that took most time, and the longest idle gaps by what the host
was doing.  Kept with the benchmark so that every PR computes the same
number the same way; checked against a small trace recorded on the v5e
(benchmark/tests/test_trace_reduce.py).

What the trace of a TPU v5e holds (looked at by hand, PR 23): one plane per
chip, ``/device:TPU:<n>``, with the lines ``XLA Modules`` (one event per
program execution, named ``jit_<fn>(<fingerprint>)``) and ``XLA Ops`` (one
event per HLO operation, nested under control flow); and ``/host:CPU`` with
one line per thread, where ``jax.profiler.TraceAnnotation`` spans appear
under their own names.  Times are nanoseconds on one clock, but the
device's ran 1.3-1.5 ms behind the host's in the traces looked at (a program
"started" before its launch), so a program is matched to a host span with
``SKEW_NS`` of tolerance and gaps of a millisecond are not attributed
sharply.
"""

from __future__ import annotations

import re
from pathlib import Path

DEVICE_PLANE = re.compile(r"^/device:TPU:(\d+)$")
SPAN_PREFIX = "bench:"
SKEW_NS = 3e6


def find_trace(trace_dir) -> Path:
    found = sorted(Path(trace_dir).rglob("*.xplane.pb"))
    if not found:
        raise SystemExit(f"benchmark: the profiler wrote no trace under {trace_dir}")
    return found[-1]


def _events(line) -> list:
    return [(e.name, float(e.start_ns), float(e.duration_ns)) for e in line.events]


def short_name(hlo: str) -> str:
    """``%fusion.3 = bf16[...] fusion(...)`` -> ``fusion.3``."""
    head = hlo.split(" = ", 1)[0].strip()
    return head.lstrip("%") or hlo[:60]


def union(intervals: list) -> list:
    """Merged [start, end) intervals."""
    out = []
    for a, b in sorted(intervals):
        if out and a <= out[-1][1]:
            out[-1][1] = max(out[-1][1], b)
        else:
            out.append([a, b])
    return out


def clip(intervals: list, lo: float, hi: float) -> list:
    return [[max(a, lo), min(b, hi)] for a, b in intervals
            if min(b, hi) > max(a, lo)]


def self_times(events: list) -> dict:
    """Seconds per operation name, a parent's time less its children's
    (``while`` bodies are nested inside the ``while`` event)."""
    out: dict = {}
    stack: list = []   # [name, end, self_ns]

    def close(upto: float) -> None:
        while stack and stack[-1][1] <= upto:
            name, _end, self_ns = stack.pop()
            out[name] = out.get(name, 0.0) + self_ns / 1e9

    for name, start, dur in sorted(events, key=lambda e: (e[1], -e[2])):
        close(start)
        if stack:
            stack[-1][2] -= min(dur, stack[-1][1] - start)
        stack.append([short_name(name), start + dur, dur])
    close(float("inf"))
    return out


def reduce_trace(path, window_span: str = "traced") -> dict:
    """See the module docstring.  Returns ``busy_s`` and ``window_s``
    (averaged over the chips that ran anything), ``device_ops`` and
    ``idle_gaps`` (at most 10 [name, seconds] each), ``modules``
    ({program name: [executions, seconds]}, per chip averaged),
    ``longest_program_in`` ({host span name: [seconds of the longest
    program that started inside each instance of the span]}) and
    ``chips``.  Raises SystemExit if no operation ran on a device."""
    from jax.profiler import ProfileData

    data = ProfileData.from_file(str(path))
    devices, host_spans = {}, []
    for plane in data.planes:
        m = DEVICE_PLANE.match(plane.name)
        if m:
            lines = {line.name: _events(line) for line in plane.lines}
            devices[int(m.group(1))] = (lines.get("XLA Ops", []),
                                        lines.get("XLA Modules", []))
        elif plane.name == "/host:CPU":
            for line in plane.lines:
                for name, start, dur in _events(line):
                    if name.startswith(SPAN_PREFIX):
                        host_spans.append((name[len(SPAN_PREFIX):], start,
                                           start + dur))
    devices = {k: v for k, v in devices.items() if v[0] or v[1]}
    if not devices:
        raise SystemExit("benchmark: the trace holds no device operation "
                         "(no /device:TPU plane with XLA Ops)")
    first = min(devices)

    marks = [(a, b) for n, a, b in host_spans if n == window_span]
    if marks:
        lo, hi = marks[0][0], marks[-1][1]
    else:   # no window marked: everything the trace holds
        every = ([(s, s + d) for ops, mods in devices.values()
                  for _n, s, d in ops + mods]
                 + [(a, b) for _n, a, b in host_spans])
        lo, hi = min(a for a, _b in every), max(b for _a, b in every)

    busy, ops_total, modules, gaps_by = [], {}, {}, {}
    for ops, mods in devices.values():
        ops = [(n, s, d) for n, s, d in ops if lo <= s < hi]
        mods = [(n, s, d) for n, s, d in mods if lo <= s < hi]
        merged = clip(union([[s, s + d] for _n, s, d in ops]), lo, hi)
        busy.append(sum(b - a for a, b in merged) / 1e9)
        for name, sec in self_times(ops).items():
            ops_total[name] = ops_total.get(name, 0.0) + sec
        for name, _s, d in mods:
            row = modules.setdefault(name, [0, 0.0])
            row[0] += 1
            row[1] += d / 1e9
        edges = [lo] + [x for ab in merged for x in ab] + [hi]
        for a, b in zip(edges[0::2], edges[1::2]):
            if b > a:
                name = _covering(host_spans, a, b, window_span)
                gaps_by[name] = gaps_by.get(name, 0.0) + (b - a) / 1e9
    # Per host span instance, the longest program execution that started
    # inside it on the first chip: how a reader finds "the program this
    # call ran" without a stable program name.
    longest_in: dict = {}
    mods0 = sorted((s, d) for _n, s, d in devices[first][1])
    for name, a, b in host_spans:
        inside = [d for s, d in mods0 if a - SKEW_NS <= s < b]
        if inside and lo <= a < hi:
            longest_in.setdefault(name, []).append(max(inside) / 1e9)
    n = len(devices)
    top = lambda d: [[k, v / n] for k, v in
                     sorted(d.items(), key=lambda kv: -kv[1])[:10]]
    return {"busy_s": sum(busy) / n, "window_s": (hi - lo) / 1e9,
            "device_ops": top(ops_total), "idle_gaps": top(gaps_by),
            "modules": {k: [c / n, s / n] for k, (c, s) in modules.items()},
            "longest_program_in": longest_in, "chips": n}


def _covering(host_spans: list, a: float, b: float, skip: str) -> str:
    """What the host was doing in the gap [a, b): the shortest (innermost)
    host span that covers at least half of it; failing that, the one that
    covers most of it; ``host:unmarked`` where none overlaps."""
    half, inner, most = (b - a) / 2, None, None
    for name, s, e in host_spans:
        if name == skip:
            continue
        overlap = min(b, e) - max(a, s)
        if overlap <= 0:
            continue
        if overlap >= half and (inner is None or e - s < inner[0]):
            inner = (e - s, name)
        if most is None or overlap > most[0]:
            most = (overlap, name)
    if inner is not None:
        return inner[1]
    return most[1] if most is not None else "host:unmarked"
