"""Spans recorded from the benchmark's own files, around the calls into
each layer (spans inside the program are a later change).  Kept in memory;
on the host's monotonic clock, and, while the profiler runs, also written
into the profiler's trace (``bench:<name>``) so that a device idle gap can
be attributed to what the host was doing."""

from __future__ import annotations

import contextlib
import time


class Spans:
    def __init__(self, annotate: bool = False):
        self.rows: list = []      # (name, start_s, end_s), time.monotonic
        self.counts: dict = {}
        self._annotate = None
        if annotate:
            import jax

            self._annotate = jax.profiler.TraceAnnotation

    @contextlib.contextmanager
    def span(self, name: str):
        note = self._annotate(f"bench:{name}") if self._annotate else None
        if note is not None:
            note.__enter__()
        t0 = time.monotonic()
        try:
            yield
        finally:
            self.rows.append((name, t0, time.monotonic()))
            if note is not None:
                note.__exit__(None, None, None)

    def count(self, name: str, n: float = 1) -> None:
        self.counts[name] = self.counts.get(name, 0) + n

    def total(self, name: str, t0: float = float("-inf"),
              t1: float = float("inf")) -> tuple:
        """(seconds, samples) of ``name`` spans that START in [t0, t1)."""
        rows = [(b - a) for n, a, b in self.rows if n == name and t0 <= a < t1]
        return sum(rows), len(rows)

    def wrap(self, obj, attr: str, name: str):
        """Replace ``obj.attr`` on the instance by a version inside a span.
        Fails loudly if the attribute is gone: the reader built on it
        would otherwise report nothing for ever."""
        fn = getattr(obj, attr, None)
        if not callable(fn):
            raise SystemExit(
                f"benchmark: {type(obj).__name__}.{attr} is gone; the "
                f"span {name!r} wrapped it (benchmark/harness/spans.py)")

        def wrapped(*args, **kwargs):
            with self.span(name):
                return fn(*args, **kwargs)

        setattr(obj, attr, wrapped)
        return fn
