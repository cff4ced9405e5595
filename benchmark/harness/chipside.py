"""What the process that holds the chip does in every runner: turn the
compile cache on and name the device (no chip is a failure), profile a few
seconds of a --trace 1 window, read the peak of device memory; and how a
chip-less parent listens to such a child."""

from __future__ import annotations

import json
import subprocess
import sys
import tempfile
import time
from pathlib import Path

from benchmark.harness.spans import Spans

def log(**fields) -> None:
    """A line before the result line (medians, counts, what was compared)."""
    print(json.dumps(fields), flush=True)


def chip_start(ctx: dict) -> dict:
    """Compile cache on, the device named, no chip a failure."""
    import jax

    from benchmark.harness.peaks import peaks
    from starway_tpu.utils.chip import (device_info, enable_compile_cache,
                                        require_accelerator)

    t_imported = time.monotonic()
    cache = enable_compile_cache()
    info = require_accelerator() if ctx["chip"] else device_info()
    t_device = time.monotonic()
    if ctx["chip"]:
        peaks(info["kind"])
        if info["count"] < ctx["cell"]["chips"]:
            raise SystemExit(f"benchmark: {ctx['cell']['name']} needs "
                             f"{ctx['cell']['chips']} chips, JAX reports "
                             f"{info['count']}")
    # The TPU runtime's own start (5.7-11 s from run to run, my chip runs,
    # PR 23) is no work of the program's or the benchmark's: it is printed
    # here and left out of ``setup_s`` (see :func:`setup_seconds`).
    ctx["device_init_s"] = t_device - t_imported
    log(event="device", device=info, compile_cache=cache, jax=jax.__version__,
        imports_s=t_imported - ctx["t_start"],
        device_init_s=ctx["device_init_s"])
    return info


def setup_seconds(ctx: dict, t0: float, device_init_s: "float | None" = None) -> float:
    """``setup_s``: process start to the first timed operation at ``t0``
    (imports, weights or payloads made, every program loaded or compiled and
    warmed), less the seconds the TPU runtime took to hand over the chip."""
    init = ctx.get("device_init_s", 0.0) if device_init_s is None else device_init_s
    return t0 - ctx["t_start"] - init


class Profile:
    """The profiler over ``seconds`` of a --trace 1 run, from ``start_at``
    on.  Stopping a trace blocks its thread for far longer than the trace
    lasts (13-17 s for 3 s of a serving window, 4 s for 1.5 s: my chip runs,
    PR 23), so a window that must not be stalled traces its LAST seconds
    and is stopped by ``stop()`` after it has closed (``stop_by_tick``
    False); ``tick`` then only starts it."""

    def __init__(self, ctx: dict, spans: Spans, start_at: float,
                 seconds: float, stop_by_tick: bool = True):
        self.chip = ctx["chip"]
        self.start_at = start_at
        self.stop_at = start_at + seconds if stop_by_tick else float("inf")
        self.dir = None
        self.state = "before" if ctx["args"].trace else "done"
        self.spans = spans
        self._mark = None

    def tick(self, now: float) -> None:
        if self.state == "before" and now >= self.start_at:
            import jax

            self.dir = tempfile.mkdtemp(prefix="bench_trace_")
            opts = jax.profiler.ProfileOptions()
            opts.python_tracer_level = 0
            jax.profiler.start_trace(self.dir, profiler_options=opts)
            self._mark = self.spans.span("traced")
            self._mark.__enter__()
            self.state = "running"
        elif self.state == "running" and now >= self.stop_at:
            self.stop()

    def stop(self) -> None:
        if self.state == "running":
            import jax

            self._mark.__exit__(None, None, None)
            t_a = time.monotonic()
            jax.profiler.stop_trace()
            self.state = "done"
            log(event="profiler_stopped", stop_trace_s=time.monotonic() - t_a)

    def reduce(self) -> "dict | None":
        if self.dir is None:
            return None
        import shutil

        if not self.chip:   # a CPU rehearsal has no device plane to reduce
            shutil.rmtree(self.dir, ignore_errors=True)
            return None

        from benchmark.harness.trace_reduce import find_trace, reduce_trace

        try:
            return reduce_trace(find_trace(self.dir))
        finally:
            shutil.rmtree(self.dir, ignore_errors=True)


def memory_peak(chips: int) -> int:
    import jax

    peak = 0
    for dev in jax.devices()[:chips]:
        st = dev.memory_stats() or {}
        peak = max(peak, int(st.get("peak_bytes_in_use", 0)))
    return peak


def spawn_chip_child(ctx: dict):
    """The process that will hold the chip, started by a chip-less parent
    with the same arguments plus ``--role chip``; it talks in JSON lines on
    its standard output and listens on its standard input."""
    args = ctx["args"]
    cmd = [sys.executable, str(Path(__file__).resolve().parents[1] / "run.py"),
           "--workload", args.workload, "--seed", str(args.seed),
           "--seconds", str(args.seconds), "--trace", str(args.trace),
           "--role", "chip"]
    cmd += [x for kv in args.override for x in ("--override", kv)]
    if not ctx["chip"]:
        cmd.append("--no-chip")
    return subprocess.Popen(cmd, stdin=subprocess.PIPE, stdout=subprocess.PIPE,
                            text=True, bufsize=1)


def child_event(child, want: str) -> dict:
    """Echo the child's lines until its ``want`` event; a child that ends
    first has failed."""
    for line in child.stdout:
        line = line.rstrip("\n")
        try:
            msg = json.loads(line)
        except ValueError:
            msg = None
        if isinstance(msg, dict) and msg.get("event") == want:
            return msg
        print(line, flush=True)
    raise SystemExit(f"benchmark: the chip's process ended (exit "
                     f"{child.wait()}) before its {want!r} event")


def free_port() -> int:
    import socket

    with socket.socket() as s:
        s.bind(("127.0.0.1", 0))
        return s.getsockname()[1]
