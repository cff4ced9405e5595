"""Transfer-time estimation: the ``evaluate_perf`` analogue.

The reference exposes UCX's transport model estimate
(``ucp_ep_evaluate_perf``, reference: src/bindings/main.cpp:452-467,666-678)
as seconds-to-transfer-msg_size.  The TPU build replaces it with an explicit
alpha-beta link model per transport (SURVEY.md section 5 "Tracing /
profiling": "keep an evaluate_perf analogue backed by an ICI/DCN link
model")::

    t(bytes) = alpha + bytes / beta

Default betas reflect TPU v5e-class hardware (ICI ~45 GB/s per link
direction, DCN ~12.5 GB/s per host NIC) and measured host-loopback numbers;
calibrate with :func:`calibrate` from observed samples.

Estimates are PER-ENDPOINT when live calibration has run (the reference's
``ucp_ep_evaluate_perf`` queries the endpoint, not a transport class:
two peers with different link quality report differently):
:func:`autocalibrate` (client side) and :func:`autocalibrate_ep` (server
side, probing one accepted endpoint) attach the fitted (alpha, beta) to
the CONNECTION, and both engines' ``evaluate_perf`` prefer that over the
class table.  Probes ride the reserved PROBE_TAG both directions — the
peer's matcher consumes and drops them (core/matching.py, sw_engine.cpp).
"""

from __future__ import annotations

import contextlib
import sys
import threading
import time
import weakref

# ------------------------------------------------------ per-stage telemetry
#
# The data plane records wall time + bytes per pipeline stage so a bench
# regression is attributable to the stage that moved (DESIGN.md §12):
#
#   ``stage`` -- device-to-host staging (D2H) on the send side (device.py)
#   ``tx``    -- transport writes (socket sendmsg / sm ring) (core/conn.py)
#   ``rx``    -- transport reads (core/conn.py)
#   ``place`` -- host-to-device placement (H2D) on the receive side
#
# and where a device message WAITS (:data:`MSG_STAGES`; DESIGN.md §12 has
# the table: from -> to, thread, the metric that reads each):
#
#   ``post``        -- an ``asend`` / ``arecv`` / ``aflush`` call (api.py)
#   ``fetch_start`` -- starting a queued send's D2H copy (device.py)
#   ``issue``       -- enqueueing a handoff's chip-to-chip copy (device.py)
#   ``land``        -- that copy issued -> resident (matching.py, engine.py)
#   ``settle``      -- resident -> its completions fired (engine.py)
#   ``loop_hop``    -- a completion called off the loop -> applied on it
#   ``place_queue`` -- a received message waiting for the placer (engine.py)
#   ``ring_wait``   -- a producer blocked on a full sm ring (core/conn.py)
#
# These ride the message as plain ``perf_counter`` stamps and are recorded
# ONCE, where it settles (:func:`record_stages`: every phase under the
# message's tag).
#
# Recording is two perf_counter calls and a few list-slot ``+=`` per
# transport syscall, with NO lock (the note above :data:`_live` says why)
# -- noise next to the syscall itself.  A sample lands ONCE, in the
# recorder's :class:`StageScope` (per worker, so two concurrent clients --
# or bench loopback's two roles -- never pollute each other's
# ``evaluate_perf_detail()["stages"]``); the whole-process view bench.py
# and the bench CLI report (:func:`stage_snapshot`) adds the scopes up
# when it is read.


class StageScope:
    """Per-worker stage accumulator (same shape as the module aggregate).

    ``ring`` optionally carries a core/swtrace.py TraceRing: each recorded
    sample then also lands as an EV_STAGE span in the worker's trace, so
    a bench run's Chrome export shows the stage timeline per op stream.
    """

    __slots__ = ("_lock", "_stages", "_msg", "ring", "__weakref__")

    def __init__(self, ring=None):
        # ``record``'s samples (the layers above the engines: stage_span),
        # this scope's alone, under its lock.
        self._lock = threading.Lock()
        self._stages: dict[str, list] = {}
        # record_stage's and record_phase's samples (the engines, the
        # device plane, the API), written with no lock.  They are counted
        # HERE only: the module's view adds the scopes up when it is read
        # (_live), and keeps what a scope held when it goes (_retire).
        self._msg: dict[str, list] = {}
        self.ring = ring
        _live.add(self)
        weakref.finalize(self, _retire, self._msg).atexit = False

    def record(self, name: str, seconds: float, nbytes: int = 0) -> None:
        with self._lock:
            acc = self._stages.get(name)
            if acc is None:
                self._stages[name] = [1, seconds, nbytes]
            else:
                acc[0] += 1
                acc[1] += seconds
                acc[2] += nbytes

    def snapshot(self) -> dict:
        with self._lock:
            return _render_stages(self._stages, [self._msg])

    def reset(self) -> None:
        with self._lock:
            self._stages.clear()
            self._msg.clear()


_annotation = None  # jax.profiler.TraceAnnotation, bound at first use


_NO_NOTE = contextlib.nullcontext()  # a phase that nobody is tracing


def xfer_note(name: str):
    """``with xfer_note(name):`` -- a ``TraceAnnotation("sw:xfer.<name>")``
    while a profiler session runs, else a null context: a transport phase
    that runs on ONE thread lands in ``/host:CPU`` of the xplane on the
    clock of the device's programs, as ``sw:serve.*`` do.  jax is never
    imported for this: a process that has none (the engines' chip-less
    peers) pays a dict lookup, and with no session running the price is
    a flag check."""
    global _annotation
    note = _annotation
    if note is None:
        if "jax" not in sys.modules:
            return _NO_NOTE
        try:
            from jax.profiler import TraceAnnotation
        except Exception:  # jax is mid-import on another thread
            return _NO_NOTE
        note = _annotation = TraceAnnotation
    if not note.is_enabled():
        return _NO_NOTE
    return note("sw:xfer." + name)


class stage_span:
    """One host phase of a layer ABOVE the engines (the serve scope of
    DESIGN.md §13: ``serve.step``, ``serve.admit``, ``bridge.emit`` ...),
    entered as a context manager and recorded three ways at once:

    * ``jax.profiler.TraceAnnotation("sw:<name>")`` -- the phase lands in
      ``/host:CPU`` of the profiler's xplane, on the clock of the device's
      programs, whenever ANY profiler session runs (a flag check when none
      does);
    * ``scope.record(name, seconds)`` -- the owner's :class:`StageScope`;
    * with a ring on the scope (``swtrace.active()`` when the owner was
      built), an ``EV_STAGE`` event whose tag is ``tag`` (the request id
      for per-request phases), so ``python -m starway_tpu.trace`` draws it.

    ``t0`` / ``seconds`` stay readable after the block: the serve logs
    take their durations from the same two clock reads.  jax is imported
    at first use only -- the engines import this module and stay jax-free.
    """

    __slots__ = ("scope", "name", "tag", "t0", "seconds", "_note")

    def __init__(self, scope: StageScope, name: str, tag: int = 0):
        self.scope, self.name, self.tag = scope, name, tag
        self.t0 = self.seconds = 0.0

    def __enter__(self):
        global _annotation
        if _annotation is None:
            from jax.profiler import TraceAnnotation

            _annotation = TraceAnnotation
        self._note = _annotation("sw:" + self.name)
        self._note.__enter__()
        self.t0 = time.perf_counter()
        return self

    def __exit__(self, *exc):
        self.seconds = time.perf_counter() - self.t0
        self._note.__exit__(*exc)
        scope = self.scope
        scope.record(self.name, self.seconds)
        if scope.ring is not None:
            from .core import swtrace

            scope.ring.rec(swtrace.EV_STAGE, self.tag, 0, 0, self.name,
                           self.seconds)
        return False


def percentile(sorted_vals, q: float) -> float:
    """Nearest-rank percentile of an ascending sequence (stdlib-only) --
    the one implementation both the driver bench and the bench CLI's
    stage p-tiles report through."""
    if not sorted_vals:
        return 0.0
    idx = min(len(sorted_vals) - 1,
              max(0, round(q / 100 * (len(sorted_vals) - 1))))
    return sorted_vals[idx]


def _sum_into(total: dict, msg: dict) -> None:
    """``msg`` may be written meanwhile, with no lock: the copy of its
    items is one atomic call."""
    for name, (count, seconds, nbytes) in list(msg.items()):
        acc = total.setdefault(name, [0, 0.0, 0])
        acc[0] += count
        acc[1] += seconds
        acc[2] += nbytes


def _render_stages(stages: dict, msgs) -> dict:
    """``stages`` (its lock held) and the ``msgs`` dicts as one view."""
    total = {name: list(acc) for name, acc in stages.items()}
    for msg in msgs:
        _sum_into(total, msg)
    out = {}
    for name, (count, seconds, nbytes) in total.items():
        out[name] = {
            "count": count,
            "seconds": seconds,
            "bytes": nbytes,
            "gbps": (nbytes / seconds / 1e9) if seconds > 0 else 0.0,
        }
    return out


def record_stage(name: str, seconds: float, nbytes: int = 0,
                 scope: "StageScope | None" = None) -> None:
    """Accumulate one sample for pipeline stage ``name`` (``tx`` / ``rx``
    a transport syscall, ``stage`` / ``place`` a message) into ``scope``,
    the recording worker's :class:`StageScope` -- and so into
    :func:`stage_snapshot` -- with no lock taken: :func:`record_phase`
    with no tag, closed now.  Its body written out, because ``tx`` and
    ``rx`` run hundreds of times a message (each paid two locks until
    PR 35) and a second call doubles what a sample costs."""
    if scope is None:
        scope = _orphans
    msg = scope._msg
    acc = msg.get(name) or msg.setdefault(name, [0, 0.0, 0])
    acc[0] += 1
    acc[1] += seconds
    acc[2] += nbytes
    ring = scope.ring
    if ring is not None:
        ring.span(time.perf_counter(), 0, nbytes, name, seconds)


#: Where a device message waits: the stages recorded once a message by
#: :func:`record_stages` (the table is in DESIGN.md §12).
MSG_STAGES = ("post", "fetch_start", "issue", "land", "settle", "loop_hop",
              "place_queue", "ring_wait")

# A message settles on the threads that can least afford a lock another
# thread holds: the poster between two posts, an engine between two
# landings (chip, PR 35: the same records under a process-wide lock and
# the scope's cost `hbm_duplex.a2a_16m_x4` 0.44 ms of a 5.6 ms round;
# PERF.md section 6).  So stages are accumulated as the worker counters
# and the swpulse histograms are (core/swtrace.py): plain ``+=`` on a list
# slot of the recording worker's scope, no lock, ONE place.  A stage has
# one writing thread a worker as a rule (the loop's for ``post`` /
# ``loop_hop``, the engine's for ``tx`` / ``rx`` / ``issue`` / ``land`` /
# ``settle`` / ``ring_wait``, the placer's for ``place`` /
# ``place_queue``); the window in which two writers could lose a sample
# is theoretical and telemetry tolerates it.  The module's view is the
# sum over the scopes, taken when read.
_live: "weakref.WeakSet[StageScope]" = weakref.WeakSet()
_retired: dict[str, list] = {}  # what the scopes that are gone had recorded


def _retire(msg: dict) -> None:
    """A scope's finaliser.  It can run wherever the collector does, so
    it takes no lock either."""
    _sum_into(_retired, msg)


_orphans = StageScope()  # samples recorded with no scope


def record_phase(scope: "StageScope | None", tag: int, name: str,
                 seconds: float, nbytes: int, t_end: float) -> None:
    """ONE phase a message (or an op) waited in, ``t_end`` the
    ``perf_counter`` reading that closed it: into ``scope``, no lock
    taken (and so into :func:`stage_snapshot`, which adds the scopes up).
    With a ring on the scope it lands as an ``EV_STAGE`` whose tag is
    ``tag`` -- the message's -- and whose time is ``t_end``, not the time
    of this call."""
    if scope is None:
        scope = _orphans
    msg = scope._msg
    acc = msg.get(name) or msg.setdefault(name, [0, 0.0, 0])
    acc[0] += 1
    acc[1] += seconds
    acc[2] += nbytes
    ring = scope.ring
    if ring is not None:
        ring.span(t_end, tag, nbytes, name, seconds)


def record_stages(scope: "StageScope | None", tag: int, stages) -> None:
    """Every phase of ONE message at once, where it settles: ``stages``
    is ``[(name, seconds, nbytes, t_end), ...]`` from the stamps the
    message carried (:func:`record_phase` each).  One call a message; the
    phases share its tag, so ``python -m starway_tpu.trace`` draws them
    as one chain, each where it ran."""
    for name, seconds, nbytes, t_end in stages:
        record_phase(scope, tag, name, seconds, nbytes, t_end)


def stage_snapshot() -> dict:
    """``{stage: {"count", "seconds", "bytes", "gbps"}}`` accumulated since
    process start (or the last :func:`stage_reset`) -- the whole-process
    aggregate; per-worker views live on ``Worker.stage_scope``."""
    return _render_stages({}, [_retired] + [s._msg for s in list(_live)])


def stage_reset() -> None:
    """Drop accumulated stage samples (bench warmup boundary)."""
    _retired.clear()
    for scope in list(_live):
        scope._msg.clear()


# transport -> (alpha seconds, beta bytes/second)
LINK_MODELS: dict[str, tuple[float, float]] = {
    "inproc": (2.0e-6, 30.0e9),  # same-process memcpy / HBM-to-HBM handoff
    "sm": (25.0e-6, 5.0e9),  # same-host shared-memory rings (core/shmring.py)
    "tcp": (30.0e-6, 2.5e9),  # host loopback / DCN-adjacent bootstrap path
    "ici": (1.0e-6, 45.0e9),  # v5e ICI per-link, one direction
    "dcn": (50.0e-6, 12.5e9),  # cross-slice data-center network
}

# Where each PRIOR came from (VERDICT r4 #5: an estimate from an
# uncalibrated constant must say so).  calibrate() replaces these with a
# live-fit note; conn_estimate_detail reports per-endpoint fits.
PROVENANCE: dict[str, str] = {
    "inproc": "prior: same-process handoff, measured host-loopback class",
    "sm": "prior: shared-memory ring class, measured host-loopback",
    "tcp": "prior: loopback/DCN-adjacent TCP class estimate",
    "ici": "prior: TPU v5e ICI ~45 GB/s per link per direction (public "
           "v5e system specs; 4x ICI links/chip) — no live ICI probe has "
           "ever run in this process",
    "dcn": "prior: ~100 Gbps-class host NIC (12.5 GB/s) cross-slice "
           "estimate — no live DCN probe has ever run in this process",
}

# Transports whose class entry was replaced by a live calibrate() fit.
CALIBRATED: set[str] = set()


def _apply(model: tuple[float, float], msg_size: int) -> float:
    """t(bytes) = alpha + bytes / beta — the one place the model runs."""
    alpha, beta = model
    return alpha + max(0, int(msg_size)) / beta


def estimate(transport: str, msg_size: int) -> float:
    """Estimated seconds to transfer ``msg_size`` bytes over ``transport``.

    Always > 0, matching the reference contract (tests/test_basic.py:445-457).
    """
    return estimate_detail(transport, msg_size)["seconds"]


def conn_estimate(conn, transport: str, msg_size: int) -> float:
    """Per-endpoint estimate: a live-calibrated model attached to the
    connection (``conn.perf_model``, set by :func:`autocalibrate` /
    :func:`autocalibrate_ep`) wins over the transport-class table —
    both engines' ``evaluate_perf`` route through here.  Delegates to
    :func:`conn_estimate_detail` so the resolution policy lives once."""
    return conn_estimate_detail(conn, transport, msg_size)["seconds"]


def estimate_detail(transport: str, msg_size: int,
                    scope: "StageScope | None" = None) -> dict:
    """:func:`estimate` with honesty attached: the model, whether it came
    from a live fit, and its provenance."""
    key = transport if transport in LINK_MODELS else "tcp"
    alpha, beta = LINK_MODELS[key]
    return {
        "seconds": _apply((alpha, beta), msg_size),
        "alpha": alpha,
        "beta": beta,
        "transport": key,
        "calibrated": key in CALIBRATED,
        "source": PROVENANCE.get(key, "prior: unknown transport class"),
        # Live per-stage pipeline timings (stage/tx/rx/place -- see
        # record_stage), so a model estimate and the measured data plane
        # sit side by side.  Scoped to the querying worker when it passes
        # its StageScope; the whole-process aggregate otherwise.
        "stages": scope.snapshot() if scope is not None else stage_snapshot(),
    }


def conn_estimate_detail(conn, transport: str, msg_size: int,
                         scope: "StageScope | None" = None) -> dict:
    """:func:`conn_estimate` with honesty attached (VERDICT r4 #5): a
    caller can tell a live per-endpoint fit from a class fit from a
    spec-sheet prior — confident numbers from uncalibrated constants are
    worse than numbers that say "uncalibrated"."""
    model = getattr(conn, "perf_model", None)
    if model is not None:
        alpha, beta = model
        return {
            "seconds": _apply(model, msg_size),
            "alpha": alpha,
            "beta": beta,
            "transport": transport,
            "calibrated": True,
            "source": "live per-endpoint fit (autocalibrate/"
                      "autocalibrate_ep over PROBE_TAG)",
            "stages": (scope.snapshot() if scope is not None
                       else stage_snapshot()),
        }
    return estimate_detail(transport, msg_size, scope=scope)


async def _probe_samples(send, flush, sizes):
    """(bytes, seconds) enqueue-to-flush samples over PROBE_TAG probes."""
    import time

    import numpy as np

    from .core.matching import PROBE_TAG

    samples = []
    for size in sizes:
        buf = np.zeros(size, dtype=np.uint8)
        # warmup
        await send(buf, PROBE_TAG)
        await flush()
        t0 = time.perf_counter()
        await send(buf, PROBE_TAG)
        await flush()
        samples.append((size, time.perf_counter() - t0))
    return samples


async def autocalibrate(client, transport: str = "inproc",
                        sizes=(1 << 10, 1 << 16, 1 << 20, 1 << 24)) -> tuple[float, float]:
    """Fit the link model from live one-way probes on a connected Client.

    Measures enqueue-to-flush time per size, which tracks the transport's
    alpha/beta -- the role ucp_ep_evaluate_perf's model plays in the
    reference.  Probes ride the reserved PROBE_TAG, which both engines'
    matchers consume and drop on arrival (core/matching.py) -- probing a
    live connection cannot pollute the peer's matching state or be claimed
    by wildcard receives.

    The fit lands twice: on ``transport``'s class-table entry (the
    fallback every uncalibrated estimate uses) and on THIS client's
    connection, so ``client.evaluate_perf`` reports the endpoint's own
    measured link from then on.
    """
    samples = await _probe_samples(client.asend, client.aflush, sizes)
    model = calibrate(transport, samples)
    conn = getattr(client, "_client", client).primary_conn
    if conn is not None:
        conn.perf_model = model
    return model


async def autocalibrate_ep(server, client_ep,
                           sizes=(1 << 10, 1 << 16, 1 << 20, 1 << 24)) -> tuple[float, float]:
    """Server-side per-endpoint calibration: probe ONE accepted endpoint
    (``server.asend(ep, ...)`` + ``aflush_ep``) and attach the fitted
    (alpha, beta) to that endpoint's connection only — the class table is
    untouched, so two peers on different links report different estimates
    from their own live probes (``server.evaluate_perf(ep, n)``)."""
    samples = await _probe_samples(
        lambda buf, tag: server.asend(client_ep, buf, tag),
        lambda: server.aflush_ep(client_ep), sizes)
    model = fit_alpha_beta(samples)
    client_ep._conn.perf_model = model
    return model


def fit_alpha_beta(samples: list[tuple[float, float]]) -> tuple[float, float]:
    """Least-squares (alpha, beta) from (bytes, seconds) samples."""
    if len(samples) < 2:
        raise ValueError("need at least two (bytes, seconds) samples")
    n = len(samples)
    sx = sum(b for b, _ in samples)
    sy = sum(t for _, t in samples)
    sxx = sum(b * b for b, _ in samples)
    sxy = sum(b * t for b, t in samples)
    denom = n * sxx - sx * sx
    if denom == 0:
        raise ValueError("degenerate samples")
    inv_beta = (n * sxy - sx * sy) / denom
    alpha = (sy - inv_beta * sx) / n
    return max(alpha, 1e-9), 1.0 / max(inv_beta, 1e-15)


def calibrate(transport: str, samples: list[tuple[float, float]]) -> tuple[float, float]:
    """:func:`fit_alpha_beta`, committed to ``transport``'s class-table
    entry (the fallback for uncalibrated endpoints).  Returns the fit."""
    model = fit_alpha_beta(samples)
    LINK_MODELS[transport] = model
    CALIBRATED.add(transport)
    PROVENANCE[transport] = (
        f"live class fit from {len(samples)} probe samples")
    return model
