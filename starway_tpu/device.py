"""Device data plane: jax.Array payloads over the fabric.

This is the TPU-native replacement for the reference's zero-copy RDMA into
preallocated NumPy buffers (reference: src/bindings/main.hpp:155-161 captures
raw host pointers; BASELINE.json north star: "asend/arecv/aflush async
primitives operate on jax.Array device buffers in HBM").

Three transfer paths, chosen per connection:

* **in-process, device payload -> device sink**: the sender hands the
  ``jax.Array`` itself to the receiver's matcher; the receiver ISSUES the
  copy onto its target device (PJRT, no wait) -- on TPU hardware with both
  devices in the same process an HBM-to-HBM copy over ICI with zero host
  staging -- and completes receive, send and flush when it is resident,
  waited for beside the engine.  (Same-device delivery is a reference
  handoff.)
* **in-process, mixed host/device**: one host copy at the boundary
  (``np.asarray`` of the payload, or ``device_put`` of the staged bytes).
* **cross-process (TCP / DCN bootstrap path)**: payload bytes are staged to
  host, streamed, and re-materialised on the receiver's device.  Real
  cross-host device DMA (jax.transfer-style) can slot in behind the same
  sink protocol when available.

The tag matcher stays byte-oriented; device awareness enters through two
small duck-typed protocols (no jax import in the core):

* :class:`DevicePayload` -- wraps an array for sending (``nbytes``,
  ``as_host_view()``, ``.array``).
* :class:`DeviceRecvSink` -- wraps a :class:`DeviceBuffer` for receiving
  (``nbytes``, ``host_staging()``, ``place()`` / ``deliver()``,
  ``accept_device()`` / ``land()`` / ``deliver_device()``).

A staged payload crosses the host WHOLE: one device-to-host copy and one
placement a message, whatever its size.  The overlap comes from the queue
of messages, not from pieces of one (DESIGN.md §12): queued sends' copies
are started ahead of the TX pump (:class:`_PrefetchWindow`), and a
receive's placement runs beside the engine thread (:class:`Beside`).
"""

from __future__ import annotations

import logging
import queue
import sys
import threading
import time
from collections import deque
from typing import Optional

from . import perf

logger = logging.getLogger("starway_tpu")


def _np_dtype(dtype):
    """Normalise numpy / jax.numpy scalar types / strings to np.dtype
    (ml_dtypes like bfloat16 included -- by NAME too, which np.dtype
    alone rejects; reshard/api.py round-trips dtypes as strings)."""
    import numpy as np

    d = getattr(dtype, "dtype", None)
    if isinstance(d, np.dtype):
        return d
    try:
        return np.dtype(dtype)
    except TypeError:
        import ml_dtypes

        return np.dtype(getattr(ml_dtypes, str(dtype)))


# ------------------------------------------------------- PJRT entry points
#
# Two private PJRT entry points carry the device plane, skipping
# jax.device_put's per-call Python dispatch:
#
# * device -> device: xla_client.batched_copy_array_to_devices_with_sharding;
#   the per-target plumbing (sharding, device list) is resolved once per
#   sink and cached.
# * host -> device: the client's buffer_from_pyval, exactly ONE copy
#   (force_copy=True: the result never aliases the source).
#
# jax/jaxlib 0.9.0 is the one supported installation.  There is no
# jax.device_put fallback behind these: it would be a second data path
# (another dispatch cost, and zero-copy aliasing of host memory on CPU
# targets) that no test and no chip run covers.  Anything else installed
# fails here with one clear error.

_pjrt = None


def pjrt_entry_points():
    """``(copy_fn, copy_semantics, h2d_semantics)``, resolved once."""
    global _pjrt
    if _pjrt is None:
        try:
            from jax._src.lib import xla_client as xc

            _pjrt = (xc.batched_copy_array_to_devices_with_sharding,
                     xc.ArrayCopySemantics.ALWAYS_COPY,
                     xc.HostBufferSemantics.IMMUTABLE_ONLY_DURING_CALL)
        except (ImportError, AttributeError) as e:
            raise RuntimeError(
                "starway-tpu's device plane needs jaxlib 0.9.0's PJRT entry "
                "points (batched_copy_array_to_devices_with_sharding, "
                "ArrayCopySemantics, HostBufferSemantics); the installed "
                f"jaxlib does not offer them: {e}") from e
    return _pjrt


def _copy_to_device(array, device, plan_cache):
    """Copy ``array`` onto ``device``; ``plan_cache`` is a one-slot list the
    caller owns (per-sink), holding the resolved (device_list, sharding)."""
    copy_fn, sem, _ = pjrt_entry_points()
    plan = plan_cache[0]
    if plan is None:
        from jax.sharding import SingleDeviceSharding

        sharding = SingleDeviceSharding(device)
        plan = plan_cache[0] = (sharding._internal_device_list, sharding)
    dev_list, sharding = plan
    return copy_fn([array], [dev_list], [sharding], [sem])[0]


def _fast_h2d(np_arr, device):
    """One-copy H2D of ``np_arr`` onto ``device`` via PJRT, or None when
    ``device`` is None: with no target device the caller's jax.device_put
    is what honours jax's default-device context.

    The runtime must have finished reading the source buffer when this
    returns: the caller recycles a pooled staging buffer, and a sender
    overwrites its payload, the moment it does.  force_copy=True plus
    IMMUTABLE_ONLY_DURING_CALL promise that, and on a TPU v5 lite they
    keep it (PR 21 chip run: the source overwritten right after the call,
    1 MiB and 256 MiB intact; chip_smoke.py phases a and b repeat the
    check on every run by sending back to back from one reused buffer).
    The hazard is real: ``jax.device_put`` of 256 MiB returned in under a
    millisecond there and delivered the OVERWRITTEN bytes."""
    if device is None:
        return None
    return device.client.buffer_from_pyval(
        np_arr, device, force_copy=True,
        host_buffer_semantics=pjrt_entry_points()[2])


# ------------------------------------------------------- staging buffer pool
#
# Host staging buffers for streamed (TCP/sm) device payloads are reused
# across transfers instead of np.empty'd per transfer: first-touch page
# faults on a fresh multi-MiB buffer cost more than the memcpy it serves.
# Exact-size buckets (transfer sizes repeat in steady-state workloads),
# bounded total bytes.  A buffer is recycled ONLY when placement provably
# copied out of it (_fast_h2d force_copy); a sink with no target device
# places through jax.device_put, which may zero-copy-alias host memory on
# CPU targets, and an aliased buffer must never be handed to the next
# transfer.


class _StagingPool:
    def __init__(self, cap_bytes: int = 64 << 20):
        self._lock = threading.Lock()
        self._buckets: dict[int, list] = {}
        self._held = 0
        self._cap = cap_bytes
        self.hits = 0
        self.misses = 0

    def get(self, nbytes: int):
        import numpy as np

        from .core import swtrace

        with self._lock:
            bucket = self._buckets.get(nbytes)
            if bucket:
                self._held -= nbytes
                self.hits += 1
                swtrace.GLOBAL.staging_hits += 1
                return bucket.pop()
            self.misses += 1
            swtrace.GLOBAL.staging_misses += 1
        return np.empty(nbytes, dtype=np.uint8)

    def put(self, arr) -> None:
        n = int(arr.nbytes)
        with self._lock:
            if self._held + n > self._cap:
                return  # dropped: the pool stays bounded
            self._buckets.setdefault(n, []).append(arr)
            self._held += n


_staging_pool = _StagingPool()


# ---------------------------------------------------------- prefetch window
#
# The device-to-host copy of a queued send is STARTED (copy_to_host_async,
# no wait) when the send is posted, so the copies of the messages queued
# behind the one on the transport run while that one drains: with any queue
# the next message's transfer is the overlap, and it costs one call a
# message.  Bounded in BYTES like the staging pool: a thousand queued sends
# pin at most ``cap_bytes`` of host copies; the rest wait unstarted, in post
# order, and start as earlier sends settle.  One message is always
# admitted, whatever its size (it would otherwise never start).

_PF_NONE, _PF_WAITING, _PF_STARTED, _PF_SETTLED = range(4)  # a payload's _pf


class _PrefetchWindow:
    def __init__(self, cap_bytes: int = 64 << 20):
        self._lock = threading.Lock()
        self._cap = cap_bytes
        self._held = 0      # bytes of copies started and not yet settled
        self._depth = 0     # how many copies those are
        self._waiting: deque = deque()
        self.peak_bytes = 0
        self.peak_depth = 0

    def _admit(self, payload) -> bool:
        """Lock held.  Account ``payload`` as started if it fits."""
        from .core import swtrace

        n = payload.nbytes
        if self._held and self._held + n > self._cap:
            return False
        payload._pf = _PF_STARTED
        self._held += n
        self._depth += 1
        self.peak_bytes = max(self.peak_bytes, self._held)
        self.peak_depth = max(self.peak_depth, self._depth)
        swtrace.GLOBAL.prefetch_started += 1
        return True

    def post(self, payload) -> None:
        """A send was queued: start its copy now, or when there is room."""
        with self._lock:
            if self._waiting or not self._admit(payload):
                payload._pf = _PF_WAITING
                self._waiting.append(payload)
                return
        payload.start_fetch()

    def settle(self, payload) -> None:
        """The send completed or failed: its bytes leave the window and the
        sends waiting behind it start, as far as they fit."""
        start = []
        with self._lock:
            if payload._pf == _PF_STARTED:
                self._held -= payload.nbytes
                self._depth -= 1
            payload._pf = _PF_SETTLED
            waiting = self._waiting
            while waiting:
                nxt = waiting[0]
                if nxt._pf == _PF_WAITING:
                    if not self._admit(nxt):
                        break
                    start.append(nxt)
                waiting.popleft()  # started now, or settled while waiting
        for p in start:
            p.start_fetch()


_prefetch = _PrefetchWindow()


class Beside:
    """One lazily started daemon thread that runs thunks in order BESIDE an
    engine thread, for what must never hold that thread: a wait on the
    device (a receive's placement, a pull's completion).  Whoever owns it
    closes it; thunks still queued then are run first."""

    def __init__(self, name: str):
        self._name = name
        self._lock = threading.Lock()
        self._q: "queue.Queue" = queue.Queue()
        self._thread = None

    def submit(self, thunk) -> None:
        with self._lock:
            if self._thread is None:
                self._thread = threading.Thread(
                    target=self._run, name=self._name, daemon=True)
                self._thread.start()
        self._q.put(thunk)

    def _run(self) -> None:
        while True:
            thunk = self._q.get()
            if thunk is None:
                return
            try:
                thunk()
            except Exception:
                logger.exception("%s: thunk failed", self._name)

    def close(self) -> None:
        self._q.put(None)


_jax_array_type = None


def is_device_payload(buffer) -> bool:
    global _jax_array_type
    if isinstance(buffer, DeviceBuffer):
        return True
    if _jax_array_type is None:
        jax = sys.modules.get("jax")
        if jax is None:
            return False
        try:
            _jax_array_type = jax.Array
        except Exception:
            return False
    return isinstance(buffer, _jax_array_type)


class DeviceBuffer:
    """Mutable holder for a receive target living in device memory.

    jax.Arrays are immutable, so "receive into a preallocated device buffer"
    means: the framework materialises the received payload as a jax.Array on
    ``device`` and swaps it into ``.array``.  The previous array (if any) is
    dropped, letting XLA reuse its HBM.

    >>> sink = DeviceBuffer((1024,), jnp.bfloat16, device=jax.devices()[1])
    >>> tag, length = await server.arecv(sink, tag=7, tag_mask=MASK)
    >>> sink.array  # received payload, resident on devices()[1]
    """

    def __init__(self, shape, dtype, device=None, array=None):
        self.shape = tuple(int(s) for s in shape)
        self.dtype = _np_dtype(dtype)
        self.device = device
        self.array = array
        self._plan = [None]  # resolved copy plan, see _copy_to_device
        # How the last receive landed: "device" (array handoff -- inproc or
        # PJRT pull) or "staged" (bytes streamed through host staging).
        self.last_transport = None

    @classmethod
    def like(cls, array, device=None) -> "DeviceBuffer":
        """A sink shaped like ``array``, targeting ``device`` (default: the
        device ``array`` lives on)."""
        dev = device
        if dev is None:
            devs = getattr(array, "devices", None)
            if callable(devs):
                ds = devs()
                dev = next(iter(ds)) if ds else None
        return cls(array.shape, array.dtype, device=dev)

    @property
    def nbytes(self) -> int:
        n = 1
        for s in self.shape:
            n *= s
        return n * self.dtype.itemsize


class DevicePayload:
    """Send-side wrapper: a jax.Array and the host view it is staged into.

    ONE full-payload device-to-host copy a message: ``start_fetch()`` starts
    it without waiting (the prefetch window calls it for queued sends) and
    ``as_host_view()`` waits for it, on whoever needs the bytes first: the
    engine's TX pump when the message's first payload byte is due (eager
    sends on the Python engine hand the payload itself to ``TxData``), or
    the poster for the sends that need a flat view up front (send_device).
    The duck protocol core/ sees is ``nbytes`` + ``as_host_view()``."""

    __slots__ = ("array", "nbytes", "scope", "tag", "_host_view", "_pf",
                 "_fetch")

    def __init__(self, array):
        self.array = array
        self.nbytes = int(array.nbytes)
        self.scope = None  # owning worker's perf.StageScope (send_device)
        self.tag = 0       # the message's tag, for its stages (send_device)
        self._host_view: Optional[memoryview] = None
        self._pf = _PF_NONE  # _PrefetchWindow's state for this send
        # Stamps of start_fetch, (t0, t1): the ``fetch_start`` stage,
        # recorded with ``stage`` when the view is taken (DESIGN.md §12).
        self._fetch = None

    def start_fetch(self) -> None:
        with perf.xfer_note("fetch_start"):
            t0 = time.perf_counter()
            try:
                self.array.copy_to_host_async()
            except Exception:
                # Best-effort (a deleted or donated array raises here):
                # the np.asarray of as_host_view still blocks, or raises
                # where the TX pump can fail the send.
                logger.debug("copy_to_host_async refused", exc_info=True)
            self._fetch = (t0, time.perf_counter())

    def as_host_view(self) -> memoryview:
        if self._host_view is None:
            import numpy as np

            with perf.xfer_note("stage"):
                t0 = time.perf_counter()
                host = np.ascontiguousarray(np.asarray(self.array))
                # view(uint8) first: extension dtypes (ml_dtypes bfloat16
                # et al) have no buffer-protocol format char, so
                # memoryview() on the raw array raises for exactly the
                # payloads TPU work ships most.
                self._host_view = memoryview(host.view(np.uint8)).cast("B")
                t1 = time.perf_counter()
            fetch = self._fetch
            if fetch is None:
                perf.record_stage("stage", t1 - t0, self.nbytes, self.scope)
            else:
                f0, f1 = fetch
                perf.record_stages(self.scope, self.tag, (
                    ("fetch_start", f1 - f0, self.nbytes, f1),
                    ("stage", t1 - t0, self.nbytes, t1)))
        return self._host_view


class DeviceRecvSink:
    """Receive-side adapter bridging the byte matcher to a DeviceBuffer.

    Streamed (TCP/sm) payloads land in a pooled host staging buffer; when
    the last byte has arrived ONE placement puts them on the device
    (:meth:`place`, which blocks until they are resident -- the Python
    engine runs it beside its thread, DESIGN.md §12) and :meth:`deliver`
    swaps the array into the DeviceBuffer."""

    __slots__ = ("devbuf", "scope", "queued", "issue_s", "_staging",
                 "_staging_view")

    def __init__(self, devbuf: DeviceBuffer):
        self.devbuf = devbuf
        self.scope = None  # owning worker's perf.StageScope (post_device_recv)
        # Stamps the message's stages are recorded from (DESIGN.md §12):
        # ``queued``, (tag, seconds) of the wait for the placer thread,
        # set by the engine when that thread takes the message and
        # recorded with ``place``; ``issue_s``, the seconds accept_device
        # spent enqueueing a copy onto another chip, recorded by the
        # engine when the handoff settles.
        self.queued = None
        self.issue_s = 0.0
        self._staging = None
        self._staging_view: Optional[memoryview] = None

    @property
    def nbytes(self) -> int:
        return self.devbuf.nbytes

    def host_staging(self) -> memoryview:
        """Host bounce buffer for streamed (TCP) payloads (pooled)."""
        if self._staging_view is None:
            self._staging = _staging_pool.get(self.nbytes)
            self._staging_view = memoryview(self._staging).cast("B")
        return self._staging_view

    def place(self, length: int):
        """Staged bytes fully arrived: ONE host-to-device copy of them.
        Returns the array once it is RESIDENT; only then does the staging
        buffer go back to the pool, and may the caller complete the receive
        (the PR 21 hazard, _fast_h2d)."""
        staging, self._staging, self._staging_view = self._staging, None, None
        placed, copied = self._put(staging[:length], length)
        if copied:
            _staging_pool.put(staging)
        return placed

    def deliver(self, array) -> None:
        self.devbuf.array = array
        self.devbuf.last_transport = "staged"

    def finalize_from_host(self, length: int) -> None:
        """place + deliver in the caller's thread (the native engine's
        completion callback; a spill copied into the staging buffer)."""
        self.deliver(self.place(length))

    def accept_host(self, view, length: int) -> None:
        """Complete host bytes already in hand (in-process delivery, or an
        owned unexpected-queue spill): place straight from the source view,
        eliding the staging memcpy, where that is safe.

        With a target device, _fast_h2d (PJRT buffer_from_pyval with
        force_copy=True) performs exactly one copy and never aliases the
        source, so it is safe on every target.  A sink with NO target
        device places through jax.device_put, which is not safe on CPU
        targets: jax zero-copies aligned host numpy buffers onto the CPU
        device, which would alias the SENDER's buffer — and send
        completion explicitly licenses the sender to reuse it (pinned by
        tests/test_device.py::test_host_to_device_inline_snapshots, which
        fails loudly if a jax release changes either behavior).
        Accelerator targets always copy host->HBM, so the elision stands
        there."""
        import numpy as np
        import jax

        raw = np.frombuffer(view, dtype=np.uint8, count=length)
        if (self.devbuf.device is None
                and jax.local_devices()[0].platform == "cpu"):
            raw = raw.copy()  # private snapshot; aliasing it is then fine
        self.deliver(self._put(raw, length)[0])

    def _put(self, raw, length: int) -> tuple:
        """``(array, copied)``: ONE host-to-device copy of ``raw`` viewed as
        the sink's dtype (and shape, when the payload fills the buffer
        exactly), blocked on until the bytes are resident -- what receive
        completion means.  The seconds blocked are the ``place`` stage.
        ``copied`` is False where the array may alias ``raw``: a sink with no
        target device places through jax.device_put (jax's default device),
        which zero-copies host memory on CPU targets."""
        import jax

        arr = raw.view(self.devbuf.dtype)
        if length == self.nbytes:
            arr = arr.reshape(self.devbuf.shape)
        with perf.xfer_note("place"):
            t0 = time.perf_counter()
            placed = _fast_h2d(arr, self.devbuf.device)
            copied = placed is not None
            if not copied:
                placed = jax.device_put(arr)
            placed.block_until_ready()
            t1 = time.perf_counter()
        queued, self.queued = self.queued, None
        if queued is None:
            perf.record_stage("place", t1 - t0, length, self.scope)
        else:
            tag, waited = queued
            perf.record_stages(self.scope, tag, (
                ("place_queue", waited, 0, t0),
                ("place", t1 - t0, length, t1)))
        return placed, copied

    def accept_device(self, array):
        """Direct device handoff (in-process path).  Source and target
        device match, or the sink names no device: a reference handoff,
        complete on return (None).  They differ: the HBM -> HBM copy over
        ICI is ISSUED, an enqueue, and returned IN FLIGHT.  Nothing waits
        here -- the caller may hold a worker lock, and a round of handoffs
        must all be in flight at once (DESIGN.md §12).  Completion still
        means "data resident on target", the reference's recv-complete
        semantics: whoever took the copy waits for it (:meth:`land`) beside
        its engine and only then swaps it in (:meth:`deliver_device`)."""
        target = self.devbuf.device
        if target is not None:
            src_devs = array.devices() if hasattr(array, "devices") else set()
            if src_devs != {target}:
                with perf.xfer_note("issue"):
                    t0 = time.perf_counter()
                    copy = _copy_to_device(array, target, self.devbuf._plan)
                    self.issue_s = time.perf_counter() - t0
                return copy
        self.deliver_device(array)
        return None

    @staticmethod
    def land(copy) -> None:
        """Block until ``copy`` (from :meth:`accept_device`) is resident."""
        # (the placer's part of the ``land`` stage)
        with perf.xfer_note("land"):
            copy.block_until_ready()

    def deliver_device(self, array) -> None:
        self.devbuf.array = array
        self.devbuf.last_transport = "device"


# ------------------------------------------------------ cross-process pull
#
# The reference's whole value is zero-copy RDMA directly into the receiver's
# buffer (reference: src/bindings/main.cpp:370,1172).  The TPU equivalent
# for device payloads crossing processes is the PJRT transfer server
# (jax.experimental.transfer, the DCN cross-slice transfer machinery):
# the sender registers the array for pull, a tiny descriptor rides the
# framed stream for tag matching, and the receiver pulls the buffer
# device-to-device over the PJRT data socket -- pinned staging and
# streaming overlap live inside PJRT, not in Python, and the framework
# never materialises the payload on the host.  Negotiated per connection
# ("devpull" in HELLO/HELLO_ACK); peers without it (no jax in the process)
# get staged DATA frames.


def devpull_supported() -> bool:
    """Capability probe (no server started): jax live, its backend up, and
    a platform the PJRT transfer server has actually served pulls on --
    the CPU backend (tests/test_devpull.py) and the ``tpu`` platform
    proper (chip_smoke.py phase b, TPU v5 lite, jaxlib 0.9.0).

    MUST NOT initialise a backend: this runs during the TCP handshake, and
    backend bring-up can block for seconds.  A process whose jax backend is
    not up yet simply negotiates no devpull -- device payloads are staged
    for that connection, which is always correct."""
    import sys

    from . import config

    if not config.devpull_enabled():
        return False
    jax = sys.modules.get("jax")
    if jax is None:
        return False
    from jax._src import xla_bridge

    # _default_backend is assigned only when backend bring-up has fully
    # completed (checking the _backends dict instead would race: it is
    # populated entry-by-entry while another thread still holds the init
    # lock, and the default_backend() call below would then block on that
    # lock -- the handshake hang this guard exists to prevent).
    if xla_bridge._default_backend is None:
        return False
    return jax.default_backend() in ("cpu", "tpu")


class TransferManager:
    """Per-worker PJRT transfer server wrapper.

    Owned by a Worker; dropped at worker close so unpulled sends die with
    the worker (the close-cancels-in-flight contract).  Server creation and
    peer connections are lazy; completion waits run on one daemon thread
    (:class:`Beside`) so the engine loop never blocks on a transfer.
    """

    def __init__(self, host: str):
        import itertools

        self._host = host
        self._server = None
        self._failed = False
        self._conns: dict = {}  # address -> TransferConnection
        self._uuid = itertools.count(1)
        self._lock = threading.Lock()
        self._beside = Beside("starway-devpull")
        self._closed = False

    # ------------------------------------------------------------- server
    def _ensure_server(self):
        with self._lock:
            if self._server is None and not self._failed and not self._closed:
                try:
                    import jax
                    from jax.experimental import transfer

                    # local_devices, not devices: under jax.distributed the
                    # global list leads with process 0's devices, which are
                    # non-addressable from other members.
                    client = jax.local_devices()[0].client
                    # Explicit transport addresses: without them the
                    # same-host "local bulk transport" path aborts (probed
                    # on this jax version).
                    self._server = transfer.start_transfer_server(
                        client, f"{self._host}:0", [f"{self._host}:0"])
                except Exception:
                    logger.warning("PJRT transfer server unavailable; "
                                   "device payloads fall back to host "
                                   "staging", exc_info=True)
                    self._failed = True
            return self._server

    # -------------------------------------------------------------- sender
    def offer(self, array):
        """Register ``array`` for remote pull; returns the descriptor dict
        (or None when the server cannot start -- caller falls back)."""
        srv = self._ensure_server()
        if srv is None:
            return None
        uid = next(self._uuid)
        srv.await_pull(uid, [array])
        return {
            "u": uid,
            "a": srv.address(),
            "n": int(array.nbytes),
            "s": list(array.shape),
            "d": str(array.dtype),
        }

    # ------------------------------------------------------------ receiver
    def pull(self, desc: dict, device, on_done, on_fail) -> None:
        """Pull ``desc`` onto ``device`` (None = default), asynchronously.

        Everything that can block (server start, peer connect, the transfer
        itself) runs on the manager's completion thread -- the caller is
        typically the engine thread and must never stall.  Exactly one of
        the callbacks fires, on that thread.
        """
        self._beside.submit(
            lambda: self._do_pull(desc, device, on_done, on_fail))

    def _do_pull(self, desc: dict, device, on_done, on_fail):
        try:
            srv = self._ensure_server()
            if srv is None:
                on_fail("transfer server unavailable")
                return
            import jax
            import numpy as np
            from jax.sharding import SingleDeviceSharding

            with self._lock:
                conn = self._conns.get(desc["a"])
            if conn is None:
                conn = srv.connect(desc["a"])
                with self._lock:
                    conn = self._conns.setdefault(desc["a"], conn)
            # Default to a LOCAL device: under jax.distributed, devices()[0]
            # is global device 0 -- non-addressable on every other member,
            # and a pull spec'd onto it yields an array whose value this
            # process cannot even read.
            dev = device if device is not None else jax.local_devices()[0]
            try:
                dt = np.dtype(desc["d"])
            except TypeError:
                import ml_dtypes  # bfloat16 etc. are extension dtypes

                dt = np.dtype(getattr(ml_dtypes, desc["d"]))
            spec = jax.ShapeDtypeStruct(
                tuple(desc["s"]), dt, sharding=SingleDeviceSharding(dev))
            (arr,) = conn.pull(int(desc["u"]), [spec])
            arr.block_until_ready()
        except Exception as exc:
            on_fail(str(exc))
            return
        on_done(arr)

    def close(self) -> None:
        """Drop the server: unpulled offers die (close-cancel contract)."""
        with self._lock:
            self._closed = True
            self._server = None
            self._conns.clear()
        self._beside.close()


class PulledPayload:
    """Duck-typed payload for a pulled array (matcher contract)."""

    __slots__ = ("array", "nbytes", "_host_view")

    def __init__(self, array):
        self.array = array
        self.nbytes = int(array.nbytes)
        self._host_view = None

    def as_host_view(self) -> memoryview:
        if self._host_view is None:
            import numpy as np

            host = np.ascontiguousarray(np.asarray(self.array))
            self._host_view = memoryview(host.view(np.uint8)).cast("B")
        return self._host_view


class RemoteMsg:
    """Receiver-side handle for one DEVPULL descriptor.

    Owned by the conn that received it (flush accounting) and referenced by
    the matcher's InboundMsg (``msg.remote``).  ``start(msg)`` is invoked by
    matcher fire thunks -- after the worker lock is released -- once the
    message is claimed by a receive (or force-started by a FLUSH barrier).
    """

    __slots__ = ("desc", "conn", "manager", "started")

    def __init__(self, desc: dict, conn, manager: TransferManager):
        self.desc = desc
        self.conn = conn
        self.manager = manager
        self.started = False

    @property
    def nbytes(self) -> int:
        return int(self.desc["n"])

    def start(self, msg) -> None:
        worker = self.conn.worker
        # Start thunks can be queued from two paths concurrently (a
        # post_recv claim and a FLUSH force-start): the check-and-set must
        # be atomic or the uuid gets pulled twice.
        with worker.lock:
            if self.started:
                return
            self.started = True
            pr = msg.posted
        device = None
        if pr is not None and not isinstance(pr.buf, memoryview):
            device = pr.buf.devbuf.device if isinstance(pr.buf, DeviceRecvSink) else None
        self.manager.pull(
            self.desc, device,
            lambda arr, m=msg: worker._on_pull_done(m, PulledPayload(arr), None),
            lambda err, m=msg: worker._on_pull_done(m, None, err),
        )


def send_device(worker, conn, buffer, tag, done, fail):
    """Route a device payload: direct array handoff in-process, PJRT pull
    when the peer negotiated it, host staging otherwise."""
    from . import config

    if isinstance(buffer, DeviceBuffer):
        if buffer.array is None:
            raise ValueError("DeviceBuffer has no array to send")
        payload = DevicePayload(buffer.array)
    else:
        payload = DevicePayload(buffer)
    payload.scope = getattr(worker, "stage_scope", None)
    payload.tag = tag
    if conn is not None and conn.kind == "inproc":
        worker.submit_send(conn, payload, tag, done, fail, payload)
        return
    if (conn is not None and getattr(conn, "devpull_ok", False)
            and payload.nbytes >= config.devpull_threshold()):
        mgr = worker.transfer_manager()
        desc = mgr.offer(payload.array) if mgr is not None else None
        if desc is not None:
            worker.submit_devpull(conn, desc, tag, done, fail, payload)
            return
    # Who needs the whole payload as a flat host view at POST time:
    # * a session conn's replay journal must OWN every eager frame's bytes
    #   past local completion (core/conn.py sess_wrap snapshots flat views);
    # * §19 integrity conns checksum at framing time (the CRC folds once
    #   over the full view; DESIGN.md §19);
    # * multi-rail striping (DESIGN.md §17) slices chunks at random offsets;
    # * a rendezvous send completes at header-on-wire, and that completion
    #   licenses the caller to delete or donate the array, so nothing may
    #   still read it afterwards;
    # * the native engine, whose ABI takes a raw pointer + length.
    # They take the snapshot in the poster's thread, as they always have.
    flat = (config.session_enabled() or config.integrity_enabled()
            if conn is None else
            getattr(conn, "sess", None) is not None
            or bool(getattr(conn, "csum_ok", False)))
    stripe_thr = config.stripe_threshold()
    flat = flat or (stripe_thr > 0 and payload.nbytes >= stripe_thr
                    and bool(getattr(conn, "rails", None)))
    if (flat or payload.nbytes > config.rndv_threshold()
            or not getattr(worker, "lazy_device_tx", False)):
        worker.submit_send(conn, payload.as_host_view(), tag, done, fail,
                           payload)
        return
    # Every other staged send, whatever its size: the copy is started now
    # if the prefetch window has room, and the TX pump waits for it
    # (as_host_view) when the message's first payload byte is due -- never
    # the poster.  Eager completion keeps its meaning: ``done`` fires when
    # the last byte is written, after the ONE copy has left the device, so
    # it still licenses the caller to delete or donate the array
    # (DESIGN.md §12).
    def settled_done():
        _prefetch.settle(payload)
        done()

    def settled_fail(reason):
        _prefetch.settle(payload)
        fail(reason)

    _prefetch.post(payload)
    try:
        worker.submit_send(conn, payload, tag, settled_done, settled_fail,
                           payload)
    except Exception:
        _prefetch.settle(payload)  # refused at submit: neither will fire
        raise


def post_device_recv(worker, buffer, tag, mask, done, fail):
    if not isinstance(buffer, DeviceBuffer):
        raise TypeError("device receives require a DeviceBuffer sink")
    sink = DeviceRecvSink(buffer)
    sink.scope = getattr(worker, "stage_scope", None)
    worker.post_recv(sink, tag, mask, done, fail, owner=sink)
