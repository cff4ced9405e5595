"""Device data plane: jax.Array payloads over the fabric.

This is the TPU-native replacement for the reference's zero-copy RDMA into
preallocated NumPy buffers (reference: src/bindings/main.hpp:155-161 captures
raw host pointers; BASELINE.json north star: "asend/arecv/aflush async
primitives operate on jax.Array device buffers in HBM").

Three transfer paths, chosen per connection:

* **in-process, device payload -> device sink**: the sender hands the
  ``jax.Array`` itself to the receiver's matcher; the receiver materialises
  it on its target device with ``jax.device_put`` -- on TPU hardware with
  both devices in the same process this is an HBM-to-HBM copy over ICI with
  zero host staging.  (Same-device delivery is a reference handoff.)
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
  (``nbytes``, ``host_staging()``, ``finalize_from_host()``,
  ``accept_device()``).
"""

from __future__ import annotations

import logging
import sys
import threading
import time
from typing import Optional

logger = logging.getLogger("starway_tpu")


def _record_stage(name: str, seconds: float, nbytes: int, scope=None) -> None:
    from . import perf

    perf.record_stage(name, seconds, nbytes, scope)


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


def _rx_overlap_ok(device) -> bool:
    """Chunked receive placement (async H2D per completed chunk + one
    device-side concatenate) only pays on accelerator targets where the
    DMA genuinely overlaps the remaining stream reads; on CPU the
    concatenate costs more than it hides.  Module-level so tests can
    force the path on the virtual CPU mesh."""
    return device is not None and getattr(device, "platform", "cpu") != "cpu"


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
    """Send-side wrapper: a jax.Array plus a lazily-created host view.

    Two staging modes feed the framed stream:

    * ``as_host_view()`` -- one full-payload D2H (the in-process delivery
      path, and the fallback for engines without chunked TX support).
    * ``chunked(chunk_bytes)`` + ``host_chunk(pos)`` -- incremental D2H:
      the TX pump asks for the chunk containing byte ``pos`` and the
      payload kicks off the async device-to-host copy of the NEXT chunk
      before returning, so staging chunk k+1 overlaps the transport write
      of chunk k (DESIGN.md §12).  The duck protocol core/conn.py sees is
      just ``nbytes`` + ``host_chunk``.
    """

    __slots__ = ("array", "nbytes", "scope", "_host_view", "_flat",
                 "_chunk_elems", "_chunk_b", "_dev_chunks", "_host_chunks")

    def __init__(self, array):
        self.array = array
        self.nbytes = int(array.nbytes)
        self.scope = None  # owning worker's perf.StageScope (send_device)
        self._host_view: Optional[memoryview] = None
        self._flat = None  # chunked mode state (see chunked())
        self._chunk_elems = 0
        self._chunk_b = 0
        self._dev_chunks: Optional[dict] = None
        self._host_chunks: Optional[dict] = None

    def as_host_view(self) -> memoryview:
        if self._host_view is None:
            import numpy as np

            t0 = time.perf_counter()
            host = np.ascontiguousarray(np.asarray(self.array))
            # view(uint8) first: extension dtypes (ml_dtypes bfloat16 et
            # al) have no buffer-protocol format char, so memoryview()
            # on the raw array raises for exactly the payloads TPU work
            # ships most.
            self._host_view = memoryview(host.view(np.uint8)).cast("B")
            _record_stage("stage", time.perf_counter() - t0, self.nbytes,
                          self.scope)
        return self._host_view

    # ------------------------------------------------------- chunked D2H
    def chunked(self, chunk_bytes: int) -> Optional["DevicePayload"]:
        """Arm incremental staging, or None when it cannot help (payload
        smaller than two chunks, pipelining disabled, or the array refuses
        the flat view).  Arming prefetches chunk 0 so its D2H runs while
        the message header is still being written."""
        if chunk_bytes <= 0 or self.nbytes < 2 * chunk_bytes:
            return None
        try:
            flat = self.array.reshape(-1)
            itemsize = _np_dtype(flat.dtype).itemsize
            elems = chunk_bytes // itemsize
            if elems <= 0 or self.nbytes < 2 * elems * itemsize:
                return None
            self._flat = flat
            self._chunk_elems = elems
            self._chunk_b = elems * itemsize
            self._dev_chunks = {}
            self._host_chunks = {}
            self._prefetch(0)
        except Exception:
            logger.debug("chunked staging unavailable for this payload",
                         exc_info=True)
            return None
        return self

    def _prefetch(self, k: int) -> None:
        """Start the async D2H of chunk ``k`` (device-side slice +
        copy_to_host_async); no-op past the end or when already started."""
        if k * self._chunk_b >= self.nbytes or k in self._dev_chunks:
            return
        if self._host_chunks is not None and k in self._host_chunks:
            return
        sl = self._flat[k * self._chunk_elems:(k + 1) * self._chunk_elems]
        try:
            sl.copy_to_host_async()
        except Exception:
            pass  # best-effort: np.asarray below still blocks correctly
        self._dev_chunks[k] = sl

    def host_chunk(self, pos: int) -> tuple[int, memoryview]:
        """(chunk_start, host_view) for the chunk containing byte ``pos``,
        prefetching the following chunk before materialising this one."""
        import numpy as np

        k = pos // self._chunk_b
        self._prefetch(k)
        self._prefetch(k + 1)
        view = self._host_chunks.get(k)
        if view is None:
            t0 = time.perf_counter()
            host = np.ascontiguousarray(np.asarray(self._dev_chunks.pop(k)))
            view = memoryview(host).cast("B")
            _record_stage("stage", time.perf_counter() - t0, len(view),
                          self.scope)
            self._host_chunks[k] = view
            # The pump only moves forward: chunk k-1 is fully on the wire.
            self._host_chunks.pop(k - 1, None)
        return k * self._chunk_b, view


class DeviceRecvSink:
    """Receive-side adapter bridging the byte matcher to a DeviceBuffer.

    Streamed (TCP/sm) payloads land in a pooled host staging buffer; on
    accelerator targets the conn's RX pump reports progress via
    :meth:`staged` and every completed chunk starts its async H2D while
    later chunks are still on the wire, with one device-side concatenate
    at :meth:`finalize_from_host` (DESIGN.md §12)."""

    __slots__ = ("devbuf", "scope", "_staging", "_staging_view",
                 "_chunk_elems", "_chunk_b", "_placed", "_recyclable")

    def __init__(self, devbuf: DeviceBuffer):
        self.devbuf = devbuf
        self.scope = None  # owning worker's perf.StageScope (post_device_recv)
        self._staging = None
        self._staging_view: Optional[memoryview] = None
        self._chunk_elems = 0  # >0 = chunked placement armed
        self._chunk_b = 0
        self._placed: Optional[list] = None
        self._recyclable = True

    @property
    def nbytes(self) -> int:
        return self.devbuf.nbytes

    def host_staging(self) -> memoryview:
        """Host bounce buffer for streamed (TCP) payloads (pooled)."""
        if self._staging_view is None:
            from . import config

            self._staging = _staging_pool.get(self.nbytes)
            self._staging_view = memoryview(self._staging).cast("B")
            chunk = config.chunk_bytes()
            itemsize = self.devbuf.dtype.itemsize
            elems = chunk // itemsize if chunk > 0 else 0
            if (elems > 0 and self.nbytes >= 2 * elems * itemsize
                    and _rx_overlap_ok(self.devbuf.device)):
                self._chunk_elems = elems
                self._chunk_b = elems * itemsize
                self._placed = []
        return self._staging_view

    def staged(self, received: int) -> None:
        """RX progress hook (engine thread): start the async H2D of every
        fully-arrived chunk.  No-op unless chunked placement is armed.

        Chunked placement is purely an overlap optimisation -- the staging
        buffer receives every byte regardless -- so any failure here (or in
        the finalize assemble) disarms it and the transfer falls back to
        one full-buffer placement instead of killing the engine thread."""
        if not self._chunk_b:
            return
        try:
            while (len(self._placed) + 1) * self._chunk_b <= received:
                off = len(self._placed) * self._chunk_b
                self._place_chunk(off, self._chunk_b)
        except Exception:
            logger.warning("chunked H2D placement failed; falling back to "
                           "full-buffer placement", exc_info=True)
            self._disarm_chunks()

    def _disarm_chunks(self) -> None:
        self._chunk_elems = self._chunk_b = 0
        self._placed = None

    def _place_chunk(self, off: int, nbytes: int) -> None:
        # Armed only for a concrete target (_rx_overlap_ok): always PJRT.
        t0 = time.perf_counter()
        arr = self._staging[off:off + nbytes].view(self.devbuf.dtype)
        self._placed.append(_fast_h2d(arr, self.devbuf.device))
        _record_stage("place", time.perf_counter() - t0, nbytes, self.scope)

    def finalize_from_host(self, length: int) -> None:
        """Staged bytes fully arrived: view as dtype/shape, place on device."""
        import numpy as np

        assembled = False
        if self._placed:
            try:
                self._finalize_chunked(length)
                assembled = True
            except Exception:
                logger.warning("chunked H2D assemble failed; falling back "
                               "to full-buffer placement", exc_info=True)
                self._disarm_chunks()
        if not assembled:
            self._place(np.asarray(self._staging[:length]), length)
        if self._recyclable and self._staging is not None:
            _staging_pool.put(self._staging)
        self._staging = None
        self._staging_view = None
        self._disarm_chunks()
        self._recyclable = True

    def _finalize_chunked(self, length: int) -> None:
        """Assemble the chunk arrays placed mid-stream into the delivered
        array (one device-side concatenate, pinned to the target device)."""
        import contextlib

        import jax
        import jax.numpy as jnp

        done_b = len(self._placed) * self._chunk_b
        if done_b < length:
            self._place_chunk(done_b, length - done_b)
        t0 = time.perf_counter()
        dev = self.devbuf.device
        # buffer_from_pyval chunks are uncommitted: pin the assemble to
        # the target device or jax's default device would claim it.
        ctx = jax.default_device(dev) if dev is not None else contextlib.nullcontext()
        with ctx:
            arr = (jnp.concatenate(self._placed) if len(self._placed) > 1
                   else self._placed[0])
            if length == self.nbytes:
                arr = arr.reshape(self.devbuf.shape)
        if dev is not None and arr.devices() != {dev}:
            arr = _copy_to_device(arr, dev, self.devbuf._plan)
        self.devbuf.array = arr
        self.devbuf.last_transport = "staged"
        _record_stage("place", time.perf_counter() - t0, 0, self.scope)

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
        t0 = time.perf_counter()
        placed = _fast_h2d(self._as_target(raw, length), self.devbuf.device)
        if placed is not None:
            placed.block_until_ready()  # recv-complete = data resident
            self.devbuf.array = placed
            self.devbuf.last_transport = "staged"
            _record_stage("place", time.perf_counter() - t0, length, self.scope)
            return
        dev = self.devbuf.device
        platform = dev.platform if dev is not None else jax.local_devices()[0].platform
        if platform == "cpu":
            raw = raw.copy()  # private snapshot; aliasing it is then fine
            self._place(raw, length)
        else:
            # H2D device_put is async: the DMA reads the source view after
            # the call returns, and completion licenses the sender to reuse
            # that buffer.  Block until the data is resident (the same
            # recv-complete semantics accept_device enforces).
            self._place(raw, length)
            self.devbuf.array.block_until_ready()

    def _as_target(self, raw, length: int):
        """View staged uint8 bytes as the sink's dtype (and shape, when the
        payload fills the buffer exactly)."""
        arr = raw.view(self.devbuf.dtype)
        if length == self.nbytes:
            arr = arr.reshape(self.devbuf.shape)
        return arr

    def _place(self, raw, length: int) -> None:
        import jax

        arr = self._as_target(raw, length)
        t0 = time.perf_counter()
        placed = _fast_h2d(arr, self.devbuf.device)
        if placed is None:  # no target device: jax's default device
            self._recyclable = False  # device_put may alias `raw` (CPU)
            placed = jax.device_put(arr)
        self.devbuf.array = placed
        self.devbuf.last_transport = "staged"
        _record_stage("place", time.perf_counter() - t0, length, self.scope)

    def accept_device(self, array) -> None:
        """Direct device handoff (in-process path): HBM -> HBM over ICI when
        source and target devices differ, reference handoff when they match."""
        import jax

        self.devbuf.last_transport = "device"
        target = self.devbuf.device
        if target is not None:
            src_devs = array.devices() if hasattr(array, "devices") else set()
            if src_devs == {target}:
                self.devbuf.array = array
                return
            self.devbuf.array = _copy_to_device(array, target, self.devbuf._plan)
            # Make completion mean "data resident on target", matching the
            # reference's recv-complete semantics.
            self.devbuf.array.block_until_ready()
        else:
            self.devbuf.array = array


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
    peer connections are lazy; completion waits run on one daemon thread so
    the engine loop never blocks on a transfer.
    """

    def __init__(self, host: str):
        import itertools
        import queue
        import threading

        self._host = host
        self._server = None
        self._failed = False
        self._conns: dict = {}  # address -> TransferConnection
        self._uuid = itertools.count(1)
        self._lock = threading.Lock()
        self._q: "queue.Queue" = queue.Queue()
        self._thread = None
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
        self._submit(lambda: self._do_pull(desc, device, on_done, on_fail))

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

    def _submit(self, thunk) -> None:
        import threading

        with self._lock:
            if self._thread is None:
                self._thread = threading.Thread(
                    target=self._run, name="starway-devpull", daemon=True)
                self._thread.start()
        self._q.put(thunk)

    def _run(self):
        while True:
            thunk = self._q.get()
            if thunk is None:
                return
            try:
                thunk()
            except Exception:
                logger.exception("devpull completion callback failed")

    def close(self) -> None:
        """Drop the server: unpulled offers die (close-cancel contract)."""
        with self._lock:
            self._closed = True
            self._server = None
            self._conns.clear()
        self._q.put(None)


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
    # A session conn's replay journal must OWN every eager frame's bytes
    # past local completion (core/conn.py sess_wrap snapshots flat host
    # views), but a chunked payload is re-staged lazily from the device
    # buffer -- which the eager contract lets the caller delete or donate
    # once ``done`` fires.  Journaled eager sends therefore take the full
    # host snapshot below instead of the chunked pipeline.
    journaled = (config.session_enabled() if conn is None
                 else getattr(conn, "sess", None) is not None)
    # §19 integrity conns checksum at framing time, which needs the whole
    # payload resident: device sends on them take the flat host snapshot
    # too (the CRC folds once over the full view; DESIGN.md §19).
    journaled = journaled or (
        config.integrity_enabled() if conn is None
        else bool(getattr(conn, "csum_ok", False)))
    # Multi-rail striping (DESIGN.md §17) needs a flat host view -- chunks
    # are random-offset slices, and the §12 lazy-chunked pipeline stages
    # strictly in order.  A stripe-eligible device send therefore takes
    # the full host snapshot; the stripe scheduler's chunk-level dispatch
    # then supplies the transport overlap the pipeline would have.
    stripe_thr = config.stripe_threshold()
    striped = (stripe_thr > 0 and payload.nbytes >= stripe_thr
               and bool(getattr(conn, "rails", None)))
    if (getattr(worker, "supports_chunked_tx", False)
            and not journaled and not striped
            and payload.nbytes <= config.rndv_threshold()):
        # Framed-stream staging pipelines: the TX pump pulls host chunks
        # incrementally so the D2H of chunk k+1 overlaps the write of
        # chunk k (core/conn.py TxData; DESIGN.md §12).  Eager payloads
        # only: an eager send completes when the LAST chunk is staged and
        # written, so completion still licenses the caller to delete or
        # donate the array.  A rendezvous send completes at header-on-wire
        # with lazy staging still reading the array afterwards, which
        # would silently revoke that license -- rndv payloads keep the
        # full up-front host snapshot instead.
        chunked = payload.chunked(config.chunk_bytes())
        if chunked is not None:
            worker.submit_send(conn, chunked, tag, done, fail, payload)
            return
    view = payload.as_host_view()
    worker.submit_send(conn, view, tag, done, fail, payload)


def post_device_recv(worker, buffer, tag, mask, done, fail):
    if not isinstance(buffer, DeviceBuffer):
        raise TypeError("device receives require a DeviceBuffer sink")
    sink = DeviceRecvSink(buffer)
    sink.scope = getattr(worker, "stage_scope", None)
    worker.post_recv(sink, tag, mask, done, fail, owner=sink)
