"""Shared-memory transport ("sm") semantics.

The reference's UCX layer negotiates shared memory between same-host
processes whenever ``UCX_TLS`` allows it (reference: benchmark.md:114-126);
its tests exercise whichever transport UCX picks on loopback.  This suite
pins the TPU build's explicit sm upgrade (core/shmring.py): negotiation and
fallback, integrity across process boundaries, the flush-vs-close delivery
contract (the reference's core semantic, tests/test_basic.py:190-415), ring
wrap/backpressure with a deliberately tiny ring, and segment cleanup (no
``/dev/shm`` leaks).

The main suite (test_basic.py) additionally runs its whole transport matrix
over ``sm`` in-process; this file covers what only dedicated setups can.
"""

import asyncio
import contextlib
import multiprocessing as mp
import os

import numpy as np
import pytest

from starway_tpu import Client, Server
from starway_tpu.core import shmring

pytestmark = pytest.mark.asyncio

SERVER_ADDR = "127.0.0.1"
# Mid-stream-at-close must not be winnable by a fast machine: like the
# reference (8 GiB, tests/test_basic.py:190-415) and test_basic.py here
# (1 GiB), the margin is sheer size -- far beyond ring + socket buffering.
INFLIGHT_BYTES = 1 << 30


def _shm_segments() -> set[str]:
    return {f for f in os.listdir(shmring.SHM_DIR) if f.startswith("sw-")}


@pytest.fixture
def shm_baseline():
    """Segments present before the test (e.g. another process's) are not this
    test's leaks; only a delta is."""
    return _shm_segments()


def _shm_leftovers(baseline=frozenset()) -> set[str]:
    return _shm_segments() - set(baseline)



@pytest.fixture
def sm_env(monkeypatch):
    import platform

    if platform.machine() not in ("x86_64", "AMD64"):
        pytest.skip("python sm transport requires x86-64 (TSO ring publication)")
    monkeypatch.setenv("STARWAY_TLS", "tcp,sm")
    monkeypatch.setenv("STARWAY_NATIVE", "0")


# ==============================================================================
# Ring unit behaviour
# ==============================================================================


def test_ring_byte_stream_wrap_and_backpressure():
    seg = shmring.ShmSegment.create("ringunit", ring_size=4096)
    try:
        tx, rx = seg.tx_rx(creator=True)
        peer_tx, peer_rx = seg.tx_rx(creator=False)
        assert tx is peer_rx is seg.rings[0] and rx is peer_tx is seg.rings[1]

        # fill to capacity; writes beyond it are refused
        blob = bytes(range(256)) * 16  # 4096
        assert tx.write(memoryview(blob)) == 4096
        assert tx.write(memoryview(b"x")) == 0
        assert tx.free() == 0 and peer_rx.readable() == 4096

        # partial consume frees space; subsequent write wraps the boundary
        out = bytearray(3000)
        assert peer_rx.read_into(memoryview(out)) == 3000
        assert out == bytearray(blob[:3000])
        assert tx.write(memoryview(blob[:2000])) == 2000
        out2 = bytearray(4096)
        n = peer_rx.read_into(memoryview(out2))
        assert n == 4096 - 3000 + 2000
        assert bytes(out2[:n]) == blob[3000:] + blob[:2000]
        assert peer_rx.readable() == 0
    finally:
        seg.unlink()
        seg.close()
    assert seg.key not in _shm_segments()


@pytest.mark.parametrize("slotted", [False, True], ids=["plain", "slot-records"])
def test_ring_bulk_copies_match_small_ones(slotted):
    """Puts and takes of ``BULK_COPY`` bytes and more copy through numpy
    (the interpreter lock released), smaller ones through memoryviews: the
    same bytes either way, across the ring's wrap, with and without slot
    records."""
    size = 4 * shmring.BULK_COPY
    seg = shmring.ShmSegment.create("bulk", ring_size=size)
    try:
        if slotted:
            seg.enable_integrity()
        tx, _ = seg.tx_rx(creator=True)
        _, rx = seg.tx_rx(creator=False)
        rng = np.random.default_rng(64)
        # 3/4 of the ring a lap: the second lap's put and take both wrap.
        for lap, piece in enumerate((size * 3 // 4, size * 3 // 4,
                                     shmring.BULK_COPY - 1, 100)):
            blob = rng.integers(0, 256, piece, dtype=np.uint8).tobytes()
            assert piece >= shmring.BULK_COPY or lap >= 2
            sent = 0
            out = bytearray(piece)
            got = 0
            while got < piece:
                if sent < piece:
                    sent += tx.write(memoryview(blob)[sent:])
                got += rx.read_into(memoryview(out)[got:])
            assert bytes(out) == blob, (lap, piece)
        assert rx.readable() == 0
    finally:
        seg.unlink()
        seg.close()
    assert seg.key not in _shm_segments()


def test_ring_portable_atomics_path(monkeypatch):
    """The non-TSO cursor path (native acquire/release atomics via ctypes,
    forced here with STARWAY_SM_FORCE_ATOMICS) must carry the same byte
    stream — including mixing with a plain-mmap peer on the SAME segment,
    which is exactly the situation when only one side is off-x86."""
    from starway_tpu.core import native

    if native.atomics() is None:
        pytest.skip("native lib (portable sm atomics) unavailable")

    monkeypatch.setenv("STARWAY_SM_FORCE_ATOMICS", "1")
    seg = shmring.ShmSegment.create("atomics", ring_size=4096)
    try:
        tx, rx = seg.tx_rx(creator=True)
        assert tx._at is not None  # the forced path is actually in use
        monkeypatch.delenv("STARWAY_SM_FORCE_ATOMICS")
        # plain-mmap view of the same segment: the cross-convention pairing
        plain = shmring.ShmSegment.attach(seg.key, seg.nonce, seg.ring_size)
        peer_tx, peer_rx = plain.tx_rx(creator=False)
        assert peer_rx._at is None

        blob = bytes(range(256)) * 8  # 2048
        assert tx.write(memoryview(blob)) == 2048
        out = bytearray(2048)
        assert peer_rx.read_into(memoryview(out)) == 2048
        assert out == bytearray(blob)
        # and the reverse direction, plain producer -> atomic consumer
        assert peer_tx.write(memoryview(blob[:512])) == 512
        out2 = bytearray(512)
        assert rx.read_into(memoryview(out2)) == 512
        assert out2 == bytearray(blob[:512])
        assert tx.free() == 4096 and rx.readable() == 0
        plain.close()
    finally:
        seg.unlink()
        seg.close()
    assert seg.key not in _shm_segments()


async def test_sm_exchange_with_portable_atomics(port, sm_env, monkeypatch,
                                                 shm_baseline):
    """Full sm negotiation + a framed payload with every Python cursor op
    routed through the native atomics (the off-x86 configuration, forced
    on this x86 host)."""
    from starway_tpu.core import native

    if native.atomics() is None:
        pytest.skip("native lib (portable sm atomics) unavailable")
    monkeypatch.setenv("STARWAY_SM_FORCE_ATOMICS", "1")

    async with _pair(port) as (server, client):
        ep = server.list_clients().pop()
        assert ep.view_transports() == [("shm", "sm")]
        payload = np.random.default_rng(5).integers(
            0, 255, 1 << 18, dtype=np.uint8)
        buf = np.zeros(1 << 18, dtype=np.uint8)
        fut = server.arecv(buf, 0x5A, (1 << 64) - 1)
        await client.asend(payload, 0x5A)
        tag, n = await asyncio.wait_for(fut, 15)
        assert (tag, n) == (0x5A, len(payload))
        np.testing.assert_array_equal(buf, payload)
    assert not _shm_leftovers(shm_baseline)


def test_segment_attach_validation():
    seg = shmring.ShmSegment.create("attach", ring_size=8192)
    try:
        with pytest.raises(ValueError):
            shmring.ShmSegment.attach(seg.key, seg.nonce ^ 1, seg.ring_size)
        with pytest.raises(ValueError):
            shmring.ShmSegment.attach(seg.key, seg.nonce, seg.ring_size * 2)
        with pytest.raises(ValueError):
            shmring.ShmSegment.attach("../etc/passwd", 0, 8192)
        with pytest.raises(OSError):
            shmring.ShmSegment.attach("sw-no-such-segment", 0, 8192)
        ok = shmring.ShmSegment.attach(seg.key, seg.nonce, seg.ring_size)
        ok.close()
    finally:
        seg.unlink()
        seg.close()


# ==============================================================================
# In-process negotiation details
# ==============================================================================


@contextlib.asynccontextmanager
async def _pair(port):
    server = Server()
    client = Client()
    server.listen(SERVER_ADDR, port)
    await client.aconnect(SERVER_ADDR, port)
    try:
        yield server, client
    finally:
        await client.aclose()
        await server.aclose()


async def test_sm_negotiated_transport_visible(port, sm_env, shm_baseline):
    async with _pair(port) as (server, client):
        ep = server.list_clients().pop()
        assert ep.view_transports() == [("shm", "sm")]
    assert not _shm_leftovers(shm_baseline)


async def test_sm_fallback_when_acceptor_disables(port, monkeypatch, shm_baseline):
    # Server side never maps the offer => ACK carries no "sm": traffic stays
    # on TCP and the offered segment is cleaned up.
    monkeypatch.setenv("STARWAY_NATIVE", "0")
    monkeypatch.setenv("STARWAY_TLS", "tcp,sm")
    server = Server()
    server.listen(SERVER_ADDR, port)
    monkeypatch.setenv("STARWAY_TLS", "tcp")

    from starway_tpu.core import engine as engine_mod

    orig = engine_mod.ServerWorker._on_hello

    def no_sm_hello(self, conn, info, fires):
        info = {k: v for k, v in info.items() if not k.startswith("sm_")}
        return orig(self, conn, info, fires)

    monkeypatch.setattr(engine_mod.ServerWorker, "_on_hello", no_sm_hello)
    monkeypatch.setenv("STARWAY_TLS", "tcp,sm")

    client = Client()
    await client.aconnect(SERVER_ADDR, port)
    ep = server.list_clients().pop()
    assert ep.view_transports() == [("lo", "tcp")]

    buf = np.zeros(64, dtype=np.uint8)
    fut = server.arecv(buf, 0, 0)
    await client.asend(np.arange(64, dtype=np.uint8), 7)
    await fut
    np.testing.assert_array_equal(buf, np.arange(64, dtype=np.uint8))
    await client.aclose()
    await server.aclose()
    assert not _shm_leftovers(shm_baseline)


async def test_sm_tiny_ring_streams_large_messages(port, sm_env, monkeypatch, shm_baseline):
    # 4 KiB rings force hundreds of wrap/backpressure cycles per message.
    monkeypatch.setenv("STARWAY_SM_RING", "4096")
    async with _pair(port) as (server, client):
        ep = server.list_clients().pop()
        assert ep.view_transports() == [("shm", "sm")]
        rng = np.random.default_rng(7)
        payload = rng.integers(0, 256, 1 << 20, dtype=np.uint8)
        buf = np.zeros(1 << 20, dtype=np.uint8)
        fut = server.arecv(buf, 0, 0)
        await client.asend(payload, 5)
        await fut
        np.testing.assert_array_equal(buf, payload)
        # reverse direction across the same rings
        buf2 = np.zeros(1 << 20, dtype=np.uint8)
        fut2 = client.arecv(buf2, 0, 0)
        await server.asend(ep, payload, 6)
        await fut2
        np.testing.assert_array_equal(buf2, payload)
    assert not _shm_leftovers(shm_baseline)


# ==============================================================================
# Flow control: the ring's size, and a message larger than the ring
# ==============================================================================


def _conns(server, client):
    """(the acceptor's conn, the connector's conn) of a ``_pair``."""
    return server.list_clients().pop()._conn, client._client.primary_conn


async def _stream_through_small_ring(port, monkeypatch, integrity=False):
    """One payload of four rings' worth, client -> server, over a 64 KiB
    ring.  Returns the producer's starving bytes, the consumer's doorbell
    bytes (each the bytes left in its ring when it was sent) and the
    connector's worker."""
    from starway_tpu.core import conn as conn_mod

    ring = 65536
    monkeypatch.setenv("STARWAY_SM_RING", str(ring))
    if integrity:
        monkeypatch.setenv("STARWAY_INTEGRITY", "1")
    starving, replies = [], []
    async with _pair(port) as (server, client):
        consumer, producer = _conns(server, client)
        assert consumer.sm_ring == producer.sm_ring == ring
        assert consumer.sm_rx.slotted is producer.sm_tx.slotted is integrity

        def bell_from(conn, seen):
            sent = conn._doorbell

            def bell(fires, val=conn_mod.DB_DATA):
                seen(val)
                sent(fires, val)
            monkeypatch.setattr(conn, "_doorbell", bell)

        bell_from(producer, lambda val: val == conn_mod.DB_STARVING
                  and starving.append(val))
        bell_from(consumer, lambda val: replies.append(
            consumer.sm_rx.readable()))
        payload = np.random.default_rng(36).integers(
            0, 256, 4 * ring, dtype=np.uint8)
        buf = np.zeros(4 * ring, dtype=np.uint8)
        fut = server.arecv(buf, 0x36, (1 << 64) - 1)
        await asyncio.wait_for(client.asend(payload, 0x36), 30)
        assert await asyncio.wait_for(fut, 30) == (0x36, 4 * ring)
        np.testing.assert_array_equal(buf, payload)
        return starving, replies, client._client


@pytest.mark.parametrize("integrity", [False, True],
                         ids=["plain", "slot-records"])
async def test_sm_message_streams_through_small_ring(
        port, sm_env, monkeypatch, shm_baseline, integrity):
    """A message larger than the ring arrives byte for byte: its producer
    parks on the full ring (at least three times for four rings' worth)
    and is woken each time, and the whole message is ONE ``ring_wait``
    sample, whatever its blocks."""
    starving, _replies, tx = await _stream_through_small_ring(
        port, monkeypatch, integrity)
    assert len(starving) >= 3, starving
    waits = tx.stage_scope.snapshot()["ring_wait"]
    assert waits["count"] == 1 and waits["seconds"] > 0, waits
    assert not _shm_leftovers(shm_baseline)


async def test_sm_starving_byte_answered_once(port, sm_env, monkeypatch,
                                              shm_baseline):
    """One reply a starving byte: the consumer sends nothing else during a
    one-way transfer, so its doorbell bytes count the replies, and the
    producer is woken exactly as often as it parked.  Each reply follows a
    drain: the consumer's ring is empty when it rings."""
    starving, replies, _tx = await _stream_through_small_ring(
        port, monkeypatch)
    assert len(starving) >= 3 and len(replies) == len(starving), \
        (starving, replies)
    assert not any(replies), replies
    assert not _shm_leftovers(shm_baseline)


@pytest.mark.parametrize("native_engine", [False, True],
                         ids=["python", "native"])
async def test_sm_default_ring_size(port, monkeypatch, shm_baseline,
                                    native_engine):
    """With no ``STARWAY_SM_RING`` a conn's ring is ``DEFAULT_RING`` in
    either engine (the connector's engine sizes the segment; the acceptor
    follows its header), and both say so."""
    from starway_tpu.core import native

    if native_engine and not native.available():
        pytest.skip("native engine unavailable (no toolchain)")
    monkeypatch.delenv("STARWAY_SM_RING", raising=False)
    monkeypatch.setenv("STARWAY_TLS", "tcp,sm")
    monkeypatch.setenv("STARWAY_NATIVE", "1" if native_engine else "0")
    async with _pair(port) as (server, client):
        for _ in range(3000):  # the native engine lists an accept a moment late
            if server.list_clients():
                break
            await asyncio.sleep(0.01)
        acceptor = server.list_clients().pop()._conn
        assert acceptor.transports() == [("shm", "sm")]
        assert acceptor.sm_ring == shmring.DEFAULT_RING
        if not native_engine:
            connector = client._client.primary_conn
            assert connector.sm_ring == connector._sm.ring_size \
                == shmring.DEFAULT_RING
        buf = np.zeros(1024, dtype=np.uint8)
        fut = server.arecv(buf, 0x1, (1 << 64) - 1)
        await asyncio.wait_for(client.asend(np.full(1024, 7, np.uint8), 0x1), 15)
        await asyncio.wait_for(fut, 15)
        assert int(buf[0]) == int(buf[-1]) == 7
    assert not _shm_leftovers(shm_baseline)


async def test_sm_message_under_ring_never_waits(port, sm_env, monkeypatch,
                                                 shm_baseline):
    """A message smaller than the ring meets no full ring: no starving
    byte, no ``ring_wait`` sample."""
    monkeypatch.delenv("STARWAY_SM_RING", raising=False)
    async with _pair(port) as (server, client):
        n = shmring.DEFAULT_RING // 4
        payload = np.random.default_rng(4).integers(0, 256, n, dtype=np.uint8)
        buf = np.zeros(n, dtype=np.uint8)
        fut = server.arecv(buf, 0x2, (1 << 64) - 1)
        await asyncio.wait_for(client.asend(payload, 0x2), 30)
        await asyncio.wait_for(fut, 30)
        await asyncio.wait_for(client.aflush(), 30)
        np.testing.assert_array_equal(buf, payload)
        for worker in (client._client, server._server):
            assert "ring_wait" not in worker.stage_scope.snapshot()
    assert not _shm_leftovers(shm_baseline)


# ==============================================================================
# Cross-process: integrity, flush-vs-close, peer death
# ==============================================================================


def _child_client_send_sm(port, with_flush, nbytes):
    os.environ["STARWAY_TLS"] = "tcp,sm"
    os.environ["STARWAY_NATIVE"] = "0"

    async def inner():
        client = None
        for i in range(60):
            client = Client()
            try:
                await client.aconnect(SERVER_ADDR, port)
                break
            except Exception:
                if i == 59:
                    raise
                await asyncio.sleep(0.25)
        send_buf = np.arange(nbytes, dtype=np.uint8)
        await client.asend(send_buf, 0)
        if with_flush:
            await client.aflush()
        await client.aclose()

    asyncio.run(inner())


@pytest.mark.parametrize("with_flush", [False, True])
async def test_sm_client_send_flush_semantics(port, sm_env, with_flush, shm_baseline):
    """The delivery contract holds over rings: close-without-flush aborts the
    in-flight rendezvous send; flush guarantees delivery (the reference pins
    this with 8 GiB in-flight sends, tests/test_basic.py:190-415)."""
    server = Server()
    server.listen(SERVER_ADDR, port)
    connected = asyncio.Event()
    loop = asyncio.get_running_loop()
    server.set_accept_cb(lambda ep: loop.call_soon_threadsafe(connected.set))

    ctx = mp.get_context("spawn")
    p = ctx.Process(target=_child_client_send_sm, args=(port, with_flush, INFLIGHT_BYTES), daemon=True)
    p.start()
    await asyncio.wait_for(connected.wait(), timeout=120)
    ep = next(iter(server.list_clients()))
    assert ep.view_transports() == [("shm", "sm")]

    recv_buf = np.zeros(INFLIGHT_BYTES, dtype=np.uint8)
    if with_flush:
        await server.arecv(recv_buf, 0, 0)
        np.testing.assert_array_equal(recv_buf, np.arange(INFLIGHT_BYTES, dtype=np.uint8))
        p.join()
    else:
        done = False

        def _done(sender_tag, length):
            nonlocal done
            done = True

        def _fail(error):
            nonlocal done
            done = True

        server.recv(recv_buf, 0, 0, _done, _fail)
        await asyncio.sleep(1.5)
        assert not done
        p.kill()
        p.join()
    p.close()
    await server.aclose()
    assert not _shm_leftovers(shm_baseline)


def _child_client_echo(port, native_engine):
    """Send 32 MiB, flush, then expect a 1 KiB echo; exit 0 proves both
    directions delivered through whatever transport was negotiated."""
    os.environ["STARWAY_TLS"] = "tcp,sm"
    os.environ["STARWAY_NATIVE"] = "1" if native_engine else "0"

    async def inner():
        client = None
        for i in range(60):
            client = Client()
            try:
                await client.aconnect(SERVER_ADDR, port)
                break
            except Exception:
                if i == 59:
                    raise
                await asyncio.sleep(0.25)
        payload = np.arange(32 << 20, dtype=np.uint8)
        await client.asend(payload, 0x7)
        await client.aflush()
        buf = np.zeros(1024, dtype=np.uint8)
        _, ln = await client.arecv(buf, 0x8, (1 << 64) - 1)
        assert ln == 1024 and np.array_equal(buf, (np.arange(1024) % 256).astype(np.uint8))
        await client.aclose()

    asyncio.run(inner())


@pytest.mark.parametrize(
    "server_native,client_native",
    [(False, True), (True, False), (True, True)],
    ids=["py-server/native-client", "native-server/py-client", "native/native"],
)
async def test_sm_engine_interop(port, monkeypatch, shm_baseline, server_native, client_native):
    """The sm ring layout is a cross-engine contract (CLAUDE.md "two
    engines, one contract"): every engine pairing must negotiate sm and move
    data both ways across a real process boundary."""
    from starway_tpu.core import native

    if not native.available():
        pytest.skip("native engine unavailable (no toolchain)")
    monkeypatch.setenv("STARWAY_TLS", "tcp,sm")
    monkeypatch.setenv("STARWAY_NATIVE", "1" if server_native else "0")

    server = Server()
    server.listen(SERVER_ADDR, port)
    ctx = mp.get_context("spawn")
    p = ctx.Process(target=_child_client_echo, args=(port, client_native), daemon=True)
    p.start()
    for _ in range(3000):
        if server.list_clients():
            break
        await asyncio.sleep(0.01)
    ep = next(iter(server.list_clients()))

    recv_buf = np.zeros(32 << 20, dtype=np.uint8)
    _, ln = await server.arecv(recv_buf, 0x7, (1 << 64) - 1)
    assert ln == 32 << 20
    np.testing.assert_array_equal(recv_buf, np.arange(32 << 20, dtype=np.uint8))
    assert ep.view_transports() == [("shm", "sm")]
    await server.asend(ep, (np.arange(1024) % 256).astype(np.uint8), 0x8)
    p.join(120)  # child asserts the echo landed; exit 0 proves delivery
    assert p.exitcode == 0
    p.close()
    await server.aclose()
    assert not _shm_leftovers(shm_baseline)


async def test_sm_peer_kill_leaves_recv_pending(port, sm_env, shm_baseline):
    """SIGKILL mid-transfer: posted receives stay pending (reference peer
    -death semantics), the engine survives, and the segment pages are
    reclaimed because both sides unlinked the name at negotiation."""
    server = Server()
    server.listen(SERVER_ADDR, port)
    connected = asyncio.Event()
    loop = asyncio.get_running_loop()
    server.set_accept_cb(lambda ep: loop.call_soon_threadsafe(connected.set))

    ctx = mp.get_context("spawn")
    p = ctx.Process(target=_child_client_send_sm, args=(port, True, INFLIGHT_BYTES), daemon=True)
    p.start()
    await asyncio.wait_for(connected.wait(), timeout=120)

    done = False

    def _done(sender_tag, length):
        nonlocal done
        done = True

    def _fail(error):
        nonlocal done
        done = True

    recv_buf = np.zeros(INFLIGHT_BYTES, dtype=np.uint8)
    server.recv(recv_buf, 0, 0, _done, _fail)
    await asyncio.sleep(0.2)  # transfer underway
    p.kill()
    p.join()
    p.close()
    await asyncio.sleep(1.0)
    assert not done  # pending forever, not failed
    await server.aclose()
    assert not _shm_leftovers(shm_baseline)
