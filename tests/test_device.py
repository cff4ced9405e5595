"""Device-plane tests on the virtual 8-device CPU mesh (no TPU needed).

Covers the BASELINE.json north star shape: asend/arecv operating on
jax.Array device buffers, including cross-device delivery (the ICI path on
real hardware) and host-staged delivery over real sockets.
"""

import asyncio

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from starway_tpu import Client, DeviceBuffer, Server

pytestmark = pytest.mark.asyncio

SERVER_ADDR = "127.0.0.1"
MASK = (1 << 64) - 1



@pytest.fixture(params=["inproc", "tcp"])
def transport(request, monkeypatch):
    if request.param == "tcp":
        monkeypatch.setenv("STARWAY_TLS", "tcp")
    return request.param


async def _pair(port):
    server = Server()
    client = Client()
    server.listen(SERVER_ADDR, port)
    await client.aconnect(SERVER_ADDR, port)
    return server, client


async def test_device_to_device_transfer(port, transport):
    devices = jax.devices()
    assert len(devices) >= 8, "conftest should provide 8 virtual devices"
    server, client = await _pair(port)
    try:
        src = jax.device_put(jnp.arange(2048, dtype=jnp.float32), devices[0])
        sink = DeviceBuffer((2048,), jnp.float32, device=devices[3])

        recv_fut = server.arecv(sink, 7, MASK)
        await asyncio.sleep(0.01)
        await client.asend(src, 7)
        tag, length = await recv_fut

        assert tag == 7
        assert length == src.nbytes
        assert sink.array is not None
        assert sink.array.devices() == {devices[3]}
        np.testing.assert_array_equal(np.asarray(sink.array), np.asarray(src))
    finally:
        await client.aclose()
        await server.aclose()


async def test_device_to_host_transfer(port, transport):
    server, client = await _pair(port)
    try:
        src = jnp.arange(512, dtype=jnp.uint8)
        host_sink = np.zeros(512, dtype=np.uint8)

        recv_fut = server.arecv(host_sink, 9, MASK)
        await asyncio.sleep(0.01)
        await client.asend(src, 9)
        tag, length = await recv_fut

        assert (tag, length) == (9, 512)
        np.testing.assert_array_equal(host_sink, np.asarray(src))
    finally:
        await client.aclose()
        await server.aclose()


async def test_host_to_device_transfer(port, transport):
    server, client = await _pair(port)
    try:
        src = np.random.randint(0, 255, 1024, dtype=np.uint8)
        sink = DeviceBuffer((256,), jnp.float32, device=jax.devices()[5])
        assert sink.nbytes == 1024

        recv_fut = server.arecv(sink, 11, MASK)
        await asyncio.sleep(0.01)
        await client.asend(src, 11)
        tag, length = await recv_fut

        assert (tag, length) == (11, 1024)
        assert sink.array.devices() == {jax.devices()[5]}
        np.testing.assert_array_equal(
            np.asarray(sink.array), src.view(np.float32).reshape(256)
        )
    finally:
        await client.aclose()
        await server.aclose()


async def test_host_to_device_inline_snapshots(port):
    """The staging-eliding accept_host path must SNAPSHOT: mutating the
    sender's buffer after send completion must not change the delivered
    array.  On CPU targets jax.device_put zero-copies aligned numpy
    buffers (this test caught it doing exactly that), so accept_host makes
    a private copy there; on accelerators H2D always copies.  Fails loudly
    if either behavior shifts under a jax upgrade."""
    server, client = await _pair(port)
    try:
        src = np.arange(1024, dtype=np.uint8) % 251
        want = src.copy()
        sink = DeviceBuffer((1024,), jnp.uint8)
        recv_fut = server.arecv(sink, 12, MASK)
        await asyncio.sleep(0.01)
        await client.asend(src, 12)
        await recv_fut
        src[:] = 0  # sender reuses its buffer post-completion
        np.testing.assert_array_equal(np.asarray(sink.array), want)
    finally:
        await client.aclose()
        await server.aclose()


async def test_device_unexpected_then_post(port):
    """Device message arriving before the recv is posted parks in the
    unexpected queue holding the array reference (no host copy)."""
    server, client = await _pair(port)
    try:
        src = jax.device_put(jnp.full((64,), 3.5, dtype=jnp.bfloat16), jax.devices()[2])
        await client.asend(src, 21)
        await asyncio.sleep(0.05)

        sink = DeviceBuffer((64,), jnp.bfloat16, device=jax.devices()[6])
        tag, length = await server.arecv(sink, 21, MASK)
        assert (tag, length) == (21, src.nbytes)
        assert sink.array.devices() == {jax.devices()[6]}
        np.testing.assert_array_equal(np.asarray(sink.array), np.asarray(src))
    finally:
        await client.aclose()
        await server.aclose()


async def test_server_to_client_device_send(port):
    server, client = await _pair(port)
    try:
        ep = server.list_clients().pop()
        src = jnp.linspace(0, 1, 128, dtype=jnp.float32)
        sink = DeviceBuffer.like(src, device=jax.devices()[4])

        recv_fut = client.arecv(sink, 13, MASK)
        await asyncio.sleep(0.01)
        await server.asend(ep, src, 13)
        tag, length = await recv_fut
        assert (tag, length) == (13, src.nbytes)
        np.testing.assert_allclose(np.asarray(sink.array), np.asarray(src))
    finally:
        await client.aclose()
        await server.aclose()


async def test_devicebuffer_send_side(port):
    """A DeviceBuffer holding an array can itself be the send payload."""
    server, client = await _pair(port)
    try:
        holder = DeviceBuffer((32,), jnp.int32, array=jnp.arange(32, dtype=jnp.int32))
        host_sink = np.zeros(32 * 4, dtype=np.uint8)
        recv_fut = server.arecv(host_sink, 15, MASK)
        await asyncio.sleep(0.01)
        await client.asend(holder, 15)
        tag, length = await recv_fut
        assert (tag, length) == (15, 128)
        np.testing.assert_array_equal(
            host_sink.view(np.int32), np.arange(32, dtype=np.int32)
        )
    finally:
        await client.aclose()
        await server.aclose()


# ----------------------------------------------- the whole-message plane


class _FakePayload:
    """What _PrefetchWindow sees of a DevicePayload."""

    def __init__(self, nbytes, log):
        from starway_tpu import device

        self.nbytes, self._pf, self._log = nbytes, device._PF_NONE, log

    def start_fetch(self):
        self._log.append(self)


@pytest.mark.parametrize("case", ["fifo", "oversize", "settled_waiting"])
def test_prefetch_window_rules(case):
    """The bytes-bounded window of device-to-host copies started ahead of
    the TX pump: post order, one message always admitted, and a send that
    settles while it still waits is skipped, never started."""
    from starway_tpu import device

    log: list = []
    win = device._PrefetchWindow(cap_bytes=100)
    if case == "fifo":
        ps = [_FakePayload(40, log) for _ in range(5)]
        for p in ps:
            win.post(p)
        assert log == ps[:2] and win._held == 80
        win.settle(ps[0])
        assert log == ps[:3] and win._held == 80
        for p in ps[1:]:
            win.settle(p)
        assert log == ps and win._held == 0 and win._depth == 0
        assert win.peak_bytes == 80 and win.peak_depth == 2
    elif case == "oversize":
        big, small = _FakePayload(1000, log), _FakePayload(10, log)
        win.post(big)          # alone in the window: admitted past the cap
        win.post(small)        # waits: nothing overtakes, nothing fits
        assert log == [big] and win._held == 1000
        win.settle(big)
        assert log == [big, small] and win._held == 10
        win.settle(small)
        assert win._held == 0 and not win._waiting
    else:
        a, b, c = (_FakePayload(60, log) for _ in range(3))
        for p in (a, b, c):
            win.post(p)
        assert log == [a]
        win.settle(b)          # cancelled while waiting (the conn died)
        win.settle(a)
        assert log == [a, c] and win._held == 60
        win.settle(c)
        win.settle(c)          # idempotent
        assert win._held == 0 and win._depth == 0 and not win._waiting


def test_recv_sink_place_blocks_until_resident_then_recycles():
    """place(): ONE copy out of the pooled staging buffer, returned only
    when the array is resident; only then is the buffer recycled, and a
    rewrite of it by the next transfer leaves the placed bytes alone."""
    from starway_tpu import device, perf

    nbytes = 160 * 1024 + 64  # a bucket no other suite uses
    dev = jax.devices()[2]
    first = device.DeviceRecvSink(DeviceBuffer((nbytes,), jnp.uint8, device=dev))
    view = first.host_staging()
    view[:] = bytes([7]) * nbytes
    perf.stage_reset()
    placed = first.place(nbytes)
    assert placed.is_ready() and placed.devices() == {dev}
    assert first.devbuf.array is None, "place() must not deliver"
    assert perf.stage_snapshot()["place"]["count"] == 1
    second = device.DeviceRecvSink(DeviceBuffer((nbytes,), jnp.uint8, device=dev))
    hits0 = device._staging_pool.hits
    view2 = second.host_staging()
    assert device._staging_pool.hits == hits0 + 1, "staging not recycled"
    view2[:] = bytes([9]) * nbytes       # the next message's bytes
    first.deliver(placed)
    assert first.devbuf.last_transport == "staged"
    assert np.asarray(first.devbuf.array).min() == 7
    assert np.asarray(first.devbuf.array).max() == 7
    second.finalize_from_host(nbytes)
    assert int(np.asarray(second.devbuf.array)[0]) == 9


# ------------------------------------- in-process handoffs across devices


@pytest.fixture
def gate(monkeypatch):
    """The wait for a cross-device copy (DeviceRecvSink.land), held until
    the test opens it: what is in flight on a chip for a millisecond stays
    in flight here for as long as the test looks at it."""
    import threading

    from starway_tpu import device

    opened = threading.Event()
    real = device.DeviceRecvSink.land

    def gated(copy):
        assert opened.wait(30), "the test never opened the gate"
        real(copy)

    monkeypatch.setattr(device.DeviceRecvSink, "land", staticmethod(gated))
    yield opened
    opened.set()


async def _until(cond, what, timeout=10.0):
    loop = asyncio.get_running_loop()
    t_end = loop.time() + timeout
    while not cond():
        assert loop.time() < t_end, f"never happened: {what}"
        await asyncio.sleep(0.005)


def _reason(fut) -> str:
    return str(fut.exception())


@pytest.mark.parametrize("case", [
    "posted_first", "unexpected_then_post", "receiver_closes",
    "sender_closes", "recv_deadline", "copy_fails", "same_device"])
async def test_cross_device_handoff_lifecycle(port, gate, monkeypatch, case):
    """An in-process device payload for a sink on ANOTHER device: the copy
    is issued at delivery and waited for beside the engine.  Matching is
    decided at delivery; the receive, its send and a flush behind them
    complete only when the copy is resident, in delivery order; a close,
    a dead peer, a deadline or a failed copy settles both ends with the
    stable reasons and leaves nothing in flight."""
    from starway_tpu import device

    n = 4
    devs = jax.devices()
    server, client = await _pair(port)
    rx = server._server
    counters = rx.counters_snapshot
    srcs = [jax.device_put(jnp.full((256,), k + 1, jnp.float32), devs[0])
            for k in range(n)]
    sinks = [DeviceBuffer((256,), jnp.float32, device=devs[1 + k])
             for k in range(n)]
    order: list = []

    def note(fut, name):
        fut.add_done_callback(lambda _f: order.append(name))
        return fut

    try:
        if case == "same_device":
            sink = DeviceBuffer((256,), jnp.float32, device=devs[0])
            recv = server.arecv(sink, 1, MASK)
            send = client.asend(srcs[0], 1)
            # Inline on this thread, nothing to wait for: done on return.
            assert send.done() and recv.done()
            await client.aflush()
            assert sink.array is srcs[0] and sink.last_transport == "device"
            assert counters()["handoffs"] == 0
            return

        if case == "unexpected_then_post":
            sends = [client.asend(srcs[k], k) for k in range(n)]
            await asyncio.gather(*sends)     # parked: no copy, nothing held
            assert counters()["handoffs"] == 0
            recvs = [note(server.arecv(sinks[k], k, MASK), f"recv{k}")
                     for k in range(n)]
            flush = None
        elif case == "recv_deadline":
            n = 1
            outcome: list = []
            sink = device.DeviceRecvSink(sinks[0])
            rx.post_recv(sink, 0, MASK, lambda *a: outcome.append(a),
                         lambda r: outcome.append(r), owner=sink, timeout=0.05)
            recvs = []
            sends = [client.asend(srcs[0], 0)]
            flush = client.aflush()
        else:
            if case in ("receiver_closes", "sender_closes", "copy_fails"):
                n = 2
            recvs = [note(server.arecv(sinks[k], k, MASK), f"recv{k}")
                     for k in range(n)]
            sends = [note(client.asend(srcs[k], k), f"send{k}") for k in range(n)]
            flush = note(client.aflush(), "flush")

        # Every copy is ISSUED while the first has not landed ...
        await _until(lambda: counters()["handoffs"] == n, "all issued")
        assert counters()["handoffs_overlapped"] == n - 1
        assert len(rx.matcher.inflight) == len(rx.matcher.landing) == n
        # ... and nothing completed on the strength of an enqueue.
        await asyncio.sleep(0.05)
        pending = recvs + ([flush] if flush else [])
        if case != "unexpected_then_post":
            pending += sends
        assert not any(f.done() for f in pending)
        assert all(s.array is None for s in sinks)
        c0 = counters()
        assert c0["recvs_completed"] == 0
        if case != "unexpected_then_post":
            assert client._client.counters_snapshot()["sends_completed"] == 0

        if case == "receiver_closes":
            await server.aclose()
            await asyncio.wait(recvs + sends + [flush], timeout=10)
            assert all("cancel" in _reason(f) for f in recvs)
            assert all("not connected" in _reason(f) for f in sends + [flush])
            assert not rx.matcher.inflight and not rx.matcher.landing
            gate.set()
            await asyncio.sleep(0.05)
            assert all(s.array is None for s in sinks)   # copies dropped
            return
        if case == "sender_closes":
            await client.aclose()
            await asyncio.wait(sends + [flush], timeout=10)
            assert all("cancel" in _reason(f) for f in sends + [flush])
            assert not any(f.done() for f in recvs)
            gate.set()    # the copies were issued: the receives still land
            assert await asyncio.wait_for(asyncio.gather(*recvs), 10) == \
                [(k, srcs[k].nbytes) for k in range(n)]
        elif case == "recv_deadline":
            await _until(lambda: outcome, "the deadline")
            assert outcome == ["timed out"] or "timed out" in outcome[0]
            assert not rx.matcher.inflight and not sends[0].done()
            gate.set()    # the send (and the flush) still wait for the copy
            await asyncio.wait_for(asyncio.gather(sends[0], flush), 10)
            assert sinks[0].array is None and len(outcome) == 1
        elif case == "copy_fails":
            def broken(copy):
                raise RuntimeError("link down")

            monkeypatch.setattr(device.DeviceRecvSink, "land",
                                staticmethod(broken))
            gate.set()    # handoff 0 lands; handoff 1's wait raises
            await asyncio.wait(recvs + sends + [flush], timeout=10)
            assert await recvs[0] == (0, srcs[0].nbytes) and sends[0].exception() is None
            assert "device handoff failed" in _reason(recvs[1])
            assert "device handoff failed" in _reason(sends[1])
            assert flush.exception() is None     # resolved, as a failed pull is
            assert sinks[1].array is None
        else:
            gate.set()
            await asyncio.wait_for(
                asyncio.gather(*recvs, *sends, *([flush] if flush else [])), 10)
            await asyncio.sleep(0)    # the last done-callbacks
            if case == "posted_first":
                assert order == [f"{op}{k}" for k in range(n)
                                 for op in ("recv", "send")] + ["flush"]
            else:
                assert order == [f"recv{k}" for k in range(n)]
            for k in range(n):
                assert sinks[k].array.devices() == {devs[1 + k]}
                assert sinks[k].array.is_ready()
                assert sinks[k].last_transport == "device"
                assert float(sinks[k].array[0]) == k + 1
            assert counters()["recvs_completed"] == n
            await asyncio.wait_for(client.aflush(), 10)   # nothing held: inline
        assert not rx.matcher.inflight and not rx.matcher.landing
        assert not rx.matcher.held_flushes
    finally:
        gate.set()
        for w in (client, server):
            try:
                await w.aclose()
            except Exception:
                pass   # closed by the case itself


async def test_cross_device_handoffs_all_to_all_under_switching():
    """Four workers, every one sending to every other at once, round after
    round, with the interpreter switching threads every 10 us: posters,
    four placer threads and eight engine threads race on the matchers'
    handoff state.  Every round's twelve receives, sends and flushes
    complete with the right bytes, and when it is over nothing is held:
    no handoff in flight, no barrier waiting, every worker idle."""
    import sys

    n, rounds, devs = 4, 12, jax.devices()
    servers, clients = [], {}
    for k in range(n):
        s = Server()
        s.listen(SERVER_ADDR, 0)
        servers.append(s)
    for a in range(n):
        for b in range(n):
            if a != b:
                clients[(a, b)] = c = Client()
                await c.aconnect_address(servers[b].get_worker_address())
    pairs = sorted(clients)
    old = sys.getswitchinterval()
    sys.setswitchinterval(1e-5)
    try:
        for r in range(rounds):
            srcs = {(a, b): jax.device_put(
                jnp.full((4096,), 100 * r + 10 * a + b, jnp.int32), devs[a])
                for a, b in pairs}
            sinks = {(a, b): DeviceBuffer((4096,), jnp.int32, device=devs[b])
                     for a, b in pairs}
            recvs = [servers[b].arecv(sinks[(a, b)], a, MASK) for a, b in pairs]
            sends = [clients[(a, b)].asend(srcs[(a, b)], a) for a, b in pairs]
            flushes = [c.aflush() for c in clients.values()]
            await asyncio.wait_for(asyncio.gather(*recvs, *sends, *flushes), 60)
            for a, b in pairs:
                got = sinks[(a, b)].array
                assert got.devices() == {devs[b]} and got.is_ready()
                assert int(got[0]) == int(got[-1]) == 100 * r + 10 * a + b
    finally:
        sys.setswitchinterval(old)
    workers = [s._server for s in servers] + [c._client for c in clients.values()]
    assert sum(w.counters_snapshot()["handoffs"] for w in workers) == rounds * len(pairs)
    for w in workers:
        assert not w.matcher.landing and not w.matcher.inflight
        assert not w.matcher.held_flushes and not w.flush_records
        assert w._busy == 0
    for c in clients.values():
        await c.aclose()
    for s in servers:
        await s.aclose()


# ------------------------------- where a message waits (DESIGN.md §12, §13)


def _stage(worker, name: str) -> dict:
    return worker.stage_scope.snapshot().get(
        name, {"count": 0, "seconds": 0.0, "bytes": 0})


@pytest.mark.parametrize("case", [
    "handoff_across_devices", "handoff_same_device", "staged_over_full_ring",
    "loop_hop_from_engine_thread", "no_loop_hop_inline", "ring_carries_tag"])
async def test_message_stages(port, monkeypatch, case):
    """A message carries its own stamps and records them once, into the
    scope of the worker it waited in: ``post`` a call of the API;
    ``issue`` / ``land`` / ``settle`` a handoff onto another device;
    ``fetch_start`` / ``place_queue`` a staged message and ``ring_wait`` a
    block on a full ring; ``loop_hop`` a completion that crossed to the
    event loop; with a ring on the scope every phase of one message under
    that message's tag."""
    from starway_tpu import trace as trace_mod
    from starway_tpu.core import swtrace

    devs = jax.devices()
    if case == "staged_over_full_ring":
        import platform

        if platform.machine() not in ("x86_64", "AMD64"):
            pytest.skip("python sm transport requires x86-64")
        monkeypatch.setenv("STARWAY_TLS", "sm,tcp")
        monkeypatch.setenv("STARWAY_SM_RING", "65536")  # under one message
    elif case == "loop_hop_from_engine_thread":
        monkeypatch.setenv("STARWAY_TLS", "tcp")
    if case in ("staged_over_full_ring", "loop_hop_from_engine_thread"):
        monkeypatch.setenv("STARWAY_NATIVE", "0")
        monkeypatch.setenv("STARWAY_DEVPULL", "0")
    if case == "ring_carries_tag":
        monkeypatch.setenv("STARWAY_TRACE", "1")
        swtrace.reset()
    server, client = await _pair(port)
    rx, tx = server._server, client._client
    try:
        if case in ("loop_hop_from_engine_thread", "no_loop_hop_inline"):
            n = 5
            sinks = [np.empty(256, np.uint8) for _ in range(n)]
            recvs = [server.arecv(b, 0x70 + k, MASK) for k, b in enumerate(sinks)]
            await asyncio.sleep(0.05)
            await asyncio.gather(*(client.asend(np.full(256, k, np.uint8), 0x70 + k)
                                   for k in range(n)))
            await asyncio.gather(*recvs)
            await client.aflush()
            assert _stage(rx, "post")["count"] == n          # the receives
            assert _stage(tx, "post")["count"] == n + 1      # sends + flush
            if case == "no_loop_hop_inline":
                # Matched and completed on the poster's own thread, which
                # is the loop's: nothing crossed.
                assert _stage(rx, "loop_hop")["count"] == 0
                assert _stage(tx, "loop_hop")["count"] == 0
            else:
                # Completions fired on the engines' threads: every one
                # crossed, and waited a positive time for the loop.
                assert _stage(rx, "loop_hop")["count"] == n
                assert _stage(tx, "loop_hop")["count"] == n + 1
                assert _stage(rx, "loop_hop")["seconds"] > 0
            return

        n = 3
        words = (1 << 20) // 4 if case == "staged_over_full_ring" else 4096
        same = case == "handoff_same_device"
        srcs = [jax.device_put(jnp.full((words,), k + 1, jnp.int32), devs[0])
                for k in range(n)]
        sinks = [DeviceBuffer((words,), jnp.int32,
                              device=devs[0] if same else devs[1 + k])
                 for k in range(n)]
        recvs = [server.arecv(s, 0xA0 + k, MASK) for k, s in enumerate(sinks)]
        await asyncio.sleep(0.02)
        sends = [client.asend(a, 0xA0 + k) for k, a in enumerate(srcs)]
        await asyncio.gather(*sends, *recvs)
        await client.aflush()
        for k, s in enumerate(sinks):
            assert int(s.array[0]) == int(s.array[-1]) == k + 1
        assert _stage(rx, "post")["count"] == n
        assert _stage(tx, "post")["count"] == n + 1

        if case == "handoff_same_device":
            # A reference handoff, complete on return: no copy, no wait.
            assert set(rx.stage_scope.snapshot()) == {"post"}
            assert set(tx.stage_scope.snapshot()) <= {"post", "loop_hop"}
            assert rx.counters_snapshot()["handoffs"] == 0
        elif case == "staged_over_full_ring":
            assert _stage(tx, "fetch_start")["count"] == n
            assert _stage(tx, "stage")["count"] == n
            assert _stage(rx, "place_queue")["count"] == n
            assert _stage(rx, "place")["count"] == n
            assert _stage(rx, "place_queue")["seconds"] >= 0
            # 16 rings' worth a message: the producer blocked again and
            # again, and a message's blocks are ONE sample, recorded when
            # its last byte is in the ring, however many puts it took.
            waits, puts = _stage(tx, "ring_wait"), _stage(tx, "tx")
            assert 1 <= waits["count"] <= n + 1 < puts["count"], (waits, puts)
            assert waits["seconds"] > 0
        else:
            assert rx.counters_snapshot()["handoffs"] == n
            for name in ("issue", "land", "settle"):
                got = _stage(rx, name)
                assert got["count"] == n, (name, got)
                assert got["seconds"] >= 0
            assert _stage(rx, "issue")["bytes"] == n * words * 4
            assert not set(tx.stage_scope.snapshot()) & {"issue", "land", "settle"}

        if case == "ring_carries_tag":
            # One message, one identifier: its phases on the receiving
            # worker, in the order their stamps were taken ...
            want = ["post", "issue", "land", "settle", "loop_hop"]
            for k in range(n):
                mine = [e for e in rx.trace_events()
                        if e[1] == swtrace.EV_STAGE and e[2] == 0xA0 + k]
                assert sorted((e[5] for e in mine), key=want.index) == want, mine
                ends = {e[5]: e[0] for e in mine}
                assert (ends["post"] <= ends["issue"] <= ends["land"]
                        <= ends["settle"]), ends
            assert all(e[2] for e in rx.trace_events()
                       if e[1] == swtrace.EV_STAGE), "an untagged message stage"
            # ... and the Chrome export draws them as ONE chain a message.
            doc = trace_mod.to_chrome(
                [{"worker": rx.trace_label, "events": rx.trace_events()}])
            spans = [e for e in doc["traceEvents"] if e.get("cat") == "stage"]
            tracks = {e["tid"] for e in spans if e["args"]["tag"] == 0xA1}
            assert len(tracks) == 1, tracks
            chain = sorted((e for e in spans if e["tid"] in tracks),
                           key=lambda e: e["ts"] + e["dur"])
            assert [e["name"] for e in chain if e["name"] != "loop_hop"] == want[:4]
            names = {e["args"]["name"] for e in doc["traceEvents"]
                     if e["ph"] == "M" and e["name"] == "thread_name"}
            assert "msg tag=0xa1" in names, names
    finally:
        await client.aclose()
        await server.aclose()
