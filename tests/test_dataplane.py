"""Data-plane pipelining tests (DESIGN.md §12).

Covers the hot path: device payloads staged whole across the framed stream
(one prefetched fetch, one placement beside the engine), the pooled staging buffers,
the gathered socket TX pump, per-stage telemetry, and -- the pinned
regression -- batched completion delivery: a burst of N completions crosses
the engine->asyncio boundary in O(1) ``call_soon_threadsafe`` hops, not N.
"""

import asyncio
import time

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from starway_tpu import Client, DeviceBuffer, Server, device, perf

pytestmark = pytest.mark.asyncio

ADDR = "127.0.0.1"
MASK = (1 << 64) - 1


async def _pair(port):
    server = Server()
    client = Client()
    server.listen(ADDR, port)
    await client.aconnect(ADDR, port)
    for _ in range(200):
        if server.list_clients():
            break
        await asyncio.sleep(0.005)
    return server, client, server.list_clients().pop()


def _force_tcp(monkeypatch, *, native: bool, tls: str = "tcp"):
    """The framed stream (no pull) over ``tls``: "tcp" is the real socket,
    "sm,tcp" the shared-memory ring behind it (Python engine only)."""
    monkeypatch.setenv("STARWAY_TLS", tls)
    monkeypatch.setenv("STARWAY_NATIVE", "1" if native else "0")
    monkeypatch.setenv("STARWAY_DEVPULL", "0")  # exercise the framed stream


# ------------------------------------------------- completion batching


@pytest.mark.parametrize("engine", ["python", "native"])
async def test_completion_batch_single_trampoline_hop(port, monkeypatch, engine):
    """A burst of N engine-thread completions reaches asyncio in O(1)
    call_soon_threadsafe hops (the api-layer trampoline batches them);
    pinned for BOTH engines."""
    if engine == "native":
        from starway_tpu.core import native

        if not native.available():
            pytest.skip("native engine unavailable")
    _force_tcp(monkeypatch, native=(engine == "native"))
    server, client, _ep = await _pair(port)
    loop = asyncio.get_running_loop()
    try:
        n_ops = 32
        sinks = [np.empty(256, dtype=np.uint8) for _ in range(n_ops)]
        recv_futs = [server.arecv(b, 0x900 + i, MASK) for i, b in enumerate(sinks)]
        await asyncio.sleep(0.1)  # recvs posted on the engine

        hops = {"n": 0}
        orig = loop.call_soon_threadsafe

        def counting(cb, *args):
            hops["n"] += 1
            return orig(cb, *args)

        monkeypatch.setattr(loop, "call_soon_threadsafe", counting)
        payloads = [np.full(256, i % 251, dtype=np.uint8) for i in range(n_ops)]
        send_futs = [client.asend(p, 0x900 + i) for i, p in enumerate(payloads)]
        # Block the loop thread: every send/recv completion (2*n_ops of
        # them) must pile up behind ONE scheduled drain, not n per op.
        time.sleep(0.5)
        await asyncio.gather(*send_futs, *recv_futs)
        monkeypatch.setattr(loop, "call_soon_threadsafe", orig)

        assert 1 <= hops["n"] <= n_ops // 4, (
            f"{2 * n_ops} completions took {hops['n']} call_soon_threadsafe "
            "hops; expected an O(1) batch")
        for i, b in enumerate(sinks):
            np.testing.assert_array_equal(b, payloads[i])
    finally:
        await client.aclose()
        await server.aclose()


# --------------------------------------- staged device payloads, whole


@pytest.mark.parametrize("nbytes", [512 * 1024, 4 << 20],
                         ids=["512K", "4M"])
@pytest.mark.parametrize("tls", ["tcp", "sm,tcp"], ids=["tcp", "sm"])
async def test_staged_device_message_crosses_whole(port, monkeypatch, tls,
                                                   nbytes):
    """A device payload on the framed stream is fetched and placed WHOLE
    (DESIGN.md §12): exactly one ``stage`` and one ``place`` a message,
    whatever its size, and the receive's completion means the array is
    resident on the device named."""
    _force_tcp(monkeypatch, native=False, tls=tls)
    server, client, _ep = await _pair(port)
    try:
        src = jax.device_put(
            jnp.arange(nbytes // 4, dtype=jnp.float32), jax.devices()[0])
        sink = DeviceBuffer((nbytes // 4,), jnp.float32,
                            device=jax.devices()[3])
        perf.stage_reset()
        recv_fut = server.arecv(sink, 31, MASK)
        await asyncio.sleep(0.01)
        await client.asend(src, 31)
        tag, length = await recv_fut
        assert sink.array.is_ready(), "receive completed before residency"
        assert (tag, length) == (31, nbytes)
        assert sink.array.devices() == {jax.devices()[3]}
        assert sink.last_transport == "staged"
        np.testing.assert_array_equal(np.asarray(sink.array), np.asarray(src))
        snap = perf.stage_snapshot()
        assert snap["stage"]["count"] == 1, snap
        assert snap["place"]["count"] == 1, snap
        assert snap["stage"]["bytes"] == snap["place"]["bytes"] == nbytes
    finally:
        await client.aclose()
        await server.aclose()


async def test_staged_send_with_queued_frames_behind(port, monkeypatch):
    """Frames queued behind a staged device send whose host view is not
    materialised yet must NOT ride the same gathered sendmsg pass (their
    bytes would land inside the in-flight DATA payload).  Regression for
    the _gather_tx over-offer: a staged payload + a second send + a flush,
    all queued in one burst, must deliver both payloads intact and
    complete the flush -- after the device bytes are resident."""
    _force_tcp(monkeypatch, native=False)
    server, client, _ep = await _pair(port)
    try:
        src = jax.device_put(
            jnp.arange(256 * 1024, dtype=jnp.float32), jax.devices()[0])
        tail = np.random.randint(0, 255, 2048, dtype=np.uint8)
        sink = DeviceBuffer((256 * 1024,), jnp.float32, device=jax.devices()[1])
        tail_sink = np.empty(2048, dtype=np.uint8)
        f1 = server.arecv(sink, 61, MASK)
        f2 = server.arecv(tail_sink, 62, MASK)
        await asyncio.sleep(0.01)
        s1 = client.asend(src, 61)
        s2 = client.asend(tail, 62)
        await client.aflush()
        # The barrier covers the placement that runs beside the engine.
        assert f1.done() or sink.array is not None
        await asyncio.gather(s1, s2, f1, f2)
        np.testing.assert_array_equal(np.asarray(sink.array), np.asarray(src))
        np.testing.assert_array_equal(tail_sink, tail)
    finally:
        await client.aclose()
        await server.aclose()


async def test_flush_waits_for_device_placement(port, monkeypatch):
    """``aflush`` returns only after every byte sent is RESIDENT on the
    receiver's device: a placement held up beside the engine holds the
    FLUSH_ACK back, and the receive with it."""
    import threading

    _force_tcp(monkeypatch, native=False)
    gate = threading.Event()
    orig = device.DeviceRecvSink.place

    def slow_place(self, length):
        gate.wait(10)
        return orig(self, length)

    monkeypatch.setattr(device.DeviceRecvSink, "place", slow_place)
    server, client, _ep = await _pair(port)
    try:
        src = np.random.randint(0, 255, 256 * 1024, dtype=np.uint8)
        sink = DeviceBuffer((256 * 1024,), jnp.uint8, device=jax.devices()[2])
        recv_fut = server.arecv(sink, 63, MASK)
        await asyncio.sleep(0.01)
        await client.asend(src, 63)
        flush_fut = asyncio.ensure_future(client.aflush())
        await asyncio.sleep(0.3)
        assert not flush_fut.done(), "flush returned before residency"
        assert not recv_fut.done(), "receive completed before residency"
        assert sink.array is None
        gate.set()
        await asyncio.wait_for(asyncio.gather(flush_fut, recv_fut), 10)
        assert sink.array.is_ready()
        np.testing.assert_array_equal(np.asarray(sink.array), src)
    finally:
        gate.set()
        await client.aclose()
        await server.aclose()


async def test_prefetch_window_is_bounded_in_bytes(port, monkeypatch):
    """200 device sends queued at once: the copies started ahead of the TX
    pump never hold more than the window's bound, every send is prefetched
    in the end, and every payload arrives intact."""
    _force_tcp(monkeypatch, native=False)
    nbytes, n_msgs = 64 * 1024, 200
    window = device._PrefetchWindow(cap_bytes=8 * nbytes)
    monkeypatch.setattr(device, "_prefetch", window)
    server, client, _ep = await _pair(port)
    try:
        srcs = [jax.device_put(jnp.full((nbytes,), i % 251, dtype=jnp.uint8),
                               jax.devices()[i % 4]) for i in range(n_msgs)]
        sinks = [DeviceBuffer((nbytes,), jnp.uint8, device=jax.devices()[4])
                 for _ in range(n_msgs)]
        recvs = [server.arecv(b, 0xA00 + i, MASK) for i, b in enumerate(sinks)]
        await asyncio.sleep(0.05)
        sends = [client.asend(a, 0xA00 + i) for i, a in enumerate(srcs)]
        await asyncio.gather(*sends, *recvs)
        await client.aflush()
        assert 0 < window.peak_bytes <= 8 * nbytes, window.peak_bytes
        assert 1 <= window.peak_depth <= 8, window.peak_depth
        assert window._held == 0 and window._depth == 0
        assert not window._waiting
        for i, b in enumerate(sinks):
            assert int(np.asarray(b.array)[0]) == i % 251
            assert int(np.asarray(b.array)[-1]) == i % 251
    finally:
        await client.aclose()
        await server.aclose()


@pytest.mark.parametrize("tls", ["tcp", "sm,tcp"], ids=["tcp", "sm"])
async def test_overwrite_hazard_both_directions(port, monkeypatch, tls):
    """The PR 21 hazard.  A sender that DELETES its array the moment
    ``done`` fires (the licence eager completion gives), and a receiver
    whose pooled staging buffer is reused at once by the next message of
    the same size, still deliver every message's original bytes."""
    _force_tcp(monkeypatch, native=False, tls=tls)
    server, client, ep = await _pair(port)
    loop = asyncio.get_running_loop()
    try:
        nbytes, n_msgs = 1 << 20, 12
        want = [np.random.randint(0, 255, nbytes, dtype=np.uint8)
                for _ in range(n_msgs)]
        srcs = [jax.device_put(w, jax.devices()[0]) for w in want]
        sinks = [DeviceBuffer((nbytes,), jnp.uint8, device=jax.devices()[5])
                 for _ in range(n_msgs)]
        hits0 = device._staging_pool.hits
        recvs = [server.arecv(b, 0xB00 + i, MASK) for i, b in enumerate(sinks)]
        await asyncio.sleep(0.05)
        sent = [loop.create_future() for _ in range(n_msgs)]

        def done(i):
            srcs[i].delete()  # on the engine thread, the moment it fires
            loop.call_soon_threadsafe(sent[i].set_result, None)

        def fail(i, reason):
            loop.call_soon_threadsafe(
                sent[i].set_exception, RuntimeError(reason))

        for i in range(n_msgs):
            client.send(srcs[i], 0xB00 + i, lambda i=i: done(i),
                        lambda r, i=i: fail(i, r))
        await asyncio.wait_for(asyncio.gather(*sent, *recvs), 30)
        await client.aflush()
        assert device._staging_pool.hits > hits0, "staging never reused"
        for i, b in enumerate(sinks):
            assert b.array.is_ready()
            np.testing.assert_array_equal(np.asarray(b.array), want[i])
        # ... and the other direction, host payloads overwritten at once.
        back = [DeviceBuffer((nbytes,), jnp.uint8, device=jax.devices()[6])
                for _ in range(n_msgs)]
        recvs = [client.arecv(b, 0xC00 + i, MASK) for i, b in enumerate(back)]
        await asyncio.sleep(0.05)
        buf = np.empty(nbytes, dtype=np.uint8)
        for i in range(n_msgs):
            buf[:] = want[i]
            await server.asend(ep, buf, 0xC00 + i)  # eager: buf is ours again
        await asyncio.wait_for(asyncio.gather(*recvs), 30)
        for i, b in enumerate(back):
            np.testing.assert_array_equal(np.asarray(b.array), want[i])
    finally:
        await client.aclose()
        await server.aclose()


# ------------------------------------------------------- staging pool


async def test_staging_pool_recycles_buffers(port, monkeypatch):
    """The second streamed receive of a size reuses the first's staging
    buffer instead of allocating (pool hit), because fast-path placement
    provably copied out of it."""
    _force_tcp(monkeypatch, native=False)
    server, client, _ep = await _pair(port)
    try:
        nbytes = 96 * 1024 + 512  # unlikely to collide with other suites
        src = np.random.randint(0, 255, nbytes, dtype=np.uint8)
        hits0 = device._staging_pool.hits
        for i in range(2):
            sink = DeviceBuffer((nbytes,), jnp.uint8, device=jax.devices()[0])
            recv_fut = server.arecv(sink, 40 + i, MASK)
            await asyncio.sleep(0.01)
            await client.asend(src, 40 + i)
            await recv_fut
            np.testing.assert_array_equal(np.asarray(sink.array), src)
        assert device._staging_pool.hits > hits0, (
            "second transfer did not reuse the pooled staging buffer")
    finally:
        await client.aclose()
        await server.aclose()


# ------------------------------------------- gathered TX + telemetry


async def test_small_send_burst_gathered_in_order(port, monkeypatch):
    """A burst of small sends coalesces through the gathered sendmsg pump
    and still delivers every payload, in tag order, with per-stage tx/rx
    telemetry recorded."""
    _force_tcp(monkeypatch, native=False)
    server, client, _ep = await _pair(port)
    try:
        perf.stage_reset()
        n_msgs = 64
        sinks = [np.empty(128, dtype=np.uint8) for _ in range(n_msgs)]
        recv_futs = [server.arecv(b, 0x700 + i, MASK) for i, b in enumerate(sinks)]
        await asyncio.sleep(0.05)
        payloads = [np.full(128, (i * 7) % 251, dtype=np.uint8) for i in range(n_msgs)]
        await asyncio.gather(
            *(client.asend(p, 0x700 + i) for i, p in enumerate(payloads)))
        await asyncio.gather(*recv_futs)
        await client.aflush()
        for i, b in enumerate(sinks):
            np.testing.assert_array_equal(b, payloads[i])
        snap = perf.stage_snapshot()
        assert snap.get("tx", {}).get("count", 0) > 0, snap
        assert snap.get("rx", {}).get("count", 0) > 0, snap
        # The gather batches the burst: far fewer sendmsg passes than
        # messages (each message is 145 bytes; one pass takes many).
        assert snap["tx"]["count"] < n_msgs, snap["tx"]
        assert snap["tx"]["bytes"] >= n_msgs * 128
    finally:
        await client.aclose()
        await server.aclose()


async def test_evaluate_perf_detail_reports_stages(port, monkeypatch):
    _force_tcp(monkeypatch, native=False)
    server, client, _ep = await _pair(port)
    try:
        detail = client.evaluate_perf_detail(1 << 20)
        assert "stages" in detail and isinstance(detail["stages"], dict)
    finally:
        await client.aclose()
        await server.aclose()
