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
