"""Cross-process device plane: PJRT transfer-server pull (T_DEVPULL).

The reference's value proposition is zero-copy RDMA into the receiver's
buffer (reference: src/bindings/main.cpp:370,1172).  These tests pin the TPU
build's equivalent: device payloads crossing processes ride a PJRT pull
(descriptor over the framed stream, buffer device-to-device over the PJRT
socket) instead of being staged through host bytes, and the flush barrier
covers the pulled payload (FLUSH_ACK deferred until pulls resolve).

Runs on the virtual CPU mesh; the same code path carries TPU arrays on real
hardware (jax.experimental.transfer is the DCN cross-slice machinery).
"""

import asyncio
import gc
import multiprocessing

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from starway_tpu import Client, DeviceBuffer, Server

pytestmark = pytest.mark.asyncio

MASK = (1 << 64) - 1
N = 1 << 20  # 1 MiB: comfortably above STARWAY_DEVPULL_MIN


def _pull_available() -> bool:
    """Whether devpull would be negotiated in this process.  Brings the
    jax backend up first: the probe itself never initialises one."""
    jax.devices()
    from starway_tpu.device import devpull_supported

    return devpull_supported()


@pytest.fixture
def _needs_pull():
    if not _pull_available():
        pytest.skip("devpull_supported() is False here; payloads stage")


# A fixture, not a module-level skipif: the probe brings the jax backend
# up, and the spawned children of this file import it -- a
# jax.distributed member must initialise BEFORE its backend exists.
requires_pull = pytest.mark.usefixtures("_needs_pull")


@pytest.fixture(autouse=True)
def _force_tcp(monkeypatch):
    # The inproc fast path would bypass the wire; devpull is a wire feature.
    monkeypatch.setenv("STARWAY_TLS", "tcp")
    # devpull is negotiated by the Python engine (the C++ engine cannot run
    # JAX pulls; negotiation makes mixed pairings fall back safely).
    monkeypatch.setenv("STARWAY_NATIVE", "0")
    # The capability is only advertised once the jax backend is up (the
    # handshake never initialises a backend) -- make sure it is.
    jax.devices()


async def _pair(port):
    server = Server()
    client = Client()
    server.listen("127.0.0.1", port)
    await client.aconnect("127.0.0.1", port)
    return server, client


@requires_pull
async def test_devpull_same_host_two_workers(port):
    """Two workers over a real socket in one process: the payload must
    arrive via the pull path (array handoff), not host staging."""
    server, client = await _pair(port)
    try:
        src = jax.device_put(jnp.arange(N, dtype=jnp.uint8))
        sink = DeviceBuffer((N,), jnp.uint8)

        recv_fut = server.arecv(sink, 0x77, MASK)
        await asyncio.sleep(0.05)
        send_fut = client.asend(src, 0x77)
        # Drop the sender-side reference: the transfer server must keep the
        # buffer alive until pulled.
        del src
        gc.collect()
        await send_fut
        tag, length = await recv_fut

        assert (tag, length) == (0x77, N)
        assert sink.last_transport == "device", (
            f"expected PJRT pull, got {sink.last_transport}")
        np.testing.assert_array_equal(
            np.asarray(sink.array), np.arange(N, dtype=np.uint8))
    finally:
        await client.aclose()
        await server.aclose()


async def test_devpull_flush_covers_pull(port):
    """aflush must not complete until the receiver has pulled: after
    flush + close, the payload is resident at the receiver even though no
    receive was posted yet (force-started by the FLUSH barrier)."""
    server, client = await _pair(port)
    try:
        src = jax.device_put(jnp.full(N, 7, dtype=jnp.uint8))
        await client.asend(src, 0x88)
        await client.aflush()
        await client.aclose()

        sink = DeviceBuffer((N,), jnp.uint8)
        tag, length = await asyncio.wait_for(server.arecv(sink, 0x88, MASK), 10)
        assert (tag, length) == (0x88, N)
        np.testing.assert_array_equal(
            np.asarray(sink.array), np.full(N, 7, dtype=np.uint8))
    finally:
        await server.aclose()


async def test_devpull_disabled_falls_back_to_staging(port, monkeypatch):
    monkeypatch.setenv("STARWAY_DEVPULL", "0")
    server, client = await _pair(port)
    try:
        src = jax.device_put(jnp.arange(N, dtype=jnp.uint8))
        sink = DeviceBuffer((N,), jnp.uint8)
        recv_fut = server.arecv(sink, 0x99, MASK)
        await asyncio.sleep(0.05)
        await client.asend(src, 0x99)
        tag, length = await recv_fut
        assert (tag, length) == (0x99, N)
        assert sink.last_transport == "staged"
        np.testing.assert_array_equal(
            np.asarray(sink.array), np.arange(N, dtype=np.uint8))
    finally:
        await client.aclose()
        await server.aclose()


async def test_devpull_host_buffer_recv(port):
    """A plain host-byte receive matching a pulled payload still delivers
    (pull to device, then stage into the host buffer)."""
    server, client = await _pair(port)
    try:
        src = jax.device_put(jnp.arange(N, dtype=jnp.uint8))
        buf = np.zeros(N, dtype=np.uint8)
        recv_fut = server.arecv(buf, 0xAA, MASK)
        await asyncio.sleep(0.05)
        await client.asend(src, 0xAA)
        tag, length = await asyncio.wait_for(recv_fut, 10)
        assert (tag, length) == (0xAA, N)
        np.testing.assert_array_equal(buf, np.arange(N, dtype=np.uint8))
    finally:
        await client.aclose()
        await server.aclose()


@pytest.mark.parametrize(
    "server_native,client_native",
    [(True, True), (True, False), (False, True)],
    ids=["native/native", "native-server/py-client", "py-server/native-client"],
)
@requires_pull
async def test_devpull_engine_matrix(port, monkeypatch, server_native, client_native):
    """devpull is one wire contract across BOTH engines: every pairing
    negotiates it and the payload arrives via the pull path (the native
    engine surfaces descriptors to its wrapper, which owns the pulls)."""
    from starway_tpu.core import native

    if not native.available():
        pytest.skip("native engine unavailable")

    monkeypatch.setenv("STARWAY_NATIVE", "1" if server_native else "0")
    server = Server()
    server.listen("127.0.0.1", port)
    monkeypatch.setenv("STARWAY_NATIVE", "1" if client_native else "0")
    client = Client()
    await client.aconnect("127.0.0.1", port)
    try:
        src = jax.device_put(jnp.arange(N, dtype=jnp.uint8))
        sink = DeviceBuffer((N,), jnp.uint8)
        recv_fut = server.arecv(sink, 0x66, MASK)
        await asyncio.sleep(0.05)
        await client.asend(src, 0x66)
        tag, length = await asyncio.wait_for(recv_fut, 15)
        assert (tag, length) == (0x66, N)
        assert sink.last_transport == "device", (
            f"expected PJRT pull, got {sink.last_transport}")
        np.testing.assert_array_equal(
            np.asarray(sink.array), np.arange(N, dtype=np.uint8))

        # Unexpected-then-post, with a flush barrier that must wait for the
        # eager pull.
        src2 = jax.device_put(jnp.full(N, 9, dtype=jnp.uint8))
        await client.asend(src2, 0x67)
        await client.aflush()
        sink2 = DeviceBuffer((N,), jnp.uint8)
        tag, length = await asyncio.wait_for(server.arecv(sink2, 0x67, MASK), 15)
        assert (tag, length) == (0x67, N)
        np.testing.assert_array_equal(
            np.asarray(sink2.array), np.full(N, 9, dtype=np.uint8))

        # Flush means "payload resident at the receiver": it survives the
        # sender's close even though no receive was posted yet.
        src3 = jax.device_put(jnp.full(N, 11, dtype=jnp.uint8))
        await client.asend(src3, 0x68)
        await client.aflush()
        await client.aclose()
        sink3 = DeviceBuffer((N,), jnp.uint8)
        tag, length = await asyncio.wait_for(server.arecv(sink3, 0x68, MASK), 15)
        assert (tag, length) == (0x68, N)
        np.testing.assert_array_equal(
            np.asarray(sink3.array), np.full(N, 11, dtype=np.uint8))
    finally:
        try:
            await client.aclose()
        except Exception:
            pass  # already closed by the last phase
        await server.aclose()


@pytest.mark.parametrize("native", [False, True], ids=["py", "native"])
async def test_devpull_same_tag_fifo_with_staged(port, monkeypatch, native):
    """Mixed transports on ONE tag keep arrival order: a staged DATA
    message sent before a devpull descriptor is received first.  Pins the
    one-unexpected-stream contract on both engines (descriptor records sit
    in the same FIFO as staged messages)."""
    if native:
        from starway_tpu.core import native as native_mod

        if not native_mod.available():
            pytest.skip("native engine unavailable")
        monkeypatch.setenv("STARWAY_NATIVE", "1")

    server, client = await _pair(port)
    try:
        small = np.full(1024, 3, dtype=np.uint8)  # below devpull threshold
        big = jax.device_put(jnp.full(N, 4, dtype=jnp.uint8))
        await client.asend(small, 0xD1)
        await client.aflush()
        await client.asend(big, 0xD1)
        await client.aflush()

        buf = np.zeros(N, dtype=np.uint8)
        tag, n1 = await asyncio.wait_for(server.arecv(buf, 0xD1, MASK), 10)
        assert (tag, n1) == (0xD1, 1024), "staged message must arrive first"
        np.testing.assert_array_equal(buf[:1024], small)

        sink = DeviceBuffer((N,), jnp.uint8)
        tag, n2 = await asyncio.wait_for(server.arecv(sink, 0xD1, MASK), 10)
        assert (tag, n2) == (0xD1, N)
        np.testing.assert_array_equal(
            np.asarray(sink.array), np.full(N, 4, dtype=np.uint8))
    finally:
        await client.aclose()
        await server.aclose()


@pytest.mark.parametrize("native", [False, True], ids=["py", "native"])
@pytest.mark.parametrize("recv_first", [True, False],
                         ids=["recv-first", "descriptor-first"])
async def test_devpull_truncation(port, monkeypatch, native, recv_first):
    """A too-small receive matching a devpull payload fails with the
    truncation error on both engines, whether it was posted before the
    descriptor arrived or claims it from the unexpected stream."""
    if native:
        from starway_tpu.core import native as native_mod

        if not native_mod.available():
            pytest.skip("native engine unavailable")
        monkeypatch.setenv("STARWAY_NATIVE", "1")

    server, client = await _pair(port)
    try:
        small = np.zeros(1024, dtype=np.uint8)  # payload is N >> 1024
        big = jax.device_put(jnp.full(N, 5, dtype=jnp.uint8))
        if recv_first:
            recv_fut = server.arecv(small, 0xE1, MASK)
            await asyncio.sleep(0.05)
            await client.asend(big, 0xE1)
        else:
            # NO flush before the receive: the truncation path itself must
            # drain-pull the payload, or the barrier below hangs.
            await client.asend(big, 0xE1)
            await asyncio.sleep(0.2)  # descriptor lands unclaimed
            recv_fut = server.arecv(small, 0xE1, MASK)
        with pytest.raises(Exception, match="[Tt]runcat"):
            await asyncio.wait_for(recv_fut, 10)
        # The sender is not wedged: the flush barrier still completes
        # (the payload is drain-pulled whatever happened to the receive).
        await asyncio.wait_for(client.aflush(), 10)
    finally:
        await client.aclose()
        await server.aclose()


async def test_devpull_flush_not_blocked_by_later_send(port):
    """The FLUSH barrier waits only for descriptors that preceded it: a
    devpull sent after the flush (for a tag nobody receives) must not hold
    the barrier hostage."""
    server, client = await _pair(port)
    try:
        a = jax.device_put(jnp.full(N, 1, dtype=jnp.uint8))
        b = jax.device_put(jnp.full(N, 2, dtype=jnp.uint8))
        await client.asend(a, 0xC1)
        flush_fut = client.aflush()
        await asyncio.sleep(0.02)
        await client.asend(b, 0xC2)  # never received
        await asyncio.wait_for(flush_fut, 10)

        sink = DeviceBuffer((N,), jnp.uint8)
        tag, length = await asyncio.wait_for(server.arecv(sink, 0xC1, MASK), 10)
        assert (tag, length) == (0xC1, N)
        np.testing.assert_array_equal(
            np.asarray(sink.array), np.full(N, 1, dtype=np.uint8))
    finally:
        await client.aclose()
        await server.aclose()


# --------------------------------------------------------- multiprocess


def _child_send_device(port, flush_then_close):
    import os

    os.environ["STARWAY_TLS"] = "tcp"
    os.environ["STARWAY_NATIVE"] = "0"
    import jax

    jax.config.update("jax_platforms", "cpu")
    import asyncio

    import jax.numpy as jnp

    from starway_tpu import Client

    jax.devices()  # devpull is only advertised once the backend is up

    async def run():
        client = Client()
        for _ in range(80):
            try:
                await client.aconnect("127.0.0.1", port)
                break
            except Exception:
                client = Client()
                await asyncio.sleep(0.1)
        arr = jax.device_put(jnp.arange(N, dtype=jnp.uint8))
        await client.asend(arr, 0xBB)
        if flush_then_close:
            await client.aflush()
            await client.aclose()
        else:
            # keep the worker (and its transfer server) alive for the pull
            await asyncio.sleep(15)

    asyncio.run(run())


@requires_pull
async def test_devpull_cross_process(port):
    """Real two-process transfer: jax.Array crosses processes via the pull
    path into a DeviceBuffer, bytes never staged through this framework."""
    ctx = multiprocessing.get_context("spawn")
    proc = ctx.Process(target=_child_send_device, args=(port, False), daemon=True)
    server = Server()
    server.listen("127.0.0.1", port)
    proc.start()
    try:
        sink = DeviceBuffer((N,), jnp.uint8)
        tag, length = await asyncio.wait_for(server.arecv(sink, 0xBB, MASK), 30)
        assert (tag, length) == (0xBB, N)
        assert sink.last_transport == "device", (
            f"expected PJRT pull, got {sink.last_transport}")
        np.testing.assert_array_equal(
            np.asarray(sink.array), np.arange(N, dtype=np.uint8))
    finally:
        proc.terminate()
        proc.join(5)
        await server.aclose()


def _distributed_member(role, coord_port, data_port, q):
    """One jax.distributed member (the DCN-analogue topology of SURVEY
    section 7 step 4): joins the 2-process coordination service, then
    exchanges device payloads over devpull like any other peer."""
    import os
    import traceback

    os.environ["STARWAY_TLS"] = "tcp"
    os.environ["STARWAY_NATIVE"] = "0"
    try:
        import jax

        jax.config.update("jax_platforms", "cpu")
        from starway_tpu.mesh import bootstrap_distributed

        bootstrap_distributed(f"127.0.0.1:{coord_port}", 2,
                              0 if role == "server" else 1)
        assert jax.process_count() == 2
        # The runtime spans both members (each contributes its local
        # devices; the count per member depends on inherited XLA_FLAGS).
        assert len(jax.devices()) == 2 * len(jax.local_devices())
        jax.devices()  # devpull is only advertised once the backend is up

        import asyncio

        import jax.numpy as jnp
        import numpy as np

        from starway_tpu import Client, DeviceBuffer, Server

        async def run():
            if role == "server":
                server = Server()
                server.listen("127.0.0.1", data_port)
                sink = DeviceBuffer((N,), jnp.uint8)
                tag, length = await asyncio.wait_for(
                    server.arecv(sink, 0xD0, MASK), 60)
                assert (tag, length) == (0xD0, N)
                assert sink.last_transport == "device", sink.last_transport
                np.testing.assert_array_equal(
                    np.asarray(sink.array), np.arange(N, dtype=np.uint8))
                # Reply with a device payload the other way; flush makes it
                # resident at the peer before this side tears down.
                ep = server.list_clients().pop()
                await server.asend(
                    ep, jax.device_put(jnp.full(N, 9, dtype=jnp.uint8)), 0xD1)
                await server.aflush()
                await server.aclose()
            else:
                client = Client()
                for _ in range(100):
                    try:
                        await client.aconnect("127.0.0.1", data_port)
                        break
                    except Exception:
                        client = Client()
                        await asyncio.sleep(0.1)
                else:
                    raise RuntimeError(
                        f"could not connect to 127.0.0.1:{data_port}")
                await client.asend(
                    jax.device_put(jnp.arange(N, dtype=jnp.uint8)), 0xD0)
                sink = DeviceBuffer((N,), jnp.uint8)
                tag, length = await asyncio.wait_for(
                    client.arecv(sink, 0xD1, MASK), 60)
                assert (tag, length) == (0xD1, N)
                np.testing.assert_array_equal(
                    np.asarray(sink.array), np.full(N, 9, dtype=np.uint8))
                await client.aclose()

        asyncio.run(run())
        q.put((role, "ok"))
    except Exception:
        q.put((role, traceback.format_exc()))


@requires_pull
async def test_devpull_between_jax_distributed_members(port):
    """Two spawned processes, EACH a jax.distributed member (CPU backend),
    exchange device payloads over devpull in both directions — the
    cross-host DCN topology minus real DCN links (VERDICT r2 next #6; see
    DESIGN.md section 7 for what real-DCN validation still needs)."""
    from conftest import free_port

    ctx = multiprocessing.get_context("spawn")
    q = ctx.Queue()
    coord_port = free_port()
    while coord_port == port:
        coord_port = free_port()
    procs = [
        ctx.Process(target=_distributed_member,
                    args=(role, coord_port, port, q), daemon=True)
        for role in ("server", "client")
    ]
    for p in procs:
        p.start()
    try:
        results = {}
        loop = asyncio.get_running_loop()
        for _ in range(2):
            role, status = await loop.run_in_executor(
                None, lambda: q.get(timeout=180))
            results[role] = status
        assert results.get("server") == "ok", results.get("server")
        assert results.get("client") == "ok", results.get("client")
    finally:
        for p in procs:
            p.join(10)
            if p.is_alive():
                p.terminate()
                p.join(5)


async def test_devpull_cross_process_flush_close(port):
    """Sender flushes then closes before the receive is posted: the FLUSH
    barrier pulls the payload across, so it survives the sender's close."""
    ctx = multiprocessing.get_context("spawn")
    proc = ctx.Process(target=_child_send_device, args=(port, True), daemon=True)
    server = Server()
    server.listen("127.0.0.1", port)
    proc.start()
    try:
        proc.join(30)  # sender has flushed, closed, and exited
        sink = DeviceBuffer((N,), jnp.uint8)
        tag, length = await asyncio.wait_for(server.arecv(sink, 0xBB, MASK), 10)
        assert (tag, length) == (0xBB, N)
        np.testing.assert_array_equal(
            np.asarray(sink.array), np.arange(N, dtype=np.uint8))
    finally:
        if proc.is_alive():
            proc.terminate()
            proc.join(5)
        await server.aclose()
