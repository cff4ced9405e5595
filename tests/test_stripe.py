"""Multi-rail striping (DESIGN.md §17): one message, many transports.

The acceptance contract (ISSUE 8): with ``STARWAY_RAILS`` > 1 and
``STARWAY_STRIPE_THRESHOLD`` armed, a large asend is split at chunk
granularity, pushed across every lane concurrently (completion-driven
work stealing), and reassembled BYTE-EXACTLY by offset at the receiver --
in all four engine pairings, under FaultProxy ``duplicate``/``reorder``
chunk faults, and across a rail dying mid-message (the dead rail's
chunks redistribute onto survivors, with and without the session layer).
With the knobs unset the wire is byte-identical to the seed: no
``"rails"`` handshake key, no T_SDATA frames.

Wall-clock bounds are loose (noisy CI box): they prove "bounded, not
hung", not latency.
"""

import asyncio
import json
import socket

import numpy as np
import pytest

from starway_tpu import Client, Server
from starway_tpu.core import frames
from starway_tpu.testing.faults import FaultProxy

pytestmark = pytest.mark.asyncio

ADDR = "127.0.0.1"
MASK = (1 << 64) - 1

PAIRS = ["py-py", "native-native", "py-native", "native-py"]


@pytest.fixture(params=PAIRS)
def pair(request, monkeypatch):
    """(server_engine, client_engine, monkeypatch) with 3 rails and a
    1 MiB stripe threshold armed.  Workers sample the env at
    construction, so the per-side STARWAY_NATIVE flip happens in
    _mk_server/_mk_client."""
    s_eng, c_eng = request.param.split("-")
    if "native" in (s_eng, c_eng):
        from starway_tpu.core import native

        if not native.available():
            pytest.skip("native engine unavailable (no toolchain)")
    monkeypatch.setenv("STARWAY_TLS", "tcp")
    monkeypatch.setenv("STARWAY_RAILS", "3")
    monkeypatch.setenv("STARWAY_STRIPE_THRESHOLD", str(1 << 20))
    return s_eng, c_eng, monkeypatch


def _mk_server(eng, monkeypatch, port):
    monkeypatch.setenv("STARWAY_NATIVE", "1" if eng == "native" else "0")
    server = Server()
    server.listen(ADDR, port)
    return server


def _mk_client(eng, monkeypatch):
    monkeypatch.setenv("STARWAY_NATIVE", "1" if eng == "native" else "0")
    return Client()


async def _connect(client, server, port):
    await asyncio.wait_for(client.aconnect(ADDR, port), 30)
    for _ in range(1000):
        if server.list_clients():
            return server.list_clients().pop()
        await asyncio.sleep(0.005)
    raise AssertionError("server never accepted the client")


async def _aclose_all(*objs):
    for o in objs:
        try:
            await asyncio.wait_for(o.aclose(), timeout=15)
        except Exception:
            pass


def _counters(owner) -> dict:
    w = getattr(owner, "_client", None) or owner._server
    return w.counters_snapshot()


def _payload(n: int) -> np.ndarray:
    # Position-dependent bytes: any mis-offset chunk shows up as inequality.
    return (np.arange(n, dtype=np.uint64) % 251).astype(np.uint8)


# -------------------------------------------------- reassembly, 4 pairings


async def test_striped_reassembly_all_pairings(pair, port):
    """Byte-exact reassembly over 3 lanes in both directions, chunk
    counters live in both engines, and sub-threshold traffic stays off
    the stripe path -- the mixed-engine interop pin for ISSUE 8."""
    s_eng, c_eng, mp = pair
    server = _mk_server(s_eng, mp, port)
    client = _mk_client(c_eng, mp)
    try:
        ep = await _connect(client, server, port)
        n = 6 << 20
        payload = _payload(n)
        sink = np.zeros(n, dtype=np.uint8)
        rf = server.arecv(sink, 7, MASK)
        await asyncio.wait_for(client.asend(payload, 7), 30)
        await asyncio.wait_for(client.aflush(), 30)
        stag, ln = await asyncio.wait_for(rf, 30)
        assert (stag, ln) == (7, n)
        assert np.array_equal(sink, payload), "striped reassembly corrupt"
        # server -> client rides the same rail set (symmetric scheduler)
        sink2 = np.zeros(n, dtype=np.uint8)
        rf2 = client.arecv(sink2, 8, MASK)
        await asyncio.wait_for(server.asend(ep, payload, 8), 30)
        await asyncio.wait_for(server.aflush(), 30)
        await asyncio.wait_for(rf2, 30)
        assert np.array_equal(sink2, payload)
        cc, sc = _counters(client), _counters(server)
        assert cc["stripe_chunks_tx"] > 1, cc
        assert cc["stripe_chunks_rx"] > 1, cc
        assert sc["stripe_chunks_rx"] == cc["stripe_chunks_tx"], (cc, sc)
        # Sub-threshold messages keep the ordinary DATA path.
        before = _counters(client)["stripe_chunks_tx"]
        small = np.full(4096, 0x42, dtype=np.uint8)
        sink3 = np.zeros(4096, dtype=np.uint8)
        rf3 = server.arecv(sink3, 9, MASK)
        await asyncio.wait_for(client.asend(small, 9), 30)
        await asyncio.wait_for(rf3, 30)
        assert np.array_equal(sink3, small)
        assert _counters(client)["stripe_chunks_tx"] == before
    finally:
        await _aclose_all(client, server)


@pytest.mark.parametrize("eng", ["py", "native"])
async def test_striped_over_sm_plus_tcp(eng, port, monkeypatch):
    """tcp+sm concurrently on one host: the primary takes the sm-ring
    upgrade, the secondary rails stay on TCP, and one message stripes
    across both transport kinds byte-exactly (the Lane abstraction's
    interchangeability claim)."""
    if eng == "native":
        from starway_tpu.core import native

        if not native.available():
            pytest.skip("native engine unavailable (no toolchain)")
    else:
        from starway_tpu import config

        if not config.sm_enabled():
            pytest.skip("sm transport unavailable on this host")
    monkeypatch.setenv("STARWAY_TLS", "tcp,sm")
    monkeypatch.setenv("STARWAY_NATIVE", "1" if eng == "native" else "0")
    monkeypatch.setenv("STARWAY_RAILS", "2")
    monkeypatch.setenv("STARWAY_STRIPE_THRESHOLD", str(1 << 20))
    server = Server()
    server.listen(ADDR, port)
    client = Client()
    try:
        await _connect(client, server, port)
        if eng == "py":
            prim = client._client.primary_conn
            assert prim.sm_negotiated and len(prim.rails) == 1
        n = 6 << 20
        payload = _payload(n)
        sink = np.zeros(n, dtype=np.uint8)
        rf = server.arecv(sink, 41, MASK)
        await asyncio.wait_for(client.asend(payload, 41), 30)
        await asyncio.wait_for(client.aflush(), 30)
        await asyncio.wait_for(rf, 30)
        assert np.array_equal(sink, payload), "sm+tcp stripe corrupt"
        assert _counters(client)["stripe_chunks_tx"] > 1
    finally:
        await _aclose_all(client, server)


# ------------------------------------------------- chunk faults via proxy


@pytest.mark.parametrize("mode", ["duplicate", "reorder"])
async def test_striped_reassembly_under_chunk_faults(mode, port, monkeypatch):
    """FaultProxy duplicates / reorders T_SDATA units on the faulted
    direction: the receiver's offset dedup must keep the assembly
    byte-exact (chunks are idempotent and unordered by design)."""
    monkeypatch.setenv("STARWAY_TLS", "tcp")
    monkeypatch.setenv("STARWAY_NATIVE", "0")
    monkeypatch.setenv("STARWAY_RAILS", "2")
    monkeypatch.setenv("STARWAY_STRIPE_THRESHOLD", str(1 << 20))
    monkeypatch.setenv("STARWAY_STRIPE_CHUNK", str(256 << 10))
    server = Server()
    server.listen(ADDR, port)
    proxy = FaultProxy(ADDR, port, mode=mode, limit_bytes=1 << 20).start()
    client = Client()
    try:
        await asyncio.wait_for(client.aconnect(ADDR, proxy.port), 30)
        for _ in range(1000):
            if server.list_clients():
                break
            await asyncio.sleep(0.005)
        n = 4 << 20
        payload = _payload(n)
        sink = np.zeros(n, dtype=np.uint8)
        rf = server.arecv(sink, 11, MASK)
        await asyncio.wait_for(client.asend(payload, 11), 30)
        await asyncio.wait_for(client.aflush(), 30)
        await asyncio.wait_for(rf, 30)
        assert np.array_equal(sink, payload), f"corrupt under {mode}"
        if mode == "duplicate":
            # Duplicated chunks were drained, not double-counted: the
            # assembly ingests exactly the message's chunk set.
            sc = _counters(server)
            assert sc["stripe_chunks_rx"] == _counters(client)["stripe_chunks_tx"]
    finally:
        proxy.stop()
        await _aclose_all(client, server)


# ------------------------------------------------- rail death mid-message


def _client_rails(client):
    return list(client._client.primary_conn.rails)


async def test_rail_death_redistribution_no_session(port, monkeypatch):
    """A secondary lane dies mid-stripe WITHOUT sessions: its chunks
    re-queue onto the survivors (the payload is pinned until SACK, so the
    resend is legal) and the transfer still completes byte-exactly."""
    monkeypatch.setenv("STARWAY_TLS", "tcp")
    monkeypatch.setenv("STARWAY_NATIVE", "0")
    monkeypatch.setenv("STARWAY_RAILS", "3")
    monkeypatch.setenv("STARWAY_STRIPE_THRESHOLD", str(1 << 20))
    monkeypatch.setenv("STARWAY_STRIPE_CHUNK", str(256 << 10))
    server = Server()
    server.listen(ADDR, port)
    # Every lane runs through a byte pipe that can be stalled: while it
    # holds the bytes no chunk is SACKed, so the rail killed below holds
    # claimed chunks however this thread and the engine's are scheduled
    # (killing it "soon after asend" lost that race on a loaded box).
    proxy = FaultProxy(ADDR, port).start()
    client = Client()
    try:
        await _connect(client, server, proxy.port)
        rails = _client_rails(client)
        assert len(rails) == 2
        n = 32 << 20
        payload = _payload(n)
        sink = np.zeros(n, dtype=np.uint8)
        rf = server.arecv(sink, 21, MASK)
        proxy.stall()
        send_fut = client.asend(payload, 21)
        stripe = client._client.primary_conn.stripe
        for _ in range(6000):
            if any(src.rail_offs.get(rails[0].conn_id)
                   or src.done_offs.get(rails[0].conn_id)
                   for src in list(stripe.by_id.values())):
                break
            await asyncio.sleep(0.005)
        else:
            raise AssertionError("the rail never claimed a chunk")
        # Kill one secondary while its chunks are in flight (shutdown is
        # syscall-safe from this thread; the engine sees the reset).
        rails[0].sock.shutdown(socket.SHUT_RDWR)
        proxy.unstall()
        await asyncio.wait_for(send_fut, 30)
        await asyncio.wait_for(client.aflush(), 60)
        await asyncio.wait_for(rf, 60)
        assert np.array_equal(sink, payload), "corrupt after rail death"
        cc = _counters(client)
        assert cc["rail_resteals"] > 0, cc  # the dead rail held chunks
        assert len(_client_rails(client)) == 1  # pruned from the group
    finally:
        proxy.stop()
        await _aclose_all(client, server)


async def test_rail_death_with_session_does_not_suspend(port, monkeypatch):
    """Sessions journal per-MESSAGE, never per-lane: a secondary rail
    dying mid-stripe redistributes its chunks instead of suspending the
    session (no resume cycle), and a PRIMARY death afterwards takes the
    normal suspend -> redial -> re-dispatch path with the striped message
    still delivered exactly once."""
    monkeypatch.setenv("STARWAY_TLS", "tcp")
    monkeypatch.setenv("STARWAY_NATIVE", "0")
    monkeypatch.setenv("STARWAY_SESSION", "1")
    monkeypatch.setenv("STARWAY_SESSION_GRACE", "20")
    monkeypatch.setenv("STARWAY_RAILS", "3")
    monkeypatch.setenv("STARWAY_STRIPE_THRESHOLD", str(1 << 20))
    monkeypatch.setenv("STARWAY_STRIPE_CHUNK", str(256 << 10))
    server = Server()
    server.listen(ADDR, port)
    client = Client()
    try:
        await _connect(client, server, port)
        rails = _client_rails(client)
        assert len(rails) == 2
        n = 32 << 20
        payload = _payload(n)
        sink = np.zeros(n, dtype=np.uint8)
        rf = server.arecv(sink, 31, MASK)
        send_fut = client.asend(payload, 31)
        rails[0].sock.shutdown(socket.SHUT_RDWR)
        await asyncio.wait_for(send_fut, 30)
        await asyncio.wait_for(client.aflush(), 60)
        await asyncio.wait_for(rf, 60)
        assert np.array_equal(sink, payload)
        cc = _counters(client)
        assert cc["sessions_resumed"] == 0, "rail death must not suspend"
        # Now the PRIMARY dies mid-stripe: suspend + redial + wholesale
        # re-dispatch; receiver offset dedup keeps delivery exactly-once.
        sink2 = np.zeros(n, dtype=np.uint8)
        rf2 = server.arecv(sink2, 32, MASK)
        send2 = client.asend(payload, 32)
        client._client.primary_conn.sock.shutdown(socket.SHUT_RDWR)
        await asyncio.wait_for(send2, 60)
        await asyncio.wait_for(client.aflush(), 90)
        await asyncio.wait_for(rf2, 90)
        assert np.array_equal(sink2, payload), "corrupt across resume"
        cc = _counters(client)
        assert cc["sessions_resumed"] >= 1, cc
        assert _counters(server)["recvs_completed"] == 2
    finally:
        await _aclose_all(client, server)


# ------------------------------------------------------------ seed parity


@pytest.mark.parametrize("eng", ["py", "native"])
async def test_seed_parity_striping_unset(eng, port, monkeypatch):
    """With STARWAY_RAILS/STRIPE_THRESHOLD unset the HELLO carries no
    rails offer and a large send emits plain DATA frames -- the wire is
    byte-identical to the seed for old peers."""
    if eng == "native":
        from starway_tpu.core import native

        if not native.available():
            pytest.skip("native engine unavailable (no toolchain)")
    monkeypatch.setenv("STARWAY_TLS", "tcp")
    monkeypatch.delenv("STARWAY_RAILS", raising=False)
    monkeypatch.delenv("STARWAY_STRIPE_THRESHOLD", raising=False)
    monkeypatch.setenv("STARWAY_NATIVE", "1" if eng == "native" else "0")
    listener = socket.socket(socket.AF_INET, socket.SOCK_STREAM)
    listener.bind((ADDR, port))
    listener.listen(4)
    client = Client()
    try:
        fut = client.aconnect(ADDR, port)
        conn, _ = listener.accept()
        conn.settimeout(10)
        hdr = b""
        while len(hdr) < frames.HEADER_SIZE:
            hdr += conn.recv(frames.HEADER_SIZE - len(hdr))
        ftype, _a, blen = frames.unpack_header(hdr)
        assert ftype == frames.T_HELLO
        body = b""
        while len(body) < blen:
            body += conn.recv(blen - len(body))
        hello = json.loads(body.decode())
        assert "rails" not in hello and "rail_of" not in hello, hello
        conn.sendall(frames.pack_hello_ack("seedpeer"))
        await asyncio.wait_for(fut, 30)
        assert not _client_rails(client) if eng == "py" else True
        conn.close()
    finally:
        listener.close()
        try:
            await asyncio.wait_for(client.aclose(), 10)
        except Exception:
            pass


async def test_striped_e2e_markers_per_message(port, monkeypatch):
    """swscope: striping emits ONE EV_E2E marker per message on the
    primary (directions :sx/:sr, ordinal = msg id), never per chunk, so
    trace --merge flow pairing survives striping."""
    from starway_tpu.core import swtrace

    monkeypatch.setenv("STARWAY_TLS", "tcp")
    monkeypatch.setenv("STARWAY_NATIVE", "0")
    monkeypatch.setenv("STARWAY_RAILS", "2")
    monkeypatch.setenv("STARWAY_STRIPE_THRESHOLD", str(1 << 20))
    monkeypatch.setenv("STARWAY_TRACE", "1")
    server = Server()
    server.listen(ADDR, port)
    client = Client()
    try:
        await _connect(client, server, port)
        n = 4 << 20
        payload = _payload(n)
        sink = np.zeros(n, dtype=np.uint8)
        rf = server.arecv(sink, 51, MASK)
        await asyncio.wait_for(client.asend(payload, 51), 30)
        await asyncio.wait_for(client.aflush(), 30)
        await asyncio.wait_for(rf, 30)

        def e2e(worker, suffix):
            return [(tag, reason) for (_t, ev, tag, _c, _n, reason, _d)
                    in worker.trace_events()
                    if ev == swtrace.EV_E2E and reason.endswith(suffix)]

        tx = e2e(client._client, ":sx")
        rx = e2e(server._server, ":sr")
        assert len(tx) == 1 and len(rx) == 1, (tx, rx)
        # Same trace-conn id and same msg-id ordinal at both ends.
        assert tx[0][0] == rx[0][0] == 1
        assert tx[0][1].split(":")[0] == rx[0][1].split(":")[0]
        # Chunks themselves never reach the ordinal stream.
        assert not e2e(client._client, ":tx") and not e2e(server._server, ":rx")
    finally:
        await _aclose_all(client, server)


# ------------------------------------- lane-weighted tail claiming (§17)


class _StubConn:
    """Bare conn stand-in for white-box RailGroup policy tests."""

    def __init__(self, cid):
        self.conn_id = cid
        self.alive = True
        self.sock = object()
        self.tx = []
        self.dirty = False
        self.csum_ok = False
        self.retx_offs = set()

    def kick_tx(self, fires):
        pass


def _stub_group(nlanes):
    from starway_tpu.core.lane import RailGroup

    group = RailGroup(_StubConn(1))
    for i in range(1, nlanes):
        group.add_rail(_StubConn(i + 1))
    return group


def _queue_source(group, nchunks, chunk=4096):
    from starway_tpu.core.lane import StripeSource

    payload = memoryview(bytes(nchunks * chunk))
    src = StripeSource(group.next_msg_id, 5, payload, None, None, None, chunk)
    group.next_msg_id += 1
    group.by_id[src.msg_id] = src
    group.queue.append(src)
    return src


def test_weighted_tail_decline_policy(monkeypatch):
    """White-box: under STARWAY_STRIPE_WEIGHTED a slow lane (EWMA below
    half the fastest live lane's) declines *steal* claims in a message's
    tail -- and ONLY there: dispatch claims, head-of-message steals, and
    the fastest lane itself always claim."""
    monkeypatch.setenv("STARWAY_STRIPE_WEIGHTED", "1")
    group = _stub_group(2)
    fast, slow = group.lanes
    fast.ewma_bps = 100e6
    slow.ewma_bps = 10e6
    src = _queue_source(group, nchunks=8)
    # Head of the message (8 pending > 2 lanes): the slow lane steals.
    assert group.claim_next(slow, steal=True) is not None
    # Drain to the tail (2 pending <= 2 lanes).
    while len(src.pending) > 2:
        assert group.claim_next(fast, steal=True) is not None
    assert group.claim_next(slow, steal=True) is None, \
        "slow lane must decline a tail steal"
    assert slow.tail_declines == 1
    assert len(src.pending) == 2, "a declined chunk must stay pending"
    # Dispatch-time claims are never declined (liveness: every requeue
    # path re-feeds lanes through dispatch).
    assert group.claim_next(slow, steal=False) is not None
    # The fastest lane never declines its own tail.
    assert group.claim_next(fast, steal=True) is not None
    # Knob off: pure work stealing, no declines anywhere.
    monkeypatch.setenv("STARWAY_STRIPE_WEIGHTED", "0")
    src2 = _queue_source(group, nchunks=2)
    assert group.claim_next(slow, steal=True) is not None
    assert slow.tail_declines == 1


def test_weighted_decline_scans_past_declined_tail(monkeypatch):
    """A slow lane declining msg N's tail must still claim from msg N+1
    queued behind it -- idling the lane entirely would halve striped
    throughput exactly when the knob is meant to help."""
    monkeypatch.setenv("STARWAY_STRIPE_WEIGHTED", "1")
    group = _stub_group(2)
    fast, slow = group.lanes
    fast.ewma_bps = 100e6
    slow.ewma_bps = 10e6
    tail_src = _queue_source(group, nchunks=1)   # msg N: in its tail
    bulk_src = _queue_source(group, nchunks=16)  # msg N+1: plenty of work
    got = group.claim_next(slow, steal=True)
    assert got is not None and got[0] is bulk_src, \
        "slow lane must skip the declined tail and claim the next message"
    assert slow.tail_declines >= 1
    assert len(tail_src.pending) == 1  # the tail chunk stays for the
    got2 = group.claim_next(fast, steal=True)  # fast lane
    assert got2 is not None and got2[0] is tail_src


def test_weighted_decline_needs_ewma_and_peers(monkeypatch):
    """No decline without data (cold EWMA) and no decline when the slow
    lane is the only live one -- the chunk would strand."""
    monkeypatch.setenv("STARWAY_STRIPE_WEIGHTED", "1")
    group = _stub_group(2)
    fast, slow = group.lanes
    _queue_source(group, nchunks=1)
    # Cold EWMA (no chunks carried yet): claim.
    assert group.claim_next(slow, steal=True) is not None
    fast.ewma_bps = 100e6
    slow.ewma_bps = 1e6
    _queue_source(group, nchunks=1)
    # Fast lane dead: the slow lane is the tail's only carrier.
    fast.conn.alive = False
    assert group.claim_next(slow, steal=True) is not None


async def test_weighted_striped_transfer_all_pairings(pair, port):
    """End-to-end with the knob armed: striped transfers stay byte-exact
    across every engine pairing (the policy biases scheduling, never
    correctness), and lane EWMAs converge on the Python side."""
    s_eng, c_eng, mp = pair
    mp.setenv("STARWAY_STRIPE_WEIGHTED", "1")
    server = _mk_server(s_eng, mp, port)
    client = _mk_client(c_eng, mp)
    try:
        await _connect(client, server, port)
        n = 4 << 20
        payload = _payload(n)
        sink = np.zeros(n, dtype=np.uint8)
        for i in range(3):
            sink[:] = 0
            rf = server.arecv(sink, 40 + i, MASK)
            await asyncio.wait_for(client.asend(payload, 40 + i), 30)
            await asyncio.wait_for(client.aflush(), 30)
            await asyncio.wait_for(rf, 30)
            assert np.array_equal(sink, payload), f"iter {i}"
        if c_eng == "py":
            conn = client._client.primary_conn
            group = getattr(conn, "stripe", None)
            assert group is not None
            carried = [ln for ln in group.lanes if ln.chunks_tx > 0]
            assert carried and all(ln.ewma_bps > 0 for ln in carried)
    finally:
        await _aclose_all(client, server)


# ------------------------------------------------------------------ soak


@pytest.mark.slow
async def test_striped_many_gib_soak(port, monkeypatch):
    """Multi-GiB striped soak: repeated large transfers over 3 lanes stay
    byte-exact (checksummed) and the counters balance."""
    monkeypatch.setenv("STARWAY_TLS", "tcp")
    monkeypatch.setenv("STARWAY_RAILS", "3")
    monkeypatch.setenv("STARWAY_STRIPE_THRESHOLD", str(1 << 20))
    server = Server()
    server.listen(ADDR, port)
    client = Client()
    try:
        await _connect(client, server, port)
        n = 512 << 20
        payload = _payload(n)
        want = int(payload.astype(np.uint64).sum())
        sink = np.zeros(n, dtype=np.uint8)
        for i in range(5):  # 2.5 GiB striped total
            sink[:] = 0
            rf = server.arecv(sink, 100 + i, MASK)
            await asyncio.wait_for(client.asend(payload, 100 + i), 300)
            await asyncio.wait_for(client.aflush(), 300)
            await asyncio.wait_for(rf, 300)
            assert int(sink.astype(np.uint64).sum()) == want, f"iter {i}"
        cc, sc = _counters(client), _counters(server)
        assert sc["stripe_chunks_rx"] == cc["stripe_chunks_tx"]
    finally:
        await _aclose_all(client, server)
