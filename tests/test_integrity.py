"""End-to-end data integrity (DESIGN.md §19): negotiated frame checksums,
poisoned-conn recovery, and chunk-level retransmit.

The acceptance contract (ISSUE 11): with ``STARWAY_INTEGRITY=1`` both
engines negotiate ``csum`` and every framed message verifies end to end.
A FaultProxy bit-flip on (a) an eager DATA frame, (b) a striped T_SDATA
chunk, and (c) an sm ring slot is DETECTED -- never delivered as good
bytes: (b) recovers by single-chunk retransmit (T_SNACK) without a conn
reset, (a)/(c) poison the conn with the stable ``"corrupt"`` reason --
which without sessions takes the §10 failure contract and with
``STARWAY_SESSION=1`` suspends + replays so the op still completes
exactly-once with verified bytes.  With the env unset the HELLO is
byte-identical to the seed (raw-socket inspection, the §17/§18 pattern).
"""

import asyncio
import json
import socket

import numpy as np
import pytest

from starway_tpu import Client, Server
from starway_tpu.core import frames, shmring
from starway_tpu.testing.faults import FaultProxy

pytestmark = pytest.mark.asyncio

ADDR = "127.0.0.1"
MASK = (1 << 64) - 1

PAIRS = ["py-py", "native-native", "py-native", "native-py"]


def _need_native(*engines):
    if "native" in engines:
        from starway_tpu.core import native

        if not native.available():
            pytest.skip("native engine unavailable (no toolchain)")


@pytest.fixture(params=PAIRS)
def pair(request, monkeypatch):
    s_eng, c_eng = request.param.split("-")
    _need_native(s_eng, c_eng)
    monkeypatch.setenv("STARWAY_TLS", "tcp")
    monkeypatch.setenv("STARWAY_INTEGRITY", "1")
    return s_eng, c_eng, monkeypatch


def _mk_server(eng, monkeypatch, port):
    monkeypatch.setenv("STARWAY_NATIVE", "1" if eng == "native" else "0")
    server = Server()
    server.listen(ADDR, port)
    return server


def _mk_client(eng, monkeypatch):
    monkeypatch.setenv("STARWAY_NATIVE", "1" if eng == "native" else "0")
    return Client()


async def _aclose_all(*objs):
    for o in objs:
        try:
            await asyncio.wait_for(o.aclose(), timeout=15)
        except Exception:
            pass


def _counters(owner) -> dict:
    w = getattr(owner, "_client", None) or owner._server
    return w.counters_snapshot()


async def _wait_counter(owner, name, minimum, timeout=20.0):
    for _ in range(int(timeout / 0.02)):
        if _counters(owner).get(name, 0) >= minimum:
            return
        await asyncio.sleep(0.02)
    raise AssertionError(
        f"{name} never reached {minimum}: {_counters(owner)}")


def _payload(n: int) -> np.ndarray:
    # Position-dependent bytes: any mis-offset/corrupt region shows up.
    return ((np.arange(n, dtype=np.uint64) * 7 + 13) % 251).astype(np.uint8)


# ------------------------------------------------------------ crc32c unit


# swcheck: allow(marker-slow): 0xE3069283 is the CRC check VALUE, not a payload size
def test_crc32c_check_vector_and_chaining():
    """The standard CRC32C check vector, incremental chaining, and --
    when the native export exists -- bit-exact agreement between the
    pure-Python fallback and the hardware path (mixed engine pairs
    depend on the two computing ONE function)."""
    assert frames.crc32c(b"123456789") == 0xE3069283
    c = frames.crc32c(b"1234")
    assert frames.crc32c(b"56789", c) == 0xE3069283
    assert frames.crc32c(b"") == 0
    data = bytes(_payload(70001))
    native_fn = frames._crc32c_fn()
    via_default = frames.crc32c(data)
    saved = frames._crc_native
    try:
        frames._crc_native = False  # force the table fallback
        via_table = frames.crc32c(data)
    finally:
        frames._crc_native = saved
    assert via_table == via_default
    if native_fn is not False and native_fn is not None:
        assert via_default == frames.crc32c(data)  # native path agrees


def test_pack_csum_for_covers_header_and_payload():
    hdr = frames.pack_data_header(7, 5)
    pre = frames.pack_csum_for(hdr, memoryview(b"hello"))
    ftype, cf, ch = frames.unpack_header(pre)
    assert ftype == frames.T_CSUM
    assert ch == frames.crc32c(hdr)
    assert cf == frames.crc32c(b"hello", ch)
    # SDATA: crc_head additionally covers the 24-byte sub-header.
    sh = frames.pack_sdata_header(7, 3, 0, 5, 5)
    pre = frames.pack_csum_for(sh, memoryview(b"hello"))
    _, cf2, ch2 = frames.unpack_header(pre)
    assert ch2 == frames.crc32c(sh)  # header+sub, all of sh
    assert cf2 == frames.crc32c(b"hello", ch2)


# ------------------------------------------------------------ seed parity


@pytest.mark.parametrize("eng", ["py", "native"])
async def test_seed_parity_integrity_unset(eng, port, monkeypatch):
    """With STARWAY_INTEGRITY unset the HELLO carries no "csum" key --
    the wire is byte-identical to the seed for old peers (raw-socket
    inspection, the §17/§18 seed-parity pattern)."""
    _need_native(eng)
    monkeypatch.setenv("STARWAY_TLS", "tcp")
    monkeypatch.delenv("STARWAY_INTEGRITY", raising=False)
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
        assert "csum" not in hello, hello
        conn.sendall(frames.pack_hello_ack("seedpeer"))
        await asyncio.wait_for(fut, 30)
        conn.close()
    finally:
        listener.close()
        try:
            await asyncio.wait_for(client.aclose(), 10)
        except Exception:
            pass


# --------------------------------------------- negotiation, four pairings


async def test_negotiated_transfer_all_pairings(pair, port):
    """Clean traffic with integrity on: eager + large messages verify and
    deliver byte-exactly in every engine pairing, zero csum failures."""
    s_eng, c_eng, mp = pair
    server = _mk_server(s_eng, mp, port)
    client = _mk_client(c_eng, mp)
    try:
        await asyncio.wait_for(client.aconnect(ADDR, port), 30)
        for i, n in enumerate((512, 64 << 10, 3 << 20)):
            payload = _payload(n)
            sink = np.zeros(n, dtype=np.uint8)
            rf = server.arecv(sink, 50 + i, MASK)
            await asyncio.wait_for(client.asend(payload, 50 + i), 30)
            await asyncio.wait_for(client.aflush(), 30)
            await asyncio.wait_for(rf, 30)
            assert np.array_equal(sink, payload), n
        for owner in (client, server):
            snap = _counters(owner)
            assert snap["csum_fail"] == 0, snap
            assert snap["chunk_retx"] == 0, snap
    finally:
        await _aclose_all(client, server)


# ------------------------- (a) corrupt eager frame: poison, then recovery


@pytest.mark.parametrize("where", ["payload", "header"])
async def test_eager_bitflip_poisons_without_session(pair, port, where):
    """A bit-flip on a non-striped DATA frame (payload or header) poisons
    the receiver's conn with the stable "corrupt" reason: queued receives
    keep the §10 peer-death contract, the receiver's dirty flush fails
    "corrupt", and nothing corrupt is ever delivered."""
    s_eng, c_eng, mp = pair
    server = _mk_server(s_eng, mp, port)
    proxy = FaultProxy(ADDR, port, mode="corrupt", corrupt_ftype=3,
                       corrupt_where=where).start()
    client = _mk_client(c_eng, mp)
    try:
        await asyncio.wait_for(client.aconnect(ADDR, proxy.port), 30)
        # Dirty the server's conn (sent, unflushed) so its flush is armed.
        back = np.zeros(64, dtype=np.uint8)
        cf = client.arecv(back, 0x9, MASK)
        ep = None
        for _ in range(1000):
            if server.list_clients():
                ep = server.list_clients().pop()
                break
            await asyncio.sleep(0.005)
        assert ep is not None
        server.asend(ep, np.ones(64, dtype=np.uint8), 0x9)
        await asyncio.wait_for(cf, 30)
        # The corrupted message: never delivered as good bytes.
        n = 256 << 10
        sink = np.zeros(n, dtype=np.uint8)
        rf = server.arecv(sink, 0xA, MASK)
        await asyncio.wait_for(client.asend(_payload(n), 0xA), 30)
        await _wait_counter(server, "csum_fail", 1)
        assert proxy.corrupted_units == 1
        await asyncio.sleep(0.3)
        assert not rf.done(), "corrupt bytes reached the receiver"
        with pytest.raises(Exception) as e:
            await asyncio.wait_for(server.aflush(), 20)
        assert "corrupt" in str(e.value).lower(), e.value
        rf.cancel()
    finally:
        await _aclose_all(client, server)
        proxy.stop()


async def test_eager_bitflip_recovers_with_session(pair, port):
    """The same bit-flip with STARWAY_SESSION=1: the poisoned conn
    suspends, redials, and the journal replay re-delivers VERIFIED bytes
    -- the receive completes exactly-once with the right payload."""
    s_eng, c_eng, mp = pair
    mp.setenv("STARWAY_SESSION", "1")
    # Generous grace: the 1-core CI box can starve the redial for long
    # stretches when the rest of the suite shares the core.
    mp.setenv("STARWAY_SESSION_GRACE", "120")
    server = _mk_server(s_eng, mp, port)
    proxy = FaultProxy(ADDR, port, mode="corrupt", corrupt_ftype=3).start()
    client = _mk_client(c_eng, mp)
    try:
        await asyncio.wait_for(client.aconnect(ADDR, proxy.port), 30)
        n = 256 << 10
        payload = _payload(n)
        sink = np.zeros(n, dtype=np.uint8)
        rf = server.arecv(sink, 0xB, MASK)
        await asyncio.wait_for(client.asend(payload, 0xB), 30)
        await asyncio.wait_for(client.aflush(), 60)
        await asyncio.wait_for(rf, 60)
        assert np.array_equal(sink, payload), "replayed bytes corrupt"
        assert proxy.corrupted_units == 1
        assert _counters(server)["csum_fail"] >= 1
        assert (_counters(client)["sessions_resumed"]
                + _counters(server)["sessions_resumed"]) >= 1
    finally:
        await _aclose_all(client, server)
        proxy.stop()


async def test_truncation_detected(pair, port):
    """A frame truncated mid-payload desyncs the stream: the §19 CRC
    catches the splice (the 'payload' now ends with the next frame's
    bytes) and the conn poisons instead of delivering garbage."""
    s_eng, c_eng, mp = pair
    server = _mk_server(s_eng, mp, port)
    proxy = FaultProxy(ADDR, port, mode="corrupt", corrupt_ftype=3,
                       corrupt_kind="truncate", corrupt_bytes=7).start()
    client = _mk_client(c_eng, mp)
    try:
        await asyncio.wait_for(client.aconnect(ADDR, proxy.port), 30)
        n = 128 << 10
        sink = np.zeros(n, dtype=np.uint8)
        rf = server.arecv(sink, 0xC, MASK)
        await asyncio.wait_for(client.asend(_payload(n), 0xC), 30)
        # The truncated frame is short: the receiver only observes the
        # splice once later traffic supplies the missing byte count --
        # the next frame's bytes then fold into the payload CRC and fail.
        await asyncio.wait_for(client.asend(_payload(4096), 0xC1), 30)
        await _wait_counter(server, "csum_fail", 1)
        assert proxy.corrupted_units == 1
        assert not rf.done()
        rf.cancel()
    finally:
        await _aclose_all(client, server)
        proxy.stop()


# --------------------- (b) corrupt striped chunk: single-chunk retransmit


async def test_striped_chunk_bitflip_single_retx(pair, port):
    """A bit-flip inside ONE striped chunk's payload: the receiver NACKs
    (T_SNACK), the sender re-dispatches just that chunk through the §17
    offset-dedup reassembly, and the transfer completes byte-exactly
    WITHOUT any conn reset -- in all four engine pairings."""
    s_eng, c_eng, mp = pair
    mp.setenv("STARWAY_RAILS", "3")
    mp.setenv("STARWAY_STRIPE_THRESHOLD", str(1 << 20))
    mp.setenv("STARWAY_STRIPE_CHUNK", str(256 << 10))
    server = _mk_server(s_eng, mp, port)
    proxy = FaultProxy(ADDR, port, mode="corrupt", corrupt_ftype=12).start()
    client = _mk_client(c_eng, mp)
    try:
        await asyncio.wait_for(client.aconnect(ADDR, proxy.port), 30)
        n = 8 << 20
        payload = _payload(n)
        sink = np.zeros(n, dtype=np.uint8)
        rf = server.arecv(sink, 0xD, MASK)
        await asyncio.wait_for(client.asend(payload, 0xD), 60)
        await asyncio.wait_for(client.aflush(), 60)
        await asyncio.wait_for(rf, 60)
        assert np.array_equal(sink, payload), "corrupt chunk delivered"
        assert proxy.corrupted_units == 1
        cc, sc = _counters(client), _counters(server)
        assert cc["chunk_retx"] >= 1, cc   # sender re-dispatched the chunk
        assert sc["csum_fail"] >= 1, sc    # receiver detected + NACKed
        # No conn reset: nothing cancelled, no session machinery, and a
        # fresh transfer still rides the same conns.
        assert cc["ops_cancelled"] == 0 and sc["ops_cancelled"] == 0
        sink2 = np.zeros(1 << 20, dtype=np.uint8)
        rf2 = server.arecv(sink2, 0xE, MASK)
        await asyncio.wait_for(client.asend(payload[: 1 << 20], 0xE), 30)
        await asyncio.wait_for(client.aflush(), 30)
        await asyncio.wait_for(rf2, 30)
        assert np.array_equal(sink2, payload[: 1 << 20])
    finally:
        await _aclose_all(client, server)
        proxy.stop()


# ------------------------------------ (c) corrupt sm ring slot at dequeue


def test_sm_slot_record_unit_detection():
    """Ring-level §19 slot records: a bit flipped in the mapped segment
    after the producer published is caught AT DEQUEUE (SmCorrupt), as is
    a replayed (stale-seqno) record -- the torn-write detection the
    byte-stream ring is blind to."""
    seg = shmring.ShmSegment.create("integ")
    try:
        seg.enable_integrity()
        tx = seg.tx_rx(True)[0]      # producer view of ring 0
        rx = seg.tx_rx(False)[1]     # the peer's consumer view of ring 0
        data = bytes(_payload(5000))
        assert tx.write(memoryview(data)) == 5000
        out = bytearray(5000)
        assert rx.read_into(memoryview(out)) == 5000
        assert bytes(out) == data
        # Bit-flip inside a published record's payload.
        assert tx.write(memoryview(data)) == 5000
        idx = (tx.tail - 100) & (tx.size - 1)
        seg.rings[0]._data[idx] ^= 0x08
        with pytest.raises(shmring.SmCorrupt):
            while rx.read_into(memoryview(out)):
                pass
        # Stale slot seqno: a verbatim replay of an old record region
        # cannot verify (the CRC covers the free-running slot counter).
        seg2 = shmring.ShmSegment.create("integ2")
        try:
            seg2.enable_integrity()
            tx2 = seg2.tx_rx(True)[0]
            rx2 = seg2.tx_rx(False)[1]
            assert tx2.write(memoryview(data)) == 5000
            assert rx2.read_into(memoryview(out)) == 5000
            tx2._tx_seq = 0  # producer "replays" slot 0's framing
            assert tx2.write(memoryview(data)) == 5000
            with pytest.raises(shmring.SmCorrupt):
                while rx2.read_into(memoryview(out)):
                    pass
        finally:
            seg2.unlink()
            seg2.close()
    finally:
        seg.unlink()
        seg.close()


@pytest.mark.parametrize("s_eng", ["py", "native"])
async def test_sm_slot_corruption_poisons_conn(s_eng, port, monkeypatch):
    """End-to-end sm-slot corruption: the (py) producer's ring write is
    wrapped to flip one byte AFTER the record published -- the torn-write
    shape -- and the CONSUMER (python or native engine) detects it at
    dequeue and poisons the conn with "corrupt" instead of parsing the
    garbage."""
    _need_native(s_eng)
    monkeypatch.setenv("STARWAY_TLS", "tcp,sm")
    monkeypatch.setenv("STARWAY_INTEGRITY", "1")
    server = _mk_server(s_eng, monkeypatch, port)
    client = _mk_client("py", monkeypatch)
    state = {"armed": False, "hit": False}
    orig_write = shmring.Ring.write

    def corrupt_write(self, src):
        tail0 = self.tail
        n = orig_write(self, src)
        if state["armed"] and not state["hit"] and n > 64:
            idx = (tail0 + shmring.REC_HDR + n // 2) & (self.size - 1)
            self._data[idx] ^= 0x40
            state["hit"] = True
        return n

    monkeypatch.setattr(shmring.Ring, "write", corrupt_write)
    try:
        await asyncio.wait_for(client.aconnect(ADDR, port), 30)
        prim = client._client.primary_conn
        assert prim.sm_negotiated and prim.csum_ok
        n = 256 << 10
        sink = np.zeros(n, dtype=np.uint8)
        rf = server.arecv(sink, 0xF, MASK)
        state["armed"] = True
        await asyncio.wait_for(client.asend(_payload(n), 0xF), 30)
        await _wait_counter(server, "csum_fail", 1)
        assert state["hit"]
        await asyncio.sleep(0.2)
        assert not rf.done(), "corrupt sm bytes reached the receiver"
        rf.cancel()
    finally:
        await _aclose_all(client, server)


# -------------------------------------------------- poison reason plumbing


async def test_poison_fails_queued_sends_with_corrupt_reason(port,
                                                             monkeypatch):
    """In-flight ops on a poisoned conn report "corrupt", not a generic
    cancel: corrupt inbound traffic poisons the PY receiver while it has
    its own unfinished sends queued -- their fail reason carries the
    keyword (the §10-contract wording of ISSUE 11)."""
    monkeypatch.setenv("STARWAY_TLS", "tcp")
    monkeypatch.setenv("STARWAY_INTEGRITY", "1")
    monkeypatch.setenv("STARWAY_NATIVE", "0")
    server = Server()
    server.listen(ADDR, port)
    proxy = FaultProxy(ADDR, port, mode="corrupt", corrupt_ftype=3).start()
    client = Client()
    try:
        await asyncio.wait_for(client.aconnect(ADDR, proxy.port), 30)
        ep = None
        for _ in range(1000):
            if server.list_clients():
                ep = server.list_clients().pop()
                break
            await asyncio.sleep(0.005)
        assert ep is not None
        # A big rndv send queued on the server (s->c is NOT proxied-
        # corrupted, but it cannot finish instantly) ...
        big = _payload(64 << 20)
        sf = server.asend(ep, big, 0x20)
        # (ON the conn: the engine handles a wakeup's reads before its
        # submitted ops, so on a loaded box the poison below could land
        # first and the send then fail as "not connected", never queued.)
        await _wait_counter(server, "bytes_tx", 1 << 16)
        # ... while the client's corrupted send poisons the server conn.
        await asyncio.wait_for(client.asend(_payload(256 << 10), 0x21), 30)
        await _wait_counter(server, "csum_fail", 1)
        done, pending = await asyncio.wait({sf}, timeout=20)
        assert sf in done, "queued send never settled after poison"
        exc = sf.exception()
        if exc is not None:
            assert "corrupt" in str(exc).lower(), exc
        # (rndv local-completion may legally have fired before the
        # poison landed; the flush below then reports the poison.)
        with pytest.raises(Exception) as e:
            await asyncio.wait_for(server.aflush(), 20)
        assert "corrupt" in str(e.value).lower(), e.value
    finally:
        await _aclose_all(client, server)
        proxy.stop()
