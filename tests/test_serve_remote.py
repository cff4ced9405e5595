"""Serving over the transport (models/remote_serving.py): requests arrive
as tagged messages on a Server, SlotServer admits them, tokens stream back
per-request over the connection — and every request's greedy output is
bit-identical to the standalone generate() oracle.

Matrix: the same contract over the in-process fast path, real TCP
sockets, and the C++ native engine (VERDICT r4 #2 "works over inproc,
tcp AND the native engine"), plus a multiprocess test driving concurrent
client processes against one serving process.
"""

import asyncio
import multiprocessing as mp
import os
import time

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from starway_tpu.models import LlamaConfig, SlotServer, init_params
from starway_tpu.models.generate import generate

pytestmark = pytest.mark.asyncio

ADDR = "127.0.0.1"


@pytest.fixture(params=["inproc", "tcp", "native"])
def transport(request, monkeypatch):
    if request.param == "inproc":
        # Ambient env must not silently turn this leg into tcp/native.
        monkeypatch.delenv("STARWAY_TLS", raising=False)
        monkeypatch.delenv("STARWAY_NATIVE", raising=False)
    elif request.param == "tcp":
        monkeypatch.setenv("STARWAY_TLS", "tcp")
        monkeypatch.setenv("STARWAY_NATIVE", "0")
    elif request.param == "native":
        from starway_tpu.core import native

        if not native.available():
            pytest.skip("native engine unavailable (no toolchain)")
        monkeypatch.setenv("STARWAY_TLS", "tcp")
        monkeypatch.setenv("STARWAY_NATIVE", "1")
    return request.param


@pytest.fixture(scope="module")
def cfg():
    return LlamaConfig.preset("debug")


@pytest.fixture(scope="module")
def params(cfg):
    return init_params(jax.random.PRNGKey(0), cfg)


def _oracle(params, cfg, prompt, max_new):
    out = generate(params, cfg, jnp.asarray([prompt], jnp.int32), max_new)
    return np.asarray(out[0, len(prompt):])


async def _serve_and_query(cfg, params, reqs, port, n_sessions=1):
    """One bridge, n_sessions concurrent client sessions, reqs round-robin
    across them; returns the per-request token arrays in reqs order."""
    from starway_tpu.models.remote_serving import (RemoteGenerateSession,
                                                   RemoteSlotServer)

    slot = SlotServer(params, cfg, n_slots=2, max_len=64, chunk=4)
    bridge = RemoteSlotServer(slot)
    bridge.server.listen(ADDR, port)
    serve_task = asyncio.create_task(bridge.serve())

    sessions = [await RemoteGenerateSession.aconnect(ADDR, port)
                for _ in range(n_sessions)]
    try:
        outs = await asyncio.gather(*(
            sessions[i % n_sessions].generate(p, m)
            for i, (p, m) in enumerate(reqs)))
    finally:
        bridge.stop()
        await serve_task
        for s in sessions:
            await s.aclose()
        await bridge.aclose()
    return outs


async def test_remote_matches_generate(cfg, params, transport, port):
    """More requests than slots through one remote session: every greedy
    continuation equals standalone generate()."""
    rng = np.random.default_rng(1)
    reqs = [(list(rng.integers(1, cfg.vocab_size, n)), m)
            for n, m in [(3, 6), (7, 4), (12, 9), (5, 1), (2, 11)]]
    outs = await _serve_and_query(cfg, params, reqs, port)
    for (prompt, max_new), got in zip(reqs, outs):
        np.testing.assert_array_equal(got, _oracle(params, cfg, prompt,
                                                   max_new))


async def test_remote_concurrent_sessions(cfg, params, transport, port):
    """Three sessions (connections) interleaving requests on one bridge:
    tag routing keeps every stream on its own request."""
    rng = np.random.default_rng(2)
    reqs = [(list(rng.integers(1, cfg.vocab_size, n)), m)
            for n, m in [(4, 5), (9, 7), (2, 3), (6, 8), (3, 4), (8, 2)]]
    outs = await _serve_and_query(cfg, params, reqs, port, n_sessions=3)
    for (prompt, max_new), got in zip(reqs, outs):
        np.testing.assert_array_equal(got, _oracle(params, cfg, prompt,
                                                   max_new))


async def test_remote_streaming_chunks(cfg, params, transport, port):
    """The per-chunk callback sees the same tokens, in order, as the
    final result — streaming is not a re-delivery."""
    from starway_tpu.models.remote_serving import (RemoteGenerateSession,
                                                   RemoteSlotServer)

    slot = SlotServer(params, cfg, n_slots=1, max_len=64, chunk=3)
    bridge = RemoteSlotServer(slot)
    bridge.server.listen(ADDR, port)
    serve_task = asyncio.create_task(bridge.serve())
    session = await RemoteGenerateSession.aconnect(ADDR, port)
    try:
        seen: list = []
        out = await session.generate([4, 2, 8, 1], 10,
                                     on_tokens=seen.extend)
        assert seen == list(out)
        assert len(out) == 10
        # chunk=3 means the stream arrived in > 1 message
        np.testing.assert_array_equal(
            out, _oracle(params, cfg, [4, 2, 8, 1], 10))
    finally:
        bridge.stop()
        await serve_task
        await session.aclose()
        await bridge.aclose()


async def test_remote_rejects_oversized(cfg, params, transport, port):
    """A request that exceeds the server's max_len comes back as a
    rejection (empty fatal stream -> ValueError), and the serve loop
    keeps working for the next request."""
    from starway_tpu.models.remote_serving import (RemoteGenerateSession,
                                                   RemoteSlotServer)

    slot = SlotServer(params, cfg, n_slots=1, max_len=32, chunk=4)
    bridge = RemoteSlotServer(slot)
    bridge.server.listen(ADDR, port)
    serve_task = asyncio.create_task(bridge.serve())
    session = await RemoteGenerateSession.aconnect(ADDR, port)
    try:
        with pytest.raises(ValueError, match="rejected"):
            await session.generate(list(range(1, 20)), 100)
        out = await session.generate([4, 2, 8], 5)
        np.testing.assert_array_equal(out, _oracle(params, cfg, [4, 2, 8],
                                                   5))
    finally:
        bridge.stop()
        await serve_task
        await session.aclose()
        await bridge.aclose()


async def test_remote_client_rejects_oversized_prompt_locally(cfg, params,
                                                              port):
    """ASSIGN carries the server's request-size limit; generate() raises
    client-side instead of sending an unanswerable truncated request."""
    from starway_tpu.models.remote_serving import (RemoteGenerateSession,
                                                   RemoteSlotServer)

    slot = SlotServer(params, cfg, n_slots=1, max_len=64, chunk=4)
    bridge = RemoteSlotServer(slot, max_prompt_tokens=16)
    bridge.server.listen(ADDR, port)
    serve_task = asyncio.create_task(bridge.serve())
    session = await RemoteGenerateSession.aconnect(ADDR, port)
    try:
        assert session.server_max_prompt == 16
        with pytest.raises(ValueError, match="request limit"):
            await session.generate(list(range(1, 30)), 4)
    finally:
        bridge.stop()
        await serve_task
        await session.aclose()
        await bridge.aclose()


async def test_remote_intake_survives_truncated_request(cfg, params, port):
    """An oversized request truncates the server's wildcard recv; the
    bridge must re-post and keep serving everyone else (a one-request
    denial must not become a permanent one)."""
    from starway_tpu.models.remote_serving import (TAG_REQUEST,
                                                   RemoteGenerateSession,
                                                   RemoteSlotServer, _wire)

    slot = SlotServer(params, cfg, n_slots=1, max_len=64, chunk=4)
    bridge = RemoteSlotServer(slot, max_prompt_tokens=16)
    bridge.server.listen(ADDR, port)
    serve_task = asyncio.create_task(bridge.serve())
    session = await RemoteGenerateSession.aconnect(ADDR, port)
    try:
        # Raw oversized request (larger than the bridge's recv buffer);
        # sent directly so the test doesn't await a stream that cannot
        # come back (the recv fails before the nonce is parsed).
        big = np.concatenate([np.asarray([0, 4, 64], np.int32),
                              np.ones(64, np.int32)])
        await session.client.asend(_wire(big),
                                   TAG_REQUEST | session.client_id)
        await asyncio.sleep(0.2)
        out = await session.generate([4, 2, 8], 5)
        np.testing.assert_array_equal(out, _oracle(params, cfg, [4, 2, 8],
                                                   5))
    finally:
        bridge.stop()
        await serve_task
        await session.aclose()
        await bridge.aclose()


async def test_remote_malformed_request_is_rejected(cfg, params, port):
    """A length-inconsistent request gets a fatal empty stream back (the
    client errors instead of hanging), and service continues."""
    from starway_tpu.models.remote_serving import (TAG_REQUEST, TAG_TOKENS,
                                                   FULL_MASK,
                                                   RemoteGenerateSession,
                                                   RemoteSlotServer,
                                                   _recv_buf, _wire)

    slot = SlotServer(params, cfg, n_slots=1, max_len=64, chunk=4)
    bridge = RemoteSlotServer(slot)
    bridge.server.listen(ADDR, port)
    serve_task = asyncio.create_task(bridge.serve())
    session = await RemoteGenerateSession.aconnect(ADDR, port)
    try:
        nonce = 7777
        bad = np.asarray([nonce, 4, 99, 1, 2, 3], np.int32)  # n=99, 3 sent
        await session.client.asend(_wire(bad),
                                   TAG_REQUEST | session.client_id)
        buf = _recv_buf(8)
        await session.client.arecv(buf, TAG_TOKENS | nonce, FULL_MASK)
        words = buf.view(np.int32)
        assert int(words[1]) == 2 and int(words[2]) == 0  # aborted, empty
        out = await session.generate([9, 1], 4)
        np.testing.assert_array_equal(out, _oracle(params, cfg, [9, 1], 4))
    finally:
        bridge.stop()
        await serve_task
        await session.aclose()
        await bridge.aclose()


# --------------------------------------------------------- multiprocess
def _server_proc(port, ready, stop):
    os.environ["STARWAY_TLS"] = "tcp"
    os.environ["JAX_PLATFORMS"] = "cpu"
    import jax as j

    j.config.update("jax_platforms", "cpu")

    from starway_tpu.models import LlamaConfig, SlotServer, init_params
    from starway_tpu.models.remote_serving import RemoteSlotServer

    cfg = LlamaConfig.preset("debug")
    params = init_params(j.random.PRNGKey(0), cfg)

    async def main():
        slot = SlotServer(params, cfg, n_slots=2, max_len=64, chunk=4)
        bridge = RemoteSlotServer(slot)
        bridge.server.listen("127.0.0.1", port)
        ready.set()
        task = asyncio.create_task(bridge.serve())
        while not stop.is_set():
            await asyncio.sleep(0.05)
        bridge.stop()
        await task
        await bridge.aclose()

    asyncio.run(main())


def _client_proc(port, reqs, out_q):
    os.environ["STARWAY_TLS"] = "tcp"
    os.environ["JAX_PLATFORMS"] = "cpu"
    import jax as j

    j.config.update("jax_platforms", "cpu")

    from starway_tpu.models.remote_serving import RemoteGenerateSession

    async def main():
        session = None
        for _ in range(60):  # clients are connect-once: fresh per attempt
            try:
                session = await RemoteGenerateSession.aconnect(
                    "127.0.0.1", port)
                break
            except Exception:
                await asyncio.sleep(0.25)
        assert session is not None, "could not connect to serving process"
        outs = await asyncio.gather(*(session.generate(p, m)
                                      for p, m in reqs))
        await session.aclose()
        return [np.asarray(o).tolist() for o in outs]

    out_q.put(asyncio.run(main()))


def test_remote_multiprocess(cfg, params, port):
    """One serving process, two concurrent client processes over real TCP:
    every stream matches its oracle computed in THIS process."""
    mp_ctx = mp.get_context("spawn")
    ready, stop = mp_ctx.Event(), mp_ctx.Event()
    srv = mp_ctx.Process(target=_server_proc, args=(port, ready, stop))
    srv.start()
    try:
        assert ready.wait(120), "serving process never came up"
        rng = np.random.default_rng(3)
        all_reqs = [[(list(map(int, rng.integers(1, cfg.vocab_size, n))), m)
                     for n, m in [(3, 6), (8, 4)]]
                    for _ in range(2)]
        qs, clients = [], []
        for reqs in all_reqs:
            q = mp_ctx.Queue()
            c = mp_ctx.Process(target=_client_proc, args=(port, reqs, q))
            c.start()
            qs.append(q)
            clients.append(c)
        results = [q.get(timeout=300) for q in qs]
        for c in clients:
            c.join(timeout=60)
    finally:
        stop.set()
        srv.join(timeout=60)
        if srv.is_alive():
            srv.terminate()
    for reqs, outs in zip(all_reqs, results):
        for (prompt, max_new), got in zip(reqs, outs):
            np.testing.assert_array_equal(
                np.asarray(got, np.int32),
                _oracle(params, cfg, prompt, max_new))


async def test_remote_cancel_frees_slot_and_aborts_stream(cfg, params,
                                                          port):
    """Client-initiated CANCEL: the awaiting generate() raises, the slot
    frees for waiting work, and subsequent requests still match their
    oracle."""
    from starway_tpu.models.remote_serving import (RemoteGenerateSession,
                                                   RemoteSlotServer)

    slot = SlotServer(params, cfg, n_slots=1, max_len=64, chunk=2)
    bridge = RemoteSlotServer(slot)
    bridge.server.listen(ADDR, port)
    serve_task = asyncio.create_task(bridge.serve())
    session = await RemoteGenerateSession.aconnect(ADDR, port)
    try:
        handle = RemoteGenerateSession.Handle()
        first_chunk = asyncio.Event()

        async def doomed():
            with pytest.raises(ValueError, match="rejected or cancelled"):
                await session.generate(
                    [4, 2, 8, 1], 40, handle=handle,
                    on_tokens=lambda c: first_chunk.set())

        task = asyncio.create_task(doomed())
        await asyncio.wait_for(first_chunk.wait(), 120)
        await session.cancel(handle)
        await asyncio.wait_for(task, 120)

        # The only slot must now be free for a fresh request.
        out = await asyncio.wait_for(session.generate([9, 1, 5], 6), 120)
        np.testing.assert_array_equal(out, _oracle(params, cfg, [9, 1, 5],
                                                   6))
    finally:
        bridge.stop()
        await serve_task
        await session.aclose()
        await bridge.aclose()


async def test_remote_cancel_overtaking_request(cfg, params, port):
    """A CANCEL drained before its REQUEST (both queue up during one
    decode step; cancels drain first) must still abort the request —
    the stash rejects it at submit time instead of losing the cancel."""
    from starway_tpu.models.remote_serving import (RemoteGenerateSession,
                                                   RemoteSlotServer)

    slot = SlotServer(params, cfg, n_slots=1, max_len=64, chunk=4)
    bridge = RemoteSlotServer(slot)
    bridge.server.listen(ADDR, port)
    # The session needs a running serve loop to receive its ASSIGN;
    # pause the loop afterwards to stage the overtaking deterministically.
    serve_task = asyncio.create_task(bridge.serve())
    session = await RemoteGenerateSession.aconnect(ADDR, port)
    bridge.stop()
    await serve_task
    bridge._stopping = False  # re-arm (white-box: serve() is re-entrant)
    try:
        # Pre-load BOTH queues while the loop is paused: the drain order
        # processes cancels first — the CANCEL overtakes the REQUEST.
        nonce = 0
        bridge._requests.append((session.client_id, np.asarray(
            [nonce, 30, 4, 4, 2, 8, 1], np.int32), time.perf_counter()))
        bridge._cancels.append((session.client_id, nonce))
        session._nonce = 1  # nonce 0 is taken by the hand-crafted request
        task = asyncio.create_task(_await_aborted(session, nonce))
        serve_task = asyncio.create_task(bridge.serve())
        status = await asyncio.wait_for(task, 120)
        assert status == 2  # aborted, never decoded
        # Service continues for normal requests.
        out = await asyncio.wait_for(session.generate([9, 1, 5], 6), 120)
        np.testing.assert_array_equal(out, _oracle(params, cfg, [9, 1, 5],
                                                   6))
    finally:
        bridge.stop()
        await serve_task
        await session.aclose()
        await bridge.aclose()


async def _await_aborted(session, nonce):
    from starway_tpu.models.remote_serving import (FULL_MASK, TAG_TOKENS,
                                                   _recv_buf)

    buf = _recv_buf(8)
    await session.client.arecv(buf, TAG_TOKENS | nonce, FULL_MASK)
    return int(buf.view(np.int32)[1])


# ------------------------------------------- the done frame's timing trailer


async def _bridge_and_session(cfg, params, port):
    from starway_tpu.models.remote_serving import (RemoteGenerateSession,
                                                   RemoteSlotServer)

    slot = SlotServer(params, cfg, n_slots=1, max_len=64, chunk=3)
    bridge = RemoteSlotServer(slot)
    bridge.server.listen(ADDR, port)
    serve_task = asyncio.create_task(bridge.serve())
    session = await RemoteGenerateSession.aconnect(ADDR, port)

    async def finish():
        bridge.stop()
        await serve_task
        await session.aclose()
        await bridge.aclose()

    return slot, bridge, session, finish


async def test_remote_timing_trailer_roundtrip(cfg, params, transport, port):
    """The server's timing of a request rides its done frame back: over
    every transport, ``handle.timing`` is filled, the trailer's words are
    the durations of the server's own row, and both rows (one route) sit
    in request_log().  Two requests on one slot: the second one queued."""
    from starway_tpu.models import serving
    from starway_tpu.models.remote_serving import (TIMING_WORDS,
                                                   RemoteGenerateSession)

    slot, _bridge, session, finish = await _bridge_and_session(
        cfg, params, port)
    try:
        handles = [RemoteGenerateSession.Handle() for _ in range(2)]
        reqs = [([4, 2, 8, 1], 10), ([6, 6, 3], 5)]
        outs = await asyncio.gather(*(
            session.generate(p, m, handle=h)
            for (p, m), h in zip(reqs, handles)))
    finally:
        await finish()
    log = serving.request_log()
    for h, (prompt, max_new), out in zip(handles, reqs, outs):
        np.testing.assert_array_equal(out, _oracle(params, cfg, prompt,
                                                   max_new))
        timing = h.timing
        route = f"{session.client_id}:{h.nonce}"
        assert timing["route"] == route and timing["status"] == "done"
        assert timing["n_out"] == max_new
        assert timing["t_send"] <= timing["t_first_rx"] <= timing["t_done_rx"]
        us = timing["server_us"]
        assert tuple(us) == TIMING_WORDS and all(v >= 0 for v in us.values())
        srow, = [r for r in log if r["side"] == "server"
                 and r["server"] == slot.server_id and r.get("route") == route]
        assert srow["status"] == "done"
        assert (srow["t_recv"] <= srow["t_submit"] <= srow["t_admit0"]
                <= srow["t_first"] <= srow["t_first_post"]
                <= srow["t_done_post"])
        for word, (a, b) in {
                "recv_submit": ("t_recv", "t_submit"),
                "submit_admit0": ("t_submit", "t_admit0"),
                "admit0_first": ("t_admit0", "t_first"),
                "first_post": ("t_first", "t_first_post"),
                "recv_done_post": ("t_recv", "t_done_post")}.items():
            assert us[word] == round((srow[b] - srow[a]) * 1e6)
        assert us["steps"] == srow["steps"] >= 1
        # One process here, so one clock: the server's phases up to the
        # first send lie inside the client's time to first token.
        server_ttft = sum(us[w] for w in TIMING_WORDS[:4])
        client_ttft = (timing["t_first_rx"] - timing["t_send"]) * 1e6
        assert 0 < server_ttft <= client_ttft + 50
        crow, = [r for r in log if r["side"] == "client"
                 and r["route"] == route and r["t_send"] == timing["t_send"]]
        assert crow["server_us"] == us
    # The second request waited for the only slot: its queue time is real.
    first, second = (h.timing["server_us"] for h in handles)
    assert second["submit_admit0"] > first["submit_admit0"]
    assert second["submit_admit0"] >= first["admit0_first"]


async def test_remote_old_server_without_trailer(cfg, params, port,
                                                 monkeypatch):
    """A server from before the trailer sends [nonce, status, count,
    tokens]: the client parses it as ever and reports no server timing."""
    from starway_tpu.models import remote_serving
    from starway_tpu.models.remote_serving import RemoteGenerateSession

    monkeypatch.setattr(remote_serving, "_timing_trailer", lambda row: [])
    _slot, _bridge, session, finish = await _bridge_and_session(
        cfg, params, port)
    try:
        handle = RemoteGenerateSession.Handle()
        out = await session.generate([4, 2, 8, 1], 7, handle=handle)
    finally:
        await finish()
    np.testing.assert_array_equal(out, _oracle(params, cfg, [4, 2, 8, 1], 7))
    assert handle.timing["server_us"] is None
    assert handle.timing["status"] == "done" and handle.timing["n_out"] == 7


async def test_remote_old_client_ignores_trailer(cfg, params, port):
    """A client from before the trailer posts 3 + max_chunk words and reads
    words[3:3 + count]: the trailer behind the tokens changes nothing."""
    from starway_tpu.models.remote_serving import (FULL_MASK, STATUS_DONE,
                                                   TAG_REQUEST, TAG_TOKENS,
                                                   _recv_buf, _wire)

    _slot, _bridge, session, finish = await _bridge_and_session(
        cfg, params, port)
    try:
        prompt, nonce, out = [4, 2, 8, 1], 77, []
        await session.client.asend(
            _wire([nonce, 7, len(prompt), *prompt]),
            TAG_REQUEST | session.client_id)
        while True:
            buf = _recv_buf(3 + 16)
            await session.client.arecv(buf, TAG_TOKENS | nonce, FULL_MASK)
            words = buf.view(np.int32)
            out.extend(int(t) for t in words[3:3 + int(words[2])])
            if int(words[1]) == STATUS_DONE:
                break
    finally:
        await finish()
    np.testing.assert_array_equal(out, _oracle(params, cfg, prompt, 7))


async def test_remote_aborted_stream_fills_handle_timing(cfg, params, port):
    """A rejected request still ends with a client row: status aborted, no
    server timing (only a done frame carries the trailer)."""
    from starway_tpu.models.remote_serving import RemoteGenerateSession

    _slot, _bridge, session, finish = await _bridge_and_session(
        cfg, params, port)
    try:
        handle = RemoteGenerateSession.Handle()
        with pytest.raises(ValueError):
            await session.generate([1, 2, 3], 4096, handle=handle)
    finally:
        await finish()
    assert handle.timing["status"] == "aborted"
    assert handle.timing["server_us"] is None and handle.timing["n_out"] == 0
    assert handle.timing["t_first_rx"] is None


async def test_remote_bridge_spans_in_trace(cfg, params, port, monkeypatch):
    """STARWAY_TRACE=1: the bridge's drain and emit phases land in the
    served SlotServer's ring beside its serve.* spans, and aclose() retires
    that ring so the export still finds it."""
    from starway_tpu import trace
    from starway_tpu.core import swtrace

    monkeypatch.delenv("STARWAY_TLS", raising=False)
    monkeypatch.delenv("STARWAY_NATIVE", raising=False)
    monkeypatch.setenv("STARWAY_TRACE", "1")
    swtrace.reset()
    try:
        slot, _bridge, session, finish = await _bridge_and_session(
            cfg, params, port)
        try:
            await session.generate([4, 2, 8, 1], 5)
        finally:
            await finish()
        dumps = [d for d in swtrace.dump_all()
                 if d["worker"] == slot.trace_label]
        names = {e["name"] for e in trace.to_chrome(dumps)["traceEvents"]
                 if e.get("cat") == "stage"}
    finally:
        swtrace.reset()
    assert {"bridge.drain", "bridge.emit", "serve.step",
            "serve.admit"} <= names
    assert set(slot.stage_scope.snapshot()) >= names
