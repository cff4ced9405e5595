"""swtrace tests (DESIGN.md §13): per-op lifecycle tracing, the counter
registry, the flight recorder, and the tracing-off overhead guard --
plus the swscope stitching layer (DESIGN.md §15): two-process ring dumps
merged by ``python -m starway_tpu.trace --merge`` into one clock-aligned
trace with flow-connected send->recv spans, and the session-resume
(conn, epoch) track keying of the Chrome exporter.

Covers BOTH engines where they implement the surface (the trace ring and
counter registry live in core/engine.py and native/sw_engine.cpp; the
flight recorder and stage scopes live in the Python wrapper layer either
way), plus mixed-engine counter parity over real sockets.
"""

import asyncio
import json
import os
import subprocess
import sys
import threading

import numpy as np
import pytest

import jax.numpy as jnp

from starway_tpu import Client, DeviceBuffer, Server, perf
from starway_tpu.core import swtrace
from starway_tpu.testing.faults import FaultProxy

pytestmark = pytest.mark.asyncio

ADDR = "127.0.0.1"
MASK = (1 << 64) - 1


def _native_available() -> bool:
    from starway_tpu.core import native

    return native.available()


def _env(monkeypatch, *, native: bool, trace: bool = True, flight=None):
    monkeypatch.setenv("STARWAY_TLS", "tcp")
    monkeypatch.setenv("STARWAY_NATIVE", "1" if native else "0")
    monkeypatch.setenv("STARWAY_DEVPULL", "0")
    if trace:
        monkeypatch.setenv("STARWAY_TRACE", "1")
    else:
        monkeypatch.delenv("STARWAY_TRACE", raising=False)
    if flight is not None:
        monkeypatch.setenv("STARWAY_FLIGHT_DIR", str(flight))
    else:
        monkeypatch.delenv("STARWAY_FLIGHT_DIR", raising=False)
    swtrace.reset()


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


def _first_index(events, ev_name):
    for i, e in enumerate(events):
        if e[1] == ev_name:
            return i
    return None


# ------------------------------------------------------ lifecycle ordering


@pytest.mark.parametrize("engine", ["python", "native"])
async def test_lifecycle_event_order(port, monkeypatch, engine):
    """posted -> matched -> completed on the receiving worker and
    send_post -> send_done, flush_post -> flush_done on the sender, in
    ring order, on BOTH engines."""
    if engine == "native" and not _native_available():
        pytest.skip("native engine unavailable")
    _env(monkeypatch, native=(engine == "native"))
    server, client, _ep = await _pair(port)
    try:
        buf = np.empty(1024, dtype=np.uint8)
        recv_fut = server.arecv(buf, 0x77, MASK)
        await asyncio.sleep(0.05)  # recv posted before the send arrives
        await client.asend(np.ones(1024, dtype=np.uint8), 0x77)
        tag, length = await recv_fut
        assert (tag, length) == (0x77, 1024)
        await client.aflush()

        sev = server._server.trace_events()
        cev = client._client.trace_events()
        order = [_first_index(sev, name) for name in
                 ("recv_post", "recv_match", "recv_done")]
        assert None not in order, sev
        assert order == sorted(order), (
            f"recv lifecycle out of order: {[(e[1], e[2]) for e in sev]}")
        # Event payloads: tag + nbytes ride along.
        match = sev[order[1]]
        assert match[2] == 0x77 and match[4] == 1024, match
        corder = [_first_index(cev, name) for name in
                  ("send_post", "send_done", "flush_post", "flush_done")]
        assert None not in corder, cev
        assert corder == sorted(corder), (
            f"send lifecycle out of order: {[(e[1], e[2]) for e in cev]}")
        assert cev[corder[0]][2] == 0x77 and cev[corder[0]][4] == 1024
        assert _first_index(cev, "conn_up") is not None
    finally:
        await client.aclose()
        await server.aclose()


# ------------------------------------------------------- counter registry


async def test_counter_parity_mixed_engine_interop(port, monkeypatch):
    """Native client <-> Python server over real sockets: both expose the
    identical COUNTER_NAMES vocabulary with matching op accounting."""
    if not _native_available():
        pytest.skip("native engine unavailable")
    _env(monkeypatch, native=False, trace=False)
    server = Server()
    server.listen(ADDR, port)
    monkeypatch.setenv("STARWAY_NATIVE", "1")
    client = Client()
    from starway_tpu.core.native import NativeClientWorker

    assert isinstance(client._client, NativeClientWorker)
    await client.aconnect(ADDR, port)
    try:
        n_ops, nbytes = 8, 4096
        sinks = [np.empty(nbytes, dtype=np.uint8) for _ in range(n_ops)]
        recv_futs = [server.arecv(b, 0x500 + i, MASK)
                     for i, b in enumerate(sinks)]
        await asyncio.sleep(0.05)
        payloads = [np.full(nbytes, i + 1, dtype=np.uint8)
                    for i in range(n_ops)]
        await asyncio.gather(
            *(client.asend(p, 0x500 + i) for i, p in enumerate(payloads)))
        await asyncio.gather(*recv_futs)
        await client.aflush()

        cs = client._client.counters_snapshot()
        ss = server._server.counters_snapshot()
        # One vocabulary, both engines (enforced statically by swcheck's
        # contract-trace rule; exercised live here).
        assert set(cs) == set(ss) == set(swtrace.COUNTER_NAMES)
        assert cs["sends_posted"] == n_ops
        assert cs["sends_completed"] == n_ops
        assert cs["bytes_tx"] >= n_ops * nbytes
        assert cs["flushes_posted"] == 1 and cs["flushes_completed"] == 1
        assert ss["recvs_posted"] == n_ops
        assert ss["recvs_completed"] == n_ops
        assert ss["bytes_rx"] >= n_ops * nbytes
        assert cs["gather_passes"] >= 1 and cs["gather_items"] >= 1
        # ...and they surface through evaluate_perf_detail on both sides.
        assert client.evaluate_perf_detail(1024)["counters"] == \
            client._client.counters_snapshot()
    finally:
        await client.aclose()
        await server.aclose()


async def test_stage_scope_per_worker(port, port2, monkeypatch):
    """Satellite fix: stage telemetry is scoped per worker -- a second
    idle client pair no longer sees the first pair's tx/rx samples in its
    evaluate_perf_detail()["stages"]; the module API stays an aggregate."""
    _env(monkeypatch, native=False, trace=False)
    s1, c1, _ = await _pair(port)
    s2, c2, _ = await _pair(port2)
    try:
        perf.stage_reset()
        sink = np.empty(64 * 1024, dtype=np.uint8)
        fut = s1.arecv(sink, 9, MASK)
        await asyncio.sleep(0.05)
        await c1.asend(np.ones(64 * 1024, dtype=np.uint8), 9)
        await fut
        await c1.aflush()
        busy = c1.evaluate_perf_detail(1 << 20)["stages"]
        idle = c2.evaluate_perf_detail(1 << 20)["stages"]
        assert busy.get("tx", {}).get("count", 0) > 0, busy
        assert idle.get("tx", {}).get("count", 0) == 0, (
            f"idle client polluted by the busy pair's samples: {idle}")
        # Module-level aggregate still sees the whole process.
        assert perf.stage_snapshot().get("tx", {}).get("count", 0) > 0
    finally:
        for h in (c1, c2, s1, s2):
            await h.aclose()


# -------------------------------------------------------- flight recorder


@pytest.mark.parametrize("mode", ["drop", "truncate"])
async def test_flight_recorder_on_fault(port, monkeypatch, tmp_path, mode):
    """A FaultProxy-killed connection fails the flush with a non-cancel
    reason; the flight recorder dumps events + counters to
    STARWAY_FLIGHT_DIR (drop = RST mid-frame, truncate = clean EOF
    mid-frame)."""
    flight = tmp_path / "flight"
    _env(monkeypatch, native=False, flight=flight)
    server = Server()
    server.listen(ADDR, port)
    proxy = FaultProxy(ADDR, port, mode=mode, limit_bytes=8 * 1024).start()
    client = Client()
    await client.aconnect(ADDR, proxy.port)
    try:
        # Bigger than the proxy's byte budget: the conn dies mid-frame.
        await client.asend(np.ones(64 * 1024, dtype=np.uint8), 5)
        with pytest.raises(Exception) as err:
            # The dead conn fails the barrier; the timeout backstops the
            # case where the kill lands before the flush frame (both
            # reasons are non-cancel -> the recorder must trigger).
            await client.aflush(timeout=5.0)
        assert "cancel" not in str(err.value).lower()
        dumps = sorted(flight.glob("flight-*.json"))
        assert dumps, "no flight-recorder dump written"
        payload = json.loads(dumps[0].read_text())
        assert payload["trigger"] == "op-failed"
        assert set(payload["counters"]) == set(swtrace.COUNTER_NAMES)
        evs = [e[1] for e in payload["events"]]
        assert "send_post" in evs and "op_fail" in evs, evs
        n_before = len(list(flight.glob("flight-*.json")))
    finally:
        await client.aclose()
        await server.aclose()
        proxy.stop()
    # aclose after the fault adds the close-time snapshot.
    assert len(list(flight.glob("flight-*.json"))) > n_before
    triggers = {json.loads(p.read_text())["trigger"]
                for p in flight.glob("flight-*.json")}
    assert "close-after-fault" in triggers, triggers


async def test_flight_recorder_native_fault(port, monkeypatch, tmp_path):
    """Native-engine path: the wrapper's fail hook triggers the dump with
    the engine's own sw_trace events inside."""
    if not _native_available():
        pytest.skip("native engine unavailable")
    flight = tmp_path / "flight"
    _env(monkeypatch, native=True, flight=flight)
    server = Server()
    server.listen(ADDR, port)
    proxy = FaultProxy(ADDR, port, mode="drop", limit_bytes=8 * 1024).start()
    client = Client()
    await client.aconnect(ADDR, proxy.port)
    try:
        await client.asend(np.ones(64 * 1024, dtype=np.uint8), 5)
        with pytest.raises(Exception) as err:
            await client.aflush(timeout=5.0)
        assert "cancel" not in str(err.value).lower()
        dumps = sorted(flight.glob("flight-*.json"))
        assert dumps, "no flight-recorder dump written"
        payload = json.loads(dumps[0].read_text())
        assert payload["trigger"] == "op-failed"
        assert any(e[1] == "send_post" for e in payload["events"]), (
            "native sw_trace events missing from the dump")
    finally:
        await client.aclose()
        await server.aclose()
        proxy.stop()


# -------------------------------------------------------- overhead guard


async def test_tracing_off_hot_path_is_dark(port, monkeypatch):
    """With STARWAY_TRACE and STARWAY_FLIGHT_DIR unset, workers carry no
    ring and the per-op path never touches the tracing subsystem: no ring
    append, no wrapper closure, no flight I/O -- no per-op allocation or
    syscall from swtrace (the acceptance bar for the off path)."""
    _env(monkeypatch, native=False, trace=False)
    server, client, _ep = await _pair(port)
    try:
        assert client._client._trace is None
        assert server._server._trace is None

        def boom(*a, **k):
            raise AssertionError("swtrace hot-path hook ran with tracing off")

        monkeypatch.setattr(swtrace.TraceRing, "rec", boom)
        monkeypatch.setattr(swtrace.TraceRing, "span", boom)
        monkeypatch.setattr(swtrace, "wrap_op", boom)
        monkeypatch.setattr(swtrace, "flight_dump", boom)
        sinks = [np.empty(512, dtype=np.uint8) for _ in range(8)]
        futs = [server.arecv(b, 0x40 + i, MASK) for i, b in enumerate(sinks)]
        await asyncio.sleep(0.05)
        await asyncio.gather(*(client.asend(np.full(512, i, dtype=np.uint8),
                                            0x40 + i) for i in range(8)))
        await asyncio.gather(*futs)
        await client.aflush()
        # Counters still accumulate (plain int adds, no allocation).
        cs = client._client.counters_snapshot()
        assert cs["sends_posted"] == 8 and cs["sends_completed"] == 8
    finally:
        await client.aclose()
        await server.aclose()


_NO_JAX_SCRIPT = r"""
import asyncio, sys
import numpy as np
from starway_tpu import Client, Server, perf

async def main():
    server, client = Server(), Client()
    server.listen("127.0.0.1", 0)
    await client.aconnect_address(server.get_worker_address())
    sink = np.empty(512, np.uint8)
    fut = server.arecv(sink, 5, (1 << 64) - 1)
    await client.asend(np.ones(512, np.uint8), 5)
    await fut
    await client.aflush()
    posts = perf.stage_snapshot()["post"]["count"]
    await client.aclose()
    await server.aclose()
    print("posts", posts, "jax", "jax" in sys.modules)

asyncio.run(main())
"""


@pytest.mark.parametrize("case", ["three_records_an_op", "no_jax_import"])
async def test_message_stage_record_count(port, monkeypatch, case):
    """The COUNT guard beside the overhead guard (steady, no timing): the
    stamps ride the message and are recorded once where each part of it
    settles, with no lock taken.  One in-process device message across two
    devices costs its receive THREE record calls (the poster's ``post``,
    the engine's ONE call for ``issue`` / ``land`` / ``settle``, the
    loop's ``loop_hop``) and its send two; and a process that had no jax
    gets none from the stamps or their annotations."""
    if case == "no_jax_import":
        env = dict(os.environ, STARWAY_NATIVE="0")
        env.pop("STARWAY_TRACE", None)
        out = subprocess.run([sys.executable, "-c", _NO_JAX_SCRIPT], env=env,
                             capture_output=True, text=True, timeout=120)
        assert out.returncode == 0, out.stderr
        assert out.stdout.split() == ["posts", "3", "jax", "False"], out.stdout
        return
    import jax

    monkeypatch.delenv("STARWAY_TRACE", raising=False)
    monkeypatch.delenv("STARWAY_FLIGHT_DIR", raising=False)
    server, client = Server(), Client()
    server.listen(ADDR, port)
    await client.aconnect(ADDR, port)
    calls: list = []   # (worker's scope, tag, the phases of ONE call)
    inside: list = []  # a record_stages call is running on this thread
    real_many, real_one = perf.record_stages, perf.record_phase

    def many(scope, tag, stages):
        calls.append((scope, tag, tuple(st[0] for st in stages)))
        inside.append(threading.get_ident())
        try:
            real_many(scope, tag, stages)
        finally:
            inside.remove(threading.get_ident())

    def one(scope, tag, name, *rest):
        if threading.get_ident() not in inside:
            calls.append((scope, tag, (name,)))
        real_one(scope, tag, name, *rest)

    def per_syscall(*a, **k):
        raise AssertionError("a handoff recorded a tx / rx / stage / place")

    try:
        n = 4
        srcs = [jax.device_put(jnp.full((1024,), k, jnp.int32), jax.devices()[0])
                for k in range(n)]
        sinks = [DeviceBuffer((1024,), jnp.int32, device=jax.devices()[1 + k])
                 for k in range(n)]
        monkeypatch.setattr(perf, "record_stages", many)
        monkeypatch.setattr(perf, "record_phase", one)
        monkeypatch.setattr(perf, "record_stage", per_syscall)
        recvs = [server.arecv(s, 0x50 + k, MASK) for k, s in enumerate(sinks)]
        await asyncio.sleep(0.02)
        await asyncio.gather(
            *(client.asend(a, 0x50 + k) for k, a in enumerate(srcs)), *recvs)
        for _ in range(400):   # the engine's record runs behind the fires
            if sum("settle" in c[2] for c in calls) == n:
                break
            await asyncio.sleep(0.005)
        rx, tx = server._server.stage_scope, client._client.stage_scope
        for k in range(n):
            mine = [c for c in calls if c[1] == 0x50 + k]
            assert sorted(c[2] for c in mine if c[0] is rx) == [
                ("issue", "land", "settle"), ("loop_hop",), ("post",)], mine
            assert sorted(c[2] for c in mine if c[0] is tx) == [
                ("loop_hop",), ("post",)], mine
        assert len(calls) == 5 * n, calls
    finally:
        await client.aclose()
        await server.aclose()


@pytest.mark.parametrize("case", [
    "once_in_its_scope", "module_adds_the_scopes_up", "a_scope_that_goes",
    "no_scope", "reset", "ring_event"])
def test_stage_recorder_views(case):
    """ONE recorder (DESIGN.md §12, §13): ``record_stage`` (a syscall's
    ``tx`` / ``rx``, a message's ``stage`` / ``place``) and ``record_phase``
    put a sample in the recording worker's scope alone, and
    ``perf.stage_snapshot()`` is the sum over the scopes, those that are
    gone included."""
    import gc

    def seen(name):
        return perf.stage_snapshot().get(name, {"count": 0, "bytes": 0})

    name = f"t_{case}"  # this test's own stage: other tests' workers live on
    a, b = perf.StageScope(), perf.StageScope()
    if case == "once_in_its_scope":
        perf.record_stage(name, 0.5, 100, a)
        perf.record_phase(a, 7, name, 0.25, 50, 1.0)
        assert a.snapshot()[name] == {"count": 2, "seconds": 0.75,
                                      "bytes": 150, "gbps": 150 / 0.75 / 1e9}
        assert name not in b.snapshot()
    elif case == "module_adds_the_scopes_up":
        perf.record_stage(name, 0.5, 100, a)
        perf.record_stage(name, 0.5, 100, b)
        a.record(name, 9.0, 9)  # a layer above the engines: its scope's own
        assert seen(name)["count"] == 2 and seen(name)["bytes"] == 200
        assert a.snapshot()[name]["count"] == 2
    elif case == "a_scope_that_goes":
        perf.record_stage(name, 0.5, 100, a)
        del a
        gc.collect()
        assert seen(name)["count"] == 1
    elif case == "no_scope":
        perf.record_stage(name, 0.5, 100)
        perf.record_phase(None, 0, name, 0.5, 100, 1.0)
        assert seen(name)["count"] == 2
        assert name not in a.snapshot()
    elif case == "reset":
        perf.record_stage(name, 0.5, 100, a)
        del b
        gc.collect()
        perf.stage_reset()
        assert name not in perf.stage_snapshot()
    else:
        a.ring = swtrace.TraceRing(8)
        perf.record_stage(name, 0.5, 100, a)          # untagged, stamped now
        perf.record_phase(a, 0xBEEF, name, 0.25, 50, 123.0)
        untagged, tagged = a.ring.snapshot()
        assert untagged[1:] == (swtrace.EV_STAGE, 0, 0, 100, name, 0.5)
        assert tagged == (123.0, swtrace.EV_STAGE, 0xBEEF, 0, 50, name, 0.25)


# ---------------------------------------------------------- chrome export


async def test_chrome_export_spans_per_conn(port, monkeypatch, tmp_path):
    """A traced run exports well-formed Chrome trace_event JSON: every
    event carries name/ph/ts/pid/tid, op lifecycles render as complete
    spans, and send spans land on the connection's track."""
    from starway_tpu import trace as trace_mod

    _env(monkeypatch, native=False)
    server, client, _ep = await _pair(port)
    try:
        sink = np.empty(2048, dtype=np.uint8)
        fut = server.arecv(sink, 3, MASK)
        await asyncio.sleep(0.05)
        await client.asend(np.ones(2048, dtype=np.uint8), 3)
        await fut
        await client.aflush()
    finally:
        await client.aclose()
        await server.aclose()
    dumps = swtrace.dump_all()
    assert len(dumps) >= 2, [d["worker"] for d in dumps]
    out = trace_mod.write_chrome(dumps, tmp_path / "trace.json")
    doc = json.loads(out.read_text())
    events = doc["traceEvents"]
    assert events
    for e in events:
        assert {"name", "ph", "pid", "tid"} <= set(e), e
        if e["ph"] != "M":
            assert "ts" in e and e["ts"] >= 0, e
        if e["ph"] == "X":
            assert e["dur"] >= 0, e
    spans = [e for e in events if e["ph"] == "X"]
    assert any(e["name"].startswith("send tag=") for e in spans), spans
    assert any(e["name"].startswith("recv tag=") for e in spans), spans
    # Send spans sit on the conn's track (tid != 0), per-conn layout.
    assert any(e["tid"] != 0 for e in spans
               if e["name"].startswith("send tag=")), spans
    # The CLI converts flight-style dumps to the same format.
    dump_file = tmp_path / "ring.json"
    dump_file.write_text(json.dumps(
        {"worker": "w", "events": [list(ev) for ev in dumps[0]["events"]]}))
    rc = trace_mod.main([str(dump_file), "-o", str(tmp_path / "cli.json")])
    assert rc == 0
    assert json.loads((tmp_path / "cli.json").read_text())["traceEvents"]


# ------------------------------------------------- swscope: trace --merge
#
# A real two-process run: the server lives in a subprocess, both sides
# write per-process ring dumps (swtrace.write_ring_dump), and the CLI's
# --merge mode must stitch them into ONE Chrome trace whose EV_E2E
# ordinal pairs become cross-process flow events and whose EV_CLOCK
# samples align the two timelines (DESIGN.md §15).

_MERGE_SERVER = """
import asyncio, os, sys
os.environ["STARWAY_TLS"] = "tcp"
os.environ["STARWAY_TRACE"] = "1"
os.environ["STARWAY_DEVPULL"] = "0"
os.environ["STARWAY_NATIVE"] = sys.argv[1]
port, n, dump = int(sys.argv[2]), int(sys.argv[3]), sys.argv[4]
import numpy as np
from starway_tpu import Server
from starway_tpu.core import swtrace

async def main():
    server = Server()
    server.listen("127.0.0.1", port)
    print("READY", flush=True)
    bufs = [np.empty(4096, dtype=np.uint8) for _ in range(n)]
    futs = [server.arecv(bufs[i], i + 1, (1 << 64) - 1) for i in range(n)]
    await asyncio.wait_for(asyncio.gather(*futs), timeout=60)
    # Two replies: the conn is BIDIRECTIONAL, so both ends own a tx
    # ordinal sequence -- the merge must pair each with the OTHER end.
    ep = server.list_clients().pop()
    for i in range(2):
        await server.asend(ep, np.full(4096, 0xAB, dtype=np.uint8), 101 + i)
    await asyncio.wait_for(server.aflush_ep(ep), timeout=60)
    # Two-way shutdown handshake.  DONE gates the client's close on this
    # flush retiring; the BYE wait gates OUR close on the client's own
    # flush retiring.  Without either, one side tears the conn down under
    # the other's FLUSH/FLUSH_ACK (peer-reset race -> flaky test).
    print("DONE", flush=True)
    sys.stdin.readline()
    swtrace.write_ring_dump(dump)
    await server.aclose()

asyncio.run(main())
"""


@pytest.mark.parametrize("pairing", ["py-py", "py-native", "native-py"])
async def test_merge_stitches_two_process_trace(port, monkeypatch, tmp_path,
                                                pairing):
    """Two processes (and the mixed py<->native pairings) produce ring
    dumps that ``trace --merge`` stitches into one Chrome trace: every
    transferred message becomes a flow event whose send end and recv end
    sit in DIFFERENT trace processes, a clock edge aligns the tracks, and
    the wire-latency breakdown covers every pair -- the ISSUE 6
    acceptance structure."""
    from starway_tpu import trace as trace_mod
    from starway_tpu.core import swtrace as swtrace_mod

    s_eng, c_eng = pairing.split("-")
    if "native" in (s_eng, c_eng) and not _native_available():
        pytest.skip("native engine unavailable")
    n = 6
    srv_dump = tmp_path / "server.json"
    cli_dump = tmp_path / "client.json"
    _env(monkeypatch, native=(c_eng == "native"))
    env = dict(os.environ)
    env.pop("STARWAY_FLIGHT_DIR", None)
    proc = subprocess.Popen(
        [sys.executable, "-c", _MERGE_SERVER,
         "1" if s_eng == "native" else "0", str(port), str(n),
         str(srv_dump)],
        stdout=subprocess.PIPE, stdin=subprocess.PIPE, text=True, env=env,
        cwd="/root/repo")
    try:
        assert proc.stdout.readline().strip() == "READY"
        client = Client()
        await client.aconnect(ADDR, port)
        try:
            rbufs = [np.empty(4096, dtype=np.uint8) for _ in range(2)]
            rfuts = [client.arecv(rbufs[i], 101 + i, MASK) for i in range(2)]
            await asyncio.gather(*(client.asend(
                np.full(4096, i + 1, dtype=np.uint8), i + 1)
                for i in range(n)))
            await client.aflush()
            await asyncio.wait_for(asyncio.gather(*rfuts), timeout=60)
            # The one-shot handshake PING's PONG carries the clock sample;
            # it raced the data frames, so wait for it before dumping.
            for _ in range(400):
                if any(e[1] == swtrace_mod.EV_CLOCK
                       for e in client._client.trace_events()):
                    break
                await asyncio.sleep(0.005)
            events = client._client.trace_events()
            assert any(e[1] == swtrace_mod.EV_CLOCK for e in events), (
                "no clock sample on the connector")
            swtrace_mod.write_ring_dump(cli_dump)
            # Shutdown handshake (see _MERGE_SERVER): wait for the
            # server's flush before closing, then release its close.
            assert proc.stdout.readline().strip() == "DONE"
            proc.stdin.write("BYE\n")
            proc.stdin.flush()
        finally:
            await client.aclose()
        assert proc.wait(timeout=60) == 0
    finally:
        if proc.poll() is None:
            proc.kill()

    out = tmp_path / "merged.json"
    rc = trace_mod.main(["--merge", str(srv_dump), str(cli_dump),
                         "-o", str(out)])
    assert rc == 0
    doc = json.loads(out.read_text())
    summary = doc["swscope"]
    assert summary["processes"] == 2
    assert summary["pairs"] >= n + 2, summary
    assert summary["bytes_paired"] >= (n + 2) * 4096, summary
    assert summary["clock_edges"], "no clock edge between the processes"
    # Causal ordering is only as tight as the clock alignment itself: a
    # one-shot PING/PONG edge on a busy 1-core box can carry hundreds of
    # us of error (err_us is the measured RTT half-width), which dwarfs
    # real loopback wire latency -- derive the tolerance from the edges
    # instead of hard-coding one.
    slack = max(5000.0, 4.0 * max(e["err_us"] for e in summary["clock_edges"]))
    assert summary["wire_us"]["p50"] >= -slack, (summary, slack)

    evs = doc["traceEvents"]
    # Clock-aligned tracks: both processes' workers present as trace
    # processes.
    pnames = [e for e in evs if e["ph"] == "M"
              and e["name"] == "process_name"]
    assert len({e["pid"] for e in pnames}) >= 2, pnames
    # Flow events: starts and ends pair by id, across DIFFERENT pids,
    # with the (clock-aligned) send end never after the recv end.
    starts = {e["id"]: e for e in evs
              if e.get("ph") == "s" and e.get("cat") == "swscope"}
    ends = {e["id"]: e for e in evs
            if e.get("ph") == "f" and e.get("cat") == "swscope"}
    assert len(starts) == len(ends) == summary["pairs"]
    for fid, s in starts.items():
        f = ends[fid]
        assert s["pid"] != f["pid"], (s, f)
        assert s["ts"] <= f["ts"] + slack, (s, f, slack)
    # Both directions paired: flow arrows originate from BOTH processes
    # (a (tcid, ordinal)-only join would collide the two ends' ordinal
    # sequences and lose or mispair the reverse traffic).
    assert len({e["pid"] for e in starts.values()}) == 2, starts


async def test_merge_clock_alignment_sign_convention():
    """The delta propagation is exact, not just small-skew-tolerant: a
    synthetic 2 s clock skew between two processes must align to the
    TRUE 50 us wire latency (a sign error would show +/-2 s)."""
    from starway_tpu import trace as trace_mod

    tc = "deadbeef00000000"
    # Process B's clock runs 2.0 s ahead of A's; B pinged A, so B's ring
    # holds the sample offset = t_A - t_B = -2_000_000 us.  B sent at
    # true time 10.0 (stamped 12.0 on its clock); A received 50 us later.
    dump_b = {"pid": 222, "workers": [{"worker": "B", "events": [
        [12.0, "e2e", 1, 7, 4096, tc + ":tx", 0.0],
        [11.5, "clock_sample", 0, 7, 0, f"{tc}:-2000000:10", 0.0],
    ]}]}
    dump_a = {"pid": 111, "workers": [{"worker": "A", "events": [
        [10.000050, "e2e", 1, 3, 4096, tc + ":rx", 0.0],
    ]}]}
    doc = trace_mod.merge_chrome([("a", dump_a), ("b", dump_b)])
    assert doc["swscope"]["pairs"] == 1
    assert doc["swscope"]["clock_edges"][0]["offset_us"] == -2000000
    assert abs(doc["swscope"]["wire_us"]["p50"] - 50.0) < 1.0, (
        doc["swscope"]["wire_us"])
    s = [e for e in doc["traceEvents"] if e.get("ph") == "s"]
    f = [e for e in doc["traceEvents"] if e.get("ph") == "f"]
    assert len(s) == len(f) == 1
    assert abs((f[0]["ts"] - s[0]["ts"]) - 50.0) < 1.0, (s, f)


async def test_merge_ring_dump_cli_single_mode(tmp_path, port, monkeypatch):
    """Without --merge the CLI accepts write_ring_dump files too (the
    per-process shape), flattening every worker into one trace."""
    from starway_tpu import trace as trace_mod

    _env(monkeypatch, native=False)
    server, client, _ep = await _pair(port)
    try:
        sink = np.empty(1024, dtype=np.uint8)
        fut = server.arecv(sink, 4, MASK)
        await asyncio.sleep(0.05)
        await client.asend(np.ones(1024, dtype=np.uint8), 4)
        await fut
        await client.aflush()
    finally:
        await client.aclose()
        await server.aclose()
    dump = swtrace.write_ring_dump(tmp_path / "ring.json")
    rc = trace_mod.main([str(dump), "-o", str(tmp_path / "chrome.json")])
    assert rc == 0
    doc = json.loads((tmp_path / "chrome.json").read_text())
    assert any(e["ph"] == "X" for e in doc["traceEvents"])


# ------------------------------------- swscope: (conn, epoch) track keying


async def test_chrome_export_epoch_tracks_on_resume(port, monkeypatch):
    """Satellite fix: a session resume starts a NEW exporter track --
    pre- and post-resume events never interleave on one tid.  Driven by
    the tests/test_session.py machinery (FaultProxy RST mid-burst with
    STARWAY_SESSION=1)."""
    from starway_tpu import trace as trace_mod

    _env(monkeypatch, native=False)
    monkeypatch.setenv("STARWAY_SESSION", "1")
    monkeypatch.setenv("STARWAY_SESSION_GRACE", "20")
    server = Server()
    server.listen(ADDR, port)
    proxy = FaultProxy(ADDR, port).start()
    client = Client()
    await client.aconnect(ADDR, proxy.port)
    try:
        n, size = 12, 4096
        bufs = [np.zeros(size, dtype=np.uint8) for _ in range(n)]
        recvs = [server.arecv(bufs[i], i + 1, MASK) for i in range(n)]
        sends = []
        for i in range(n):
            sends.append(client.asend(
                np.full(size, (i + 1) % 251, dtype=np.uint8), i + 1))
            if i == n // 2:
                await asyncio.sleep(0.3)   # let part of the burst fly
                proxy.kill_all(rst=True)   # suspend + redial + replay
        await asyncio.wait_for(asyncio.gather(*sends), timeout=60)
        await asyncio.wait_for(client.aflush(), timeout=60)
        await asyncio.wait_for(asyncio.gather(*recvs), timeout=60)

        events = client._client.trace_events()
        resume_idx = _first_index(events, swtrace.EV_SESS_RESUME)
        assert resume_idx is not None, "no resume recorded"
        chrome = trace_mod.chrome_events("client", events, pid=1)
        labels = {e["tid"]: e["args"]["name"] for e in chrome
                  if e["ph"] == "M" and e["name"] == "thread_name"}
        epoch_tids = {t for t, name in labels.items() if "epoch" in name}
        assert epoch_tids, f"no epoch track created on resume: {labels}"
        base_tids = {t for t, name in labels.items()
                     if name.startswith("conn ") and "epoch" not in name}
        assert base_tids, labels
        # Send spans landed on BOTH incarnations' tracks...
        sends_by_tid = {}
        for e in chrome:
            if e["ph"] == "X" and e["name"].startswith("send tag="):
                sends_by_tid.setdefault(e["tid"], []).append(e)
        assert sends_by_tid.keys() & base_tids, sends_by_tid.keys()
        assert sends_by_tid.keys() & epoch_tids, (
            f"post-resume sends still on the old track: {sends_by_tid.keys()}")
        # ...and nothing COMPLETING after the resume sits on the old
        # track: the exporter keys the track by the epoch current at the
        # event's terminal record, so the old track's spans all ended
        # before the resume instant (the interleaving this fix removes).
        resume_ts = events[resume_idx][0] * 1e6
        for tid in sends_by_tid.keys() & base_tids:
            for e in sends_by_tid[tid]:
                assert e["ts"] + e["dur"] <= resume_ts + 1000, (
                    f"span ending after resume on pre-resume track: {e}")
    finally:
        await client.aclose()
        await server.aclose()
        proxy.stop()


async def test_device_payload_stage_spans_in_trace(port, monkeypatch):
    """Device-plane transfers record stage spans (D2H 'stage', H2D
    'place') into the owning worker's ring via its StageScope."""
    import jax

    _env(monkeypatch, native=False)
    server, client, _ep = await _pair(port)
    try:
        src = jax.device_put(jnp.arange(64 * 1024, dtype=jnp.float32),
                             jax.devices()[0])
        sink = DeviceBuffer((64 * 1024,), jnp.float32, device=jax.devices()[1])
        fut = server.arecv(sink, 21, MASK)
        await asyncio.sleep(0.05)
        await client.asend(src, 21)
        await fut
        cli_stages = {e[5] for e in client._client.trace_events()
                      if e[1] == swtrace.EV_STAGE}
        srv_stages = {e[5] for e in server._server.trace_events()
                      if e[1] == swtrace.EV_STAGE}
        assert "stage" in cli_stages, cli_stages   # D2H on the sender
        assert "place" in srv_stages, srv_stages   # H2D on the receiver
    finally:
        await client.aclose()
        await server.aclose()
