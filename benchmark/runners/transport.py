"""Runner of the ``transport`` kind: the tag-matched transport itself, with
payloads in HBM, driven by a transfer mix (rounds of chunks, ``aflush`` at
the end of each round, rounds until the window ends).

Two drivers, chosen by the traffic file:

``peer``    endpoint 0 is this process, the chip-less peer (JAX is never
            imported here); endpoint 1 is a child process that holds the
            chip.  They connect as two processes of one host connect by
            default; no STARWAY_* variable is set.
``inproc``  one process drives every chip of the host: endpoint k is chip
            k, with one Server that receives into its HBM and one Client
            to each other chip's Server for what it sends.

From the program the runner takes ``Server`` / ``Client`` /
``DeviceBuffer``, the workers' counters and swpulse histograms,
``perf.stage_snapshot()`` and the helpers of ``starway_tpu.utils.chip``.
Payload patterns, headers, timing and every comparison are the
benchmark's own (configs/hbm_duplex_reference.py decides what is correct).
"""

from __future__ import annotations

import asyncio
import sys
import time

import numpy as np

from benchmark.harness import spec as S
from benchmark.harness import stats, traffic as T
from benchmark.harness.chipside import (Profile, child_event, chip_start, log,
                                        memory_peak, setup_seconds,
                                        spawn_chip_child)
from benchmark.harness.spans import Spans

MASK = (1 << 64) - 1
TAG_DATA, TAG_CTRL, TAG_ACK = 0xD1 << 48, 0xC1 << 48, 0xA1 << 48
CONTINUE, VERIFY, STOP = 0, 1, 2   # control flags, OR-ed
WARM_ROUNDS = 2
TRACE_SECONDS = 3.0   # few device operations here: stopping the trace is cheap


def data_tag(src: int, dst: int, index: int) -> int:
    return TAG_DATA | (src << 40) | (dst << 32) | index


# ------------------------------------------------- device-side payloads


def device_tools(chunk_bytes: int):
    """Jitted makers of the chip's payloads: the published formula of
    configs/hbm_duplex_reference.py written again in jax.numpy (the
    reference checks what arrives, so a slip here shows as a mismatch)."""
    import jax
    import jax.numpy as jnp

    words = chunk_bytes // 4

    def u32(x):
        return jnp.asarray(x, jnp.uint32)

    @jax.jit
    def make(salt, header):
        x = jnp.arange(words, dtype=jnp.uint32) * u32(0x9E3779B1) + salt
        x = x ^ (x >> 16)
        x = x * u32(0x85EBCA6B)
        x = x ^ (x >> 13)
        x = x * u32(0xC2B2AE35)
        x = x ^ (x >> 16)
        x = x.at[:4].set(header)
        return jax.lax.bitcast_convert_type(x, jnp.uint8).reshape(-1)

    @jax.jit
    def restamp(arrays, headers):
        """New headers into the first 16 bytes of every array."""
        out = []
        for a, h in zip(arrays, headers):
            hb = jax.lax.bitcast_convert_type(h, jnp.uint8).reshape(-1)
            out.append(a.at[:16].set(hb))
        return tuple(out)

    @jax.jit
    def heads(arrays):
        """[n, 16] uint8: the first 16 bytes of every array, one fetch."""
        return jnp.stack([a[:16] for a in arrays])

    return make, restamp, heads


# ------------------------------------------------------------ endpoints


class Endpoint:
    """One endpoint's side of the rounds: what it sends and receives in
    a round, posting, flushing, and checking what arrived.  ``k`` is the
    endpoint number; ``on_device`` says whether its buffers live in HBM."""

    def __init__(self, ctx: dict, k: int, device=None):
        self.k, self.device = k, device
        self.traffic, self.seed = ctx["traffic"], ctx["args"].seed
        self.nbytes = int(self.traffic["chunk_bytes"])
        self.ref = S.load_reference(ctx["cell"]["config"])
        moves = T.transfer_round(self.traffic, self.seed, 0)
        self.out = sorted((d, i) for s, d, i in moves if s == k)
        self.inn = sorted((s, i) for s, d, i in moves if d == k)
        self.header_faults = 0
        self.byte_faults = 0
        self.landed = set()
        # On a device the stream's buffers are a ring ``resident_rounds``
        # deep, all resident in HBM (a loader's prefetch queue): round r
        # sends from and receives into set r mod depth.
        self.depth = int(self.traffic.get("resident_rounds", 1)) if device is not None else 1
        self.turn = 0
        if device is None:
            self.src = {(d, i): self.ref.chunk(self.seed, k, d, i, 0, self.nbytes)
                        for d, i in self.out}
            self.sink = {(s, i): np.empty(self.nbytes, np.uint8) for s, i in self.inn}
        else:
            import jax
            import jax.numpy as jnp

            from starway_tpu import DeviceBuffer

            self.make, self.restamp, self.heads = device_tools(self.nbytes)
            with jax.default_device(device):
                self.src_ring = [tuple(
                    self.make(jnp.uint32(self.ref.salt(self.seed, k, d, i)),
                              jnp.asarray(self.ref.header_words(0, i, self.seed, k, d)))
                    for d, i in self.out) for _ in range(self.depth)]
                self.sink_ring = [
                    {(s, i): DeviceBuffer((self.nbytes,), np.uint8, device=device,
                                          array=jnp.zeros((self.nbytes,), jnp.uint8))
                     for s, i in self.inn} for _ in range(self.depth)]
            jax.block_until_ready(self.src_ring)
            self.src_arrays, self.sink = self.src_ring[0], self.sink_ring[0]

    # -- sending side
    def stamp(self, round_no: int) -> None:
        if self.device is None:
            for (d, i), buf in self.src.items():
                buf.view(np.uint32)[:4] = self.ref.header_words(
                    round_no, i, self.seed, self.k, d)
        else:
            self.turn = round_no % self.depth
            self.sink = self.sink_ring[self.turn]
            self.src_arrays = self.src_ring[self.turn]
        if self.device is not None and self.out:
            import jax.numpy as jnp

            headers = jnp.asarray(np.stack([
                self.ref.header_words(round_no, i, self.seed, self.k, d)
                for d, i in self.out]))
            self.src_arrays = self.restamp(self.src_arrays, tuple(headers))
            self.src_ring[self.turn] = self.src_arrays

    def payload(self, d: int, i: int):
        if self.device is None:
            return self.src[(d, i)]
        return self.src_arrays[self.out.index((d, i))]

    # -- receiving side
    def check_headers(self, round_no: int) -> None:
        if not self.inn:
            return
        if self.device is None:
            first = [self.sink[key][:16] for key in self.inn]
        else:
            first = np.asarray(self.heads(tuple(self.sink[key].array
                                                for key in self.inn)))
        for (s, i), h in zip(self.inn, first):
            if not self.ref.header_ok(h, round_no, i, self.seed, s, self.k):
                self.header_faults += 1

    def verify_bytes(self, round_no: int) -> None:
        """Byte for byte, outside the timing; also where each sink lives."""
        bad = 0
        for (s, i) in self.inn:
            sink = self.sink[(s, i)]
            if self.device is None:
                got = sink
            else:
                got = np.asarray(sink.array)
                self.landed.add(sink.last_transport)
                if sink.array.devices() != {self.device}:
                    bad += self.nbytes
            bad += self.ref.mismatched_bytes(got, self.seed, s, self.k, i, round_no)
        self.byte_faults += bad


def round_order(ctx: dict, round_no: int) -> list:
    return T.transfer_round(ctx["traffic"], ctx["args"].seed, round_no)


def verdict(ctx: dict, rounds: int, fw_s: list, t0: float, t1: float,
            header_faults: int, byte_faults: int, rode: list, landed: list) -> tuple:
    """(correct, payload bytes of the window): prints the window's counts
    and every number compared beside its limit."""
    traffic = ctx["traffic"]
    nbytes = rounds * T.round_bytes(traffic)
    expected = ctx["config"]["expected"][traffic["driver"]]
    ok = bool(header_faults == 0 and byte_faults == 0 and rounds > 0
              and rode == [expected["negotiated"]]
              and landed == [expected["device_sink_landed_by"]])
    log(event="window", seconds=t1 - t0, rounds=rounds, bytes=nbytes,
        round_seconds=stats.summary(fw_s), mix=T.describe(traffic))
    log(event="correct", correct=ok, compared=[
        {"what": "header_mismatches", "value": header_faults, "limit": 0},
        {"what": "byte_mismatches", "value": byte_faults, "limit": 0},
        {"what": "negotiated", "value": rode, "limit": [expected["negotiated"]]},
        {"what": "device_sink_landed_by", "value": landed,
         "limit": [expected["device_sink_landed_by"]]}],
        chunks_checked=rounds * len(round_order(ctx, 0)),
        rounds_verified_bytewise=WARM_ROUNDS + 1)
    return ok, nbytes


# --------------------------------------------------------- driver: peer


def run_peer_parent(ctx: dict) -> dict:
    """Endpoint 0: the chip-less peer.  Owns the clock of the window."""
    child = spawn_chip_child(ctx)
    try:
        out = asyncio.run(_peer_parent(ctx, child))
        if "jax" in sys.modules:
            raise SystemExit("benchmark: the chip-less peer imported jax")
        return out
    finally:
        if child.poll() is None:
            child.kill()
        child.wait()


def _delta(after: dict, before: dict) -> dict:
    return {k: after[k] - before.get(k, 0) for k in after
            if isinstance(after[k], (int, float))}


def _hist_delta(after: dict, before: dict) -> dict:
    return {k: [a - b for a, b in zip(v, before.get(k, [0] * len(v)))]
            for k, v in after.items()}


async def _peer_parent(ctx: dict, child) -> dict:
    import starway_tpu as sw

    args, traffic = ctx["args"], ctx["traffic"]
    loop = asyncio.get_running_loop()
    waiting = loop.run_in_executor(None, child_event, child, "ready")
    me = Endpoint(ctx, 0)     # this side's payloads, while the chip's sets up
    ready = await waiting
    client = sw.Client()
    await asyncio.wait_for(
        client.aconnect_address(bytes.fromhex(ready["address"])), 60)
    ctrl = np.zeros(4, np.int64)
    ack = np.zeros(4, np.int64)

    async def one_round(r: int, flags: int) -> tuple:
        t_a = time.monotonic()
        me.stamp(r)
        recvs = [client.arecv(me.sink[(s, i)], data_tag(s, 0, i), MASK)
                 for s, d, i in round_order(ctx, r) if d == 0]
        sends = [client.asend(me.payload(d, i), data_tag(0, d, i))
                 for s, d, i in round_order(ctx, r) if s == 0]
        await asyncio.gather(*sends, *recvs)
        await client.aflush()
        me.check_headers(r)
        ctrl[:2] = (r, flags)
        got = client.arecv(ack.view(np.uint8), TAG_ACK | r, MASK)
        await client.asend(ctrl.view(np.uint8), TAG_CTRL | r)
        await got
        t_b = time.monotonic()
        if flags & VERIFY:
            me.verify_bytes(r)
        # ack: [round, header faults so far, byte faults so far, raw round us]
        return t_b - t_a, int(ack[3]) / 1e6

    r = 0
    for _ in range(WARM_ROUNDS):
        await one_round(r, VERIFY)
        r += 1
    counters0 = client._client.counters_snapshot()
    ctrl[2] = 1   # from here on the chip's side counts too
    t0 = time.monotonic()
    setup_s = setup_seconds(ctx, t0, ready["device_init_s"])
    rounds, fw_s, raw_s = 0, [], []
    while True:
        wall, raw = await one_round(r, CONTINUE)
        r += 1
        rounds += 1
        fw_s.append(wall - raw)
        raw_s.append(raw)
        if time.monotonic() - t0 >= args.seconds:
            break
    t1 = time.monotonic()
    counters1 = client._client.counters_snapshot()
    ctrl[2] = 0
    await one_round(r, VERIFY | STOP)
    result = await loop.run_in_executor(None, child_event, child, "result")
    await client.aclose()
    await loop.run_in_executor(None, child.wait)

    raw_total = sum(raw_s)
    window = (t1 - t0) - raw_total
    header_faults = me.header_faults + result["header_faults"]
    ok, nbytes = verdict(ctx, rounds, fw_s, t0, t1, header_faults,
                         me.byte_faults + result["byte_faults"],
                         result["transports"], result["landed"])
    obs = {"cell": ctx["cell"]["name"], "config": ctx["config"],
           "traffic": traffic, "bytes": nbytes, "rounds": rounds,
           "fw_seconds": sum(fw_s), "raw_seconds": raw_total,
           "counters": [_delta(counters1, counters0), result["counters"]],
           "stages": result["stages"], "hists": result["hists"],
           "trace": result.get("trace"), "device": result["device"]}
    return {"correct": ok, "attempted": rounds * len(round_order(ctx, 0)),
            "failed": header_faults,
            "e2e": {"xfer_GBps": stats.gbps(nbytes, window), "setup_s": setup_s},
            "obs": obs, "device": result["device"], "trace": result.get("trace")}


def peer_chip_main(ctx: dict) -> int:
    return asyncio.run(_peer_chip(ctx))


async def _peer_chip(ctx: dict) -> int:
    """Endpoint 1: the process that holds the chip."""
    import jax

    import starway_tpu as sw
    from starway_tpu import perf

    args, traffic = ctx["args"], ctx["traffic"]
    info = chip_start(ctx)
    dev = jax.devices()[0]
    spans = Spans(annotate=bool(args.trace))
    me = Endpoint(ctx, 1, device=dev)
    raw_host = ([np.empty(me.nbytes, np.uint8) for _ in me.inn]
                if args.trace else [])
    for b in raw_host:
        b[:] = 7
    server = sw.Server()
    server.listen("127.0.0.1", 0)
    log(event="ready", address=server.get_worker_address().hex(),
        device_init_s=ctx["device_init_s"])
    ep = None
    ctrl = np.zeros(4, np.int64)
    ack = np.zeros(4, np.int64)
    prof = None
    counters0 = stages0 = hists0 = None
    counters1 = stages1 = hists1 = None
    r = 0
    while True:
        with spans.span("round"):
            me.stamp(r)
            recvs = [server.arecv(me.sink[(s, i)], data_tag(s, 1, i), MASK)
                     for s, d, i in round_order(ctx, r) if d == 1]
            got_ctrl = server.arecv(ctrl.view(np.uint8), TAG_CTRL | r, MASK)
            if ep is None:
                for _ in range(6000):
                    if server.list_clients():
                        break
                    await asyncio.sleep(0.01)
                ep = sorted(server.list_clients())[0]
            with spans.span("post_sends"):
                sends = [server.asend(ep, me.payload(d, i), data_tag(1, d, i))
                         for s, d, i in round_order(ctx, r) if s == 1]
            with spans.span("await_transfers"):
                await asyncio.gather(*sends, *recvs)
            with spans.span("flush"):
                await server.aflush()
            with spans.span("check_headers"):
                me.check_headers(r)
            await got_ctrl
        flags, counting = int(ctrl[1]), int(ctrl[2])
        if counting and counters0 is None:
            # The first counted round has just ended: the window's deltas
            # start here (one round late on both ends, so they cancel).
            counters0 = server._server.counters_snapshot()
            stages0, hists0 = perf.stage_snapshot(), server._server.hists_snapshot()
            prof = Profile(ctx, spans, time.monotonic() + 0.4 * args.seconds,
                           TRACE_SECONDS)
        if not counting and counters0 is not None and counters1 is None:
            counters1 = server._server.counters_snapshot()
            stages1, hists1 = perf.stage_snapshot(), server._server.hists_snapshot()
        if prof is not None:
            prof.tick(time.monotonic())
        if flags & VERIFY:
            me.verify_bytes(r)
        raw_us = 0
        if args.trace and counting:
            with spans.span("raw_round"):
                t_a = time.monotonic()
                _raw_round(jax, dev, raw_host, me.src_arrays)
                raw_us = int((time.monotonic() - t_a) * 1e6)
        ack[:] = (r, me.header_faults, me.byte_faults, raw_us)
        await server.asend(ep, ack.view(np.uint8), TAG_ACK | r)
        r += 1
        if flags & STOP:
            break
    await server.aflush()
    if prof is not None:
        prof.stop()
    transports = sorted({t for _d, t in ep.view_transports()})
    peak = memory_peak(ctx["cell"]["chips"])
    trace = prof.reduce() if prof is not None else None
    stages = {k: _delta(stages1.get(k, {}), stages0.get(k, {}))
              for k in (stages1 or {})}
    log(event="result", device=dict(info, memory_peak_bytes=peak),
        header_faults=me.header_faults, byte_faults=me.byte_faults,
        transports=transports, landed=sorted(str(x) for x in me.landed),
        counters=_delta(counters1 or {}, counters0 or {}), stages=stages,
        hists=_hist_delta(hists1 or {}, hists0 or {}), trace=trace)
    await asyncio.sleep(0.05)
    await server.aclose()
    return 0


def _raw_round(jax, dev, host_bufs, device_arrays) -> None:
    """The same bytes over the same hardware without the transport:
    ``jax.device_put`` of as many host chunks and a fetch of as many device
    chunks, both directions in flight at once."""
    ups = [jax.device_put(b, dev) for b in host_bufs]
    for a in device_arrays:
        a.copy_to_host_async()
    downs = [np.asarray(a) for a in device_arrays]
    jax.block_until_ready(ups)
    del downs


# ------------------------------------------------------- driver: inproc


def run_inproc(ctx: dict) -> dict:
    return asyncio.run(_inproc(ctx))


async def _inproc(ctx: dict) -> dict:
    import jax

    import starway_tpu as sw
    from starway_tpu import perf

    args, traffic = ctx["args"], ctx["traffic"]
    ctx["device"] = info = chip_start(ctx)
    n = int(traffic["workers"])
    devs = jax.devices()[:n]
    if len(devs) < n and ctx["chip"]:
        raise SystemExit(f"benchmark: {n} endpoints need {n} chips")
    devs = [devs[k % len(devs)] for k in range(n)]
    spans = Spans(annotate=bool(args.trace))
    ends = [Endpoint(ctx, k, device=devs[k]) for k in range(n)]
    servers = []
    for k in range(n):
        s = sw.Server()
        s.listen("127.0.0.1", 0)
        servers.append(s)
    clients = {}
    for a in range(n):
        for b in range(n):
            if a != b:
                c = sw.Client()
                await asyncio.wait_for(c.aconnect_address(
                    servers[b].get_worker_address()), 30)
                clients[(a, b)] = c

    async def one_round(r: int, verify: bool) -> float:
        """Seconds from the first post to the last flush: the transport's
        part of the round, without the benchmark's stamping and checking
        (those stay inside the window that ``xfer_GBps`` is taken over)."""
        with spans.span("round"):
            for e in ends:
                e.stamp(r)
            order = round_order(ctx, r)
            t_a = time.monotonic()
            with spans.span("post"):
                recvs = [servers[d].arecv(ends[d].sink[(s, i)],
                                          data_tag(s, d, i), MASK)
                         for s, d, i in order]
                sends = [clients[(s, d)].asend(ends[s].payload(d, i),
                                               data_tag(s, d, i))
                         for s, d, i in order]
            with spans.span("await_transfers"):
                await asyncio.gather(*sends, *recvs)
            with spans.span("flush"):
                await asyncio.gather(*(c.aflush() for c in clients.values()))
            t_b = time.monotonic()
            with spans.span("check_headers"):
                for e in ends:
                    e.check_headers(r)
        if verify:
            for e in ends:
                e.verify_bytes(r)
        return t_b - t_a

    def raw_round() -> float:
        t_a = time.monotonic()
        with spans.span("raw_round"):
            moved = [jax.device_put(ends[s].payload(d, i), devs[d])
                     for s, d, i in round_order(ctx, 0)]
            jax.block_until_ready(moved)
        return time.monotonic() - t_a

    r = 0
    for _ in range(WARM_ROUNDS):
        await one_round(r, True)
        if args.trace:
            raw_round()
        r += 1
    workers = [s._server for s in servers] + [c._client for c in clients.values()]
    snap = lambda: [w.counters_snapshot() for w in workers]
    counters0, stages0 = snap(), perf.stage_snapshot()
    hists0 = [s._server.hists_snapshot() for s in servers]
    t0 = time.monotonic()
    setup_s = setup_seconds(ctx, t0)
    prof = Profile(ctx, spans, t0 + 0.4 * args.seconds, TRACE_SECONDS)
    rounds, fw_s, raw_s = 0, [], []
    while True:
        prof.tick(time.monotonic())
        fw_s.append(await one_round(r, False))
        if args.trace:
            raw_s.append(raw_round())
        r += 1
        rounds += 1
        if time.monotonic() - t0 >= args.seconds:
            break
    t1 = time.monotonic()
    prof.stop()
    counters1, stages1 = snap(), perf.stage_snapshot()
    hists1 = [s._server.hists_snapshot() for s in servers]
    await one_round(r, True)
    rode = sorted({t for s in servers for ep in s.list_clients()
                   for _d, t in ep.view_transports()})
    peak = memory_peak(ctx["cell"]["chips"])
    for c in clients.values():
        await c.aclose()
    for s in servers:
        await s.aclose()

    header_faults = sum(e.header_faults for e in ends)
    ok, nbytes = verdict(ctx, rounds, fw_s, t0, t1, header_faults,
                         sum(e.byte_faults for e in ends), rode,
                         sorted({str(x) for e in ends for x in e.landed}))
    trace = prof.reduce()
    hist_sum: dict = {}
    for h1, h0 in zip(hists1, hists0):
        for k, v in _hist_delta(h1, h0).items():
            acc = hist_sum.setdefault(k, [0] * len(v))
            hist_sum[k] = [a + b for a, b in zip(acc, v)]
    obs = {"cell": ctx["cell"]["name"], "config": ctx["config"],
           "traffic": traffic, "bytes": nbytes, "rounds": rounds,
           "fw_seconds": sum(fw_s), "raw_seconds": sum(raw_s),
           "counters": [_delta(a, b) for a, b in zip(counters1, counters0)],
           "stages": {k: _delta(stages1[k], stages0.get(k, {})) for k in stages1},
           "hists": hist_sum, "trace": trace, "device": info}
    window = (t1 - t0) - sum(raw_s)
    return {"correct": ok, "attempted": rounds * len(round_order(ctx, 0)),
            "failed": header_faults,
            "e2e": {"xfer_GBps": stats.gbps(nbytes, window), "setup_s": setup_s},
            "obs": obs, "device": dict(info, memory_peak_bytes=peak),
            "trace": trace}


# ------------------------------------------------------------- entry points


def run(ctx: dict) -> dict:
    driver = ctx["traffic"]["driver"]
    if driver == "peer":
        return run_peer_parent(ctx)
    if driver == "inproc":
        return run_inproc(ctx)
    raise SystemExit(f"benchmark: the transport runner has no driver {driver!r}")


def run_role(role: str, ctx: dict) -> int:
    if role == "chip":
        return peer_chip_main(ctx)
    raise SystemExit(f"benchmark: the transport runner has no role {role!r}")
