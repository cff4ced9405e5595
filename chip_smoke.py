#!/usr/bin/env python3
"""The quickest proof that starway-tpu still starts on the chip.

    python chip_smoke.py            # one TPU chip: phases a-d
    python chip_smoke.py --chips 4  # one four-chip host: the cross-chip
                                    # paths and their one-chip answers only

One process holds the chip.  It drives the system's main path once
through the entry points a user calls (``starway_tpu.Server`` / ``Client``
/ ``DeviceBuffer``, ``RemoteSlotServer``, ``RemoteGenerateSession``,
``SlotServer``, ``PagedSlotServer``, ``generate``, ``Trainer``) at the
published widths of a model the repo supports, checks what comes out
against the repo's own plain references, and prints one JSON object per
phase.  The LAST line of stdout is

    {"ok": true, "device": {"platform": "tpu", "kind": "...", "count": N}}

and is printed only if every phase ran and passed.  Without a TPU (JAX on
the CPU, or no starway_tpu package beside this file) it exits non-zero
before compiling anything and prints no such line.

Depth is cut so that one 16 GB chip holds the model, and the weights are
random, made from SEED; each phase line says what was cut.  Nothing here
is a speed: seconds are printed so that a cold run and a warm run (the
persistent compile cache, starway_tpu/utils/chip.py) can be told apart.
"""

from __future__ import annotations

import argparse
import asyncio
import contextlib
import dataclasses
import faulthandler
import functools
import gc
import json
import logging
import math
import os
import socket
import subprocess
import sys
import time
from pathlib import Path

REPO = Path(__file__).resolve().parent
sys.path.insert(0, str(REPO))

SEED = 0
MASK = (1 << 64) - 1
KiB, MiB, GiB = 1 << 10, 1 << 20, 1 << 30
DEADLINE_S = 1150  # the contract allows 1200 s, compilation included

# Phase a: BASELINE.json's "4B-1GB" range, across STARWAY_RNDV_THRESHOLD.
INPROC_SIZES = (4, 64 * KiB, MiB, 8 * MiB + 1, 256 * MiB, GiB)
# Phase b: real sockets, both engines, and the chip-less peer process.
SOCKET_SIZES = (MiB, 8 * MiB + 1, 256 * MiB)

# Phase c: llama3-8b at its published widths; depth cut 32 -> 8 so that
# 5.6 GB of bf16 weights plus an 8 x 2048 cache fit one 16 GB chip.
SERVE = {"preset": "llama3-8b", "n_layers": 8, "n_slots": 8, "max_len": 2048,
         "chunk": 8, "page": 64}
# (prompt tokens, new tokens): ragged, 5-1500 in, 16-64 out.  The dense
# bf16 server ingests them inside its decode chunks, at widths 128 and 256
# (the 1100- and 1500-token prompts); the int8 and the paged server admit
# through three prompt buckets (32 or 64 / 256 / 2048).
REQUESTS = ((5, 16), (12, 24), (200, 32), (230, 48), (1100, 64), (1500, 64),
            (29, 20))
RERUN = 2  # the request re-run through standalone generate()

# Phase e: Kimi-K2-Instruct's block at its published widths (64 heads over a
# 512 + 64 latent row, 7168 wide, 384-wide router, 8 experts a token), one
# dense and one routed layer, 12 of the 384 experts held (one chip of 32),
# an eighth of the vocabulary: 2.9 GB of bf16 weights.
LATENT = {"n_layers": 2, "held": 12, "vocab": 20480, "n_slots": 8,
          "max_len": 2048, "chunk": 8}

# Phase d: llama2-7b at its published widths (vocab 32000 leaves room that
# llama3-8b's 128256-row embedding and head do not); depth cut 32 -> 4 and
# batch 2 x 2048 tokens are what the chip's compiler fits beside bf16
# adamw state (tests/test_aot_tpu.py asks it).
TRAIN = {"preset": "llama2-7b", "n_layers": 4, "batch": 2, "seq": 2048,
         "steps": 3}

# Tolerances, stated before the first chip run, as max |a - r| / max |r|.
# Both sides of every comparison compute in bf16 (8 significant bits, ulp
# 2**-8); neither is exact.  Loss and gradient norm, each one reduction
# over a whole step: the repo's own bound for a bf16 kernel against its
# lax oracle (scripts/kernel_bench.py check_numerics), 2e-2.  Logits after
# eight layers, each rounding the residual stream once more: 8 ulp = 2**-5.
# An int8 KV cache rounds k and v once more by about a bf16 ulp: twice that.
TOL_BF16 = 2e-2
TOL_LOGITS = 2.0 ** -5
TOL_LOGITS_INT8 = 2.0 ** -4
# A routed model (phase e): where two experts' scores lie within a bf16
# rounding of each other, the two sides of a comparison choose differently
# and that token's logits move by a tenth of their range or more (0.16-0.28
# of max |logit| in a cell's run, PERF.md section 2, PR 26), so one token
# says little.  Held: the MEAN gap (0.0013-0.0033 read for bf16, 0.017 for
# int8 arithmetic), no single token further than half the range (a wrong
# token reads about 1), and at most 2% of positions past TOL_LOGITS.
TOL_ROUTED_MEAN = 0.008
TOL_FLIP = 0.5
FLIPPED_ROWS = 0.02


class SmokeFailure(Exception):
    """A phase's check did not hold."""


def check(cond, what: str) -> None:
    if not cond:
        raise SmokeFailure(what)


def emit(**fields) -> None:
    print(json.dumps(fields), flush=True)


def pattern(nbytes: int, salt: int):
    """``nbytes`` of a pattern distinct per ``salt`` (and SEED), cheap
    enough to make at 1 GiB: a multiplied, xor-shifted 64-bit ramp."""
    import numpy as np

    x = np.arange(-(-nbytes // 8), dtype=np.uint64)
    x += np.uint64((SEED * 0x1000193 + salt) * 0x9E3779B1 + 1)
    x *= np.uint64(0x9E3779B97F4A7C15)
    x ^= x >> np.uint64(29)
    return x.view(np.uint8)[:nbytes]


def _free_port() -> int:
    with socket.socket() as s:
        s.bind(("127.0.0.1", 0))
        return s.getsockname()[1]


class CompileClock:
    """Seconds JAX spent compiling (or fetching from the persistent cache)
    and the cache's hits, so a warm second run is visibly warm."""

    def __init__(self):
        import jax

        self.seconds = 0.0
        self.programs = 0
        self.cache_hits = 0
        jax.monitoring.register_event_duration_secs_listener(self._duration)
        jax.monitoring.register_event_listener(self._event)

    def _duration(self, event: str, seconds: float, **_kw) -> None:
        if event == "/jax/core/compile/backend_compile_duration":
            self.seconds += seconds
            self.programs += 1

    def _event(self, event: str, **_kw) -> None:
        if event == "/jax/compilation_cache/cache_hits":
            self.cache_hits += 1

    def read(self) -> tuple:
        return self.seconds, self.programs, self.cache_hits


class _Warnings(logging.Handler):
    """The library logs and carries on where a fast path gives way to a
    slower one (a failed chunked placement, an unusable transfer server):
    correct bytes, wrong path.  A transport phase that logged a warning
    has not proved the path it names."""

    def __init__(self):
        super().__init__(logging.WARNING)
        self.seen: list = []

    def emit(self, record: logging.LogRecord) -> None:
        self.seen.append(f"{record.name}: {record.getMessage()}")


@contextlib.contextmanager
def no_library_warnings():
    trap = _Warnings()
    loggers = [logging.getLogger(n) for n in ("starway_tpu", "starway")]
    for lg in loggers:
        lg.addHandler(trap)
    try:
        yield
    finally:
        for lg in loggers:
            lg.removeHandler(trap)
    check(not trap.seen, f"the library warned: {trap.seen[:3]}")


@contextlib.contextmanager
def phase(name: str, clock: CompileClock, **what):
    """One JSON line per phase: what ran, seconds, compile seconds."""
    t0, (c0, p0, h0) = time.perf_counter(), clock.read()
    detail: dict = {}
    yield detail
    c1, p1, h1 = clock.read()
    emit(phase=name, ok=True, **what, **detail,
         seconds=round(time.perf_counter() - t0, 3),
         compile_seconds=round(c1 - c0, 3), programs_compiled=p1 - p0,
         compile_cache_hits=h1 - h0)


# --------------------------------------------------------------- transport


def _select(tls: "str | None", native: bool) -> str:
    """Point the workers made next at a transport list (None = the
    default, whose in-process path carries same-process peers) and an
    engine.  Asking for an engine and silently getting the other fails."""
    import starway_tpu as sw

    if tls is None:
        os.environ.pop("STARWAY_TLS", None)
    else:
        os.environ["STARWAY_TLS"] = tls
    os.environ["STARWAY_NATIVE"] = "1" if native else "0"
    want = "native" if native else "python"
    check(sw.check_sys_libs() == want,
          f"asked for the {want} engine, check_sys_libs() says "
          f"{sw.check_sys_libs()!r}")
    return want


async def _pair(tls: "str | None", native: bool):
    """A Server and a Client of this process joined over ``tls``."""
    import starway_tpu as sw

    want = _select(tls, native)
    server = sw.Server()
    server.listen("127.0.0.1", 0)
    client = sw.Client()
    await asyncio.wait_for(
        client.aconnect_address(server.get_worker_address()), 30)
    ep = await _accepted(server)
    worker = type(server._server).__name__
    check(worker.startswith("Native") == native,
          f"asked for the {want} engine, the server runs {worker}")
    return server, client, ep


async def _accepted(server):
    for _ in range(2000):
        eps = server.list_clients()
        if eps:
            return sorted(eps)[-1]
        await asyncio.sleep(0.005)
    raise SmokeFailure("the server never saw its client")


def _same_bytes(array, expect) -> bool:
    import numpy as np

    return np.array_equal(np.asarray(array).view(np.uint8).reshape(-1), expect)


async def _transfer_checks(server, client, ep, dev, sizes, *, tag0: int,
                           d2d_expect) -> list:
    """host->HBM twice back to back from ONE reused host buffer,
    HBM->HBM, HBM->host, per size; every byte verified after aflush.

    The reused source is the point: send completion licenses the sender
    to overwrite its buffer (eager) -- after aflush for a rendezvous-sized
    payload -- so a placement whose DMA still read the host buffer after
    completion, or a recycled staging buffer (device._StagingPool) that a
    running DMA still read, shows up as a mismatch in the FIRST delivery.
    """
    import numpy as np

    from starway_tpu import DeviceBuffer, config

    rows = []
    tag = tag0
    for i, n in enumerate(sizes):
        t0 = time.perf_counter()
        rndv = n > config.rndv_threshold()
        expect = [pattern(n, 4 * i + j) for j in range(2)]
        sinks = [DeviceBuffer((n,), np.uint8, device=dev) for _ in range(2)]
        recvs = [server.arecv(s, tag + j, MASK) for j, s in enumerate(sinks)]
        src = np.empty(n, np.uint8)
        for j in range(2):
            src[:] = expect[j]
            await client.asend(src, tag + j)
            if rndv:
                await client.aflush()
        await client.aflush()
        for j, fut in enumerate(recvs):
            got_tag, length = await asyncio.wait_for(fut, 300)
            check((got_tag, length) == (tag + j, n),
                  f"recv {tag + j}: got tag {got_tag} length {length}")
        src[:] = 0xEE  # and once more after the flush, before verifying
        for j, s in enumerate(sinks):
            check(s.array.devices() == {dev},
                  f"{n} B host->HBM landed on {s.array.devices()}, not {dev}")
            check(_same_bytes(s.array, expect[j]),
                  f"{n} B host->HBM delivery {j} differs from what was sent")
        h2d = sinks[1].last_transport
        check(h2d == "staged", f"host->HBM reported transport {h2d!r}")

        # HBM -> HBM through the same connection (a same-chip handoff
        # in-process; over a socket, staged or pulled as negotiated).
        moved = DeviceBuffer((n,), np.uint8, device=dev)
        fut = server.arecv(moved, tag + 2, MASK)
        await client.asend(sinks[0].array, tag + 2)
        await client.aflush()
        await asyncio.wait_for(fut, 300)
        check(moved.array.devices() == {dev},
              f"{n} B HBM->HBM landed on {moved.array.devices()}")
        check(_same_bytes(moved.array, expect[0]),
              f"{n} B HBM->HBM differs from what was sent")
        d2d = moved.last_transport
        check(d2d == d2d_expect(n),
              f"{n} B HBM->HBM rode {d2d!r}, expected {d2d_expect(n)!r}")

        # HBM -> host.
        back = np.empty(n, np.uint8)
        fut = client.arecv(back, tag + 3, MASK)
        await server.asend(ep, sinks[1].array, tag + 3)
        await server.aflush()
        await asyncio.wait_for(fut, 300)
        check(np.array_equal(back, expect[1]),
              f"{n} B HBM->host differs from what was sent")
        rows.append({"bytes": n, "host_to_hbm": h2d, "hbm_to_hbm": d2d,
                     "hbm_to_host": "host", "verified": True,
                     "seconds": round(time.perf_counter() - t0, 3)})
        tag += 4
        del sinks, moved, back, src, expect
        gc.collect()
    return rows


def _pjrt_entry_points() -> dict:
    """The device plane's two private PJRT entry points, resolved as the
    data path resolves them.  There is no fallback behind them any more:
    a missing one raises here, and the transfers above would have too."""
    from starway_tpu import device

    device.pjrt_entry_points()
    return {"fast_copy": "live", "fast_h2d": "live"}


async def phase_transport_inproc(dev, sizes=INPROC_SIZES) -> dict:
    server, client, ep = await _pair(None, native=False)
    try:
        rows = await _transfer_checks(server, client, ep, dev, sizes,
                                      tag0=0xA000, d2d_expect=lambda n: "device")
        transports = ep.view_transports()
        check(any(t == "inproc" for _d, t in transports),
              f"same-process peers negotiated {transports}, not inproc")
    finally:
        await client.aclose()
        await server.aclose()
    return {"engine": "python", "transports": transports, "transfers": rows,
            **_pjrt_entry_points()}


def _d2d_over_socket(n: int) -> str:
    """Which transport a jax.Array rides between two workers that both
    hold the chip: the PJRT pull where the backend offers it and the
    payload is worth a pull, the framed stream otherwise."""
    from starway_tpu import config, device

    return ("device" if device.devpull_supported()
            and n >= config.devpull_threshold() else "staged")


async def phase_transport_sockets(dev, sizes=SOCKET_SIZES) -> dict:
    import starway_tpu as sw
    from starway_tpu import device, perf
    from starway_tpu.core import native as native_mod

    out: dict = {"devpull_supported": device.devpull_supported(),
                 "engines": []}
    for native in (False, True):
        perf.stage_reset()
        pool0 = (device._staging_pool.hits, device._staging_pool.misses)
        server, client, ep = await _pair("tcp", native)
        try:
            rows = await _transfer_checks(
                server, client, ep, dev, sizes, tag0=0xB000 + 0x100 * native,
                d2d_expect=_d2d_over_socket)
            transports = ep.view_transports()
            check(any("tcp" in t for _d, t in transports),
                  f"STARWAY_TLS=tcp negotiated {transports}")
        finally:
            await client.aclose()
            await server.aclose()
        stages = {k: v["count"] for k, v in perf.stage_snapshot().items()}
        # A staged payload crosses the host WHOLE on both engines: one
        # "place" a staged device receive (two host->HBM a size, and the
        # HBM->HBM one where it was not pulled), one "stage" a staged
        # device send (that HBM->HBM one, and the HBM->host of its size:
        # one rule decides pull or stage for both).
        staged_d2d = sum(r["hbm_to_hbm"] == "staged" for r in rows)
        want = {"place": 2 * len(rows) + staged_d2d, "stage": 2 * staged_d2d}
        check({k: stages.get(k, 0) for k in want} == want,
              f"staged payloads did not cross whole: stages {stages}, "
              f"expected {want}")
        out["engines"].append({
            "engine": sw.check_sys_libs(), "transports": transports,
            "transfers": rows, "stage_samples": stages,
            "staging_pool": {
                "hits": device._staging_pool.hits - pool0[0],
                "misses": device._staging_pool.misses - pool0[1]},
            "prefetch_peak": {"bytes": device._prefetch.peak_bytes,
                              "depth": device._prefetch.peak_depth}})
    out["sw_version"] = native_mod.load().sw_version().decode()

    # A chip-less peer PROCESS (JAX never imported there), started by the
    # process that holds the chip, never the reverse: over tcp on the
    # Python engine, over the shared-memory rings on the native one.
    out["peers"] = [await _peer_exchange(dev, sizes, tls, native)
                    for tls, native in (("tcp", False), ("sm,tcp", True))]
    return out


PEER_SEND, PEER_BACK = 0xC000, 0xC800


async def _peer_exchange(dev, sizes, tls: str, native: bool) -> dict:
    import jax
    import numpy as np

    import starway_tpu as sw
    from starway_tpu import DeviceBuffer

    want = _select(tls, native)
    server = sw.Server()
    server.listen("127.0.0.1", 0)
    salt = 1000 + 100 * native
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               PYTHONPATH=os.pathsep.join(
                   [str(REPO), os.environ.get("PYTHONPATH", "")]))
    proc = subprocess.Popen(
        [sys.executable, "-c", "import chip_smoke; chip_smoke.peer_main()",
         server.get_worker_address().hex(), str(salt), *map(str, sizes)],
        cwd=REPO, env=env, stdout=subprocess.PIPE, text=True)
    try:
        ep = await _accepted(server)
        rows = []
        for i, n in enumerate(sizes):
            sink = DeviceBuffer((n,), np.uint8, device=dev)
            await asyncio.wait_for(server.arecv(sink, PEER_SEND + i, MASK), 300)
            check(sink.array.devices() == {dev},
                  f"peer's {n} B landed on {sink.array.devices()}")
            check(_same_bytes(sink.array, pattern(n, salt + 2 * i)),
                  f"{n} B from the peer differ from what it sent")
            reply = jax.device_put(pattern(n, salt + 2 * i + 1), dev)
            await server.asend(ep, reply, PEER_BACK + i)
            await asyncio.wait_for(server.aflush(), 300)
            rows.append({"bytes": n, "peer_to_hbm": sink.last_transport,
                         "verified": True})
        transports = ep.view_transports()
        loop = asyncio.get_running_loop()
        stdout, _ = await loop.run_in_executor(
            None, functools.partial(proc.communicate, timeout=120))
        check(proc.returncode == 0, f"the peer exited {proc.returncode}")
        report = json.loads(stdout.strip().splitlines()[-1])
        check(report["verified"], f"the peer's replies differed: {report}")
        check(not report["jax_imported"], "the peer imported jax")
        check(report["engine"] == want,
              f"the peer ran the {report['engine']} engine")
        check(any(tls.split(",")[0] in t for _d, t in transports),
              f"STARWAY_TLS={tls} negotiated {transports} with the peer")
    finally:
        if proc.poll() is None:
            proc.kill()
        proc.wait()
        await server.aclose()
    return {"engine": want, "transports": transports, "transfers": rows,
            "peer": report}


def peer_main() -> None:
    """The chip-less peer: host bytes out, a device payload's bytes back.
    Runs in a child started with ``python -c``; never imports jax."""
    import numpy as np

    import starway_tpu as sw

    addr, salt = bytes.fromhex(sys.argv[1]), int(sys.argv[2])
    sizes = [int(a) for a in sys.argv[3:]]

    async def run() -> bool:
        client = sw.Client()
        await asyncio.wait_for(client.aconnect_address(addr), 30)
        ok = True
        for i, n in enumerate(sizes):
            back = np.empty(n, np.uint8)
            fut = client.arecv(back, PEER_BACK + i, MASK)
            await client.asend(pattern(n, salt + 2 * i), PEER_SEND + i)
            await asyncio.wait_for(fut, 300)
            ok = ok and np.array_equal(back, pattern(n, salt + 2 * i + 1))
        await asyncio.wait_for(client.aflush(), 300)
        await client.aclose()
        return ok

    verified = asyncio.run(run())
    emit(verified=bool(verified), jax_imported="jax" in sys.modules,
         engine=sw.check_sys_libs())


# ------------------------------------------------------------ served model


def serve_config(**overrides):
    from starway_tpu.models import LlamaConfig

    return LlamaConfig.preset(SERVE["preset"], n_layers=SERVE["n_layers"],
                              **overrides)


def make_requests(cfg, requests=REQUESTS) -> list:
    import numpy as np

    rng = np.random.default_rng(SEED)
    return [(rng.integers(1, cfg.vocab_size, n).astype(np.int32), m)
            for n, m in requests]


def _plain_attn(cfg=None):
    """The plain lax path: no Pallas on any backend."""
    from starway_tpu.ops.attention import blockwise_attention

    scale = cfg.latent.sm_scale if cfg is not None and cfg.latent else None
    return functools.partial(blockwise_attention, causal=True, sm_scale=scale)


def _lax_ops():
    """Programs traced inside run every operation of ``starway_tpu.ops`` in
    its lax form (the plain side of a comparison: the routed experts'
    grouped matmul is the one the callers are after).  The steering
    substitutes the ONE decision function, here, not an option of the
    program."""
    from unittest import mock

    from starway_tpu.ops import dispatch

    return mock.patch.object(dispatch, "use_kernels", lambda: False)


def make_reference(cfg, max_len: int, n_new: int):
    """Teacher-forced reference, independent of every kernel under test:
    ONE plain-lax forward (blockwise attention, no cache, no Pallas) over
    prompt + generated tokens, right-padded to ``max_len`` so that every
    request shares one program (``n_new``: the most tokens any request
    generates).  Returns, per generated token, how far the
    reference's logit for the token the server chose lies below the
    reference's own maximum, as a share of max |logit|: 0 where the two
    agree, a rounding error's worth at a bf16 near-tie, large for a wrong
    token."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from starway_tpu.models import forward

    plain = _plain_attn(cfg)

    @jax.jit
    def gaps(params, padded, at, chosen):
        with _lax_ops():
            logits = forward(params, padded, cfg, plain)[0][at]  # [n, V] f32
        top = logits.max(-1)
        got = jnp.take_along_axis(logits, chosen[:, None], -1)[:, 0]
        return (top - got) / jnp.abs(logits).max(-1), jnp.isfinite(logits).all()

    def token_gaps(params, prompt, new):
        padded = np.zeros((1, max_len), np.int32)
        padded[0, :len(prompt)] = prompt
        padded[0, len(prompt):len(prompt) + len(new)] = new
        # Token i of `new` is predicted at position len(prompt) + i - 1.
        at = np.full(n_new, len(prompt) - 1, np.int32)
        at[:len(new)] += np.arange(len(new), dtype=np.int32)
        chosen = np.full(n_new, new[0], np.int32)
        chosen[:len(new)] = new
        g, finite = gaps(params, jnp.asarray(padded), jnp.asarray(at),
                         jnp.asarray(chosen))
        check(bool(finite), "the reference's logits are not finite")
        return np.asarray(g)[:len(new)]

    return token_gaps


async def _serve_over_wire(slot, reqs, tls, native: bool) -> tuple:
    """examples/serve_remote.py at real size: the SlotServer bridged onto
    a transport Server, three sessions submitting concurrently."""
    from starway_tpu.models import RemoteGenerateSession, RemoteSlotServer

    engine = _select(tls, native)
    port = _free_port()
    bridge = RemoteSlotServer(slot)
    bridge.server.listen("127.0.0.1", port)
    serve_task = asyncio.ensure_future(bridge.serve())
    sessions = []
    try:
        for _ in range(3):
            sessions.append(await asyncio.wait_for(
                RemoteGenerateSession.aconnect("127.0.0.1", port), 30))
        chunks = [0] * len(reqs)

        async def one(i, prompt, max_new):
            def on_tokens(_c, i=i):
                chunks[i] += 1

            return await sessions[i % 3].generate(prompt, max_new,
                                                  on_tokens=on_tokens)

        work = asyncio.gather(*(one(i, p, m) for i, (p, m) in enumerate(reqs)))
        done, _ = await asyncio.wait({work, serve_task}, timeout=420,
                                     return_when=asyncio.FIRST_COMPLETED)
        if work not in done:
            work.cancel()
            if serve_task in done:
                serve_task.result()  # the serve loop died: raise its error
            raise SmokeFailure("the token streams did not complete in 420 s")
        outs = work.result()
        transports = sorted(bridge.server.list_clients())[0].view_transports()
        bridge.stop()
        await asyncio.wait_for(serve_task, 60)
    finally:
        serve_task.cancel()
        for s in sessions:
            await s.aclose()
        await bridge.aclose()
    return outs, {"engine": engine, "transports": transports,
                  "stream_chunks": chunks}


def _check_streams(name, params, reqs, outs, token_gaps, cfg, tol,
                   mean_tol=None) -> dict:
    """``tol``: half the widest gap any one token may have; ``mean_tol``
    (routed models): the mean gap over all tokens."""
    import numpy as np

    worst, total = 0.0, 0.0
    for i, ((prompt, max_new), out) in enumerate(zip(reqs, outs)):
        check(len(out) == max_new,
              f"{name}: request {i} returned {len(out)} of {max_new} tokens")
        check(((out >= 0) & (out < cfg.vocab_size)).all(),
              f"{name}: request {i} returned a token outside the vocabulary")
        gaps = token_gaps(params, prompt, np.asarray(out))
        gap = float(gaps.max())
        check(gap <= 2 * tol,
              f"{name}: request {i} chose a token {gap:.4f} of max|logit| "
              f"below the plain reference's best (allowed {2 * tol})")
        worst, total = max(worst, gap), total + float(gaps.sum())
    tokens = int(sum(len(o) for o in outs))
    got = {"requests": len(reqs), "tokens": tokens,
           "worst_gap_to_reference": round(worst, 5), "gap_allowed": 2 * tol}
    if mean_tol is not None:
        check(total / tokens <= mean_tol,
              f"{name}: mean gap {total / tokens:.5f} to the plain "
              f"reference (allowed {mean_tol})")
        got.update(mean_gap_to_reference=round(total / tokens, 6),
                   mean_gap_allowed=mean_tol)
    return got


def _first_difference(a, b) -> "int | None":
    import numpy as np

    diff = np.flatnonzero(np.asarray(a) != np.asarray(b))
    return int(diff[0]) if len(diff) else None


def _rel_err(a, r, skip: float = 0.0) -> float:
    """max |a - r| / max |r|; with ``skip``, over all but that share of
    the rows (the positions a routed model's flipped experts moved)."""
    import jax.numpy as jnp

    a, r = a.astype(jnp.float32), r.astype(jnp.float32)
    rows = jnp.max(jnp.abs(a - r), -1).reshape(-1)
    return float(jnp.quantile(rows, 1.0 - skip) / (jnp.max(jnp.abs(r)) + 1e-9))


def _pallas_vs_lax_logits(params, cfg, tokens) -> dict:
    """The Pallas path against the plain lax path at logit level on one
    prompt: the flash prefill over all of it, and one cached decode step
    (prefill of S-1 tokens, then the decode kernel at position S-1)."""
    import jax

    from starway_tpu.models import forward, prefill
    from starway_tpu.models.generate import decode_step

    s = tokens.shape[1]
    with _lax_ops():
        plain = jax.jit(lambda p, t: forward(p, t, cfg, _plain_attn(cfg)))(
            params, tokens)
    flash = jax.jit(lambda p, t: forward(p, t, cfg))(params, tokens)
    skip = FLIPPED_ROWS if cfg.routed is not None else 0.0
    err_prefill = _rel_err(flash, plain, skip)
    _logits, cache = jax.jit(lambda p, t: prefill(p, cfg, t, s))(
        params, tokens[:, :s - 1])
    step, _cache = jax.jit(
        lambda p, c, t: decode_step(p, c, t, s - 1, cfg))(
            params, cache, tokens[:, s - 1])
    err_decode = _rel_err(step, plain[:, s - 1])
    check(err_prefill < TOL_LOGITS,
          f"flash prefill logits differ from the lax path by {err_prefill}")
    check(err_decode < (TOL_FLIP if skip else TOL_LOGITS),
          f"cached decode logits differ from the lax path by {err_decode}")
    return {"tokens": s, "prefill_rel_err": round(err_prefill, 5),
            "decode_rel_err": round(err_decode, 5), "allowed": TOL_LOGITS}


async def phase_serve(dev, requests=REQUESTS) -> dict:
    import jax
    import jax.numpy as jnp
    import numpy as np

    from starway_tpu.models import (PagedSlotServer, SlotServer, generate,
                                    init_params)

    cfg = serve_config()
    with jax.default_device(dev):
        params = init_params(jax.random.PRNGKey(SEED), cfg)
    jax.block_until_ready(params)
    reqs = make_requests(cfg, requests)
    token_gaps = make_reference(cfg, SERVE["max_len"],
                                max(m for _p, m in reqs))
    int8 = dataclasses.replace(cfg, kv_quant="int8")
    kw = dict(n_slots=SERVE["n_slots"], max_len=SERVE["max_len"],
              chunk=SERVE["chunk"])
    out: dict = {"weights_gb": round(sum(
        x.nbytes for x in jax.tree_util.tree_leaves(params)) / 1e9, 2)}

    # Dense bf16 over the in-process path, paged over tcp on the Python
    # engine, int8 KV over tcp on the native engine: every server over a
    # different carrier, all against the same plain reference.
    variants = (
        ("slot_server", lambda: SlotServer(params, cfg, **kw), None, False,
         cfg, TOL_LOGITS),
        ("paged_slot_server",
         lambda: PagedSlotServer(params, cfg, page=SERVE["page"], **kw),
         "tcp", False,
         cfg, TOL_LOGITS),
        ("slot_server_int8_kv", lambda: SlotServer(params, int8, **kw), "tcp",
         True, int8, TOL_LOGITS_INT8),
    )
    streams = {}
    for name, make, tls, native, vcfg, tol in variants:
        slot = make()
        outs, how = await _serve_over_wire(slot, reqs, tls, native)
        out[name] = {**how, **_check_streams(name, params, reqs, outs,
                                             token_gaps, vcfg, tol)}
        streams[name] = outs
        del slot
        gc.collect()

    # One request again through standalone generate().  Whether greedy
    # tokens are bit-equal across programs on the chip is recorded, not
    # assumed: bf16 near-ties may break differently in another program.
    prompt, max_new = reqs[RERUN]
    alone = np.asarray(generate(params, cfg, jnp.asarray(prompt[None]),
                                max_new)[0, len(prompt):])
    gap = float(token_gaps(params, prompt, alone).max())
    check(gap <= 2 * TOL_LOGITS,
          f"generate() chose a token {gap:.4f} of max|logit| below the "
          f"plain reference's best")
    dense = streams["slot_server"]
    out["generate_rerun"] = {
        "request": RERUN, "worst_gap_to_reference": round(gap, 5),
        "first_difference_from_slot_server": _first_difference(
            alone, dense[RERUN])}
    out["first_difference_from_slot_server"] = {
        name: [_first_difference(a, b) for a, b in zip(outs, dense)]
        for name, outs in streams.items() if name != "slot_server"}
    longest = max(reqs, key=lambda r: len(r[0]))[0]
    out["pallas_vs_lax"] = _pallas_vs_lax_logits(
        params, cfg, jnp.asarray(longest[None, :min(len(longest), 1024)]))
    return out


# ------------------------------------------- latent attention, routed experts


def latent_config(**overrides):
    """Kimi-K2-Instruct's block at its published widths, as one chip of the
    32 that share each layer holds it (LATENT)."""
    from starway_tpu.models.llama import LatentAttn, LlamaConfig, RoutedFFN

    mscale = 0.1 * math.log(32.0) + 1.0
    kw = dict(
        vocab_size=LATENT["vocab"], d_model=7168, n_layers=LATENT["n_layers"],
        n_heads=64, n_kv_heads=64, d_ff=18432, rope_theta=50000.0,
        norm_eps=1e-6, rope_scaling=("yarn", 32.0, 4096, 1, 1, 1.0, True),
        latent=LatentAttn(q_rank=1536, kv_rank=512, nope_dim=128, rope_dim=64,
                          v_dim=128, sm_scale=192 ** -0.5 * mscale * mscale),
        routed=RoutedFFN(n_experts=384, top_k=8, d_expert=2048,
                         n_held=LATENT["held"], n_shared=1, scale=2.827,
                         first_dense=1))
    kw.update(overrides)
    return LlamaConfig(**kw)


def phase_latent_moe(dev, requests=REQUESTS) -> dict:
    """The latent cache, the absorbed decode kernel and the dropless
    grouped matmul through ``SlotServer`` and ``generate()``, against the
    plain lax forward (expanded attention, lax experts) at logit level."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from starway_tpu.models import SlotServer, generate, init_params, serving

    cfg = latent_config()
    with jax.default_device(dev):
        params = init_params(jax.random.PRNGKey(SEED), cfg)
    jax.block_until_ready(params)
    reqs = make_requests(cfg, requests)
    token_gaps = make_reference(cfg, LATENT["max_len"],
                                max(m for _p, m in reqs))
    out: dict = {"weights_gb": round(sum(
        x.nbytes for x in jax.tree_util.tree_leaves(params)) / 1e9, 2)}
    slot = SlotServer(params, cfg, n_slots=LATENT["n_slots"],
                      max_len=LATENT["max_len"], chunk=LATENT["chunk"])
    rids = [slot.submit(p, m) for p, m in reqs]
    done = slot.run()
    out["slot_server"] = _check_streams(
        "latent slot_server", params, reqs, [done[r] for r in rids],
        token_gaps, cfg, TOL_FLIP / 2, mean_tol=TOL_ROUTED_MEAN)
    rows = [r for r in serving.step_log()
            if r["server"] == slot.server_id and "moe_assign" in r]
    # A chunk's count may be 0: near the end one request decodes beside
    # seven freed slots whose pending tokens no longer change.
    check(rows and any(r["moe_assign"] > 0 for r in rows)
          and all(0 <= r["moe_touched"] <= LATENT["held"] for r in rows),
          "the routed model's step_log rows carry no pair counts")
    out["held_experts_touched_a_step"] = round(
        sum(r["moe_touched"] for r in rows) / len(rows), 2)
    del slot
    gc.collect()
    prompt, max_new = reqs[RERUN]
    alone = np.asarray(generate(params, cfg, jnp.asarray(prompt[None]),
                                max_new)[0, len(prompt):])
    gaps = token_gaps(params, prompt, alone)
    check(gaps.max() <= TOL_FLIP and gaps.mean() <= TOL_ROUTED_MEAN,
          f"generate() chose tokens {gaps.max():.4f} (mean {gaps.mean():.5f}) "
          f"of max|logit| below the plain reference's best")
    out["generate_rerun"] = {"request": RERUN,
                             "worst_gap_to_reference": round(float(gaps.max()), 5),
                             "mean_gap_to_reference": round(float(gaps.mean()), 6)}
    longest = max(reqs, key=lambda r: len(r[0]))[0]
    out["pallas_vs_lax"] = _pallas_vs_lax_logits(
        params, cfg, jnp.asarray(longest[None, :min(len(longest), 1024)]))
    return out


# ----------------------------------------------------------------- trainer


def train_config():
    from starway_tpu.models import LlamaConfig

    return LlamaConfig.preset(TRAIN["preset"], n_layers=TRAIN["n_layers"],
                              remat=True)


def _loss_and_grad_norm(cfg, attn_fn):
    """jit(params, batch) -> (loss, global gradient norm in f32)."""
    import jax
    import jax.numpy as jnp

    from starway_tpu.models import loss_fn

    def run(params, batch):
        loss, grads = jax.value_and_grad(loss_fn)(params, batch, cfg, attn_fn)
        sq = sum(jnp.sum(jnp.square(g.astype(jnp.float32)))
                 for g in jax.tree_util.tree_leaves(grads))
        return loss, jnp.sqrt(sq)

    return jax.jit(run)


def _train_batch(cfg, batch: int, seq: int):
    import numpy as np

    return np.random.default_rng(SEED + 1).integers(
        0, cfg.vocab_size, (batch, seq + 1), dtype=np.int32)


def phase_train(dev) -> dict:
    """Trainer.step_sync x 3: the only phase that runs the flash BACKWARD
    kernels.  First-step loss and gradient norm beside the same step with
    the plain lax attention."""
    import math

    import jax
    import jax.numpy as jnp
    import optax

    from starway_tpu.models import Trainer, init_params

    cfg = train_config()
    with jax.default_device(dev):
        params = init_params(jax.random.PRNGKey(SEED), cfg)
        tokens = jnp.asarray(_train_batch(cfg, TRAIN["batch"], TRAIN["seq"]))
    ref_loss, ref_norm = map(float, _loss_and_grad_norm(cfg, _plain_attn())(
        params, tokens))
    loss, norm = map(float, _loss_and_grad_norm(cfg, None)(params, tokens))
    check(math.isfinite(loss) and math.isfinite(norm),
          f"loss {loss} / gradient norm {norm} not finite")
    check(abs(loss - ref_loss) <= TOL_BF16 * abs(ref_loss),
          f"first-step loss {loss} vs {ref_loss} with lax attention")
    check(abs(norm - ref_norm) <= TOL_BF16 * ref_norm,
          f"gradient norm {norm} vs {ref_norm} with lax attention")
    trainer = Trainer(cfg, optax.adamw(1e-3), params)
    del params
    losses = [trainer.step_sync(tokens) for _ in range(TRAIN["steps"])]
    check(all(math.isfinite(x) for x in losses), f"losses {losses}")
    check(abs(losses[0] - loss) <= TOL_BF16 * abs(loss),
          f"Trainer's first loss {losses[0]} vs loss_fn's {loss}")
    check(losses[-1] < losses[0],
          f"three adamw steps on one batch did not lower the loss: {losses}")
    return {"losses": [round(x, 4) for x in losses],
            "first_loss": round(loss, 5), "first_loss_lax": round(ref_loss, 5),
            "grad_norm": round(norm, 5), "grad_norm_lax": round(ref_norm, 5),
            "allowed_rel": TOL_BF16,
            "params_m": round(sum(x.size for x in jax.tree_util.tree_leaves(
                trainer.state.params)) / 1e6, 1)}


# -------------------------------------------------------------- four chips

MESH_AXES = {"dp": 1, "tp": 2, "sp": 2}


async def phase_cross_chip(devices, sizes=(MiB, 256 * MiB)) -> dict:
    """asend/arecv pingpong HBM(chip 0) -> HBM(chip k) -> HBM(chip 0),
    k = 1..3, beside the raw jax.device_put of the same array; every sink
    must really live on its target device."""
    import jax
    import numpy as np

    from starway_tpu import DeviceBuffer

    server, client, ep = await _pair(None, native=False)
    rows, tag = [], 0xD000
    try:
        for k in range(1, len(devices)):
            for i, n in enumerate(sizes):
                want = pattern(n, 50 + 10 * k + i)
                src = jax.device_put(want, devices[0])
                src.block_until_ready()
                there = DeviceBuffer((n,), np.uint8, device=devices[k])
                back = DeviceBuffer((n,), np.uint8, device=devices[0])
                t0 = time.perf_counter()
                fut = server.arecv(there, tag, MASK)
                await client.asend(src, tag)
                await asyncio.wait_for(fut, 120)
                fut = client.arecv(back, tag + 1, MASK)
                await server.asend(ep, there.array, tag + 1)
                await asyncio.wait_for(fut, 120)
                await client.aflush()
                fw = time.perf_counter() - t0
                t0 = time.perf_counter()
                raw_there = jax.device_put(src, devices[k])
                raw_there.block_until_ready()
                raw_back = jax.device_put(raw_there, devices[0])
                raw_back.block_until_ready()
                raw = time.perf_counter() - t0
                for what, arr, dev in (
                        ("asend there", there.array, devices[k]),
                        ("asend back", back.array, devices[0]),
                        ("device_put there", raw_there, devices[k]),
                        ("device_put back", raw_back, devices[0])):
                    check(arr.devices() == {dev},
                          f"{what}: {n} B live on {arr.devices()}, not {dev}")
                    check(_same_bytes(arr, want), f"{what}: {n} B differ")
                check(there.last_transport == back.last_transport == "device",
                      f"chip 0 -> chip {k} rode {there.last_transport!r}")
                rows.append({"to_chip": k, "bytes": n, "transport": "device",
                             "verified": True,
                             "roundtrip_seconds": round(fw, 5),
                             "device_put_seconds": round(raw, 5)})
                tag += 2
    finally:
        await client.aclose()
        await server.aclose()
    return {"transfers": rows, **_pjrt_entry_points()}


def _abstract(tree, shardings):
    import jax

    return jax.tree_util.tree_map(
        lambda a, s: jax.ShapeDtypeStruct(a.shape, a.dtype, sharding=s),
        tree, shardings)


def _param_shardings(cfg, axes: dict, devices):
    """(mesh, NamedSharding tree): the weights laid out by ``param_specs``
    (tensor-parallel over "tp") on a mesh of ``devices``."""
    import jax
    from jax.sharding import NamedSharding

    from starway_tpu.models import param_specs
    from starway_tpu.parallel import make_mesh

    mesh = make_mesh(axes, devices)
    return mesh, jax.tree_util.tree_map(lambda s: NamedSharding(mesh, s),
                                        param_specs(cfg))


def _abstract_params(cfg, shardings):
    import jax

    from starway_tpu.models import init_params

    return _abstract(jax.eval_shape(lambda k: init_params(k, cfg),
                                    jax.random.PRNGKey(0)), shardings)


def _mesh_train(devices):
    """(cfg, mesh, param shardings, optimizer, jitted make_train_step) of
    the dp x tp x sp train step, ring attention over sp."""
    import jax
    import optax

    from starway_tpu.models import make_train_step
    from starway_tpu.models.llama import make_sharded_attn

    cfg = train_config()
    mesh, p_sh = _param_shardings(cfg, MESH_AXES, devices)
    tx = optax.adamw(1e-3)
    step = jax.jit(make_train_step(cfg, tx, make_sharded_attn(mesh)),
                   donate_argnums=(0, 1))
    return cfg, mesh, p_sh, tx, step


def mesh_train_program(devices):
    """(the jitted step ``--chips 4`` runs, abstract args): what
    tests/test_aot_tpu.py compiles for the described v5e:2x2."""
    import jax
    import jax.numpy as jnp
    from jax.sharding import NamedSharding, PartitionSpec as P

    cfg, mesh, p_sh, tx, step = _mesh_train(devices)
    params = _abstract_params(cfg, p_sh)
    # The optimizer state as tx.init(sharded params) lays it out: moments
    # follow their parameter, the step count (no input to follow) is
    # replicated.
    on_mesh, rep = set(mesh.devices.flat), NamedSharding(mesh, P())
    init = jax.jit(tx.init).lower(params).compile()
    opt = _abstract(jax.eval_shape(tx.init, params), jax.tree_util.tree_map(
        lambda s: s if s.device_set == on_mesh else rep,
        init.output_shardings))
    batch = jax.ShapeDtypeStruct((TRAIN["batch"], TRAIN["seq"] + 1), jnp.int32,
                                 sharding=NamedSharding(mesh, P("dp", None)))
    return step, (params, opt, batch)


def phase_mesh_train(devices) -> dict:
    """One dense train step on the real dp x tp x sp mesh (tensor-parallel
    params, ring attention over sp: collectives over ICI) beside the same
    step on one chip."""
    import math

    import jax
    from jax.sharding import NamedSharding, PartitionSpec as P

    from starway_tpu.models import init_params, make_train_step
    from starway_tpu.models.llama import make_sharded_attn

    cfg, mesh, p_sh, tx, mesh_step = _mesh_train(devices)
    tokens = _train_batch(cfg, TRAIN["batch"], TRAIN["seq"])

    def one_step(params, batch, attn_fn, step):
        loss0, norm = map(float, _loss_and_grad_norm(cfg, attn_fn)(
            params, batch))
        params, _opt, loss = step(params, tx.init(params), batch)
        jax.block_until_ready(params)
        return float(loss), loss0, norm, params

    with jax.default_device(devices[0]):
        params = init_params(jax.random.PRNGKey(SEED), cfg)
    host = jax.device_get(params)
    one_loss, one_loss0, one_norm, params = one_step(
        params, jax.device_put(tokens, devices[0]), None,
        jax.jit(make_train_step(cfg, tx), donate_argnums=(0, 1)))
    del params
    gc.collect()

    loss, loss0, norm, sharded = one_step(
        jax.device_put(host, p_sh),
        jax.device_put(tokens, NamedSharding(mesh, P("dp", None))),
        make_sharded_attn(mesh), mesh_step)
    wq = sharded["layers"]["wq"]
    check(len(wq.sharding.device_set) == len(devices)
          and wq.addressable_shards[0].data.size < wq.size,
          f"wq is not sharded over the mesh: {wq.sharding}")
    for a, b, what in ((loss, one_loss, "train-step loss"),
                       (loss0, one_loss0, "loss"),
                       (norm, one_norm, "gradient norm")):
        check(math.isfinite(a) and abs(a - b) <= TOL_BF16 * abs(b),
              f"mesh {what} {a} vs one chip {b}")
    return {"mesh": MESH_AXES, "loss": round(loss, 5),
            "loss_one_chip": round(one_loss, 5), "grad_norm": round(norm, 5),
            "grad_norm_one_chip": round(one_norm, 5), "allowed_rel": TOL_BF16}


TP_REQUESTS = ((5, 16), (200, 32), (230, 24), (1100, 32))


def tp_chunk_program(devices, ingest=None):
    """(mesh, the SlotServer decode-chunk program, abstract args) with
    the weights tensor-parallel over ``devices`` -- pure GSPMD, as
    __graft_entry__.py's serving phase shards them.  Lower it under
    ``jax.set_mesh(mesh)``, as SlotServer.step runs it.  ``ingest``: the
    mixed chunk of that piece width (what phase_tp_serve's server runs
    while prompts come in) instead of the plain one."""
    import jax
    import jax.numpy as jnp
    from jax.sharding import NamedSharding, PartitionSpec as P

    from starway_tpu.models import init_cache
    from starway_tpu.models.serving import (PIECE_FIELDS, _compiled_chunk,
                                            _compiled_ingest_chunk)

    cfg = serve_config()
    mesh, p_sh = _param_shardings(cfg, {"tp": len(devices)}, devices)
    n = SERVE["n_slots"]
    state = (jax.eval_shape(lambda: init_cache(cfg, n, SERVE["max_len"])),
             *(jax.ShapeDtypeStruct((n,), d) for d in
               (jnp.int32, jnp.int32, bool, jnp.int32)),
             jax.eval_shape(jax.random.PRNGKey, 0))
    rep = NamedSharding(mesh, P())
    state = jax.tree_util.tree_map(
        lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype, sharding=rep), state)
    if ingest is None:
        run = _compiled_chunk(cfg, n, SERVE["max_len"], SERVE["chunk"], 0.0,
                              None, None, None)
    else:
        run = _compiled_ingest_chunk(cfg, n, SERVE["max_len"], SERVE["chunk"],
                                     ingest, 0.0, None, None, None)
        state += tuple(jax.ShapeDtypeStruct(shape, jnp.int32, sharding=rep)
                       for shape in ((SERVE["chunk"], len(PIECE_FIELDS)),
                                     (SERVE["chunk"], ingest)))
    return mesh, run, (_abstract_params(cfg, p_sh), *state)


def phase_tp_serve(devices) -> dict:
    """One tp=2 SlotServer batch at published widths beside the same
    batch on one chip, both against the plain reference."""
    import jax
    import numpy as np

    from starway_tpu.models import SlotServer, init_params

    cfg = serve_config()
    with jax.default_device(devices[0]):
        params = init_params(jax.random.PRNGKey(SEED), cfg)
    _mesh, p_sh = _param_shardings(cfg, {"tp": len(devices)}, devices)
    sharded = jax.device_put(params, p_sh)
    reqs = make_requests(cfg, TP_REQUESTS)
    token_gaps = make_reference(cfg, SERVE["max_len"],
                                max(m for _p, m in reqs))
    out, streams = {}, {}
    for name, weights in (("one_chip", params), ("tp2", sharded)):
        srv = SlotServer(weights, cfg, n_slots=SERVE["n_slots"],
                         max_len=SERVE["max_len"], chunk=SERVE["chunk"])
        rids = [srv.submit(p, m) for p, m in reqs]
        done = srv.run()
        streams[name] = [np.asarray(done[r]) for r in rids]
        out[name] = _check_streams(name, params, reqs, streams[name],
                                   token_gaps, cfg, TOL_LOGITS)
        del srv
        gc.collect()
    wq = sharded["layers"]["wq"]
    check(wq.sharding.device_set == set(devices),
          f"tp weights live on {wq.sharding.device_set}")
    out["first_difference_tp2_from_one_chip"] = [
        _first_difference(a, b)
        for a, b in zip(streams["tp2"], streams["one_chip"])]
    return out


PULL_BYTES, PULL_THERE, PULL_BACK = 64 * MiB, 0xE000, 0xE001


def _one_chip_env(chip: int, index: int) -> dict:
    """The environment of a process that owns ONE chip of a four-chip
    host, as it ran on the v5e (PR 21): the host's own TPU_* layout is
    dropped, and libtpu is told of a 1x1x1 process that sees one chip,
    with ports of its own."""
    env = {k: v for k, v in os.environ.items() if not k.startswith("TPU_")}
    env.update(
        TPU_SKIP_MDS_QUERY="true", TPU_ACCELERATOR_TYPE="v5litepod-1",
        TPU_CHIPS_PER_PROCESS_BOUNDS="1,1,1", TPU_PROCESS_BOUNDS="1,1,1",
        TPU_VISIBLE_CHIPS=str(chip), TPU_VISIBLE_DEVICES=str(chip),
        TPU_WORKER_ID="0", TPU_WORKER_HOSTNAMES="localhost",
        TPU_MESH_CONTROLLER_ADDRESS=f"localhost:{8476 + index}",
        TPU_MESH_CONTROLLER_PORT=str(8476 + index),
        TPU_RUNTIME_METRICS_PORTS=str(8441 + index),
        STARWAY_TLS="tcp", STARWAY_NATIVE="0",
        PYTHONPATH=os.pathsep.join([str(REPO),
                                    os.environ.get("PYTHONPATH", "")]))
    return env


def phase_devpull_processes() -> dict:
    """devpull between two PROCESSES that own disjoint chips: a jax.Array
    in chip 0's HBM to chip 1's HBM and back, pulled over the PJRT
    transfer socket, no host staging.  Both children are started, and have
    exited, before this process touches JAX: a chip belongs to one process
    at a time."""
    port = _free_port()
    procs = [subprocess.Popen(
        [sys.executable, "-c",
         "import chip_smoke; chip_smoke.devpull_member()", role, str(port)],
        cwd=REPO, env=_one_chip_env(chip, chip), stdin=subprocess.PIPE,
        stdout=subprocess.PIPE, text=True)
        for chip, role in enumerate(("server", "client"))]
    try:
        # Each member reports once both directions are flushed, then
        # waits for its stdin to close: neither closes its worker while
        # the other's flush still needs an answer.
        lines = [proc.stdout.readline() for proc in procs]
        for proc in procs:
            proc.stdin.close()
        codes = [proc.wait(timeout=120) for proc in procs]
    finally:
        for proc in procs:
            if proc.poll() is None:
                proc.kill()
            proc.wait()
    reports = [json.loads(l) if l.startswith("{") else {} for l in lines]
    if any(r.get("platform", "tpu") != "tpu" for r in reports):
        raise SystemExit(f"chip_smoke: no TPU: a child found {reports}")
    for r, code in zip(reports, codes):
        check(code == 0 and r.get("verified") and r["transport"] == "device"
              and r["chips_seen"] == 1,
              f"devpull between processes: exit {code}, report {r}")
    return {"established": True, "bytes": PULL_BYTES, "members": reports}


def devpull_member() -> None:
    """One of the two processes of :func:`phase_devpull_processes`; owns
    the one chip its environment shows it."""
    role, port = sys.argv[1], int(sys.argv[2])
    faulthandler.dump_traceback_later(200, exit=True)
    import jax
    import numpy as np

    import starway_tpu as sw

    devs = jax.devices()  # devpull is only advertised once the backend is up
    if devs[0].platform != "tpu":
        emit(platform=devs[0].platform)
        raise SystemExit(3)
    there, back = pattern(PULL_BYTES, 7), pattern(PULL_BYTES, 8)

    async def run() -> None:
        sink = sw.DeviceBuffer((PULL_BYTES,), np.uint8, device=devs[0])
        if role == "server":
            me = sw.Server()
            me.listen("127.0.0.1", port)
            await asyncio.wait_for(me.arecv(sink, PULL_THERE, MASK), 150)
            ep = await _accepted(me)
            await me.asend(ep, jax.device_put(back, devs[0]), PULL_BACK)
        else:
            for _ in range(300):
                me = sw.Client()  # a failed connect burns the Client
                try:
                    await me.aconnect("127.0.0.1", port)
                    break
                except Exception:
                    await asyncio.sleep(0.2)
            fut = me.arecv(sink, PULL_BACK, MASK)
            await me.asend(jax.device_put(there, devs[0]), PULL_THERE)
            await asyncio.wait_for(fut, 150)
        await asyncio.wait_for(me.aflush(), 150)
        emit(role=role, chips_seen=len(devs), transport=sink.last_transport,
             verified=_same_bytes(sink.array,
                                  there if role == "server" else back))
        await asyncio.get_running_loop().run_in_executor(None, sys.stdin.read)
        await me.aclose()

    asyncio.run(run())


# --------------------------------------------------------------------- main


def run(chips: int) -> dict:
    """Every phase for ``chips`` (1: a-d on one chip; 4: the cross-chip
    paths and what they are compared with, nothing else).  Returns the
    device JAX reports; raises on the first phase that fails."""
    # Before this process touches JAX and takes every chip of the host.
    two_processes = phase_devpull_processes() if chips == 4 else None
    import jax

    from starway_tpu import native_build
    from starway_tpu.utils.chip import device_info, enable_compile_cache

    cache_dir = enable_compile_cache()
    info = device_info()
    if info["platform"] != "tpu":
        # Before anything is built or compiled.
        raise SystemExit(
            f"chip_smoke: no TPU: jax.devices()[0].platform is "
            f"{info['platform']!r} ({info['count']} x {info['kind']}). "
            f"This script proves the chip path and does not run on the CPU.")
    if info["count"] < chips:
        raise SystemExit(f"chip_smoke: --chips {chips} needs {chips} chips, "
                         f"JAX reports {info['count']}")
    # The engine is rebuilt from native/sw_engine.cpp where it runs: a
    # copied checkout can invert the mtimes ensure_built() compares, and
    # the artifact is git-ignored anyway.  No compiler is a loud failure.
    t0 = time.perf_counter()
    so = native_build.ensure_built(force=True)
    emit(phase="setup", ok=True, device=info, chips=chips, seed=SEED,
         compile_cache_dir=cache_dir,
         compile_cache_from_env=bool(os.environ.get(
             "JAX_COMPILATION_CACHE_DIR")),
         native_engine_built=str(Path(so).relative_to(REPO)),
         native_build_seconds=round(time.perf_counter() - t0, 1),
         jax=jax.__version__)
    clock = CompileClock()
    devices = jax.devices()
    dev = devices[0]

    if chips == 4:
        emit(phase="devpull_between_processes", ok=True, **two_processes)
        devices = devices[:4]
        with phase("cross_chip_transport", clock, chips=4) as d, \
                no_library_warnings():
            d.update(asyncio.run(phase_cross_chip(devices)))
        gc.collect()
        with phase("mesh_train_step", clock, model=TRAIN,
                   cut="llama2-7b depth 32 -> 4 (as phase d)") as d:
            d.update(phase_mesh_train(devices))
        gc.collect()
        with phase("tp_slot_server", clock, model=SERVE, tp=2,
                   cut="llama3-8b depth 32 -> 8 (as phase c)") as d:
            d.update(phase_tp_serve(devices[:2]))
        return info

    with phase("a_transport_inproc", clock, sizes=list(INPROC_SIZES)) as d, \
            no_library_warnings():
        d.update(asyncio.run(phase_transport_inproc(dev)))
    with phase("b_transport_sockets", clock, sizes=list(SOCKET_SIZES)) as d, \
            no_library_warnings():
        d.update(asyncio.run(phase_transport_sockets(dev)))
    with phase("c_served_model", clock, model=SERVE,
               requests=[list(r) for r in REQUESTS],
               cut="llama3-8b depth 32 -> 8: 5.6 GB of bf16 weights and an "
                   "8 x 2048 cache fit one 16 GB chip; widths as "
                   "published") as d:
        d.update(asyncio.run(phase_serve(dev)))
    gc.collect()
    with phase("d_trainer", clock, model=TRAIN,
               cut="llama2-7b depth 32 -> 4, batch 2 x 2048: what fits "
                   "16 GB beside bf16 adamw state; widths as published") as d:
        d.update(phase_train(dev))
    gc.collect()
    with phase("e_latent_moe", clock, model=LATENT,
               requests=[list(r) for r in REQUESTS],
               cut="Kimi-K2-Instruct depth 61 -> 2 (one dense, one routed "
                   "layer), 12 of 384 experts held, vocabulary 163840 -> "
                   "20480; widths as published") as d:
        d.update(phase_latent_moe(dev))
    return info


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1,
                    help="4: run only the cross-chip paths (a four-chip host)")
    args = ap.parse_args(argv)
    # A hung phase must end the run inside the contract's time, with the
    # stack that hung it, and never print the last line.
    faulthandler.dump_traceback_later(DEADLINE_S, exit=True)
    try:
        info = run(args.chips)
    except SmokeFailure as e:
        emit(ok=False, failed=str(e))
        return 1
    faulthandler.cancel_dump_traceback_later()
    emit(claim=None, note="a bring-up proof: no speed is claimed")
    print(json.dumps({"ok": True, "device": info}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
