"""Runner of the ``serve`` kind: a dense decoder behind the program's
``SlotServer``, driven by a request mix.

Two drivers, chosen by the traffic file:

``inproc``  closed loop: ``clients`` callers in the chip's process submit
            straight to ``SlotServer`` and send their next request when the
            last completes.  One process.
``wire``    open loop: the chip-less parent (this process; JAX pinned to
            the CPU before anything imports it) holds ``sessions``
            ``RemoteGenerateSession``s and sends on a schedule; a child
            process holds the chip and serves through ``RemoteSlotServer``.

From the program the runner takes the system under test (``SlotServer``,
``RemoteSlotServer``, ``RemoteGenerateSession``, ``LlamaConfig``) and the
helpers of ``starway_tpu.utils.chip``.  Weights, traffic, timing, spans,
the trace reduction and the comparison that decides ``correct`` are the
benchmark's own.
"""

from __future__ import annotations

import asyncio
import gc
import json
import os
import sys
import time

import numpy as np

from benchmark.harness import spec as S
from benchmark.harness import stats, traffic as T
from benchmark.harness.chipside import (Profile, child_event, chip_start,
                                        free_port, log, memory_peak,
                                        setup_seconds, spawn_chip_child)
from benchmark.harness.spans import Spans

TRACE_SECONDS = 1.5          # the traced part of a --trace 1 window
TRACE_STOP_ALLOWANCE = 10.0  # stopping the profiler stalls the server


# ------------------------------------------------------------ the model


def llama_config(config: dict):
    from starway_tpu.models import LlamaConfig

    d = config["hidden_size"]
    hd = config.get("head_dim")
    return LlamaConfig(
        vocab_size=config["vocab_size"], d_model=d,
        n_layers=config["num_hidden_layers"],
        n_heads=config["num_attention_heads"],
        n_kv_heads=config["num_key_value_heads"],
        d_ff=config["intermediate_size"], rope_theta=config["rope_theta"],
        norm_eps=config["rms_norm_eps"],
        dtype=config.get("torch_dtype", "bfloat16"),
        sliding_window=config.get("sliding_window"),
        head_dim_override=(hd if hd and hd != d // config["num_attention_heads"]
                           else None))


def program_tree(model: dict) -> dict:
    """The benchmark's weights in the layout ``SlotServer`` takes."""
    return {"embed": model["embed"], "layers": dict(model["layers"]),
            "final_norm": model["final_norm"], "lm_head": model["lm_head"]}


def default_buckets(max_len: int) -> list:
    b, out = 32, []
    while b < max_len:
        out.append(b)
        b *= 2
    return out + [max_len]


def build_server(config: dict, seed: int, **kw):
    import jax

    from benchmark.harness import weights as W
    from starway_tpu.models import SlotServer

    sv = config["serve"]
    params = program_tree(W.make_model(seed, W.dims(config)))
    jax.block_until_ready(params)
    return SlotServer(params, llama_config(config), n_slots=sv["n_slots"],
                      max_len=sv["max_len"], chunk=sv["chunk"],
                      temperature=sv.get("temperature", 0.0), **kw)


def warm_up(srv, config: dict, traffic: dict) -> dict:
    """One request through every prompt bucket this mix can reach, and a
    decode chunk: every program the window will run, and no other."""
    lengths = sorted({p for p, _o in T.request_set(traffic)})
    by_bucket = {}
    for n in lengths:
        by_bucket[min(b for b in srv.buckets if b >= n)] = n
    rng = np.random.default_rng(0)
    for n in by_bucket.values():
        srv.submit(rng.integers(1, config["vocab_size"], n).astype(np.int32),
                   srv.chunk + 2)
    srv.run()
    return {"buckets_warmed": sorted(by_bucket)}


# ------------------------------------------------- requests and their times


class Book:
    """Every request of the run: what was sent, when its tokens came."""

    def __init__(self, traffic: dict, seed: int, vocab: int):
        self.seed, self.vocab = seed, vocab
        self.lengths = T.request_lengths(traffic, seed, 8192)
        self.rows: dict = {}

    def prompt(self, index: int) -> np.ndarray:
        return T.prompt_tokens(self.seed, index, self.lengths[index][0],
                               self.vocab)

    def open(self, index: int, due: float) -> dict:
        row = {"index": index, "due": due, "sent": None, "first": None,
               "last": None, "n": 0, "streamed": [], "at": [], "tokens": None,
               "failed": None, "want": self.lengths[index][1]}
        self.rows[index] = row
        return row

    @staticmethod
    def on_tokens(row: dict, tokens) -> None:
        now = time.monotonic()
        if row["first"] is None:
            row["first"] = now
        row["last"] = now
        row["n"] += len(tokens)
        row["streamed"].extend(int(t) for t in tokens)
        row["at"].append((now, len(tokens)))

    def tokens_delivered(self, t0: float, t1: float) -> int:
        """Output tokens that reached their client inside [t0, t1], of
        every request that did not fail: all the work of the window, not
        only of the requests that also finished inside it."""
        return sum(n for r in self.rows.values() if r["failed"] is None
                   for t, n in r["at"] if t0 <= t <= t1)

    def finished(self, t0: float, t1: float) -> list:
        return [r for r in self.rows.values()
                if r["tokens"] is not None and r["last"] is not None
                and t0 <= r["last"] <= t1]

    def delivery_faults(self, rows) -> list:
        """The guarantee: every request gets its tokens, in order, exactly
        once.  What was streamed must be what was returned, all of it."""
        bad = []
        for r in rows:
            toks = np.asarray(r["tokens"])
            if (len(toks) != r["want"] or r["streamed"] != [int(t) for t in toks]
                    or toks.min() < 0 or toks.max() >= self.vocab):
                bad.append(r["index"])
        return bad


def tpot_ms(rows) -> list:
    return [(r["last"] - r["first"]) / (r["n"] - 1) * 1e3
            for r in rows if r["n"] > 1]


def pick_sample(rows, seed: int, k: int) -> list:
    """The longest finished request and ``k - 1`` others drawn from the seed."""
    rows = sorted(rows, key=lambda r: r["index"])
    if not rows:
        return []
    longest = max(rows, key=lambda r: (len(r["streamed"]) + r["plen"], r["index"]))
    rest = [r for r in rows if r is not longest]
    rng = np.random.default_rng([seed & 0xFFFFFFFF, 0xC0C])
    picks = rng.permutation(len(rest))[:max(0, k - 1)]
    return [longest] + [rest[i] for i in sorted(picks)]


# --------------------------------------------------------- the chip's side


def instrument(srv, spans: Spans, live_rows) -> None:
    """--trace 1 only: spans around the scheduler's calls, from outside."""
    import jax

    run_chunk = getattr(srv, "_run_chunk", None)
    if not callable(run_chunk):
        raise SystemExit("benchmark: SlotServer._run_chunk is gone; the "
                         "spans chunk_dispatch / chunk_wait wrapped it")

    def chunk(sub):
        spans.count("live_rows", live_rows())
        spans.count("chunks")
        with spans.span("chunk"):
            with spans.span("chunk_dispatch"):
                out = run_chunk(sub)
            with spans.span("chunk_wait"):
                jax.block_until_ready(out)
        return out

    srv._run_chunk = chunk
    spans.wrap(srv, "_admit", "admit")
    spans.wrap(srv, "step", "step")


def decide_correct(ctx: dict, sample: list, faults: list, finished: int) -> dict:
    """The comparison with the plain reference, after the program's state
    is freed.  ``sample``: [(prompt ids, served ids)]."""
    config, limits = ctx["config"], ctx["config"]["correct"]
    ref = S.load_reference(ctx["cell"]["config"])
    sv = config["serve"]
    out_to = max(o for _p, o in T.request_set(ctx["traffic"]))
    t0 = time.monotonic()
    got = (ref.served_gaps(config, ctx["args"].seed, sample, sv["max_len"], out_to)
           if sample else {"gap_max": float("inf"), "gap_mean": float("inf"),
                           "tokens": 0, "sequences": 0, "finite": False})
    compared = [
        {"what": "gap_max", "value": got["gap_max"], "limit": limits["gap_max_limit"]},
        {"what": "gap_mean", "value": got["gap_mean"], "limit": limits["gap_mean_limit"]},
        {"what": "delivery_faults", "value": len(faults), "limit": 0},
    ]
    ok = bool(got["finite"] and finished > 0
              and all(c["limit"] is not None and c["value"] <= c["limit"]
                      for c in compared))
    log(event="correct", correct=ok, compared=compared,
        sample_sequences=got["sequences"], sample_tokens=got["tokens"],
        finished=finished, reference_seconds=time.monotonic() - t0,
        delivery_fault_requests=faults[:8])
    return {"correct": ok, "compared": compared}


def serving_obs(ctx, spans, rows, t0, t1, trace) -> dict:
    """What the per-layer readers of a serving cell read."""
    return {"cell": ctx["cell"]["name"], "config": ctx["config"],
            "traffic": ctx["traffic"], "spans": spans, "window": (t0, t1),
            "requests": rows, "trace": trace, "device": ctx.get("device")}


# ------------------------------------------------------- driver: inproc


def inproc_window(ctx: dict) -> dict:
    """Set-up, the measured window and the freeing of the program's state;
    everything but the comparison with the reference."""
    args, config, traffic = ctx["args"], ctx["config"], ctx["traffic"]
    if "device" not in ctx:
        ctx["device"] = chip_start(ctx)
    spans = Spans(annotate=bool(args.trace))
    book = Book(traffic, args.seed, config["vocab_size"])
    by_rid: dict = {}

    def on_tokens(rid, tokens, done):
        if tokens and rid in by_rid:
            Book.on_tokens(by_rid[rid], tokens)

    t_a = time.monotonic()
    srv = build_server(config, args.seed, on_tokens=on_tokens)
    t_b = time.monotonic()
    log(event="warm", **warm_up(srv, config, traffic),
        until_device_s=t_a - ctx["t_start"], build_s=t_b - t_a,
        warm_s=time.monotonic() - t_b)
    if args.trace:
        def live_rows():
            return sum(r["plen"] + r["n"] for r in by_rid.values())
        instrument(srv, spans, live_rows)

    nxt = [0]

    def submit(client: int) -> None:
        i = nxt[0]
        nxt[0] += 1
        row = book.open(i, time.monotonic())
        prompt = book.prompt(i)
        row.update(client=client, plen=len(prompt), sent=row["due"])
        by_rid[srv.submit(prompt, row["want"])] = row

    t0 = time.monotonic()
    setup_s = setup_seconds(ctx, t0)
    # The last seconds of the window are traced; the trace is stopped
    # after the window has closed.
    prof = Profile(ctx, spans, t0 + args.seconds - TRACE_SECONDS - 0.3,
                   TRACE_SECONDS, stop_by_tick=False)
    for c in range(int(traffic["clients"])):
        submit(c)
    end = t0 + args.seconds
    while True:
        now = time.monotonic()
        if now >= end:
            break
        prof.tick(now)
        for rid, toks in srv.step().items():
            row = by_rid.pop(rid)
            row["tokens"] = toks
            submit(row["client"])
    t1 = time.monotonic()
    prof.stop()

    rows = book.finished(t0, t1)
    tokens = book.tokens_delivered(t0, t1)
    faults = book.delivery_faults(rows)
    peak = memory_peak(ctx["cell"]["chips"])
    sample_rows = pick_sample(rows, args.seed, config["correct"]["sample_requests"])
    sample = [(book.prompt(r["index"]), np.asarray(r["tokens"], np.int32))
              for r in sample_rows]
    tp = tpot_ms(rows)
    log(event="window", seconds=t1 - t0, setup_s=setup_s,
        requests_finished=len(rows), requests_in_flight=len(by_rid),
        output_tokens=tokens, tpot_ms=stats.summary(tp),
        mix=T.describe(traffic))
    # The program's state goes before the reference comes.
    srv.params = srv.cache = None
    del srv
    gc.collect()
    e2e = {"tok_s": tokens / (t1 - t0),
           "tpot_p95_ms": stats.percentile(tp, 95) if tp else None,
           "setup_s": setup_s}
    return {"rows": rows, "sample": sample, "faults": faults, "e2e": e2e,
            "peak": peak, "spans": spans, "prof": prof, "window": (t0, t1)}


def run_inproc(ctx: dict) -> dict:
    w = inproc_window(ctx)
    verdict = decide_correct(ctx, w["sample"], w["faults"], len(w["rows"]))
    trace = w["prof"].reduce()
    return {"correct": verdict["correct"], "attempted": len(w["rows"]),
            "failed": len(w["faults"]), "e2e": w["e2e"],
            "obs": serving_obs(ctx, w["spans"], w["rows"], *w["window"], trace),
            "device": dict(ctx["device"], memory_peak_bytes=w["peak"]),
            "trace": trace}


# --------------------------------------------------------- driver: wire


def run_wire_parent(ctx: dict) -> dict:
    """The chip-less parent: starts the chip's process, offers the load,
    gathers both sides' readings."""
    child = spawn_chip_child(ctx)         # with the environment as it came
    os.environ["JAX_PLATFORMS"] = "cpu"   # before starway_tpu.models imports JAX
    try:
        return asyncio.run(_wire_parent(ctx, child))
    finally:
        if child.poll() is None:
            child.kill()
        child.wait()


async def _wire_parent(ctx: dict, child) -> dict:
    args, config, traffic = ctx["args"], ctx["config"], ctx["traffic"]
    loop = asyncio.get_running_loop()
    waiting = loop.run_in_executor(None, child_event, child, "ready")
    # While the chip's process sets up: this side's imports and inputs.
    from starway_tpu.models import RemoteGenerateSession

    book = Book(traffic, args.seed, config["vocab_size"])
    dues = T.arrival_times(traffic, args.seed, args.seconds)
    prompts = [book.prompt(i) for i in range(len(dues))]
    ready = await waiting
    sessions = [await asyncio.wait_for(RemoteGenerateSession.aconnect(
        "127.0.0.1", ready["port"]), 30) for _ in range(int(traffic["sessions"]))]

    t0 = time.monotonic()
    setup_s = setup_seconds(ctx, t0, ready["device_init_s"])

    async def one(i: int) -> None:
        row = book.open(i, t0 + dues[i])
        session = sessions[i % len(sessions)]
        handle = RemoteGenerateSession.Handle()
        delay = row["due"] - time.monotonic()
        if delay > 0:
            await asyncio.sleep(delay)
        row["sent"] = time.monotonic()
        row["plen"] = len(prompts[i])
        try:
            row["tokens"] = await session.generate(
                prompts[i], row["want"], handle=handle,
                on_tokens=lambda c: Book.on_tokens(row, c))
            row["route"] = f"{session.client_id}:{handle.nonce}"
        except Exception as e:  # the request failed: it misses every limit
            row["failed"] = repr(e)

    tasks = [asyncio.ensure_future(one(i)) for i in range(len(dues))]
    deadline = (t0 + args.seconds + float(traffic["drain_s"])
                + (TRACE_STOP_ALLOWANCE if args.trace else 0.0))
    _done, late = await asyncio.wait(tasks, timeout=deadline - time.monotonic())
    t1 = time.monotonic()
    for t in late:
        t.cancel()
    await asyncio.gather(*tasks, return_exceptions=True)
    rows = [r for r in book.rows.values() if r["tokens"] is not None]
    failed = [r for r in book.rows.values() if r["tokens"] is None]
    faults = book.delivery_faults(rows)
    sample_rows = pick_sample(rows, args.seed, config["correct"]["sample_requests"])
    child.stdin.write(json.dumps({
        "cmd": "finish", "t0": t0, "t1": t1, "faults": faults,
        "finished": len(rows),
        "sample": [{"index": r["index"],
                    "tokens": [int(t) for t in r["tokens"]]}
                   for r in sample_rows]}) + "\n")
    child.stdin.flush()
    await loop.run_in_executor(None, child_event, child, "stopped")
    for s in sessions:
        await s.aclose()
    child.stdin.write(json.dumps({"cmd": "closed"}) + "\n")
    child.stdin.flush()
    result = await loop.run_in_executor(None, child_event, child, "result")
    await loop.run_in_executor(None, child.wait)

    ttft = [(r["first"] - r["due"]) * 1e3 for r in rows]
    tp = tpot_ms(rows)
    late_ms = [(r["sent"] - r["due"]) * 1e3 for r in book.rows.values()
               if r["sent"] is not None]
    t_end = t0 + args.seconds
    half = t0 + args.seconds / 2
    log(event="window", seconds=args.seconds, drained_seconds=t1 - t0,
        requests_due=len(dues), requests_finished=len(rows),
        unfinished_when_arrivals_ended=sum(
            1 for r in book.rows.values()
            if r["tokens"] is None or r["last"] > t_end),
        ttft_ms_first_half=stats.summary(
            [(r["first"] - r["due"]) * 1e3 for r in rows if r["due"] < half]),
        ttft_ms_second_half=stats.summary(
            [(r["first"] - r["due"]) * 1e3 for r in rows if r["due"] >= half]),
        requests_failed=len(failed), ttft_ms=stats.summary(ttft),
        tpot_ms=stats.summary(tp), generator_lateness_ms=stats.summary(late_ms),
        output_tokens=sum(len(r["tokens"]) for r in rows),
        transports=result.get("transports"), mix=T.describe(traffic))
    # A failed request misses every limit: it sits at the far end of the tail.
    inf = float("inf")
    ttft_all = ttft + [inf] * len(failed)
    tp_all = tp + [inf] * len(failed)
    e2e = {"ttft_p95_ms": _finite(stats.percentile(ttft_all, 95)) if ttft_all else None,
           "tpot_p95_ms": _finite(stats.percentile(tp_all, 95)) if tp_all else None,
           "setup_s": setup_s}
    ok = bool(result["correct"] and e2e["ttft_p95_ms"] is not None
              and e2e["tpot_p95_ms"] is not None
              and result["transports_ok"])
    obs = {"cell": ctx["cell"]["name"], "config": config, "traffic": traffic,
           "spans": None, "child": result["obs"], "window": (t0, t1),
           "requests": rows, "trace": result.get("trace"),
           "device": result["device"]}
    return {"correct": ok, "attempted": len(dues),
            "failed": len(failed) + len(faults), "e2e": e2e, "obs": obs,
            "device": result["device"], "trace": result.get("trace")}


def _finite(x):
    return x if x != float("inf") and x == x else None


def wire_chip_main(ctx: dict) -> int:
    """The process that holds the chip in a ``wire`` cell."""
    return asyncio.run(_wire_chip(ctx))


async def _wire_chip(ctx: dict) -> int:
    from starway_tpu.models import RemoteSlotServer

    args, config, traffic = ctx["args"], ctx["config"], ctx["traffic"]
    info = chip_start(ctx)
    spans = Spans(annotate=bool(args.trace))
    t_a = time.monotonic()
    srv = build_server(config, args.seed)
    t_b = time.monotonic()
    log(event="warm", **warm_up(srv, config, traffic),
        until_device_s=t_a - ctx["t_start"], build_s=t_b - t_a,
        warm_s=time.monotonic() - t_b)
    bridge = RemoteSlotServer(srv)
    server_first: dict = {}
    plen_by_rid: dict = {}
    if args.trace:
        routes = getattr(bridge, "_rid_route", None)
        if routes is None or not callable(srv.on_tokens):
            raise SystemExit("benchmark: RemoteSlotServer._rid_route or its "
                             "on_tokens hook is gone; wire_ms.wire read them")
        to_bridge = srv.on_tokens
        emitted: dict = {}

        def on_tokens(rid, tokens, done):
            if tokens:
                emitted[rid] = emitted.get(rid, 0) + len(tokens)
                route = bridge._rid_route.get(rid)
                if route is not None and rid not in server_first:
                    server_first[rid] = (f"{route[0]}:{route[1]}", time.monotonic())
            if done:
                emitted.pop(rid, None)
                plen_by_rid.pop(rid, None)
            to_bridge(rid, tokens, done)

        srv.on_tokens = on_tokens
        submit = srv.submit

        def counted_submit(prompt, max_new, prefix=None):
            rid = submit(prompt, max_new, prefix)
            plen_by_rid[rid] = len(prompt)
            return rid

        srv.submit = counted_submit
        instrument(srv, spans, lambda: sum(plen_by_rid.get(r, 0) + n
                                           for r, n in emitted.items()))
    port = free_port()
    bridge.server.listen("127.0.0.1", port)
    serve_task = asyncio.ensure_future(bridge.serve())
    loop = asyncio.get_running_loop()
    log(event="ready", port=port, device_init_s=ctx["device_init_s"])
    # The load starts a fraction of a second after "ready": the trace
    # covers about the last seconds of the arrivals and is stopped inside
    # the drain, which a traced run is given longer for.
    prof = Profile(ctx, spans, time.monotonic() + 0.5 + args.seconds
                   - TRACE_SECONDS - 0.3, TRACE_SECONDS)

    async def profile_ticks():
        while prof.state != "done":
            prof.tick(time.monotonic())
            await asyncio.sleep(0.05)

    ticker = asyncio.ensure_future(profile_ticks())
    line = await loop.run_in_executor(None, sys.stdin.readline)
    cmd = json.loads(line) if line.strip() else {"cmd": "abort"}
    prof.stop()
    ticker.cancel()
    transports = sorted({t for ep in bridge.server.list_clients()
                         for _d, t in ep.view_transports()})
    bridge.stop()
    try:
        await asyncio.wait_for(serve_task, 30)
    except asyncio.TimeoutError:
        serve_task.cancel()
    # The sessions close first, then the bridge: neither end's close
    # cancels what the other still has in flight.
    log(event="stopped")
    await loop.run_in_executor(None, sys.stdin.readline)
    await bridge.aclose()
    if cmd.get("cmd") != "finish":
        return 1
    peak = memory_peak(ctx["cell"]["chips"])
    book = Book(traffic, args.seed, config["vocab_size"])
    sample = [(book.prompt(s["index"]), np.asarray(s["tokens"], np.int32))
              for s in cmd["sample"]]
    srv.params = srv.cache = None
    del srv, bridge
    gc.collect()
    verdict = decide_correct(ctx, sample, cmd["faults"], cmd["finished"])
    trace = prof.reduce()
    t0, t1 = cmd["t0"], cmd["t1"]
    admit_s, admits = spans.total("admit", t0, t1)
    step_s, steps = spans.total("step", t0, t1)
    wait_s, _ = spans.total("chunk_wait", t0, t1)
    want = ctx["config"].get("expected", {}).get("wire", {}).get("negotiated")
    log(event="result", correct=verdict["correct"],
        device=dict(info, memory_peak_bytes=peak), trace=trace,
        transports=transports,
        transports_ok=(want is None or transports == [want]),
        obs={"admit_s": admit_s, "admits": admits, "step_s": step_s,
             "steps": steps, "chunk_wait_s": wait_s,
             "chunks": spans.counts.get("chunks", 0),
             "live_rows": spans.counts.get("live_rows", 0),
             "server_first": {k: t for k, t in server_first.values()}})
    return 0


# ------------------------------------------------------------- entry points


def run(ctx: dict) -> dict:
    driver = ctx["traffic"]["driver"]
    if driver == "inproc":
        return run_inproc(ctx)
    if driver == "wire":
        return run_wire_parent(ctx)
    raise SystemExit(f"benchmark: the serve runner has no driver {driver!r}")


def run_role(role: str, ctx: dict) -> int:
    if role == "chip":
        return wire_chip_main(ctx)
    raise SystemExit(f"benchmark: the serve runner has no role {role!r}")
