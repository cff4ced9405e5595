"""The staged device path measured alone, on whatever tree runs it: a chip
process and a chip-less peer over the default negotiation (``sm``).

    python scripts/staged_path_bench.py --mode device --sizes 4,16,64,256
    python scripts/staged_path_bench.py --mode host --sizes 4

``--mode device``: the chip side's buffers are jax.Arrays / DeviceBuffers
(the staged path); ``--mode host``: numpy on both ends (the ring ALONE).
Per size: one message alone on the ring each way (``up``: peer -> chip,
``down``: chip -> peer; seconds from post to the flush's return, which means
resident), then ``stream``: 64 messages each way at once with one flush, as
``hbm_duplex.stream_4m`` posts them (only for sizes <= 4 MiB).  Only the
public API is used, so the same file measures a parent checkout
(``PYTHONPATH=<parent> python scripts/staged_path_bench.py``): that is how
PERF.md's crossover of whole against pieces was read.  One JSON line a row.
"""

from __future__ import annotations

import argparse
import asyncio
import json
import os
import statistics
import subprocess
import sys
import time

import numpy as np

MASK = (1 << 64) - 1
MiB = 1 << 20
T_UP, T_DOWN, T_CTRL, T_ACK = 0x11 << 40, 0x22 << 40, 0x33 << 40, 0x44 << 40
STREAM_N = 64


def plan(args) -> list:
    out = []
    for mib in args.sizes:
        n = mib * MiB
        reps = max(2, min(args.reps, (1024 * MiB) // n))
        out.append((n, reps, n <= 4 * MiB))
    return out


class Barrier:
    """A two-message handshake on tags of its own: both sides leave it
    together, so neither posts the next phase into the other's timing."""

    def __init__(self, send, recv):
        self.send, self.recv, self.k = send, recv, 0
        self.out, self.inn = np.zeros(8, np.uint8), np.zeros(8, np.uint8)

    async def meet(self, first: bool) -> None:
        self.k += 1
        mine, theirs = (T_CTRL, T_ACK) if first else (T_ACK, T_CTRL)
        got = self.recv(self.inn, theirs | self.k, MASK)
        await self.send(self.out, mine | self.k)
        await got


async def side(args, chip: bool, endpoint, barrier: Barrier) -> list:
    """One side's half of the plan.  ``endpoint`` has asend/arecv/aflush."""
    device = chip and args.mode == "device"
    if device:
        import jax
        import jax.numpy as jnp

        from starway_tpu import DeviceBuffer

        dev = jax.devices()[0]
        bump = jax.jit(lambda a: a + jnp.uint8(1))
    rows = []

    def source(n, seed):
        host = np.full(n, seed % 251, np.uint8)
        if not device:
            return host
        return jax.block_until_ready(jax.device_put(host, dev))

    def sink(n):
        if not device:
            return np.empty(n, np.uint8)
        return DeviceBuffer((n,), np.uint8, device=dev)

    def fresh(a):
        # A jax.Array keeps its host copy once fetched: a send must read a
        # NEW array every time, or the second fetch is free.
        return jax.block_until_ready(bump(a)) if device else a

    def check(b, seed: int, sender_is_chip: bool, sends: int) -> None:
        """Both ends of what arrived: the sender's fill value, bumped once
        a send where the sender's sources are device arrays."""
        bump = sends if sender_is_chip and args.mode == "device" else 0
        got = np.asarray(b.array if device else b)
        want = (seed % 251 + bump) % 256
        if (int(got[0]), int(got[-1])) != (want, want):
            raise SystemExit(f"{len(got)} B arrived as {got[0]}..{got[-1]}, "
                             f"sent as {want}")

    for n, reps, stream in plan(args):
        for phase, tag, sender_is_chip in (("up", T_UP, False),
                                           ("down", T_DOWN, True)):
            mine = sender_is_chip == chip
            src = source(n, 3) if mine else None
            # One sink, touched once, for every repetition (as the cell
            # reuses its sinks): a fresh np.empty would page-fault inside
            # the sender's timing.
            dst = None if mine else sink(n)
            if dst is not None and not device:
                dst[:] = 0
            secs = []
            for r in range(reps):
                if mine:
                    src = fresh(src)
                    await barrier.meet(chip)
                    t0 = time.perf_counter()
                    await endpoint.asend(src, tag | r)
                    await endpoint.aflush()
                    secs.append(time.perf_counter() - t0)
                else:
                    got = endpoint.arecv(dst, tag | r, MASK)
                    await barrier.meet(chip)
                    await got
                    check(dst, 3, sender_is_chip, r + 1)
            if mine:
                rows.append({"phase": phase, "bytes": n, "reps": reps,
                             "ms_median": 1e3 * statistics.median(secs),
                             "ms_min": 1e3 * min(secs),
                             "GBps_median": n / statistics.median(secs) / 1e9})
        if not stream:
            continue
        srcs = [source(n, 5 + i) for i in range(STREAM_N)]
        sinks = [sink(n) for _ in range(STREAM_N)]
        if not device:
            for b in sinks:
                b[:] = 0
        secs = []
        for r in range(args.rounds):
            srcs = [fresh(a) for a in srcs]
            base = (T_DOWN if chip else T_UP) | (1 << 32)
            want = (T_UP if chip else T_DOWN) | (1 << 32)
            recvs = [endpoint.arecv(b, want | i, MASK)
                     for i, b in enumerate(sinks)]
            await barrier.meet(chip)
            t0 = time.perf_counter()
            sends = [endpoint.asend(a, base | i) for i, a in enumerate(srcs)]
            await asyncio.gather(*sends, *recvs)
            await endpoint.aflush()
            secs.append(time.perf_counter() - t0)
            await barrier.meet(chip)
            check(sinks[-1], 5 + STREAM_N - 1, not chip, r + 1)
        if chip:
            both = 2 * STREAM_N * n
            rows.append({"phase": "stream", "bytes": n, "each_way": STREAM_N,
                         "rounds": args.rounds,
                         "round_ms_median": 1e3 * statistics.median(secs),
                         "round_ms_min": 1e3 * min(secs),
                         "round_ms": [round(1e3 * x, 1) for x in secs],
                         "GBps_both_ways_median":
                             both / statistics.median(secs) / 1e9})
    return rows


async def chip_main(args) -> int:
    import starway_tpu as sw

    if args.mode == "device":
        import jax

        kind = jax.devices()[0].device_kind
    else:
        kind = "none (host buffers both ends)"
    server = sw.Server()
    server.listen("127.0.0.1", 0)
    peer = subprocess.Popen(
        [sys.executable, os.path.abspath(__file__), "--peer",
         server.get_worker_address().hex(), "--mode", args.mode,
         "--sizes", ",".join(str(s) for s in args.sizes),
         "--reps", str(args.reps), "--rounds", str(args.rounds)],
        env=dict(os.environ, JAX_PLATFORMS="cpu"))
    try:
        for _ in range(6000):
            if server.list_clients():
                break
            await asyncio.sleep(0.01)
        ep = sorted(server.list_clients())[0]

        class End:
            asend = staticmethod(lambda b, t: server.asend(ep, b, t))
            arecv = staticmethod(server.arecv)
            aflush = staticmethod(server.aflush)

        barrier = Barrier(End.asend, End.arecv)
        rows = await side(args, True, End, barrier)
        transports = sorted({t for _d, t in ep.view_transports()})
        from starway_tpu import perf

        for row in rows:
            print(json.dumps(dict(row, side="chip", mode=args.mode,
                                  tree=args.tree, device=kind,
                                  transports=transports)), flush=True)
        print(json.dumps({"tree": args.tree, "mode": args.mode,
                          "stages": perf.stage_snapshot()}), flush=True)
        await asyncio.sleep(0.2)
    finally:
        peer.wait(timeout=120)
        await server.aclose()
    return peer.returncode


async def peer_main(args) -> int:
    import starway_tpu as sw

    client = sw.Client()
    await asyncio.wait_for(
        client.aconnect_address(bytes.fromhex(args.peer)), 60)
    barrier = Barrier(client.asend, client.arecv)
    rows = await side(args, False, client, barrier)
    if "jax" in sys.modules:
        raise SystemExit("the chip-less peer imported jax")
    for row in rows:
        print(json.dumps(dict(row, side="peer", mode=args.mode,
                              tree=args.tree)), flush=True)
    await client.aclose()
    return 0


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--mode", choices=("device", "host"), default="device")
    ap.add_argument("--sizes", default="4",
                    type=lambda s: [int(x) for x in s.split(",")],
                    help="message sizes in MiB")
    ap.add_argument("--reps", type=int, default=6)
    ap.add_argument("--rounds", type=int, default=5)
    ap.add_argument("--peer", help="(internal) the server's address, hex")
    args = ap.parse_args()
    try:
        import starway_tpu
    except ModuleNotFoundError:  # no PYTHONPATH: the tree this file is in
        sys.path.insert(0, os.path.dirname(os.path.dirname(
            os.path.abspath(__file__))))
        import starway_tpu

    args.tree = os.path.dirname(os.path.dirname(starway_tpu.__file__))
    return asyncio.run(peer_main(args) if args.peer else chip_main(args))


if __name__ == "__main__":
    sys.exit(main())
