"""Manual perf sweep: evaluate_perf estimate vs measured throughput.

Analogue of the reference's pingpong.py (reference: pingpong.py:11-47):
sweeps message sizes 1 B .. 1 GiB over a loopback Server/Client pair,
printing the link-model estimate next to the measured number.

Run:  python examples/pingpong.py [--tls tcp] [--max-size 1g] [--uvloop]

``--uvloop`` swaps in uvloop's event loop when the package is available
(the reference's perf script runs under uvloop, reference pingpong.py:6,47
— the asyncio scheduling overhead it removes is the remaining gap on the
pingpong headline).  Falls back to stock asyncio
with a warning when uvloop isn't installed (it is not in this sandbox).
"""

import argparse
import asyncio
import os
import sys
import time

import numpy as np

sys.path.insert(0, os.path.join(os.path.dirname(__file__), ".."))

from starway_tpu import Client, Server  # noqa: E402

PORT = 23751
TAG = 0x77


async def main(max_size: int) -> None:
    server = Server()
    server.listen("127.0.0.1", PORT)
    client = Client()
    await client.aconnect("127.0.0.1", PORT)
    ep = server.list_clients().pop()

    print(f"{'size':>12} {'est (s)':>12} {'measured (s)':>12} {'GB/s':>8}")
    size = 1
    while size <= max_size:
        buf = np.full(size, 0xA5, dtype=np.uint8)
        sink = np.empty(size, dtype=np.uint8)
        est = client.evaluate_perf(size)

        iters = 3 if size >= (1 << 28) else 10
        t0 = time.perf_counter()
        for _ in range(iters):
            recv_fut = server.arecv(sink, TAG, (1 << 64) - 1)
            await client.asend(buf, TAG)
            await recv_fut
        dt = (time.perf_counter() - t0) / iters
        gbps = size / dt / 1e9 if dt > 0 else float("inf")
        print(f"{size:>12} {est:>12.3e} {dt:>12.3e} {gbps:>8.2f}")
        size *= 16

    await client.aclose()
    await server.aclose()


if __name__ == "__main__":
    ap = argparse.ArgumentParser()
    ap.add_argument("--tls", help="STARWAY_TLS override (e.g. tcp)")
    ap.add_argument("--max-size", default="1g")
    ap.add_argument("--uvloop", action="store_true",
                    help="run under uvloop (reference pingpong.py parity)")
    args = ap.parse_args()
    if args.tls:
        os.environ["STARWAY_TLS"] = args.tls
    if args.uvloop:
        try:
            import uvloop
            asyncio.set_event_loop_policy(uvloop.EventLoopPolicy())
        except ImportError:
            print("uvloop not installed; running under stock asyncio",
                  file=sys.stderr)
    from starway_tpu.bench import parse_size

    asyncio.run(main(parse_size(args.max_size)))
