"""Cross-process device transfer over the PJRT pull path (devpull).

Two processes, each with its own JAX runtime: the child sends a jax.Array,
the parent receives it into a DeviceBuffer.  The payload moves
device-to-device over the PJRT transfer socket -- the framework never
stages the bytes through the host (sink.last_transport proves which path
ran).  The reference's closest analogue is its zero-copy RDMA into the
receiver's buffer; this is the TPU-native equivalent
(DESIGN.md section 7, tests/test_devpull.py).

Both processes pin JAX to the CPU before first use: a chip belongs to one
process at a time, so two runtimes on one host cannot both hold it.
(Whether devpull runs between two processes that own DISJOINT chips is
recorded in PERF.md, "Open questions".)

Run:  python examples/device_pull.py  [--size 16M]
"""

from __future__ import annotations

import argparse
import asyncio
import multiprocessing
import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

MASK = (1 << 64) - 1
TAG = 0x9D


def parse_size(text: str) -> int:
    mult = {"k": 1 << 10, "m": 1 << 20, "g": 1 << 30}.get(text[-1].lower(), 1)
    return int(text[:-1] if mult > 1 else text) * mult


def child(port: int, nbytes: int) -> None:
    os.environ.setdefault("STARWAY_TLS", "tcp")
    import jax

    jax.config.update("jax_platforms", "cpu")
    import jax.numpy as jnp

    from starway_tpu import Client

    jax.devices()  # devpull is advertised once the backend is up

    async def run() -> None:
        client = Client()
        for _ in range(100):
            try:
                await client.aconnect("127.0.0.1", port)
                break
            except Exception:
                client = Client()
                await asyncio.sleep(0.1)
        payload = jax.device_put(jnp.arange(nbytes, dtype=jnp.uint8))
        await client.asend(payload, TAG)
        await client.aflush()  # barrier: payload resident at the receiver
        await client.aclose()

    asyncio.run(run())


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--size", default="16M")
    args = ap.parse_args()
    nbytes = parse_size(args.size)

    os.environ.setdefault("STARWAY_TLS", "tcp")
    import time

    import numpy as np

    import jax

    jax.config.update("jax_platforms", "cpu")
    import jax.numpy as jnp

    from starway_tpu import DeviceBuffer, Server

    jax.devices()

    async def run() -> None:
        server = Server()
        server.listen("127.0.0.1", 0)
        import json

        port = json.loads(server.get_worker_address())["port"]
        ctx = multiprocessing.get_context("spawn")
        proc = ctx.Process(target=child, args=(port, nbytes), daemon=True)
        proc.start()

        sink = DeviceBuffer((nbytes,), jnp.uint8)
        t0 = time.perf_counter()
        tag, length = await asyncio.wait_for(server.arecv(sink, TAG, MASK), 60)
        dt = time.perf_counter() - t0
        assert (tag, length) == (TAG, nbytes)
        ok = bool((np.asarray(sink.array) == np.arange(nbytes, dtype=np.uint8)).all())
        print(f"received {nbytes} bytes via {sink.last_transport!r} "
              f"in {dt:.3f}s (includes peer startup) content_ok={ok}")
        proc.join(10)
        await server.aclose()

    asyncio.run(run())


if __name__ == "__main__":
    main()
