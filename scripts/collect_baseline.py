"""Measure the five BASELINE.json configs + bench scenarios; prints a
markdown table.  Run on the virtual CPU mesh by default
(STARWAY_BASELINE_REAL=1 to use the real backend for device rows)."""

from __future__ import annotations

import asyncio
import json
import os
import statistics
import sys
import time
from pathlib import Path

sys.path.insert(0, str(Path(__file__).parent.parent))

if os.environ.get("STARWAY_BASELINE_REAL") != "1":
    import jax

    jax.config.update("jax_platforms", "cpu")
    os.environ.setdefault("XLA_FLAGS", "--xla_force_host_platform_device_count=8")

import numpy as np

MASK = (1 << 64) - 1
rows: list[tuple[str, str]] = []


async def config1_pingpong_sweep():
    """pingpong 4B-1MB numpy uint8 over loopback (in-process fast path)."""
    from starway_tpu import Client, Server

    server = Server()
    server.listen("127.0.0.1", 0)
    client = Client()
    await client.aconnect_address(server.get_worker_address())
    ep = None
    for _ in range(200):
        if server.list_clients():
            ep = server.list_clients().pop()
            break
        await asyncio.sleep(0.005)
    out = []
    for size in (4, 1024, 64 * 1024, 1 << 20):
        buf = np.zeros(size, np.uint8)
        sink = np.zeros(size, np.uint8)
        rtts = []
        for i in range(300):
            t0 = time.perf_counter()
            f = server.arecv(sink, 1, MASK)
            await client.asend(buf, 1)
            await f
            f2 = client.arecv(buf, 2, MASK)
            await server.asend(ep, sink, 2)
            await f2
            if i >= 50:
                rtts.append(time.perf_counter() - t0)
        p50 = statistics.median(rtts)
        out.append(f"{size}B: rtt_p50={p50 * 1e6:.0f}us ({2 * size / p50 / 1e9:.2f} GB/s)")
    rows.append(("config 1: pingpong sweep 4B-1MB (loopback, inproc)", "; ".join(out)))
    await client.aclose()
    await server.aclose()


async def config2_fanin():
    """1 Server x 8 Clients, tag-routed fan-in."""
    from starway_tpu import Client, Server

    server = Server()
    server.listen("127.0.0.1", 0)
    addr = server.get_worker_address()
    clients = []
    for _ in range(8):
        c = Client()
        await c.aconnect_address(addr)
        clients.append(c)
    n_msgs = 200
    payload = np.zeros(1024, np.uint8)
    sink = np.zeros(1024, np.uint8)
    t0 = time.perf_counter()
    for _ in range(n_msgs):
        recvs = [server.arecv(sink, i, MASK) for i in range(8)]
        sends = [c.asend(payload, i) for i, c in enumerate(clients)]
        await asyncio.gather(*sends, *recvs)
    dt = time.perf_counter() - t0
    total = 8 * n_msgs
    rows.append(
        ("config 2: 8-client tag-matched fan-in (1KiB msgs)",
         f"{total / dt:.0f} msgs/s, {total * 1024 / dt / 1e6:.1f} MB/s")
    )
    for c in clients:
        await c.aclose()
    await server.aclose()


async def config3_worker_address():
    """Worker-address bootstrap latency (no TCP listener semantics)."""
    from starway_tpu import Client, Server

    times = []
    for _ in range(10):
        server = Server()
        blob = server.listen_address()
        t0 = time.perf_counter()
        client = Client()
        await client.aconnect_address(blob)
        times.append(time.perf_counter() - t0)
        await client.aclose()
        await server.aclose()
    rows.append(
        ("config 3: worker-address bootstrap (aconnect_address)",
         f"connect p50 = {statistics.median(times) * 1e3:.2f} ms")
    )


def config4_shuffle():
    """1GB-scale all-to-all shuffle over the 8-way mesh axis."""
    import jax
    import jax.numpy as jnp

    from starway_tpu.parallel import make_mesh, make_shuffle
    from starway_tpu.parallel.sharding import shard_array

    mesh = make_mesh({"x": 8})
    total = 1 << 28  # 256 MiB of f32 = 1 GiB
    s, b = 64, 16
    d = total // (s * b)
    x = jnp.zeros((s, b, d), jnp.float32)
    xs = shard_array(mesh, x, "x")
    shuffle = make_shuffle(mesh, "x")
    shuffle(xs).block_until_ready()  # compile
    t0 = time.perf_counter()
    iters = 3
    for _ in range(iters):
        shuffle(xs).block_until_ready()
    dt = (time.perf_counter() - t0) / iters
    nbytes = x.size * 4
    rows.append(
        ("config 4: 1GiB all-to-all shuffle (8-way mesh, jitted lax.all_to_all)",
         f"{nbytes / 1e9:.2f} GB in {dt * 1e3:.0f} ms = {nbytes / dt / 1e9:.2f} GB/s")
    )


async def config5_dp_exchange():
    """Llama gradient pytree transfer across the DP boundary."""
    import jax
    import jax.numpy as jnp

    from starway_tpu import Client, Server
    from starway_tpu.models import LlamaConfig, init_params
    from starway_tpu.parallel import ClientPort, ServerPort, recv_pytree, send_pytree

    cfg = LlamaConfig.preset("debug", n_layers=4, d_model=512, d_ff=1024)
    params = init_params(jax.random.PRNGKey(0), cfg)
    nbytes = sum(x.nbytes for x in jax.tree_util.tree_leaves(params))

    server = Server()
    server.listen("127.0.0.1", 0)
    client = Client()
    await client.aconnect_address(server.get_worker_address())
    for _ in range(200):
        if server.list_clients():
            break
        await asyncio.sleep(0.005)

    t0 = time.perf_counter()
    iters = 5
    for _ in range(iters):
        send_task = asyncio.ensure_future(
            send_pytree(ClientPort(client), params, base_tag=0x8000)
        )
        await recv_pytree(ServerPort(server), like=params, base_tag=0x8000)
        await send_task
    dt = (time.perf_counter() - t0) / iters
    rows.append(
        (f"config 5: Llama grad pytree DP transfer ({nbytes / 1e6:.0f} MB, {len(jax.tree_util.tree_leaves(params))} leaves)",
         f"{dt * 1e3:.0f} ms/transfer = {nbytes / dt / 1e9:.2f} GB/s")
    )
    await client.aclose()
    await server.aclose()


def main():
    asyncio.run(config1_pingpong_sweep())
    asyncio.run(config2_fanin())
    asyncio.run(config3_worker_address())
    config4_shuffle()
    asyncio.run(config5_dp_exchange())
    print("\n| Config | Measured |")
    print("|---|---|")
    for name, val in rows:
        print(f"| {name} | {val} |")
    out = {name: val for name, val in rows}
    Path("/tmp/baseline_results.json").write_text(json.dumps(out, indent=2))


if __name__ == "__main__":
    main()
