"""Driver benchmark: one JSON line with the headline metric.

Metric (BASELINE.json): pingpong bandwidth of a 1 MiB jax.Array moved through
the framework's asend/arecv path, compared against the raw transfer the same
hardware does without the framework.  ``vs_baseline`` is
``framework_gbps / (0.9 * raw_gbps)``: >= 1.0 means the north-star target
(">= 90% of raw link bandwidth on 1 MB pingpong") is met on this hardware.

With >= 2 visible devices the pingpong crosses devices (ICI on TPU hardware);
with one device it is a host<->device round trip (the only real data motion a
single chip can do).

Framework and raw iterations are interleaved (one of each per loop pass):
allocator and cache state drift enough between separate phases to swing
either side's p50, so measuring them back-to-back is the only way the ratio
reflects the framework rather than the phase.

One process, which holds the chip.  Without an accelerator it fails: a
number from a CPU run is never written under the name of a device metric.
Every result names the device it ran on (platform, device_kind, count).
"""

from __future__ import annotations

import asyncio
import json
import statistics
import sys
import time

sys.path.insert(0, __file__.rsplit("/", 1)[0])

MSG_BYTES = 1 << 20
WARMUP = 10
ITERS = 50
MASK = (1 << 64) - 1
PING, PONG = 0x51, 0x52


async def _pingpong(devices) -> tuple[list[float], list[float], dict]:
    """Interleaved framework/raw pingpong; returns (fw_rtts, raw_rtts,
    and the client worker's §25 swpulse percentile view)."""
    import numpy as np

    from starway_tpu import Client, DeviceBuffer, Server

    import jax
    import jax.numpy as jnp

    server = Server()
    server.listen("127.0.0.1", 0)
    client = Client()
    await client.aconnect_address(server.get_worker_address())
    for _ in range(200):
        if server.list_clients():
            break
        await asyncio.sleep(0.005)
    ep = server.list_clients().pop()

    two_dev = len(devices) >= 2
    d_src = devices[0]
    d_dst = devices[1] if two_dev else devices[0]

    if two_dev:
        payload = jax.device_put(jnp.zeros(MSG_BYTES, dtype=jnp.uint8), d_src)
        payload.block_until_ready()
    else:
        payload = np.zeros(MSG_BYTES, dtype=np.uint8)
        host = np.zeros(MSG_BYTES, dtype=np.uint8)

    # Receive targets are reused across iterations, like the reference's
    # scenarios reuse their recv buffers (benchmarks/scenarios.py).
    sink = DeviceBuffer((MSG_BYTES,), jnp.uint8, device=d_dst)
    ret = (
        DeviceBuffer((MSG_BYTES,), jnp.uint8, device=d_src)
        if two_dev
        else np.empty(MSG_BYTES, dtype=np.uint8)
    )

    async def fw_iter() -> float:
        t0 = time.perf_counter()
        srv_fut = server.arecv(sink, PING, MASK)
        cli_fut = client.arecv(ret, PONG, MASK)
        await client.asend(payload, PING)
        await srv_fut
        await server.asend(ep, sink.array if two_dev else sink, PONG)
        await cli_fut
        return time.perf_counter() - t0

    def raw_iter() -> float:
        """The same data motion without the framework: the raw-link baseline."""
        t0 = time.perf_counter()
        if two_dev:
            there = jax.device_put(payload, d_dst)
            there.block_until_ready()
            back = jax.device_put(there, d_src)
            back.block_until_ready()
        else:
            dev = jax.device_put(host, d_src)
            dev.block_until_ready()
            np.asarray(dev)
        return time.perf_counter() - t0

    from starway_tpu import perf

    fw_rtts: list[float] = []
    raw_rtts: list[float] = []
    for i in range(WARMUP + ITERS):
        if i == WARMUP:
            # Per-stage telemetry (perf.record_stage) covers measured
            # iterations only, not warmup/cold-start.
            perf.stage_reset()
        fw_dt = await fw_iter()
        raw_dt = raw_iter()
        if i >= WARMUP:
            fw_rtts.append(fw_dt)
            raw_rtts.append(raw_dt)

    # §25 swpulse: the always-on distributions, read before teardown --
    # the percentile view of the SAME run the headline p50 summarises.
    from starway_tpu.core import swtrace

    pulse = swtrace.hist_summary(client._client.hists_snapshot())
    await client.aclose()
    await server.aclose()
    return fw_rtts, raw_rtts, pulse


def _pct(sorted_vals: list, q: float) -> float:
    """Nearest-rank percentile of an ascending list (perf.percentile --
    the shared implementation the bench CLI's p-tiles also use)."""
    from starway_tpu.perf import percentile

    return percentile(sorted_vals, q)


def _stage_summary() -> str:
    """Compact per-stage breakdown (stage=D2H, tx, rx, place=H2D): average
    microseconds per recorded sample, measured iterations only."""
    from starway_tpu import perf

    snap = perf.stage_snapshot()
    parts = []
    for name in ("stage", "tx", "rx", "place"):
        s = snap.get(name)
        if s and s["count"]:
            parts.append(f"{name}:{s['seconds'] / s['count'] * 1e6:.0f}us")
    return ",".join(parts) if parts else "none"


def _active_levers() -> list:
    """§24 swfast levers armed via env for this process ([] = seed)."""
    from starway_tpu.bench import active_levers

    return active_levers()


def main() -> None:
    import jax

    from starway_tpu.utils.chip import enable_compile_cache, require_accelerator

    enable_compile_cache()
    device = require_accelerator()
    devices = jax.devices()
    fw, raw, pulse = asyncio.run(_pingpong(devices))

    fw_sorted = sorted(fw)
    fw_p10, fw_p50, fw_p90 = (_pct(fw_sorted, 10), statistics.median(fw),
                              _pct(fw_sorted, 90))
    raw_p50 = statistics.median(raw)
    fw_gbps = 2 * MSG_BYTES / fw_p50 / 1e9
    raw_gbps = 2 * MSG_BYTES / raw_p50 / 1e9
    vs_baseline = fw_gbps / (0.9 * raw_gbps) if raw_gbps > 0 else 0.0

    print(
        json.dumps(
            {
                "metric": "1MiB jax.Array pingpong bandwidth via asend/arecv "
                f"({'device-to-device' if len(devices) >= 2 else 'host-to-device'}, "
                f"{len(devices)} dev, p50 of {len(fw)} interleaved iters; "
                f"raw={raw_gbps:.2f}GB/s "
                f"p10/p50/p90_rtt={fw_p10 * 1e6:.0f}/{fw_p50 * 1e6:.0f}/"
                f"{fw_p90 * 1e6:.0f}us stages={_stage_summary()})",
                "value": round(fw_gbps, 3),
                "unit": "GB/s",
                "vs_baseline": round(vs_baseline, 3),
                # The device the number came from, as JAX reports it.
                "device": device,
                # §24: swfast levers armed via env for this run ([] = seed
                # data path): rows are self-describing.
                "levers": _active_levers(),
                # §25 swpulse: the client worker's always-on distributions
                # (log-bucket percentiles per HIST_NAMES row) from the same
                # run.
                "hists": pulse,
            }
        )
    )


def main_kernels(argv: list) -> None:
    """``bench.py --kernels [names] [flags...]``: on-chip compute rows
    (matmul ceiling, flash fwd/bwd vs stock, decode us/token, train MFU,
    'check' numerics) -- delegates to scripts/kernel_bench.py, forwarding
    any further flags (e.g. --iters)."""
    import runpy

    which = argv[0] if argv and not argv[0].startswith("-") else "all"
    rest = argv[1:] if argv and not argv[0].startswith("-") else argv
    sys.argv = ["kernel_bench.py", "--which", which, *rest]
    runpy.run_path(
        __file__.rsplit("/", 1)[0] + "/scripts/kernel_bench.py",
        run_name="__main__",
    )


if __name__ == "__main__":
    if len(sys.argv) > 1 and sys.argv[1] == "--kernels":
        main_kernels(sys.argv[2:])
    else:
        main()
