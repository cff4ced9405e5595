"""What the runtime does under the device plane's two copies, read on the
device that runs it (a chip: ``chiprun -- python scripts/device_plane_probe.py``).

Host -> device, the placement of a staged receive (device.py ``_fast_h2d``):
* ``sync``: ``buffer_from_pyval(force_copy=True, IMMUTABLE_ONLY_DURING_CALL)``,
  what the transport uses: seconds in the call, seconds until ready;
* ``async``: the same with ``IMMUTABLE_UNTIL_TRANSFER_COMPLETES``: seconds in
  the call, seconds until ready, and whether the source overwritten (a) right
  after the call and (b) after ``block_until_ready`` reaches the device;
* whether the sync call lets another Python thread run (the GIL), which
  decides whether a placement beside the engine thread overlaps it.

Device -> host, the staging of a send: ``np.asarray`` one array at a time
against ``copy_to_host_async`` on all first (the prefetch window).

Chip -> chip (``--mode d2d``, on a host with several chips: ``chiprun
--chips 4``), the in-process handoff (device.py ``_copy_to_device``): one
16 MiB copy with its wait, the all-to-all's twelve in turn (each waited for
before the next is issued: the transport before PR 34), and the twelve
issued together and waited for once (the transport since, and the
benchmark's raw round, whose ``jax.device_put`` is timed beside it).

One JSON line a row; no number from the CPU backend says anything about a
chip (the script prints the platform it ran on).
"""

from __future__ import annotations

import json
import os
import statistics
import sys
import threading
import time

import numpy as np

MiB = 1 << 20


def med_ms(xs) -> float:
    return 1e3 * statistics.median(xs)


def h2d(dev, nbytes: int, reps: int) -> dict:
    from jax._src.lib import xla_client as xc

    sems = {"sync": xc.HostBufferSemantics.IMMUTABLE_ONLY_DURING_CALL,
            "async": xc.HostBufferSemantics.IMMUTABLE_UNTIL_TRANSFER_COMPLETES}
    out = {"row": "h2d", "bytes": nbytes, "reps": reps}
    for name, sem in sems.items():
        call, ready = [], []
        stale_after_call = stale_after_ready = 0
        for r in range(reps):
            src = np.full(nbytes, 1 + r % 200, np.uint8)
            t0 = time.perf_counter()
            arr = dev.client.buffer_from_pyval(
                src, dev, force_copy=True, host_buffer_semantics=sem)
            t1 = time.perf_counter()
            src[:] = 255      # overwritten the moment the call returns
            arr.block_until_ready()
            t2 = time.perf_counter()
            call.append(t1 - t0)
            ready.append(t2 - t0)
            stale_after_call += int((np.asarray(arr) == 255).any())
            # ... and overwritten only once the array is ready.
            src[:] = 1 + r % 200
            arr = dev.client.buffer_from_pyval(
                src, dev, force_copy=True, host_buffer_semantics=sem)
            arr.block_until_ready()
            src[:] = 255
            stale_after_ready += int((np.asarray(arr) == 255).any())
        out[name] = {"call_ms": med_ms(call), "ready_ms": med_ms(ready),
                     "overwritten_bytes_seen_after_call": stale_after_call,
                     "overwritten_bytes_seen_after_ready": stale_after_ready}
    return out


def gil(dev, nbytes: int, reps: int) -> dict:
    """Python iterations another thread gets while this one places."""
    from starway_tpu import device

    stop, count = threading.Event(), [0]

    def spin():
        while not stop.is_set():
            count[0] += 1

    src = np.full(nbytes, 3, np.uint8)
    t = threading.Thread(target=spin, daemon=True)
    t0 = time.perf_counter()
    t.start()
    time.sleep(0.2)
    alone = count[0] / (time.perf_counter() - t0)
    c0, t0 = count[0], time.perf_counter()
    for _ in range(reps):
        device._fast_h2d(src, dev).block_until_ready()
    dt = time.perf_counter() - t0
    beside = (count[0] - c0) / dt
    stop.set()
    t.join(2)
    return {"row": "gil", "bytes": nbytes, "reps": reps,
            "place_ms_each": 1e3 * dt / reps,
            "spinner_iterations_per_s_alone": alone,
            "spinner_iterations_per_s_beside_placements": beside,
            "share_kept": beside / alone}


def d2h(dev, nbytes: int, n: int) -> dict:
    import jax
    import jax.numpy as jnp

    def fresh():
        with jax.default_device(dev):
            return jax.block_until_ready(
                [jnp.full((nbytes,), i, jnp.uint8) for i in range(n)])

    arrays = fresh()
    t0 = time.perf_counter()
    for a in arrays:
        np.asarray(a)
    serial = time.perf_counter() - t0
    arrays = fresh()
    t0 = time.perf_counter()
    for a in arrays:
        a.copy_to_host_async()
    started = time.perf_counter() - t0
    first = None
    for a in arrays:
        np.asarray(a)
        first = first or time.perf_counter() - t0
    ahead = time.perf_counter() - t0
    return {"row": "d2h", "bytes": nbytes, "arrays": n,
            "serial_ms_each": 1e3 * serial / n,
            "prefetched_ms_each": 1e3 * ahead / n,
            "start_all_ms": 1e3 * started, "first_ready_ms": 1e3 * first,
            "GBps_serial": n * nbytes / serial / 1e9,
            "GBps_prefetched": n * nbytes / ahead / 1e9}


def d2d(devs, nbytes: int, reps: int) -> dict:
    """Every ordered pair of ``devs`` carries ``nbytes``, as a round of the
    all-to-all does: in turn, then all in flight at once."""
    import jax
    import jax.numpy as jnp

    from starway_tpu import device

    pairs = [(a, b) for a in range(len(devs)) for b in range(len(devs)) if a != b]
    src = {}
    for a, b in pairs:
        with jax.default_device(devs[a]):
            src[(a, b)] = jnp.full((nbytes,), 16 * a + b, jnp.uint8)
    jax.block_until_ready(list(src.values()))
    plans = {b: [None] for b in range(len(devs))}

    def issue(a, b):
        return device._copy_to_device(src[(a, b)], devs[b], plans[b])

    issue(*pairs[0]).block_until_ready()   # warm the client and the plans
    one, issue_s, turn, together, put = [], [], [], [], []
    for _ in range(reps):
        t0 = time.perf_counter()
        c = issue(*pairs[0])
        t1 = time.perf_counter()
        c.block_until_ready()
        one.append(time.perf_counter() - t0)
        issue_s.append(t1 - t0)
        t0 = time.perf_counter()
        for a, b in pairs:
            issue(a, b).block_until_ready()
        turn.append(time.perf_counter() - t0)
        t0 = time.perf_counter()
        jax.block_until_ready([issue(a, b) for a, b in pairs])
        together.append(time.perf_counter() - t0)
        t0 = time.perf_counter()
        jax.block_until_ready([jax.device_put(src[(a, b)], devs[b])
                               for a, b in pairs])
        put.append(time.perf_counter() - t0)
    total = len(pairs) * nbytes
    return {"row": "d2d", "bytes": nbytes, "chips": len(devs),
            "copies_a_round": len(pairs), "reps": reps,
            "one_copy_with_wait_ms": med_ms(one),
            "one_copy_issue_ms": med_ms(issue_s),
            "round_in_turn_ms": med_ms(turn),
            "round_together_ms": med_ms(together),
            "round_device_put_ms": med_ms(put),
            "GBps_in_turn": total / statistics.median(turn) / 1e9,
            "GBps_together": total / statistics.median(together) / 1e9}


def main() -> int:
    import argparse

    import jax

    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--mode", choices=("host", "d2d"), default="host")
    args = ap.parse_args()
    dev = jax.devices()[0]
    print(json.dumps({"row": "device", "platform": dev.platform,
                      "kind": dev.device_kind, "jax": jax.__version__,
                      "devices": len(jax.devices())}),
          flush=True)
    if args.mode == "d2d":
        devs = jax.devices()[:4]
        if len(devs) < 2:
            raise SystemExit("device_plane_probe: --mode d2d needs two chips")
        for nbytes, reps in ((16 * MiB, 30), (4 * MiB, 30)):
            print(json.dumps(d2d(devs, nbytes, reps)), flush=True)
        return 0
    h2d(dev, MiB, 2)  # warm the client
    for nbytes, reps in ((4 * MiB, 20), (64 * MiB, 5), (256 * MiB, 3)):
        print(json.dumps(h2d(dev, nbytes, reps)), flush=True)
    print(json.dumps(gil(dev, 4 * MiB, 100)), flush=True)
    print(json.dumps(gil(dev, 64 * MiB, 10)), flush=True)
    for nbytes, n in ((4 * MiB, 16), (4 * MiB, 64), (64 * MiB, 4)):
        print(json.dumps(d2h(dev, nbytes, n)), flush=True)
    return 0


if __name__ == "__main__":
    sys.path.insert(0, os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))))
    sys.exit(main())
