"""Checks of the yardstick's own arithmetic: the bytes of a decode step
against a hand count, the trace reduction against a trace recorded on the
v5e, the traffic generator's steadiness rule."""

import time
from pathlib import Path

import pytest

from benchmark.harness import decode_bytes, spec as S, stats, traffic as T

DATA = Path(__file__).parent / "data"


def test_decode_step_bytes_by_hand():
    config = S.load_config(S.load_spec(), "mistral7b")
    # One layer: wq 4096x4096 + wk, wv 4096x1024 each + wo 4096x4096
    # + three 4096x14336 = 218,103,808, + two norms of 4096 = 218,112,000.
    layer = 4096 * 4096 * 2 + 4096 * 1024 * 2 + 3 * 4096 * 14336 + 2 * 4096
    assert layer == 218_103_808 + 8_192 == 218_112_000
    # 16 layers + final norm + head 4096x32000, two bytes each: 7.24 GB.
    by_hand = (16 * layer + 4096 + 4096 * 32000) * 2
    assert decode_bytes.weight_bytes(config) == by_hand == 7_241_736_192
    # K and V of one token: 2 x 8 heads x 128 x 2 B x 16 layers = 64 KiB.
    assert decode_bytes.cache_bytes_per_token(config) == 65536
    assert decode_bytes.decode_step_bytes(config, 1000) == by_hand + 65_536_000


def test_trace_reduction_on_recorded_v5e_trace():
    """tiny_v5e.xplane.pb: six launches of one jitted program of four
    matmul+tanh fusions (about 0.19 ms each), each followed by a slice
    fetched to the host, under bench:step / dispatch / wait / sleep."""
    from benchmark.harness.trace_reduce import reduce_trace

    t0 = time.monotonic()
    r = reduce_trace(DATA / "tiny_v5e.xplane.pb")
    assert time.monotonic() - t0 < 2.0
    assert r["chips"] == 1
    mods = r["modules"]
    work = [v for k, v in mods.items() if k.startswith("jit_work(")]
    assert work == [[6.0, pytest.approx(0.001157558, rel=1e-6)]]
    # Busy is the union of the operations: the three programs' durations
    # less the launch gaps inside them.
    total = sum(sec for _n, sec in mods.values())
    assert r["busy_s"] == pytest.approx(0.001193001, rel=1e-6)
    assert 0.98 * total < r["busy_s"] <= total
    assert r["window_s"] == pytest.approx(0.157621863, rel=1e-6)
    assert r["device_ops"][0][0].startswith("convolution_tanh_fusion")
    assert sum(sec for _n, sec in r["device_ops"]) == pytest.approx(r["busy_s"], rel=0.01)
    # Nearly all idle time passes while the host waits for the fetch or sleeps.
    gaps = dict(r["idle_gaps"])
    assert gaps["wait"] + gaps["sleep"] > 0.98 * (r["window_s"] - r["busy_s"])
    # The program each bench:step launched, found without its name.
    assert r["longest_program_in"]["step"] == pytest.approx(
        [0.000182346] + [0.000195] * 5, rel=0.01)


def test_every_seed_gets_the_same_sizes_in_another_order():
    mix = S.load_traffic("chat_wire_open")
    n = mix["set_size"]
    a = T.request_lengths(mix, 1, n)
    b = T.request_lengths(mix, 2 ** 31 + 7, n)
    assert sorted(a) == sorted(b) == sorted(T.request_set(mix)) and a != b
    ta, tb = T.arrival_times(mix, 1, 30.0), T.arrival_times(mix, 99, 30.0)
    assert abs(len(ta) - len(tb)) <= 0.05 * len(ta) and ta != tb
    assert abs(len(ta) / 30.0 - mix["rate_per_s"]) < 0.5
    lo, hi = mix["prompt_tokens"]["min"], mix["prompt_tokens"]["max"]
    assert all(lo <= p <= hi for p, _o in a)
    assert max(p + o for p, o in a) < S.load_config(
        S.load_spec(), "mistral7b")["serve"]["max_len"]


def test_histogram_bound_and_percentile():
    buckets = [0] * 64
    buckets[9], buckets[12] = 60, 40     # 60 values < 512, 40 values < 4096
    assert stats.hist_percentile_bound(buckets, 50) == 511.0
    assert stats.hist_percentile_bound(buckets, 99) == 4095.0
    assert stats.hist_percentile_bound([0] * 64, 50) is None
    assert stats.percentile([1, 2, 3, 4, 5], 95) == pytest.approx(4.8)


def test_every_named_file_exists():
    spec = S.load_spec()
    for cell in spec["workloads"]:
        config = S.load_config(spec, cell["config"])
        S.load_traffic(cell["traffic"])
        S.load_runner(config["runner"])
        assert S.per_layer_for(spec, cell["name"])
        assert any(m["name"] == "setup_s" for m in S.end_to_end_for(spec, cell["name"]))
    for m in spec["per_layer"]:
        assert callable(S.load_reader(m["name"]).read)
