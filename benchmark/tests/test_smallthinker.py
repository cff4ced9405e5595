"""The ``smallthinker-21b`` configuration's own yardstick: the file against
the catalog row's keys (depth is the ONE cut), ``BENCHMARK.json``'s entries,
hand counts of a decode step's bytes and operations at the published
widths, the new readers on a canned obs (every roofline share under 100),
and a rehearsal of the whole cell at a tiny size on the CPU (the runner
lays ``tests/data/rehearsal_window_moe.json`` over the files itself:
``rehearsal.json`` is the accepted benchmark's)."""

import time

import pytest

from benchmark import run as R
from benchmark.harness import spec as S, traffic as T
from benchmark.harness import window_moe_counts as C
from benchmark.harness.peaks import PEAKS

CELL = "smallthinker-21b.longdoc_closed"
V5E = PEAKS["TPU v5 lite"]
NEW_READERS = ["decode_floor_share.longdoc", "sw_moe_gmm_roofline_share.longdoc",
               "sw_decode_attn_full_roofline_share.longdoc",
               "sw_decode_attn_window_roofline_share.longdoc",
               "kv_read_MB.longdoc"]


@pytest.fixture(scope="module")
def config():
    return S.load_config(S.load_spec(), "smallthinker-21b")


def test_configuration_keeps_the_catalogs_numbers(config):
    """Every published key at its published value, but the depth."""
    published = {
        "head_dim": 128, "hidden_size": 2560, "max_position_embeddings": 16384,
        "model_name": "smallthinker_21b_instruct", "moe_ffn_hidden_size": 768,
        "moe_num_active_primary_experts": 6, "moe_num_primary_experts": 64,
        "moe_primary_router_apply_softmax": True, "norm_topk_prob": True,
        "num_attention_heads": 28, "num_hidden_layers": 52,
        "num_key_value_heads": 4, "rms_norm_eps": 1e-06,
        "rope_layout": [0, 1, 1, 1] * 13, "rope_scaling": None,
        "rope_theta": 1500000, "sliding_window_layout": [0, 1, 1, 1] * 13,
        "sliding_window_size": 4096, "tie_word_embeddings": False,
        "vocab_size": 151936}
    assert set(config["reduced"]) == {"num_hidden_layers"}
    for key, value in published.items():
        assert config[key] == (8 if key == "num_hidden_layers" else value), key
    # What the accepted readers read under kimi-k2's names says the same.
    assert config["n_routed_experts"] == config["moe_num_primary_experts"]
    assert config["first_k_dense_replace"] == 0
    sv = config["serve"]
    assert (sv["n_slots"], sv["max_len"], sv["chunk"]) == (48, 16384, 8)
    assert max(sv["prompt_buckets"]) == 14336 and all(
        b % 128 == 0 for b in sv["prompt_buckets"])


def test_benchmark_json_holds_the_configuration_and_its_one_cell():
    spec = S.load_spec()
    entry = next(c for c in spec["configs"] if c["name"] == "smallthinker-21b")
    assert entry["reduced"] == ["num_hidden_layers"]
    assert entry["source"] == S.load_config(spec, "smallthinker-21b")["source"]
    cell = S.find_cell(spec, CELL)
    assert (cell["config"], cell["traffic"], cell["chips"]) == (
        "smallthinker-21b", "longdoc_closed_c72", 1)
    assert len(spec["workloads"]) == 6
    assert sum(c["chips"] == 4 for c in spec["workloads"]) == 1
    assert {m["name"] for m in S.end_to_end_for(spec, CELL)} == {
        "tok_s", "tpot_p95_ms", "setup_s"}
    names = {m["name"] for m in S.per_layer_for(spec, CELL)}
    assert names == set(NEW_READERS) | {
        "decode_step_ms", "admit_dev_ms", "slot_occupancy.closed",
        "prefill_share.closed", "experts_touched.agent",
        "expert_load_max_over_mean.agent"}
    for m in spec["per_layer"]:
        if m["name"] in NEW_READERS:
            assert m["workloads"] == [CELL] and m["moves"] == "tpot_p95_ms"


def test_the_traffic_is_issue_30s(config):
    traffic = S.load_traffic("longdoc_closed_c72")
    assert (traffic["clients"], traffic["set_size"], traffic["pairing_seed"],
            traffic["driver"], traffic["loop"]) == (72, 16, 7, "inproc", "closed")
    pairs = T.request_set(traffic)
    prompts = sorted(p for p, _o in pairs)
    assert prompts[0] == 2009 and prompts[-2:] == [13549, 14336]
    assert max(p + o for p, o in pairs) <= config["serve"]["max_len"]
    assert (traffic["prompt_tokens"]["max"] + traffic["output_tokens"]["max"]
            == config["serve"]["max_len"])
    # About three quarters of the prompts alone lie past one window.
    assert sum(p > 4096 for p in prompts) == 12


def test_hand_count_of_the_weights_and_the_cache(config):
    """ISSUE 30's arithmetic, by hand."""
    attn = 2560 * 3584 + 2 * 2560 * 512 + 3584 * 2560 + 2 * 2560
    assert C.attention_params(config) == attn == 20_976_640
    assert C.expert_params(config) == 3 * 2560 * 768 == 5_898_240
    assert C.layer_counts(config) == (2, 6)
    assert C.kv_row_bytes(config) == 2048
    # A step that touches all 64 experts of each layer.
    want = 2 * (8 * (attn + 2560 * 64 + 64 * 5_898_240) + 2560 + 2560 * 151936)
    assert C.weight_bytes(config, 64) == want
    assert abs(want / 1e9 - 7.157) < 0.002       # 8.3 ms at 819 GB/s... less the embedding
    # 48 slots at position 7,000: the rings bound the window layers' reads.
    full, ring = 48 * 7001, 48 * 4096
    assert C.kv_bytes(config, full, ring) == (2 * full + 6 * ring) * 2048
    assert abs(C.kv_bytes(config, full, ring) / 1e9 - 3.79) < 0.01
    assert abs(C.kv_bytes(config, full, full) / 1e9 - 5.51) < 0.01   # unbounded
    floor = C.step_floor_s(config, V5E, 48, full, ring, 64, 288)
    assert abs(floor * 1e3 - (7.157 + 3.79) / 0.819) < 0.05          # bytes-bound
    assert C.step_flops(config, 48, full, ring, 288) / 197e12 < floor / 10


def test_hand_count_of_the_kernels(config):
    rows = 48 * 7001
    assert C.attn_flops(config, rows) == 4 * rows * 28 * 128
    assert C.attn_bytes(config, 48, rows) == rows * 2048 + 2 * 48 * 3584 * 2
    # 7 query heads share a kv head: 7 operations a byte, far below the ridge.
    assert C.roofline_s(C.attn_flops(config, rows),
                        C.attn_bytes(config, 48, rows), V5E) == (
        C.attn_bytes(config, 48, rows) / 819e9)
    assert C.moe_layer_flops(config, 288) == 2 * 288 * 5_898_240
    assert C.moe_layer_bytes(config, 64, 288) == (
        64 * 5_898_240 * 2 + 288 * (2 * 2560 + 2 * 768) * 2)


def _canned_obs(config, monkeypatch):
    """A traced run as the readers see it: 20 chunks at the cell's size,
    a decode step of 18 ms of which the attention kernels are 1.1 and 1.4
    ms a layer-kind and the grouped matmuls 7.5 ms."""
    rows = [{"t0": 1.0 + i, "kv_rows_full": 48 * 7001, "kv_rows_window":
             48 * 3900, "moe_assign": 288 * 8 * 8, "moe_touched": 63.4,
             "moe_max": 13, "live": 48, "n_slots": 48, "admit_s": 0.1}
            for i in range(20)]
    monkeypatch.setattr(C, "window_steps", lambda obs: rows)
    steps = 20 * 8
    return {"config": config, "window": (0.0, 45.0),
            "device": {"kind": "TPU v5 lite"},
            "trace": {"longest_program_in": {"chunk": [0.144] * 20}},
            "ops_by_name": {"ops": {
                "jit_serve_decode_chunk": {
                    "sw_decode_attn_stream.6": [2 * steps, 2 * steps * 1.1e-3],
                    "sw_decode_attn_ring.7": [6 * steps, 6 * steps * 0.55e-3],
                    "sw_moe_gmm.4": [8 * steps, 8 * steps * 0.62e-3],
                    "sw_moe_gmm.5": [8 * steps, 8 * steps * 0.32e-3]},
                "jit_serve_admit_8192": {
                    "sw_moe_gmm.9": [8.0, 0.5]}}, "chips": 1}}


def test_the_new_readers_on_a_canned_obs(config, monkeypatch):
    obs = _canned_obs(config, monkeypatch)
    got = {name: S.load_reader(name).read(obs) for name in NEW_READERS}
    assert all(v is not None for v in got.values()), got
    for name, value in got.items():
        if "share" in name:
            assert 0 < value < 100, (name, value)
    kv = (2 * 48 * 7001 + 6 * 48 * 3900) * 2048
    assert abs(got["kv_read_MB.longdoc"] - kv / 1e6) < 1e-6
    floor = (C.weight_bytes(config, 63.4) + kv) / 819e9
    assert abs(got["decode_floor_share.longdoc"] - floor / 0.018 * 100) < 1e-6
    # The admit programs' calls of the same kernel are not the decode step's.
    moe = (63.4 * 5_898_240 * 2 + 288 * (2 * 2560 + 2 * 768) * 2) / 819e9
    assert abs(got["sw_moe_gmm_roofline_share.longdoc"]
               - moe / 0.94e-3 * 100) < 1e-6
    full = (48 * 7001 * 2048 + 2 * 48 * 3584 * 2) / 819e9
    assert abs(got["sw_decode_attn_full_roofline_share.longdoc"]
               - full / 1.1e-3 * 100) < 1e-6


def test_readers_return_nothing_where_there_is_nothing_to_read():
    """On a program without the counters or the kernels (the parent)."""
    obs = {"config": {}, "window": (0.0, 1.0), "trace": None, "spans": None,
           "device": {"kind": "TPU v5 lite"}}
    for name in NEW_READERS:
        assert S.load_reader(name).read(obs) is None, name


def _rehearsal(trace: int, seed: int):
    args = R.parse(["--workload", CELL, "--seed", str(seed), "--seconds",
                    "4", "--trace", str(trace), "--no-chip"])
    ctx = R.context(args)
    ctx["t_start"] = time.monotonic()
    return ctx


def test_the_cell_rehearsed_small_on_the_cpu():
    ctx = _rehearsal(1, 2**31 + 7)
    out = S.load_runner(ctx["config"]["runner"]).run(ctx)
    assert ctx["config"]["sliding_window_size"] == 8    # the runner shrank it
    assert out["attempted"] > 0 and out["failed"] == 0 and out["correct"]
    line = R.result_line(ctx, out)
    # No device trace on the CPU: the counters' metrics are read all the same.
    assert 0 < line["metrics"]["experts_touched.agent"]["value"] <= 8
    assert line["metrics"]["expert_load_max_over_mean.agent"]["value"] >= 1
    assert line["metrics"]["kv_read_MB.longdoc"]["value"] > 0
    assert 0 < line["metrics"]["slot_occupancy.closed"]["value"] <= 100
    assert "sw_moe_gmm_roofline_share.longdoc" not in line["metrics"]
    means = C.step_means(out["obs"])
    # Rings of 8 bound the window layers' reads below the full layers'.
    assert means["rows_window"] < means["rows_full"]
    assert means["rows_window"] <= 4 * 8


def test_the_int8_control_is_not_correct_by_the_harness_own_decision():
    """Through ``serve.decide_correct``, its ``compared`` and a file's
    limits: the served tokens come out ``correct``, the int8 control at
    the same positions does not, by ``gap_mean``.  The limits here are the
    rehearsal's own (a float32 program on the CPU reads 0); the same
    decision with the CELL's limits at the cell's size is
    ``calibrate_mla_moe.py --workload smallthinker-21b.longdoc_closed``'s,
    read on the chip (PERF.md section 2)."""
    ctx = _rehearsal(0, 77)
    runner = S.load_runner(ctx["config"]["runner"])
    import json

    with open(S.BENCH / "tests" / "data" / "rehearsal_window_moe.json") as f:
        small = json.load(f)
    ctx["config"].update(small["config"])
    ctx["config"]["correct"] = dict(ctx["config"]["correct"], sample_requests=6)
    ctx["traffic"].update(small["traffic"])
    serve = runner.serve                                  # with its build_server
    w = serve.inproc_window(ctx)
    sound = serve.decide_correct(ctx, w["sample"], w["faults"], len(w["rows"]))
    assert sound["correct"], sound
    ctx["config"]["correct"]["decide_control"] = True
    control = serve.decide_correct(ctx, w["sample"], w["faults"], len(w["rows"]))
    assert not control["correct"], control
    by = {c["what"]: c for c in control["compared"]}
    assert by["gap_mean"]["value"] > by["gap_mean"]["limit"]
    ref = S.load_reference("smallthinker-21b")
    sizes = (96, max(o for _p, o in T.request_set(ctx["traffic"])))
    flips = ref.router_flips(ctx["config"], 77, w["sample"], *sizes)
    assert 0.0 <= flips["share"] < 0.5 and flips["layers"] == 8
