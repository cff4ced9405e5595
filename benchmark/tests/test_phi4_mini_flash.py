"""The ``phi4-mini-flash`` configuration's own yardstick: the file against
the catalog row's numbers (NOTHING is cut), ``BENCHMARK.json``'s entries,
the traffic of ISSUE 46, hand counts of the weights, the state, the rings
and the rows with their readers and of each kernel's bytes and operations
at the published widths, the new readers on a canned obs (every roofline
share under 100), and a rehearsal of the whole cell at a tiny size on the
CPU (the runner lays ``tests/data/rehearsal_ssm_yoco.json`` over the files
itself: ``rehearsal.json`` is the accepted benchmark's)."""

import json
import time

import pytest

from benchmark import run as R
from benchmark.harness import spec as S, traffic as T
from benchmark.harness import ssm_yoco_counts as C
from benchmark.harness.peaks import PEAKS

CELL = "phi4-mini-flash.cot_closed"
V5E = PEAKS["TPU v5 lite"]
NEW_READERS = ["kv_read_MB.cot", "state_rw_MB.cot", "admit_cross_share.cot",
               "cross_decoder_share.cot", "decode_floor_share.cot",
               "sw_decode_attn_full_roofline_share.cot",
               "sw_decode_attn_window_roofline_share.cot",
               "sw_ssm_step_roofline_share.cot",
               "sw_ssm_scan_roofline_share.cot"]


@pytest.fixture(scope="module")
def config():
    return S.load_config(S.load_spec(), "phi4-mini-flash")


def test_configuration_keeps_the_catalogs_numbers(config):
    """Every published key at its published value: no cut at all."""
    published = {
        "embd_pdrop": 0, "hidden_act": "silu", "hidden_size": 2560,
        "intermediate_size": 10240, "layer_norm_eps": 1e-05,
        "max_position_embeddings": 262144, "mb_per_layer": 2,
        "model_type": "phi4flash", "num_attention_heads": 40,
        "num_hidden_layers": 32, "num_key_value_heads": 20, "resid_pdrop": 0,
        "sliding_window": 512, "tie_word_embeddings": True, "mlp_bias": False,
        "lm_head_bias": False, "vocab_size": 200064}
    assert config["reduced"] == {}
    for key, value in published.items():
        assert config[key] == value, key
    assert (config["mamba_d_state"], config["mamba_d_conv"],
            config["mamba_expand"], config["mamba_dt_rank"]) == (16, 4, 2, 160)
    kinds = [k for period, reps in config["layout"] for _ in range(reps)
             for k in period]
    assert kinds == ["ssm", "window"] * 8 + ["ssm", "full"] + ["gmu", "cross"] * 7
    # mb_per_layer 2: a Mamba mixer (or, in the cross-decoder, the memory
    # unit that reads one) on every even layer.
    assert all((k in ("ssm", "gmu")) == (i % 2 == 0) for i, k in enumerate(kinds))
    assert {"layout", "mamba_sizes", "mamba_init", "differential_attention",
            "norm", "biases", "positions", "window", "columns"} <= set(
                config["assumed"])
    sv = config["serve"]
    assert (sv["max_len"], sv["chunk"]) == (8192, 8)
    assert sv["n_slots"] in (96, 80, 64)
    assert sv["prompt_buckets"] == [1024, 2048, 3072, 4096, 6144]
    assert config["guarantees"] == S.load_config(S.load_spec(), "kimi-k2")["guarantees"]
    assert config["correct"]["control"] == "int8"
    assert 4 <= config["correct"]["sample_requests"] <= 6


def test_benchmark_json_holds_the_configuration_and_its_one_cell():
    spec = S.load_spec()
    entry = next(c for c in spec["configs"] if c["name"] == "phi4-mini-flash")
    assert entry["reduced"] == []
    assert entry["source"] == S.load_config(spec, "phi4-mini-flash")["source"]
    cell = S.find_cell(spec, CELL)
    assert (cell["config"], cell["traffic"], cell["chips"]) == (
        "phi4-mini-flash", "cot_closed_c120", 1)
    assert [c["name"] for c in spec["workloads"]
            if c["config"] == "phi4-mini-flash"] == [CELL]
    assert {m["name"] for m in S.end_to_end_for(spec, CELL)} == {
        "tok_s", "tpot_p95_ms", "setup_s"}
    assert {m["name"] for m in S.per_layer_for(spec, CELL)} == {
        "decode_step_ms", "slot_occupancy.closed", "prefill_share.closed",
        "admit_dev_ms", *NEW_READERS}
    for m in spec["per_layer"]:
        if m["name"] in NEW_READERS:
            assert m["workloads"] == [CELL], m["name"]
    assert len(cell["why"]) <= 200 and len(entry["why"]) <= 200


def test_the_traffic_is_issue_46s(config):
    traffic = S.load_traffic("cot_closed_c120")
    sv = config["serve"]
    assert (traffic["set_size"], traffic["pairing_seed"], traffic["driver"],
            traffic["loop"]) == (32, 7, "inproc", "closed")
    assert traffic["clients"] == {96: 120, 80: 100, 64: 80}[sv["n_slots"]]
    assert traffic["prompt_tokens"] == {"dist": "lognormal", "median": 2048,
                                        "sigma": 0.5, "min": 512, "max": 6144}
    assert traffic["output_tokens"] == {"dist": "lognormal", "median": 768,
                                        "sigma": 0.6, "min": 192, "max": 2048}
    pairs = T.request_set(traffic)
    assert len(pairs) == 32
    assert max(p + o for p, o in pairs) <= sv["max_len"]
    assert (traffic["prompt_tokens"]["max"] + traffic["output_tokens"]["max"]
            == sv["max_len"])
    assert max(p for p, _o in pairs) <= max(sv["prompt_buckets"])


def test_hand_count_of_the_weights_the_state_and_the_rows(config):
    """ISSUE 46's arithmetic, by hand."""
    mlp = 2560 * 20480 + 10240 * 2560
    assert mlp == 78_643_200 and C.block_params(config) == mlp + 4 * 2560
    lam = 4 * 64 + 128
    ssm = (2560 * 10240 + 4 * 5120 + 5120 + 5120 * 192 + 160 * 5120 + 5120
           + 16 * 5120 + 5120 + 5120 * 2560)
    assert C.mixer_params(config, "ssm") == ssm == 41_241_600
    attn = 2560 * 5120 + 5120 + 2560 * 2560 + 2560 + lam
    assert C.mixer_params(config, "window") == attn == C.mixer_params(config, "full")
    assert C.mixer_params(config, "gmu") == 2 * 2560 * 5120 == 26_214_400
    cross = 2560 * 2560 + 2560 + 2560 * 2560 + 2560 + lam
    assert C.mixer_params(config, "cross") == cross
    assert C.layer_counts(config) == {"ssm": 9, "window": 8, "full": 1,
                                      "gmu": 7, "cross": 7}
    params = (9 * ssm + 9 * attn + 7 * 26_214_400 + 7 * cross
              + 32 * (mlp + 4 * 2560) + 2 * 2560 + 200064 * 2560)
    assert C.weight_bytes(config) == params * 2
    assert abs(params / 1e9 - 3.853) < 0.002          # 3,853M: 7.71 GB
    assert C.state_bytes(config) == 16 * 5120 * 4 == 327_680
    assert C.conv_tail_bytes(config) == 3 * 5120 * 2 == 30_720
    assert C.kv_row_bytes(config) == 2 * 20 * 64 * 2 == 5120
    # A slot at max_len 8,192: 66.1 MB.
    slot = (8192 * 5120 + 8 * 512 * 5120 + 9 * (327_680 + 30_720))
    assert abs(slot / 1e6 - 66.14) < 0.01
    assert C.state_rw_bytes(config, 96) == 9 * 96 * 2 * (327_680 + 30_720)
    # 96 slots around position 2,750: ONE layer's rows, read eight times,
    # are the largest part of a step's bytes and the state the smallest.
    rows = 96 * 2750
    full = C.full_read_bytes(config, rows, 8)
    assert full == 8 * rows * 5120 and abs(full / 1e9 - 10.81) < 0.01
    rings = C.ring_read_bytes(config, 96 * 512)
    assert rings == 8 * 96 * 512 * 5120 and abs(rings / 1e9 - 2.01) < 0.01
    assert C.kv_bytes(config, rows, 8, 96 * 512) == full + rings
    floor = C.step_floor_s(config, V5E, 96, 96, rows, 8, 96 * 512)
    byts = C.weight_bytes(config) + C.state_rw_bytes(config, 96) + full + rings
    assert abs(floor - byts / 819e9) < 1e-12                     # bytes-bound
    assert 0.024 < floor < 0.027                                 # about 25.9 ms
    assert full > C.weight_bytes(config) > rings > C.state_rw_bytes(config, 96)
    assert C.step_flops(config, 96, 96, rows, 8, 96 * 512) / 197e12 < floor / 4


def test_hand_count_of_the_kernels(config):
    assert C.ssm_step_flops(config, 96) == 7 * 96 * 5120 * 16
    assert C.ssm_step_bytes(config, 96) == 96 * (2 * 327_680 + (3 * 5120 + 32) * 4)
    assert C.roofline_s(C.ssm_step_flops(config, 96),
                        C.ssm_step_bytes(config, 96), V5E) == (
        C.ssm_step_bytes(config, 96) / 819e9)
    assert C.ssm_scan_flops(config, 2048) == 7 * 2048 * 5120 * 16
    assert C.ssm_scan_bytes(config, 2048) == 2048 * (3 * 5120 + 32) * 4 + 327_680
    rows = 96 * 2750
    assert C.attn_flops(config, rows) == 4 * rows * 2560
    assert C.attn_bytes(config, 96, rows) == rows * 5120 + 2 * 96 * 5120 * 2


def _canned_obs(config, monkeypatch):
    """A traced run as the readers see it: 20 chunks at the cell's size, a
    decode step of 40 ms of which the state kernel is 0.12 ms a layer, the
    decode attention over the rows 2.2 ms a reader and over a ring 0.4."""
    slots = config["serve"]["n_slots"]
    rows = [{"t0": 1.0 + i, "state_slots": slots, "kv_rows_full": slots * 2750,
             "kv_rows_window": slots * 512, "kv_full_readers": 8,
             "live": slots, "n_slots": slots, "admit_s": 0.3,
             **({"admit_rows_self": 2048, "admit_rows_cross": 1} if i % 2 else {})}
            for i in range(20)]
    monkeypatch.setattr(C, "window_steps", lambda obs: rows)
    steps = 20 * 8
    return {"config": config, "window": (0.0, 45.0),
            "device": {"kind": "TPU v5 lite"},
            "trace": {"longest_program_in": {"chunk": [0.33] * 20},
                      "modules": {"jit_serve_decode_chunk": [20, 20 * 0.32],
                                  "jit_serve_admit_2048": [10, 3.0]}},
            "ops_by_scope": {"sw_cross_decoder": 3.0, "": 3.4},
            "ops_by_name": {"ops": {
                "jit_serve_decode_chunk": {
                    "sw_ssm_step.3": [9 * steps, 9 * steps * 0.12e-3],
                    "sw_decode_attn_stream.4": [steps, steps * 2.2e-3],
                    "sw_decode_attn_stream.5": [7 * steps, 7 * steps * 2.2e-3],
                    "sw_decode_attn_ring.2": [8 * steps, 8 * steps * 0.4e-3]},
                "jit_serve_admit_2048": {
                    "sw_ssm_scan.2": [9 * 10, 9 * 10 * 2.0e-3],
                    "sw_decode_attn_stream.9": [80.0, 0.01]},
                "jit_serve_admit_6144": {
                    "sw_ssm_scan.2": [9 * 2, 9 * 2 * 6.0e-3]}}, "chips": 1}}


def test_the_new_readers_on_a_canned_obs(config, monkeypatch):
    obs = _canned_obs(config, monkeypatch)
    got = {name: S.load_reader(name).read(obs) for name in NEW_READERS}
    assert all(v is not None for v in got.values()), got
    for name, value in got.items():
        if "share" in name:
            assert 0 < value < 100, (name, value)
    slots = config["serve"]["n_slots"]
    state = 9 * slots * 2 * (327_680 + 30_720)
    assert abs(got["state_rw_MB.cot"] - state / 1e6) < 1e-6
    rows = slots * 2750 + slots * 3.5          # half a chunk a slot further
    kv = 8 * rows * 5120 + 8 * slots * 512 * 5120
    assert abs(got["kv_read_MB.cot"] - kv / 1e6) < 1e-6
    assert abs(got["admit_cross_share.cot"] - 10 / 20480 * 100) < 1e-9
    assert abs(got["cross_decoder_share.cot"] - 3.0 / 6.4 * 100) < 1e-9
    step = slots * (2 * 327_680 + (3 * 5120 + 32) * 4) / 819e9
    assert abs(got["sw_ssm_step_roofline_share.cot"] - step / 0.12e-3 * 100) < 1e-6
    # the admit programs' calls of the decode kernel are not the step's
    attn = (rows * 5120 + 2 * slots * 5120 * 2) / 819e9
    assert abs(got["sw_decode_attn_full_roofline_share.cot"]
               - attn / 2.2e-3 * 100) < 1e-6
    ring = (slots * 512 * 5120 + 2 * slots * 5120 * 2) / 819e9
    assert abs(got["sw_decode_attn_window_roofline_share.cot"]
               - ring / 0.4e-3 * 100) < 1e-6
    floor = (C.weight_bytes(config) + state + kv) / 819e9
    assert abs(got["decode_floor_share.cot"] - floor / 0.04 * 100) < 1e-6
    # each admit program's calls at its own bucket's count
    scan = (9 * 10 * C.ssm_scan_bytes(config, 2048)
            + 9 * 2 * C.ssm_scan_bytes(config, 6144)) / 819e9
    assert abs(got["sw_ssm_scan_roofline_share.cot"]
               - scan / (9 * 10 * 2.0e-3 + 9 * 2 * 6.0e-3) * 100) < 1e-6


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
    ctx = _rehearsal(1, 2**31 + 11)
    out = S.load_runner(ctx["config"]["runner"]).run(ctx)
    assert ctx["config"]["hidden_size"] == 64            # the runner shrank it
    assert out["attempted"] > 0 and out["failed"] == 0 and out["correct"]
    line = R.result_line(ctx, out)
    # No device trace on the CPU: the counters' metrics are read all the same.
    assert line["metrics"]["state_rw_MB.cot"]["value"] > 0
    assert line["metrics"]["kv_read_MB.cot"]["value"] > 0
    assert 0 < line["metrics"]["admit_cross_share.cot"]["value"] < 25
    assert 0 < line["metrics"]["slot_occupancy.closed"]["value"] <= 100
    assert "sw_ssm_step_roofline_share.cot" not in line["metrics"]
    assert "cross_decoder_share.cot" not in line["metrics"]
    means = C.step_means(out["obs"])
    assert 0 < means["slots"] <= 4 and means["readers"] == 3
    assert means["rows_full"] > means["rows_window"] > 0


def test_the_int8_control_is_not_correct_by_the_harness_own_decision():
    """Through ``serve.decide_correct``, its ``compared`` and a file's
    limits: the served tokens come out ``correct``, the int8 control at the
    same positions does not, by ``gap_mean``.  The limits here are the
    rehearsal's own (a float32 program on the CPU reads 0); the same
    decision with the CELL's limits at the cell's size is
    ``calibrate_mla_moe.py --workload phi4-mini-flash.cot_closed --flips
    0``'s, read on the chip (PERF.md section 2).  A bfloat16 state is no
    linear layer's rounding and reads on its own, for information."""
    ctx = _rehearsal(0, 78)
    runner = S.load_runner(ctx["config"]["runner"])
    with open(S.BENCH / "tests" / "data" / "rehearsal_ssm_yoco.json") as f:
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
    ref = S.load_reference("phi4-mini-flash")
    sizes = (96, max(o for _p, o in T.request_set(ctx["traffic"])))
    low = ref.control_gaps(ctx["config"], 78, w["sample"], *sizes, "bf16_state")
    assert low["finite"] and 0 <= low["gap_mean"] < by["gap_mean"]["value"]
