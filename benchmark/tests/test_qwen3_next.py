"""The ``qwen3-next`` configuration's own yardstick: the file against the
catalog row's numbers (depth, experts held and vocabulary are the cuts),
``BENCHMARK.json``'s entries, the traffic of ISSUE 40, hand counts of the
weights, the state, the rows and of each kernel's bytes and operations at
the published widths, the new readers on a canned obs (every roofline
share under 100), and a rehearsal of the whole cell at a tiny size on the
CPU (the runner lays ``tests/data/rehearsal_gdn_gqa_moe.json`` over the
files itself: ``rehearsal.json`` is the accepted benchmark's)."""

import json
import time

import pytest

from benchmark import run as R
from benchmark.harness import gdn_gqa_moe_counts as C
from benchmark.harness import spec as S, traffic as T
from benchmark.harness.peaks import PEAKS

CELL = "qwen3-next.answer_closed"
V5E = PEAKS["TPU v5 lite"]
NEW_READERS = ["decode_floor_share.answer", "sw_moe_gmm_roofline_share.answer",
               "sw_decode_attn_full_roofline_share.answer",
               "sw_kda_step_roofline_share.answer",
               "sw_kda_chunk_roofline_share.answer", "state_rw_MB.answer",
               "kv_read_MB.answer"]


@pytest.fixture(scope="module")
def config():
    return S.load_config(S.load_spec(), "qwen3-next")


def test_configuration_keeps_the_catalogs_numbers(config):
    """Every published key at its published value, but the three cuts."""
    published = {
        "decoder_sparse_step": 1, "full_attention_interval": 4,
        "head_dim": 256, "hidden_act": "silu", "hidden_size": 2048,
        "intermediate_size": 5120, "linear_conv_kernel_dim": 4,
        "linear_key_head_dim": 128, "linear_num_key_heads": 16,
        "linear_num_value_heads": 32, "linear_value_head_dim": 128,
        "max_position_embeddings": 262144, "mlp_only_layers": [],
        "model_type": "qwen3_next", "moe_intermediate_size": 512,
        "norm_topk_prob": True, "num_attention_heads": 16, "num_experts": 512,
        "num_experts_per_tok": 10, "num_hidden_layers": 48,
        "num_key_value_heads": 2, "partial_rotary_factor": 0.25,
        "rms_norm_eps": 1e-06, "rope_scaling": None, "rope_theta": 10000000,
        "shared_expert_intermediate_size": 512, "tie_word_embeddings": False,
        "use_sliding_window": False, "vocab_size": 151936}
    cut = {"num_hidden_layers": 8, "num_experts": 128, "vocab_size": 37984}
    assert set(config["reduced"]) == set(cut)
    for key, value in published.items():
        assert config[key] == cut.get(key, value), key
    assert (config["num_experts_published"], config["chips_per_layer"],
            config["expert_share"]) == (512, 4, 0)
    assert config["vocab_size"] * 4 == published["vocab_size"]
    # What the accepted readers read under kimi-k2's names says the same.
    assert config["n_routed_experts"] == config["num_experts"]
    assert config["first_k_dense_replace"] == 0
    assert {"norms", "gdn_gate_init", "gdn_conv", "gdn_l2_eps", "layout",
            "mtp"} <= set(config["assumed"])
    sv = config["serve"]
    assert (sv["max_len"], sv["chunk"]) == (4096, 8)
    assert sv["n_slots"] in (192, 160, 128)
    assert min(sv["prompt_buckets"]) <= 128 and max(sv["prompt_buckets"]) == 2048
    assert config["guarantees"] == S.load_config(S.load_spec(), "kimi-k2")["guarantees"]
    assert config["correct"]["control"] == "int8"
    assert 6 <= config["correct"]["sample_requests"] <= 10


def test_benchmark_json_holds_the_configuration_and_its_one_cell():
    spec = S.load_spec()
    entry = next(c for c in spec["configs"] if c["name"] == "qwen3-next")
    assert entry["reduced"] == ["num_hidden_layers", "num_experts", "vocab_size"]
    assert entry["source"] == S.load_config(spec, "qwen3-next")["source"]
    assert spec["configs"][-1] is entry and spec["workloads"][-1]["name"] == CELL
    cell = S.find_cell(spec, CELL)
    assert (cell["config"], cell["traffic"], cell["chips"]) == (
        "qwen3-next", "answer_closed_c240", 1)
    assert [c["name"] for c in spec["workloads"] if c["config"] == "qwen3-next"] == [CELL]
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
    for entry in spec["configs"] + spec["workloads"]:
        assert len(entry["why"]) <= 200


def test_the_traffic_is_issue_40s(config):
    traffic = S.load_traffic("answer_closed_c240")
    sv = config["serve"]
    assert (traffic["set_size"], traffic["pairing_seed"], traffic["driver"],
            traffic["loop"]) == (32, 7, "inproc", "closed")
    assert traffic["clients"] == {192: 240, 160: 200, 128: 160}[sv["n_slots"]]
    assert traffic["prompt_tokens"] == {"dist": "lognormal", "median": 512,
                                        "sigma": 0.8, "min": 64, "max": 2048}
    assert traffic["output_tokens"] == {"dist": "lognormal", "median": 768,
                                        "sigma": 0.6, "min": 192, "max": 2048}
    pairs = T.request_set(traffic)
    assert len(pairs) == 32
    assert max(p + o for p, o in pairs) <= sv["max_len"]
    assert (traffic["prompt_tokens"]["max"] + traffic["output_tokens"]["max"]
            == sv["max_len"])
    # Short in, long out: the median output is past the median prompt.
    prompts, outs = sorted(p for p, _o in pairs), sorted(o for _p, o in pairs)
    assert prompts[16] < outs[16] and max(prompts) <= max(sv["prompt_buckets"])


def test_hand_count_of_the_weights_the_state_and_the_rows(config):
    """ISSUE 40's arithmetic, by hand."""
    gdn = (2048 * 12288 + 4096 * 2048 + 2048 * 64 + 4 * 8192 + 64 + 128
           + 2 * 2048)
    assert C.gdn_params(config) == gdn == 33_722_560
    attn = 2048 * 8192 + 2 * 2048 * 512 + 4096 * 2048 + 2 * 256 + 2 * 2048
    assert C.attention_params(config) == attn == 27_267_584
    assert C.expert_params(config) == 3 * 2048 * 512 == 3_145_728
    assert C.layer_counts(config) == (6, 2)
    assert C.state_bytes(config) == 32 * 128 * 128 * 4 == 2_097_152
    assert C.conv_tail_bytes(config) == 3 * 8192 * 2 == 49_152
    assert C.kv_row_bytes(config) == 2 * 2 * 256 * 2 == 2048
    # 192 slots: the matrices alone 4.83 GB a step, read and written.
    assert 6 * 192 * 2 * 2_097_152 == 4_831_838_208
    assert C.state_rw_bytes(config, 192) == 6 * 192 * 2 * (2_097_152 + 49_152)
    # 192 slots at position 1,000 in both attention layers: 0.79 GB.
    rows = 192 * 1001
    assert C.kv_bytes(config, rows) == 2 * rows * 2048
    # A step that touches all 128 held experts of each layer: the weights
    # 7.18 GB (the issue's 7.33 less the embedding table, which a step
    # gathers rows of).
    ffn = 2048 * 512 + 3 * 2048 * 512 + 2048 + 128 * 3_145_728
    want = (6 * gdn + 2 * attn + 8 * ffn + 2048 + 2048 * 37984) * 2
    assert C.weight_bytes(config, 128) == want
    assert abs(want / 1e9 - 7.179) < 0.001
    floor = C.step_floor_s(config, V5E, 192, 192, rows, 128, 192 * 10 * 128 / 512)
    byts = want + C.state_rw_bytes(config, 192) + C.kv_bytes(config, rows)
    assert abs(floor - byts / 819e9) < 1e-12                     # bytes-bound
    assert 0.014 < floor < 0.017                                 # about 15.8 ms
    # State and touched experts: nine tenths of a step's bytes.
    experts = 8 * 128 * 3_145_728 * 2
    assert 0.85 < (C.state_rw_bytes(config, 192) + experts) / byts < 0.93
    assert C.step_flops(config, 192, 192, rows, 480) / 197e12 < floor / 10


def test_hand_count_of_the_kernels(config):
    assert C.gdn_step_flops(config, 192) == 7 * 192 * 32 * 128 * 128
    assert C.gdn_step_bytes(config, 192) == 192 * (2 * 2_097_152 + 6 * 32 * 128 * 4)
    # 7 operations on 8 bytes of state: bytes-bound by three orders.
    assert C.roofline_s(C.gdn_step_flops(config, 192),
                        C.gdn_step_bytes(config, 192), V5E) == (
        C.gdn_step_bytes(config, 192) / 819e9)
    # A 512-token bucket: 8 chunks a value head.
    chunks = 8 * 32
    assert C.gdn_chunk_flops(config, 512) == chunks * 2 * 64 * 128 * (3 * 128 + 64)
    assert C.gdn_chunk_bytes(config, 512) == (
        chunks * (5 * 64 * 128 + 64 * 64 + 128) + 32 * 128 * 128) * 4
    assert C.gdn_chunk_flops(config, 500) == C.gdn_chunk_flops(config, 512)
    rows = 192 * 1001
    assert C.attn_flops(config, rows) == 4 * rows * 16 * 256
    assert C.attn_bytes(config, 192, rows) == rows * 2048 + 2 * 192 * 4096 * 2
    assert C.moe_layer_bytes(config, 120, 480) == (
        120 * 3_145_728 * 2 + 480 * (2 * 2048 + 2 * 512) * 2)


def _canned_obs(config, monkeypatch):
    """A traced run as the readers see it: 20 chunks at the cell's size, a
    decode step of 28 ms of which the state kernel is 1.4 ms a layer, the
    decode attention 1.2 and the grouped matmuls 1.1."""
    slots = config["serve"]["n_slots"]
    rows = [{"t0": 1.0 + i, "state_slots": slots, "kv_rows_full": slots * 1001,
             "moe_assign": 480 * 8 * 8, "moe_touched": 118.0, "moe_max": 12,
             "live": slots, "n_slots": slots, "admit_s": 0.02}
            for i in range(20)]
    monkeypatch.setattr(C, "window_steps", lambda obs: rows)
    steps = 20 * 8
    return {"config": config, "window": (0.0, 45.0),
            "device": {"kind": "TPU v5 lite"},
            "trace": {"longest_program_in": {"chunk": [0.25] * 20},
                      "modules": {"jit_serve_decode_chunk": [20, 20 * 0.224],
                                  "jit_serve_admit_512": [30, 0.4]}},
            "ops_by_name": {"ops": {
                "jit_serve_decode_chunk": {
                    "sw_kda_step.3": [6 * steps, 6 * steps * 1.4e-3],
                    "sw_decode_attn_stream.4": [2 * steps, 2 * steps * 1.2e-3],
                    "sw_moe_gmm.4": [8 * steps, 8 * steps * 0.7e-3],
                    "sw_moe_gmm.5": [8 * steps, 8 * steps * 0.4e-3]},
                "jit_serve_admit_512": {
                    "sw_kda_chunk.2": [6 * 30, 6 * 30 * 0.4e-3],
                    "sw_moe_gmm.9": [8.0, 0.5]},
                "jit_serve_admit_2048": {
                    "sw_kda_chunk.2": [6 * 4, 6 * 4 * 1.5e-3]}}, "chips": 1}}


def test_the_new_readers_on_a_canned_obs(config, monkeypatch):
    obs = _canned_obs(config, monkeypatch)
    got = {name: S.load_reader(name).read(obs) for name in NEW_READERS}
    assert all(v is not None for v in got.values()), got
    for name, value in got.items():
        if "share" in name:
            assert 0 < value < 100, (name, value)
    slots = config["serve"]["n_slots"]
    state = 6 * slots * 2 * (2_097_152 + 49_152)
    assert abs(got["state_rw_MB.answer"] - state / 1e6) < 1e-6
    rows = slots * 1001 + slots * 3.5          # half a chunk a slot further
    assert abs(got["kv_read_MB.answer"] - 2 * rows * 2048 / 1e6) < 1e-6
    step = slots * (2 * 2_097_152 + 6 * 32 * 128 * 4) / 819e9
    assert abs(got["sw_kda_step_roofline_share.answer"] - step / 1.4e-3 * 100) < 1e-6
    attn = (rows * 2048 + 2 * slots * 4096 * 2) / 819e9
    assert abs(got["sw_decode_attn_full_roofline_share.answer"]
               - attn / 1.2e-3 * 100) < 1e-6
    floor = (C.weight_bytes(config, 118.0) + state + 2 * rows * 2048) / 819e9
    assert abs(got["decode_floor_share.answer"] - floor / 0.028 * 100) < 1e-6
    # The admit programs' calls of the grouped matmul are not the decode step's.
    moe = (118.0 * 3_145_728 * 2 + 480 * (2 * 2048 + 2 * 512) * 2) / 819e9
    assert abs(got["sw_moe_gmm_roofline_share.answer"] - moe / 1.1e-3 * 100) < 1e-6
    # Each admit program's calls at its own bucket's count.
    chunk = (6 * 30 * C.gdn_chunk_bytes(config, 512)
             + 6 * 4 * C.gdn_chunk_bytes(config, 2048)) / 819e9
    assert abs(got["sw_kda_chunk_roofline_share.answer"]
               - chunk / (6 * 30 * 0.4e-3 + 6 * 4 * 1.5e-3) * 100) < 1e-6


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
    assert 0 < line["metrics"]["experts_touched.agent"]["value"] <= 8
    assert line["metrics"]["expert_load_max_over_mean.agent"]["value"] >= 1
    assert line["metrics"]["state_rw_MB.answer"]["value"] > 0
    assert line["metrics"]["kv_read_MB.answer"]["value"] > 0
    assert 0 < line["metrics"]["slot_occupancy.closed"]["value"] <= 100
    assert "sw_kda_step_roofline_share.answer" not in line["metrics"]
    means = C.step_means(out["obs"])
    assert 0 < means["slots"] <= 4 and means["rows"] > means["slots"]


def test_the_int8_control_is_not_correct_by_the_harness_own_decision():
    """Through ``serve.decide_correct``, its ``compared`` and a file's
    limits: the served tokens come out ``correct``, the int8 control at
    the same positions does not, by ``gap_mean``.  The limits here are the
    rehearsal's own (a float32 program on the CPU reads 0); the same
    decision with the CELL's limits at the cell's size is
    ``calibrate_mla_moe.py --workload qwen3-next.answer_closed --flips
    0``'s, read on the chip (PERF.md section 2).  A bfloat16 state is no
    linear layer's rounding and reads on its own, for information."""
    ctx = _rehearsal(0, 78)
    runner = S.load_runner(ctx["config"]["runner"])
    with open(S.BENCH / "tests" / "data" / "rehearsal_gdn_gqa_moe.json") as f:
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
    ref = S.load_reference("qwen3-next")
    sizes = (96, max(o for _p, o in T.request_set(ctx["traffic"])))
    low = ref.control_gaps(ctx["config"], 78, w["sample"], *sizes, "bf16_state")
    assert low["finite"] and 0 < low["gap_mean"] < by["gap_mean"]["value"]
