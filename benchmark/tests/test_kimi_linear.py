"""The ``kimi-linear`` configuration's own yardstick: the file against the
catalog row's numbers (depth, experts held and vocabulary are the cuts),
``BENCHMARK.json``'s entries, the traffic of ISSUE 32, hand counts of the
weights, the state, the cache and of each new kernel's bytes and
operations at the published widths, the new readers on a canned obs (every
roofline share under 100), and a rehearsal of the whole cell at a tiny size
on the CPU (the runner lays ``tests/data/rehearsal_kda.json`` over the
files itself: ``rehearsal.json`` is the accepted benchmark's)."""

import json
import time

import pytest

from benchmark import run as R
from benchmark.harness import kda_mla_moe_counts as C
from benchmark.harness import spec as S, traffic as T
from benchmark.harness.peaks import PEAKS

CELL = "kimi-linear.reason_closed"
V5E = PEAKS["TPU v5 lite"]
NEW_READERS = ["sw_kda_step_roofline_share.reason",
               "sw_mla_decode_attn_roofline_share.reason",
               "sw_moe_gmm_roofline_share.reason",
               "sw_kda_chunk_roofline_share.reason",
               "decode_floor_share.reason", "state_rw_MB.reason"]


@pytest.fixture(scope="module")
def config():
    return S.load_config(S.load_spec(), "kimi-linear")


def test_configuration_keeps_the_catalogs_numbers(config):
    """Every published key at its published value, but the three cuts."""
    published = {
        "first_k_dense_replace": 1, "head_dim": 72, "hidden_act": "silu",
        "hidden_size": 2304, "intermediate_size": 9216, "kv_lora_rank": 512,
        "linear_attn_config": {
            "full_attn_layers": [4, 8, 12, 16, 20, 24, 27], "head_dim": 128,
            "kda_layers": [1, 2, 3, 5, 6, 7, 9, 10, 11, 13, 14, 15, 17, 18,
                           19, 21, 22, 23, 25, 26],
            "num_heads": 32, "short_conv_kernel_size": 4},
        "mla_use_nope": True, "model_max_length": 1048576,
        "model_type": "kimi_linear", "moe_intermediate_size": 1024,
        "moe_layer_freq": 1, "moe_renormalize": True,
        "moe_router_activation_func": "sigmoid", "num_attention_heads": 32,
        "num_expert_group": 1, "num_experts": 256, "num_experts_per_token": 8,
        "num_hidden_layers": 27, "num_key_value_heads": 32,
        "num_nextn_predict_layers": 0, "num_shared_experts": 1,
        "q_lora_rank": None, "qk_nope_head_dim": 128, "qk_rope_head_dim": 64,
        "rms_norm_eps": 1e-05, "rope_scaling": None, "rope_theta": 10000,
        "routed_scaling_factor": 2.446, "tie_word_embeddings": False,
        "topk_group": 1, "use_grouped_topk": True, "v_head_dim": 128,
        "vocab_size": 163840}
    cut = {"num_hidden_layers": 8, "num_experts": 16, "vocab_size": 20480}
    assert set(config["reduced"]) == set(cut)
    for key, value in published.items():
        assert config[key] == cut.get(key, value), key
    assert (config["num_experts_published"], config["chips_per_layer"],
            config["expert_share"]) == (256, 16, 0)
    # What the accepted readers read under kimi-k2's names says the same.
    assert config["n_routed_experts"] == config["num_experts"]
    sv = config["serve"]
    assert (sv["max_len"], sv["chunk"]) == (6144, 8)
    assert sv["n_slots"] in (256, 192, 128)
    assert min(sv["prompt_buckets"]) <= 128 and max(sv["prompt_buckets"]) == 4096
    assert config["guarantees"] == S.load_config(S.load_spec(), "kimi-k2")["guarantees"]
    assert config["correct"]["control"] == "int8"
    assert 6 <= config["correct"]["sample_requests"] <= 10


def test_benchmark_json_holds_the_configuration_and_its_one_cell():
    spec = S.load_spec()
    entry = next(c for c in spec["configs"] if c["name"] == "kimi-linear")
    assert entry["reduced"] == ["num_hidden_layers", "num_experts", "vocab_size"]
    assert entry["source"] == S.load_config(spec, "kimi-linear")["source"]
    cell = S.find_cell(spec, CELL)
    assert (cell["config"], cell["traffic"], cell["chips"]) == (
        "kimi-linear", "reason_closed_c320", 1)
    assert [c["name"] for c in spec["workloads"] if c["config"] == "kimi-linear"] == [CELL]
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


def test_the_traffic_is_issue_32s(config):
    traffic = S.load_traffic("reason_closed_c320")
    sv = config["serve"]
    assert (traffic["set_size"], traffic["pairing_seed"], traffic["driver"],
            traffic["loop"]) == (32, 7, "inproc", "closed")
    assert traffic["clients"] == {256: 320, 192: 240, 128: 160}[sv["n_slots"]]
    assert traffic["prompt_tokens"] == {"dist": "lognormal", "median": 512,
                                        "sigma": 0.8, "min": 64, "max": 4096}
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


def test_hand_count_of_the_weights_the_state_and_the_cache(config):
    """ISSUE 32's arithmetic, by hand."""
    kda = (3 * 2304 * 4096 + 4096 * 2304 + 2 * (2304 * 128 + 128 * 4096)
           + 2304 * 32 + 4 * 3 * 4096 + 4096 + 32 + 128 + 2 * 2304)
    assert C.kda_params(config) == kda == 39_518_880
    latent = (2304 * 32 * 192 + 2304 * 576 + 512 + 512 * 32 * 256
              + 32 * 128 * 2304 + 2 * 2304)
    assert C.latent_params(config) == latent == 29_119_488
    assert C.expert_params(config) == 3 * 2304 * 1024 == 7_077_888
    assert C.layer_counts(config) == (6, 2)
    assert C.state_bytes(config) == 32 * 128 * 128 * 4 == 2_097_152
    assert C.conv_tail_bytes(config) == 3 * 3 * 4096 * 2 == 73_728
    # 256 slots: the matrices alone 6.44 GB a step, read and written.
    assert 6 * 256 * 2 * 2_097_152 == 6_442_450_944
    assert C.state_rw_bytes(config, 256) == 6 * 256 * 2 * (2_097_152 + 73_728)
    # 256 slots at position 2,000 in both latent layers.
    rows = 256 * 2001
    assert C.latent_bytes(config, rows) == 2 * rows * 1152
    # A step that touches all 16 held experts of each routed layer.
    want = ((6 * kda + 2 * latent) * 2 + 3 * 2304 * 9216 * 2
            + 7 * (2304 * 256 * 2 + 256 * 4 + 17 * 7_077_888 * 2)
            + (2304 + 2304 * 20480) * 2)
    assert C.weight_bytes(config, 16) == want
    assert abs(want / 1e9 - 2.505) < 0.001              # 3.1 ms at 819 GB/s
    floor = C.step_floor_s(config, V5E, 256, 256, rows, 16, 256 * 8 * 16 / 256)
    byts = want + C.state_rw_bytes(config, 256) + C.latent_bytes(config, rows)
    assert abs(floor - byts / 819e9) < 1e-12                     # bytes-bound
    assert 0.55 < C.state_rw_bytes(config, 256) / byts < 0.70    # three fifths
    assert C.step_flops(config, 256, 256, rows, 128) / 197e12 < floor / 3


def test_hand_count_of_the_kernels(config):
    assert C.kda_step_flops(config, 256) == 7 * 256 * 32 * 128 * 128
    assert C.kda_step_bytes(config, 256) == 256 * (2 * 2_097_152 + 6 * 32 * 128 * 4)
    # 7 operations on 8 bytes of state: bytes-bound by three orders.
    assert C.roofline_s(C.kda_step_flops(config, 256),
                        C.kda_step_bytes(config, 256), V5E) == (
        C.kda_step_bytes(config, 256) / 819e9)
    # A 512-token bucket: 8 chunks a head.
    chunks = 8 * 32
    assert C.kda_chunk_flops(config, 512) == chunks * 2 * 64 * 128 * (3 * 128 + 64)
    assert C.kda_chunk_bytes(config, 512) == (
        chunks * (5 * 64 * 128 + 64 * 64 + 128) + 32 * 128 * 128) * 4
    assert C.kda_chunk_flops(config, 500) == C.kda_chunk_flops(config, 512)
    rows = 256 * 2001
    assert C.mla_decode_flops(config, rows) == 2 * rows * 32 * (512 + 64 + 512)
    assert C.mla_decode_bytes(config, 256, rows) == (
        rows * 1152 + 256 * 32 * (512 + 64 + 512) * 2)
    assert C.moe_layer_bytes(config, 16, 128) == (
        16 * 7_077_888 * 2 + 128 * (2 * 2304 + 2 * 1024) * 2)


def _canned_obs(config, monkeypatch):
    """A traced run as the readers see it: 20 chunks at the cell's size, a
    decode step of 22 ms of which the state kernel is 1.9 ms a layer, the
    latent attention 1.5 and the grouped matmuls 0.9."""
    slots = config["serve"]["n_slots"]
    rows = [{"t0": 1.0 + i, "state_slots": slots, "kv_rows_latent": slots * 2001,
             "moe_assign": 128 * 8 * 7, "moe_touched": 15.9, "moe_max": 17,
             "live": slots, "n_slots": slots, "admit_s": 0.02}
            for i in range(20)]
    monkeypatch.setattr(C, "window_steps", lambda obs: rows)
    steps = 20 * 8
    return {"config": config, "window": (0.0, 45.0),
            "device": {"kind": "TPU v5 lite"},
            "trace": {"longest_program_in": {"chunk": [0.2] * 20},
                      "modules": {"jit_serve_decode_chunk": [20, 20 * 0.176],
                                  "jit_serve_admit_512": [30, 0.4]}},
            "ops_by_name": {"ops": {
                "jit_serve_decode_chunk": {
                    "sw_kda_step.3": [6 * steps, 6 * steps * 1.9e-3],
                    "sw_mla_decode_attn.4": [2 * steps, 2 * steps * 1.5e-3],
                    "sw_moe_gmm.4": [7 * steps, 7 * steps * 0.6e-3],
                    "sw_moe_gmm.5": [7 * steps, 7 * steps * 0.3e-3]},
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
    state = 6 * slots * 2 * (2_097_152 + 73_728)
    assert abs(got["state_rw_MB.reason"] - state / 1e6) < 1e-6
    step = slots * (2 * 2_097_152 + 6 * 32 * 128 * 4) / 819e9
    assert abs(got["sw_kda_step_roofline_share.reason"] - step / 1.9e-3 * 100) < 1e-6
    rows = slots * 2001 + slots * 3.5          # half a chunk a slot further
    floor = (C.weight_bytes(config, 15.9) + state + 2 * rows * 1152) / 819e9
    assert abs(got["decode_floor_share.reason"] - floor / 0.022 * 100) < 1e-6
    # The admit programs' calls of the grouped matmul are not the decode step's.
    moe = (15.9 * 7_077_888 * 2 + 128 * (2 * 2304 + 2 * 1024) * 2) / 819e9
    assert abs(got["sw_moe_gmm_roofline_share.reason"] - moe / 0.9e-3 * 100) < 1e-6
    # Each admit program's calls at its own bucket's count.
    chunk = (6 * 30 * C.kda_chunk_bytes(config, 512)
             + 6 * 4 * C.kda_chunk_bytes(config, 2048)) / 819e9
    assert abs(got["sw_kda_chunk_roofline_share.reason"]
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
    assert line["metrics"]["state_rw_MB.reason"]["value"] > 0
    assert 0 < line["metrics"]["slot_occupancy.closed"]["value"] <= 100
    assert "sw_kda_step_roofline_share.reason" not in line["metrics"]
    means = C.step_means(out["obs"])
    assert 0 < means["slots"] <= 4 and means["rows"] > means["slots"]


def test_the_int8_control_is_not_correct_by_the_harness_own_decision():
    """Through ``serve.decide_correct``, its ``compared`` and a file's
    limits: the served tokens come out ``correct``, the int8 control at
    the same positions does not, by ``gap_mean``.  The limits here are the
    rehearsal's own (a float32 program on the CPU reads 0); the same
    decision with the CELL's limits at the cell's size is
    ``calibrate_mla_moe.py --workload kimi-linear.reason_closed``'s, read
    on the chip (PERF.md section 2).  A bfloat16 state is no linear layer's
    rounding and reads on its own, for information."""
    ctx = _rehearsal(0, 78)
    runner = S.load_runner(ctx["config"]["runner"])
    with open(S.BENCH / "tests" / "data" / "rehearsal_kda.json") as f:
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
    ref = S.load_reference("kimi-linear")
    sizes = (96, max(o for _p, o in T.request_set(ctx["traffic"])))
    low = ref.control_gaps(ctx["config"], 78, w["sample"], *sizes, "bf16_state")
    assert low["finite"] and 0 < low["gap_mean"] < by["gap_mean"]["value"]
