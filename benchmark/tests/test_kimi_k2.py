"""The ``kimi-k2`` configuration's own yardstick: hand counts of a decode
step's and the two kernels' bytes and operations at the published widths,
the by-name trace reduction on the recorded v5e trace, and a rehearsal of
the whole cell at a tiny size on the CPU (``tests/data/rehearsal.json`` is
not this PR's to edit, so the small sizes are laid over here)."""

import time

import pytest

from benchmark import run as R
from benchmark.harness import mla_moe_counts as C
from benchmark.harness import spec as S, traffic as T
from benchmark.harness.peaks import PEAKS

CELL = "kimi-k2.agent_closed"
V5E = PEAKS["TPU v5 lite"]

SMALL_CONFIG = {
    "hidden_size": 64, "num_attention_heads": 4, "num_key_value_heads": 4,
    "q_lora_rank": 24, "kv_lora_rank": 32, "qk_nope_head_dim": 16,
    "qk_rope_head_dim": 8, "v_head_dim": 16, "intermediate_size": 96,
    "moe_intermediate_size": 32, "n_routed_experts": 4,
    "n_routed_experts_published": 16, "num_experts_per_tok": 4,
    "vocab_size": 128, "num_hidden_layers": 3, "torch_dtype": "float32",
    "serve": {"n_slots": 4, "max_len": 96, "chunk": 4, "temperature": 0.0},
    "correct": {"sample_requests": 4, "control": "int8",
                "gap_max_limit": 1e-4, "gap_mean_limit": 1e-5},
}
SMALL_TRAFFIC = {
    "clients": 6, "set_size": 8,
    "prompt_tokens": {"dist": "lognormal", "median": 24, "sigma": 0.8, "min": 4, "max": 64},
    "output_tokens": {"dist": "lognormal", "median": 8, "sigma": 0.8, "min": 2, "max": 24},
}


@pytest.fixture(scope="module")
def config():
    return S.load_config(S.load_spec(), "kimi-k2")


def test_configuration_keeps_the_catalogs_numbers(config):
    """Every published key at its published value, but the three cuts."""
    published = {
        "first_k_dense_replace": 1, "hidden_size": 7168, "intermediate_size": 18432,
        "kv_lora_rank": 512, "max_position_embeddings": 131072,
        "moe_intermediate_size": 2048, "moe_layer_freq": 1, "n_group": 1,
        "n_routed_experts": 384, "n_shared_experts": 1, "num_attention_heads": 64,
        "num_experts_per_tok": 8, "num_hidden_layers": 61, "num_key_value_heads": 64,
        "num_nextn_predict_layers": 0, "q_lora_rank": 1536, "qk_nope_head_dim": 128,
        "qk_rope_head_dim": 64, "rms_norm_eps": 1e-06, "rope_theta": 50000,
        "routed_scaling_factor": 2.827, "topk_group": 1, "v_head_dim": 128,
        "vocab_size": 163840}
    cut = {"num_hidden_layers": 6, "n_routed_experts": 12, "vocab_size": 20480}
    assert set(config["reduced"]) == set(cut)
    for key, value in published.items():
        assert config[key] == cut.get(key, value), key
    assert config["n_routed_experts_published"] == 384
    assert config["chips_per_layer"] * config["n_routed_experts"] == 384
    assert config["rope_scaling"] == {
        "beta_fast": 1, "beta_slow": 1, "factor": 32, "mscale": 1,
        "mscale_all_dim": 1, "original_max_position_embeddings": 4096, "type": "yarn"}


def test_hand_count_of_the_weights(config):
    """ISSUE 26's arithmetic, by hand."""
    attn = (7168 * 1536 + 1536 * 12288 + 7168 * 576 + 512 * 16384 + 8192 * 7168
            + 1536 + 512 + 2 * 7168)
    assert C.attention_params(config) == attn
    assert abs(attn / 1e6 - 101.1) < 0.1
    assert C.expert_params(config) == 3 * 7168 * 2048 == 44_040_192
    # A step that touches all 12 held experts of each routed layer.
    want = 2 * (6 * attn + 3 * 7168 * 18432
                + 5 * (7168 * 384 + 13 * 44_040_192)
                + 7168 + 7168 * 20480) + 5 * 384 * 4
    assert C.weight_bytes(config, 12) == want
    assert abs(want / 1e9 - 8.05) < 0.02
    # 11.2 touched: what 128 x 8 pairs over 384 experts leave of 12.
    assert abs(C.weight_bytes(config, 11.19) / 1e9 - 7.70) < 0.02
    assert C.latent_row_bytes(config) == 1152


def test_hand_count_of_the_kernels(config):
    # One layer of one step over 128 slots x 1,600 live positions.
    rows = 128 * 1600
    flops = C.mla_decode_flops(config, rows)
    assert flops == 2 * rows * 64 * (576 + 512)
    byts = C.mla_decode_bytes(config, 128, rows)
    assert byts == rows * 1152 + 128 * 64 * 1088 * 2
    assert 110 < flops / byts < 122          # beside the ridge of 240
    assert C.roofline_s(flops, byts, V5E) == byts / 819e9
    # 32 pairs on 11.2 touched experts: bytes-bound by far.
    assert C.moe_layer_flops(config, 32) == 2 * 32 * 44_040_192
    assert abs(C.moe_layer_bytes(config, 11.2, 32) / 1e9 - 0.987) < 0.002
    floor = C.step_floor_s(config, V5E, 128, rows, 11.19, 32)
    assert abs(floor * 1e3 - (7.70 + 6 * rows * 1152 / 1e9) / 0.819) < 0.05


def test_by_name_reduction_on_the_recorded_trace():
    from benchmark.harness.trace_by_name import kernel, reduce_by_name
    from benchmark.harness.trace_reduce import reduce_trace

    data = S.BENCH / "tests" / "data"
    by_name = reduce_by_name(data)
    whole = reduce_trace(data / "tiny_v5e.xplane.pb")
    every = {}
    for rows in by_name["ops"].values():
        for name, (calls, sec) in rows.items():
            every[name] = every.get(name, 0.0) + sec
            assert calls >= 1
    # The ten operations reduce_trace keeps are here, at the same seconds.
    for name, sec in whole["device_ops"]:
        assert abs(every[name] - sec) < 1e-9, name
    assert kernel(by_name, "no_such_kernel") is None
    name, sec = whole["device_ops"][0]
    assert kernel(by_name, name)[1] >= sec - 1e-9


def test_readers_return_nothing_where_there_is_nothing_to_read():
    """On a program without the counters or the kernels (the parent)."""
    obs = {"config": {}, "window": (0.0, 1.0), "trace": None, "spans": None,
           "device": {"kind": "TPU v5 lite"}}
    for m in S.load_spec()["per_layer"]:
        if m.get("workloads") == [CELL]:
            assert S.load_reader(m["name"]).read(obs) is None, m["name"]


def test_the_cell_rehearsed_small_on_the_cpu():
    args = R.parse(["--workload", CELL, "--seed", str(2**31 + 5), "--seconds",
                    "4", "--trace", "1", "--no-chip"])
    ctx = R.context(args)
    ctx["t_start"] = time.monotonic()
    ctx["config"].update(SMALL_CONFIG)
    ctx["traffic"].update(SMALL_TRAFFIC)
    out = S.load_runner(ctx["config"]["runner"]).run(ctx)
    assert out["attempted"] > 0 and out["failed"] == 0 and out["correct"]
    line = R.result_line(ctx, out)
    # No device trace on the CPU: the counters' metrics are read all the same.
    assert 0 < line["metrics"]["experts_touched.agent"]["value"] <= 4
    assert line["metrics"]["expert_load_max_over_mean.agent"]["value"] >= 1
    assert "sw_moe_gmm_roofline_share" not in line["metrics"]
    from benchmark.harness.mla_moe_obs import live_rows

    rows = live_rows(out["obs"])
    lengths = [p + o for p, o in T.request_set(ctx["traffic"])]
    assert min(lengths) < rows < 4 * (max(lengths) + 4)


def test_the_int8_control_is_not_correct_by_the_harness_own_decision():
    """Through ``serve.decide_correct``, its ``compared`` and a file's
    limits: the served tokens come out ``correct``, the int8 control at
    the same positions does not, by ``gap_mean``.  At a size a test can
    hold (float32, 64 wide, 3 layers) int8 moves the logits a fifth as far
    as at the cell's (0.0015 against 0.017), so the limits here are this
    size's own (``SMALL_CONFIG``: a float32 program on the CPU reads 0).
    The same decision with the CELL's limits at the cell's size is
    ``calibrate_mla_moe.py``'s, read on the chip (PERF.md section 2)."""
    args = R.parse(["--workload", CELL, "--seed", "77", "--seconds", "3",
                    "--trace", "0", "--no-chip"])
    ctx = R.context(args)
    ctx["t_start"] = time.monotonic()
    ctx["config"].update(SMALL_CONFIG,
                         correct=dict(SMALL_CONFIG["correct"], sample_requests=6))
    ctx["traffic"].update(SMALL_TRAFFIC)
    serve = S.load_runner(ctx["config"]["runner"]).serve   # with its build_server
    w = serve.inproc_window(ctx)
    sound = serve.decide_correct(ctx, w["sample"], w["faults"], len(w["rows"]))
    assert sound["correct"], sound
    ctx["config"]["correct"]["decide_control"] = True
    control = serve.decide_correct(ctx, w["sample"], w["faults"], len(w["rows"]))
    assert not control["correct"], control
    by = {c["what"]: c for c in control["compared"]}
    assert by["gap_mean"]["value"] > by["gap_mean"]["limit"]
    ref = S.load_reference("kimi-k2")
    sizes = (96, max(o for _p, o in T.request_set(ctx["traffic"])))
    flips = ref.router_flips(ctx["config"], 77, w["sample"], *sizes)
    assert 0.0 <= flips["share"] < 0.5 and flips["layers"] == 2
