"""The ``k-exaone`` configuration's own yardstick: the file against the
catalog row's keys (depth, experts held and vocabulary are the cuts),
``BENCHMARK.json``'s entries, hand counts of a draft-and-verify step's
bytes and operations at the published widths, the new readers on a canned
obs (every share under 100), and a rehearsal of the whole cell at a tiny
size on the CPU (the runner lays ``tests/data/rehearsal_window_moe_mtp.json``
over the files itself): sound, the int8 control and a timed path broken
underneath, each through the runner's own decision."""

import json
import time

import pytest

from benchmark import run as R
from benchmark.harness import spec as S, traffic as T
from benchmark.harness import window_moe_mtp_counts as C
from benchmark.harness.peaks import PEAKS

CELL = "k-exaone.think_closed"
V5E = PEAKS["TPU v5 lite"]
NEW_READERS = ["mtp_accept_rate.think", "tokens_per_step.think",
               "mtp_draft_share.think", "decode_floor_share.think",
               "sw_moe_gmm_roofline_share.think",
               "sw_decode_attn_full_roofline_share.think",
               "sw_decode_attn_window_roofline_share.think"]


@pytest.fixture(scope="module")
def config():
    return S.load_config(S.load_spec(), "k-exaone")


def test_configuration_keeps_the_catalogs_numbers(config):
    """Every published key at its published value, but the three cuts."""
    with open("/opt/skills/guides/model-configs/architectures.jsonl") as f:
        row = next(r for r in map(json.loads, f)
                   if r["name"] == "K-EXAONE-236B-A23B")
    cut = {"num_hidden_layers": 8, "num_experts": 8, "vocab_size": 19200}
    assert set(config["reduced"]) == set(cut)
    assert config["source"] == row["source_url"]
    for key, value in row["config"].items():
        assert config[key] == cut.get(key, value), key
    assert (config["num_experts_published"], config["chips_per_layer"],
            config["expert_share"]) == (128, 16, 0)
    assert config["n_routed_experts"] == config["num_experts"]
    assert config["vocab_size"] * 8 == row["config"]["vocab_size"]
    sv = config["serve"]
    assert (sv["max_len"], sv["chunk"], sv["temperature"], sv["top_p"],
            sv["mtp"]) == (4096, 8, 1.0, 0.95, 1)
    assert sv["n_slots"] in (96, 80, 64)
    assert sv["prompt_buckets"] == [128, 256, 512, 1024]
    assert (config["sliding_window"] + sv["ring_slack"]) % 128 == 0


def test_benchmark_json_holds_the_configuration_and_its_one_cell(config):
    spec = S.load_spec()
    entry = next(c for c in spec["configs"] if c["name"] == "k-exaone")
    assert entry["reduced"] == ["num_hidden_layers", "num_experts", "vocab_size"]
    assert entry["source"] == config["source"]
    cell = S.find_cell(spec, CELL)
    assert (cell["config"], cell["traffic"], cell["chips"]) == (
        "k-exaone", "think_closed_c120", 1)
    assert sum(c["config"] == "k-exaone" for c in spec["workloads"]) == 1
    assert {m["name"] for m in S.end_to_end_for(spec, CELL)} == {
        "tok_s", "tpot_p95_ms", "setup_s"}
    names = {m["name"] for m in S.per_layer_for(spec, CELL)}
    assert names == set(NEW_READERS) | {
        "decode_step_ms", "slot_occupancy.closed", "prefill_share.closed",
        "experts_touched.agent", "expert_load_max_over_mean.agent"}
    for m in spec["per_layer"]:
        if m["name"] in NEW_READERS:
            assert m["workloads"] == [CELL]
            assert m["moves"] == ("tok_s" if m["name"] in NEW_READERS[:2]
                                  else "tpot_p95_ms")


def test_the_traffic_is_issue_37s(config):
    traffic = S.load_traffic("think_closed_c120")
    assert (traffic["clients"], traffic["set_size"], traffic["pairing_seed"],
            traffic["driver"], traffic["loop"]) == (120, 32, 7, "inproc", "closed")
    assert traffic["prompt_tokens"] == {"dist": "lognormal", "median": 384,
                                        "sigma": 0.6, "min": 64, "max": 1024}
    assert traffic["output_tokens"] == S.load_traffic(
        "reason_closed_c320")["output_tokens"]
    assert traffic["clients"] * 4 == config["serve"]["n_slots"] * 5
    pairs = T.request_set(traffic)
    assert max(p + o for p, o in pairs) <= config["serve"]["max_len"]
    assert max(p for p, _o in pairs) <= max(config["serve"]["prompt_buckets"])


def test_hand_count_of_the_weights_and_the_cache(config):
    """ISSUE 37's arithmetic, by hand."""
    attn = 6144 * 8192 + 2 * 6144 * 1024 + 8192 * 6144 + 2 * 6144 + 2 * 128
    assert C.attention_params(config) == attn
    assert abs(attn / 1e6 - 113.26) < 0.01
    assert C.expert_params(config) == 3 * 6144 * 2048 == 37_748_736
    assert C.layer_counts(config) == (3, 6, 8)       # the MTP block's among them
    assert C.kv_row_bytes(config) == 4096
    sparse = attn + 6144 * 128 + 37_748_736
    dense = attn + 3 * 6144 * 18432
    want = (dense + 8 * sparse + 2 * 6144 * 6144 + 3 * 6144
            + 6144 + 6144 * 19200)
    assert C.dense_params(config) == want
    # Every held expert touched: the weights a step reads are the model's
    # 8.79 GB less the embedding's 0.24.
    assert abs(C.weight_bytes(config, 8) / 1e9 - (8.789 - 0.236)) < 0.005
    # 96 slots at position 1,200: three full rows and six whole rings.
    full, ring = 96 * 1202, 96 * 256
    assert C.kv_bytes(config, full, ring) == (3 * full + 6 * ring) * 4096
    floor = C.step_floor_s(config, V5E, 96, full, ring, 8, 96)
    byts = C.weight_bytes(config, 8) + C.kv_bytes(config, full, ring)
    assert floor == byts / 819e9                       # bytes-bound: a third of it in operations
    assert 0.25 < C.step_flops(config, 96, full, ring, 96) / 197e12 / floor < 0.5


def test_hand_count_of_the_kernels(config):
    rows = 96 * 1202
    assert C.attn_flops(config, rows) == 4 * 2 * rows * 64 * 128
    assert C.attn_bytes(config, 96, rows) == rows * 4096 + 2 * 2 * 96 * 8192 * 2
    assert C.moe_layer_flops(config, 96) == 2 * 96 * 37_748_736
    assert C.moe_layer_bytes(config, 8, 96) == (
        8 * 37_748_736 * 2 + 96 * (2 * 6144 + 2 * 2048) * 2)


def _canned_obs(config, monkeypatch):
    """A traced run as the readers see it: 20 chunks at the cell's size,
    a step of 17 ms of which the draft's and the rule's operations are 3."""
    rows = [{"t0": 1.0 + i, "kv_rows_full": 96 * 1202, "kv_rows_window":
             96 * 256, "moe_assign": 96 * 7 * 8, "moe_touched": 7.9,
             "moe_max": 21, "live": 96, "n_slots": 96, "admit_s": 0.05,
             "spec_drafted": 96 * 8, "spec_accepted": 350, "spec_emitted": 1110}
            for i in range(20)]
    monkeypatch.setattr(C, "window_steps", lambda obs: rows)
    steps = 20 * 8
    return {"config": config, "window": (0.0, 45.0),
            "device": {"kind": "TPU v5 lite"},
            "trace": {"longest_program_in": {"chunk": [0.136] * 20}},
            "ops_by_scope": {"sw_mtp_verify": 2.2, "sw_mtp_accept": 0.1,
                             "sw_mtp_draft": 0.38, "": 0.04},
            "ops_by_name": {"ops": {
                "jit_serve_decode_chunk": {
                    "sw_decode_attn_stream.6": [3 * steps, 3 * steps * 0.9e-3],
                    "sw_decode_attn_ring.7": [6 * steps, 6 * steps * 0.2e-3],
                    "sw_moe_gmm.4": [8 * steps, 8 * steps * 0.6e-3],
                    "sw_moe_gmm.5": [8 * steps, 8 * steps * 0.35e-3]},
                "jit_serve_admit_1024": {"sw_moe_gmm.9": [8.0, 0.5]}},
                "chips": 1}}


def test_the_new_readers_on_a_canned_obs(config, monkeypatch):
    obs = _canned_obs(config, monkeypatch)
    got = {name: S.load_reader(name).read(obs) for name in NEW_READERS}
    assert all(v is not None for v in got.values()), got
    for name, value in got.items():
        if "share" in name or "rate" in name:
            assert 0 < value < 100, (name, value)
    assert abs(got["mtp_accept_rate.think"] - 350 / 768 * 100) < 1e-9
    assert abs(got["tokens_per_step.think"] - 1110 / 768) < 1e-9
    assert abs(got["mtp_draft_share.think"] - 0.48 / 2.72 * 100) < 1e-9
    floor = (C.weight_bytes(config, 7.9)
             + (3 * 96 * 1202 + 6 * 96 * 256) * 4096) / 819e9
    assert abs(got["decode_floor_share.think"] - floor / 0.017 * 100) < 1e-6
    moe = (7.9 * 37_748_736 * 2 + 96 * (2 * 6144 + 2 * 2048) * 2) / 819e9
    assert abs(got["sw_moe_gmm_roofline_share.think"] - moe / 0.95e-3 * 100) < 1e-6
    ring = (96 * 256 * 4096 + 2 * 2 * 96 * 8192 * 2) / 819e9
    assert abs(got["sw_decode_attn_window_roofline_share.think"]
               - ring / 0.2e-3 * 100) < 1e-6


def test_readers_return_nothing_where_there_is_nothing_to_read():
    """On a program without the counters, the scopes or the kernels (the
    parent), and on a trace without scope stats."""
    from benchmark.harness.trace_by_scope import reduce_by_scope

    obs = {"config": {}, "window": (0.0, 1.0), "trace": None, "spans": None,
           "device": {"kind": "TPU v5 lite"}}
    for name in NEW_READERS:
        assert S.load_reader(name).read(obs) is None, name
    assert reduce_by_scope("/nonexistent", C.SCOPES, C.CHUNK_PROGRAM, None) is None
    assert reduce_by_scope("/nonexistent", C.SCOPES, C.CHUNK_PROGRAM,
                           "ROOT %fusion.1 = f32[] fusion()") is None


def test_scopes_are_read_off_the_compilers_text():
    from benchmark.harness.trace_by_scope import scopes_by_instruction

    text = """
  %fusion.12 = bf16[96,2]{1,0} fusion(%p), kind=kLoop, metadata={op_name="jit(serve_decode_chunk)/jit(main)/while/body/sw_mtp_verify/while/body/dot_general" source_file="x.py"}
  ROOT %sort.3 = f32[96,19200]{1,0} sort(%a), metadata={op_name="jit(serve_decode_chunk)/jit(main)/while/body/sw_mtp_draft/sort"}
  %copy.1 = f32[2]{0} copy(%b), metadata={op_name="jit(serve_decode_chunk)/jit(main)/while/body/add"}
  sw_moe_gmm.84 = bf16[8]{0} custom-call(%c), metadata={op_name="jit(f)/sw_mtp_accept/sw_mtp_draft/x"}
"""
    assert scopes_by_instruction(text, C.SCOPES) == {
        "fusion.12": "sw_mtp_verify", "sort.3": "sw_mtp_draft",
        "sw_moe_gmm.84": "sw_mtp_accept"}


def _rehearsal(trace: int, seed: int):
    args = R.parse(["--workload", CELL, "--seed", str(seed), "--seconds",
                    "4", "--trace", str(trace), "--no-chip"])
    ctx = R.context(args)
    ctx["t_start"] = time.monotonic()
    return ctx


def _window(seed: int):
    """The rehearsal's window through the runner, and what it heard."""
    ctx = _rehearsal(0, seed)
    runner = S.load_runner(ctx["config"]["runner"])
    with open(S.BENCH / "tests" / "data" / "rehearsal_window_moe_mtp.json") as f:
        small = json.load(f)
    ctx["config"].update(small["config"])
    ctx["traffic"].update(small["traffic"])
    w = runner.serve.inproc_window(ctx)
    return ctx, runner, w, runner.heard.samples(w["sample"])


def test_the_cell_rehearsed_small_on_the_cpu():
    ctx = _rehearsal(1, 2**31 + 7)
    out = S.load_runner(ctx["config"]["runner"]).run(ctx)
    assert ctx["config"]["sliding_window"] == 8    # the runner shrank it
    assert out["attempted"] > 0 and out["failed"] == 0 and out["correct"]
    line = R.result_line(ctx, out)
    # No device trace on the CPU: the counters' metrics are read all the same.
    assert 5 < line["metrics"]["mtp_accept_rate.think"]["value"] < 95
    assert 1 < line["metrics"]["tokens_per_step.think"]["value"] < 2
    assert 0 < line["metrics"]["experts_touched.agent"]["value"] <= 4
    assert 0 < line["metrics"]["slot_occupancy.closed"]["value"] <= 100
    assert "mtp_draft_share.think" not in line["metrics"]
    assert "sw_moe_gmm_roofline_share.think" not in line["metrics"]
    means = C.step_means(out["obs"])
    assert means["rows_window"] <= 4 * 16 < means["rows_full"]


def test_the_int8_control_is_not_correct_by_the_runners_own_decision():
    """Through ``decide_correct``, its ``compared`` and a file's limits: the
    served log-probabilities come out ``correct``, the int8 reference's in
    their place do not.  The limits here are the rehearsal's own (a float32
    program on the CPU reads 1e-6); the same decision with the CELL's
    limits at the cell's size is read on the chip (PERF.md section 2)."""
    ctx, runner, w, samples = _window(77)
    sound = runner.decide_correct(ctx, samples, w["faults"], len(w["rows"]))
    assert sound["correct"], sound
    ctx["config"]["correct"]["decide_control"] = True
    control = runner.decide_correct(ctx, samples, w["faults"], len(w["rows"]))
    assert not control["correct"], control
    by = {c["what"]: c for c in control["compared"]}
    assert by["logp_gap_mean"]["value"] > by["logp_gap_mean"]["limit"]
    assert by["draft_logp_gap_mean"]["value"] > by["draft_logp_gap_mean"]["limit"]
    # a request that did not get what it asked for is a fault, whatever it says
    short = runner.decide_correct(ctx, samples, [3], len(w["rows"]))
    assert not short["correct"]


def test_a_rejected_drafts_write_left_in_the_ring_is_not_correct(monkeypatch):
    """The timed path broken underneath: the rings are read without the
    position masks, so a slot sees what a rejected draft left behind and
    what lies beyond its window; the log-probabilities it serves are then
    not the reference's, and the runner's decision says so."""
    from starway_tpu.models import serving
    from starway_tpu.ops import pallas_decode

    lax_twin = pallas_decode.decode_attention_lax

    def unmasked(q, k, v, pos, *, ring=False, window=None, **kw):
        return lax_twin(q, k, v, pos, ring=ring,
                        window=k.shape[-2] if ring else window, **kw)

    monkeypatch.setattr(pallas_decode, "decode_attention_lax", unmasked)
    serving._compiled_chunk.cache_clear()
    try:
        ctx, runner, w, samples = _window(78)
    finally:
        serving._compiled_chunk.cache_clear()
    verdict = runner.decide_correct(ctx, samples, w["faults"], len(w["rows"]))
    assert not verdict["correct"], verdict
    by = {c["what"]: c for c in verdict["compared"]}
    assert by["delivery_faults"]["value"] == 0
    assert by["logp_gap_mean"]["value"] > by["logp_gap_mean"]["limit"]
