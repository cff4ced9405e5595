"""Latent attention (MLA) and the sigmoid-routed dropless expert layer (the
DeepSeek-V3 / Kimi-K2 block) against the benchmark's plain reference
(benchmark/configs/kimi-k2_reference.py: float32, HIGHEST, expanded
attention, every held expert on every token), at tiny widths with seeded
weights; and the kernels in interpret mode against their lax forms."""


import numpy as np
import pytest

import jax
import jax.numpy as jnp

from benchmark.harness import spec as S
from benchmark.harness import weights_mla_moe as W

TINY = {
    "hidden_size": 64, "num_attention_heads": 4, "num_key_value_heads": 4,
    "q_lora_rank": 24, "kv_lora_rank": 32, "qk_nope_head_dim": 16,
    "qk_rope_head_dim": 8, "v_head_dim": 16, "intermediate_size": 96,
    "moe_intermediate_size": 32, "n_routed_experts": 16,
    "n_routed_experts_published": 16, "num_experts_per_tok": 4,
    "n_shared_experts": 1, "routed_scaling_factor": 2.827,
    "first_k_dense_replace": 1, "vocab_size": 128, "num_hidden_layers": 3,
    "rms_norm_eps": 1e-6, "rope_theta": 50000,
    "rope_scaling": {"beta_fast": 1, "beta_slow": 1, "factor": 32, "mscale": 1,
                     "mscale_all_dim": 1,
                     "original_max_position_embeddings": 16, "type": "yarn"},
    "torch_dtype": "float32",
}
SEED = 1234


@pytest.fixture(scope="module")
def ref():
    return S.load_reference("kimi-k2")


@pytest.fixture(scope="module")
def runner():
    return S.load_runner("serve_mla_moe")


@pytest.fixture(autouse=True)
def _highest():
    with jax.default_matmul_precision("highest"):
        yield


def _model(runner, config=TINY, seed=SEED):
    return (runner.program_tree(W.make_model(seed, W.dims(config))),
            runner.model_config(config))


def _tokens(n, s, seed=0):
    return np.random.default_rng(seed).integers(
        1, TINY["vocab_size"], (n, s)).astype(np.int32)


def test_forward_matches_reference(ref, runner):
    from starway_tpu.models import forward

    params, cfg = _model(runner)
    toks = _tokens(2, 24)
    got = forward(params, jnp.asarray(toks), cfg)
    want = ref.full_logits(TINY, SEED, toks)
    np.testing.assert_allclose(got, want, rtol=2e-4, atol=2e-4)


def test_prefill_then_cached_decode_matches_reference(ref, runner):
    """Expanded prefill fills the latent cache; absorbed decode steps read
    it: every step's logits equal the reference's full forward."""
    from starway_tpu.models.generate import decode_step, prefill

    params, cfg = _model(runner)
    toks = _tokens(2, 20, seed=1)
    want = np.asarray(ref.full_logits(TINY, SEED, toks))
    p0 = 9
    logits, cache = prefill(params, cfg, jnp.asarray(toks[:, :p0]), 32)
    assert set(cache) == {"ckv"} and cache["ckv"].shape == (3, 2, 1, 32, 128)
    np.testing.assert_allclose(logits, want[:, p0 - 1], rtol=2e-4, atol=2e-4)
    for p in range(p0, toks.shape[1]):
        logits, cache = decode_step(params, cache, jnp.asarray(toks[:, p]),
                                    jnp.full((2,), p, jnp.int32), cfg)
        np.testing.assert_allclose(logits, want[:, p], rtol=2e-4, atol=2e-4)


def test_generate_greedy_is_the_references_argmax(ref, runner):
    from starway_tpu.models import generate

    params, cfg = _model(runner)
    prompt = _tokens(2, 7, seed=2)
    out = np.asarray(generate(params, cfg, jnp.asarray(prompt), 9))
    want = np.asarray(ref.full_logits(TINY, SEED, out))
    np.testing.assert_array_equal(out[:, 7:], want[:, 6:-1].argmax(-1))


def test_slot_server_ragged_slots_match_reference(ref, runner):
    """Continuous batching with ragged prompts, more requests than slots:
    every served token is the reference's best at its position (gap 0 up
    to float32 rounding), teacher-forced: the comparison ``correct`` makes."""
    from starway_tpu.models import SlotServer, serving

    params, cfg = _model(runner)
    srv = SlotServer(params, cfg, n_slots=3, max_len=64, chunk=4)
    rng = np.random.default_rng(3)
    prompts = [rng.integers(1, 128, n).astype(np.int32)
               for n in (5, 17, 9, 30, 3)]
    wants = [6, 11, 4, 9, 13]
    rids = [srv.submit(p, m) for p, m in zip(prompts, wants)]
    done = srv.run()
    sample = [(p, done[r]) for p, r in zip(prompts, rids)]
    assert [len(done[r]) for r in rids] == wants
    got = ref.served_gaps(TINY, SEED, sample, 64, 16)
    assert got["finite"] and got["gap_max"] < 1e-5, got
    rows = [r for r in serving.step_log() if r["server"] == srv.server_id]
    decoded = [r for r in rows if "moe_assign" in r]
    assert decoded and all(
        0 < r["moe_touched"] <= 16 and 0 < r["moe_max"] <= 3
        # at most 3 pairs an expert: a touched expert fills one 16-row tile
        and r["moe_tiles"] == r["moe_touched"]
        # every pair lands on a held expert when all are held: 3 slots x
        # 4 choices x 2 routed layers x 4 steps
        and r["moe_assign"] == 3 * 4 * 2 * 4 for r in decoded)


@pytest.mark.parametrize("k", [1, 3, 4])
def test_a_latent_step_queues_its_programs_and_fetches_twice(runner, k,
                                                             monkeypatch):
    """The latent, routed model's admissions end in the same seat program:
    no fetch and no scatter a slot, whatever a step admits."""
    from starway_tpu.models import SlotServer
    from tests.test_serving import check_step_queues_then_fetches

    params, cfg = _model(runner)
    srv = SlotServer(params, cfg, n_slots=4, max_len=64, chunk=3)
    rng = np.random.default_rng(5)
    requests = [(rng.integers(1, 128, n).astype(np.int32), m, None)
                for n, m in ((5, 7), (17, 5), (9, 9))]
    rids, done = check_step_queues_then_fetches(srv, requests, k, monkeypatch)
    assert [len(done[r]) for r in rids] == [requests[i % 3][1]
                                            for i in range(k)]


@pytest.mark.parametrize("rows, value", [
    # two chunks inside the window, one after it
    ([{"t0": 0.2, "moe_touched": 8.0, "moe_tiles": 10.0},
      {"t0": 0.6, "moe_touched": 7.0, "moe_tiles": 8.0},
      {"t0": 1.5, "moe_touched": 8.0, "moe_tiles": 16.0}], 18.0 / 15.0),
    # a program from before the counter (the parent), and a dense one
    ([{"t0": 0.2, "moe_touched": 8.0}], None),
    ([{"t0": 0.2}], None),
])
def test_moe_tiles_reader(rows, value, monkeypatch):
    """``moe_tiles_per_expert.agent`` as ``benchmark/run.py`` reads it."""
    from benchmark.harness import spec as S
    from starway_tpu.models import serving

    metric = "moe_tiles_per_expert.agent"
    spec = S.load_spec()
    entry = next(m for m in spec["per_layer"] if m["name"] == metric)
    assert (entry["source"], entry["layer"], entry["moves"]) == (
        "program_counter", "model", "tpot_p95_ms")
    touched = next(m for m in spec["per_layer"]
                   if m["name"] == "experts_touched.agent")
    assert entry["workloads"] == touched["workloads"]   # the routed cells
    monkeypatch.setattr(serving, "step_log", lambda: rows)
    for cell in entry["workloads"]:   # as run.py reads a cell's line
        assert metric in {m["name"] for m in S.per_layer_for(spec, cell)}
    got = S.load_reader(metric).read({"window": (0.0, 1.0), "config": {}})
    assert got == (value if value is None else pytest.approx(value))


def test_dense_model_step_log_has_no_moe_fields():
    from starway_tpu.models import LlamaConfig, SlotServer, init_params, serving

    cfg = LlamaConfig.preset("debug", n_layers=1)
    srv = SlotServer(init_params(jax.random.PRNGKey(0), cfg), cfg, n_slots=2,
                     max_len=32, chunk=2)
    srv.submit(np.arange(1, 5), 3)
    srv.run()
    rows = [r for r in serving.step_log() if r["server"] == srv.server_id]
    assert rows and not any("moe_assign" in r for r in rows)


def test_absorbed_equals_expanded(runner):
    """The two forms of latent attention are one bilinear form regrouped."""
    from starway_tpu.models import mla
    from starway_tpu.models.llama import cfg_rope_tables
    from starway_tpu.ops import self_attention
    from starway_tpu.ops.pallas_decode import mla_decode_attention_lax

    params, cfg = _model(runner)
    lp = jax.tree_util.tree_map(lambda a: a[0], params["layers"][1])
    x = jax.random.normal(jax.random.PRNGKey(5), (2, 12, 64))
    cos, sin = cfg_rope_tables(cfg, 12)
    q, k, v, rows = mla.project_expanded(x, lp, cfg, cos, sin)
    want = self_attention(q, k, v, sm_scale=cfg.latent.sm_scale)
    qa, rows_a = mla.project_absorbed(x, lp, cfg, cos, sin)
    np.testing.assert_allclose(rows_a, rows, rtol=1e-6, atol=1e-6)
    o_lat = mla_decode_attention_lax(qa, rows[None], jnp.zeros((2,), jnp.int32),
                                     rank=32, sm_scale=cfg.latent.sm_scale)
    got = mla.expand_values(o_lat, lp, cfg)
    np.testing.assert_allclose(got, want, rtol=2e-5, atol=2e-5)


def test_router_bias_changes_the_choice_not_the_gate():
    from starway_tpu.models.moe import sigmoid_route

    x = jnp.eye(4, dtype=jnp.float32)[:1] * 2.0          # one token
    w = jnp.asarray([[2.0, 1.0, 0.5, -1.0], [0] * 4, [0] * 4, [0] * 4]) / 2.0
    s = jax.nn.sigmoid(jnp.asarray([2.0, 1.0, 0.5, -1.0]))
    idx, g = sigmoid_route(x, w, jnp.zeros(4), 2, 2.5)
    assert sorted(idx[0].tolist()) == [0, 1]
    # A bias lifts expert 3 over expert 1: it is chosen, and its gate is
    # its own (small) score over the chosen scores' sum, bias left out.
    idx, g = sigmoid_route(x, w, jnp.asarray([0.0, 0.0, 0.0, 0.6]), 2, 2.5)
    assert idx[0].tolist() == [0, 3]
    np.testing.assert_allclose(
        g[0], 2.5 * np.asarray([s[0], s[3]]) / (s[0] + s[3]), rtol=1e-6)


@pytest.mark.parametrize("share", range(4))
def test_share_holds_its_experts_numbers(share):
    """Share ``i`` of four holds experts 4i..4i+3 of the uncut layer."""
    whole = W.layer_weights(W.base_key(SEED), 1, W.dims(TINY), True)["routed"]
    part = W.layer_weights(
        W.base_key(SEED), 1,
        W.dims(dict(TINY, n_routed_experts=4, expert_share=share)),
        True)["routed"]
    for n in ("w_gate", "w_up", "w_down"):
        np.testing.assert_array_equal(part[n], whole[n][4 * share:4 * share + 4])
    np.testing.assert_array_equal(part["router"], whole["router"])


def _kimi_share(ref, runner, x, share):
    """(the reference's routed part, the program's, what every holder
    computes alike, pairs held) of share ``share`` of 4, or of the whole
    layer (``share`` None), for the sigmoid-routed layer with its shared
    expert."""
    from starway_tpu.models.llama import ffn_block

    config = TINY if share is None else dict(
        TINY, n_routed_experts=4, expert_share=share)
    d, cfg = W.dims(config), runner.model_config(config)
    w = W.layer_weights(W.base_key(SEED), 1, d, True)
    flat = x.reshape(-1, 64)
    shared = ref._swiglu(flat, w["routed"]["shared"], None)
    y, _aux, sizes = ffn_block(x, w, cfg)
    return (ref.routed_part(flat, w["routed"], d), y.reshape(-1, 64) - shared,
            shared, int(sizes.sum()))


def _smallthinker_share(ref, runner, x, share):
    """The same for the softmax-routed ReGLU layer: no shared expert, the
    router on the block's input (here the experts' input rolled by one
    token, so that the two differ)."""
    from benchmark.harness import weights_window_moe as WW
    from starway_tpu.models.llama import ffn_block

    from test_window_moe import TINY as SMALL

    config = SMALL if share is None else dict(SMALL, experts_held=2,
                                              expert_share=share)
    d, cfg = WW.dims(config), runner.model_config(config)
    w = WW.layer_weights(WW.base_key(SEED), 1, d)
    r = jnp.roll(x, 1, axis=1)
    y, _aux, sizes = ffn_block(x, w, cfg, attn_in=r)
    return (ref.routed_part(x.reshape(-1, 64), r.reshape(-1, 64), w["routed"], d),
            y.reshape(-1, 64), 0.0, int(sizes.sum()))


@pytest.mark.parametrize("model,share_of,top_k", [
    ("kimi-k2", _kimi_share, 4), ("smallthinker-21b", _smallthinker_share, 3)])
def test_shares_add_up_to_the_uncut_layer(model, share_of, top_k):
    """model-configs guide, section 4: the routed parts that the shares
    give, with what every holder computes alike (a shared expert) counted
    once, add up to what the uncut reference gives for the whole layer.
    Program and reference alike; sigmoid scoring with a shared expert and
    softmax scoring with ReLU and none."""
    ref = S.load_reference(model)
    runner = S.load_runner(S.load_config(S.load_spec(), model)["runner"])
    x = jax.random.normal(jax.random.PRNGKey(7), (2, 9, 64))
    whole_ref, _prog, shared, _pairs = share_of(ref, runner, x, None)
    want = whole_ref + shared
    total_prog = total_ref = 0.0
    pairs = 0
    for share in range(4):
        part_ref, part_prog, shared, held = share_of(ref, runner, x, share)
        total_ref += part_ref
        total_prog += part_prog
        pairs += held
    assert pairs == 2 * 9 * top_k   # every (token, choice) pair landed once
    np.testing.assert_allclose(total_ref + shared, want, rtol=1e-5, atol=1e-5)
    np.testing.assert_allclose(total_prog + shared, want, rtol=1e-4, atol=1e-4)


# Blocks of 128.  The stream runs from cell to cell (a row's last block
# computes while the next row's first is fetched, and which of the two
# buffers a row starts in is handed on), so the cases are ORDERS of
# cursors: ``c`` query positions a row, ``layer`` of a stack of two.
@pytest.mark.parametrize("t,pos,c,layer", [
    (256, (0, 129, 255), 1, 1),
    (256, (0, 129, 254), 2, 1),
    # Uneven rows: the first position, either side of a block's edge, the
    # last position.
    (512, (0, 127, 128, 511, 300), 1, 1),
    # A row of one block, then a row of many, and the reverse: the parity
    # handed over is odd after one and even after the other.
    (512, (5, 500, 7), 1, 1),
    (512, (500, 5, 400, 3, 130), 1, 0),
    (512, (300,), 1, 1),          # a first cell that is also the last
    (512, (0,), 1, 0),
    (512, (100, 512, 40), 1, 1),  # a frozen slot's cursor at max_len: clamped
    (512, (512, 512), 1, 0),
    (512, (510, 0, 127, 254), 2, 0),
], ids=lambda v: "-".join(map(str, v)) if isinstance(v, tuple) else str(v))
def test_mla_decode_kernel_matches_lax(t, pos, c, layer):
    from starway_tpu.ops.pallas_decode import (mla_decode_attention,
                                               mla_decode_attention_lax)

    k = jax.random.split(jax.random.PRNGKey(11), 2)
    L, B, H, T, r, w = 2, len(pos), 8, t, 128, 160
    latent = jax.random.normal(k[0], (L, B, 1, T, w), jnp.float32)
    q = jax.random.normal(k[1], (B, H, c, w), jnp.float32)
    pos = jnp.asarray(pos, jnp.int32)
    got = mla_decode_attention(q, latent, pos, rank=r, sm_scale=0.11,
                               layer=layer, block_k=128, interpret=True)
    want = mla_decode_attention_lax(q, latent, pos, rank=r, sm_scale=0.11,
                                    layer=layer)
    np.testing.assert_allclose(got, want, rtol=2e-5, atol=2e-5)


def _pairs_of(rng, sizes, strangers: int):
    """``local`` for a grouped matmul test: ``sizes[e]`` pairs on held
    expert ``e`` and ``strangers`` on experts not held, shuffled."""
    g = len(sizes)
    return jnp.asarray(rng.permutation(np.concatenate(
        [np.repeat(np.arange(g), sizes),
         rng.choice([-3, -1, g, g + 1], strangers)])), jnp.int32)


# ``cols``: column blocks of the weight; ``sizes``: pairs an expert (None:
# drawn, two tiles an expert or so).  Every served kimi-k2 / k-exaone /
# kimi-linear call has several column blocks, and an expert of several
# tiles is where the walk goes column block outer.
@pytest.mark.parametrize("gated,act,cols,sizes", [
    (False, "silu", 1, None), (True, "silu", 1, None), (True, "relu", 1, None),
    (False, "silu", 2, (20, 0, 3, 9, 1)), (True, "silu", 2, (20, 0, 3, 9, 1)),
    (True, "silu", 8, (7, 24, 0, 0, 17)), (False, "silu", 8, (0, 0, 0, 0, 0)),
])
def test_gmm_kernel_matches_lax(gated, act, cols, sizes, monkeypatch):
    from starway_tpu.models.moe import group_rows
    from starway_tpu.ops import pallas_gmm
    from starway_tpu.ops.pallas_gmm import gmm, gmm_lax

    rng = np.random.default_rng(13)
    G, K, tm = 5, 64, 8
    N = 256 if cols == 1 else 128 * cols
    if cols > 1:   # a block of 128 columns is all that fits
        monkeypatch.setattr(pallas_gmm, "_BLOCK_BYTES", K * 128 * 4)
    assert N // pallas_gmm.column_block(K, N, 4) == cols
    if sizes is None:
        local = jnp.asarray(rng.integers(-3, G + 2, 90), jnp.int32)
    else:
        local = _pairs_of(rng, sizes, 11)
    src, row, tile_expert, n_live, sizes = group_rows(local, G, tm)
    assert int(sizes.sum()) == int(((local >= 0) & (local < G)).sum())
    x = jnp.asarray(rng.normal(size=(src.shape[0], K)), jnp.float32)
    w = jnp.asarray(rng.normal(size=(G, K, N)), jnp.float32)
    w2 = jnp.asarray(rng.normal(size=(G, K, N)), jnp.float32) if gated else None
    got = gmm(x, w, tile_expert, n_live, tile_m=tm, w2=w2, act=act,
              interpret=True)
    want = gmm_lax(x, w, tile_expert, n_live, tm, w2=w2, act=act)
    if act == "relu":   # and not SiLU's numbers under another name
        assert not np.allclose(
            want, gmm_lax(x, w, tile_expert, n_live, tm, w2=w2), atol=1e-3)
    live = int(n_live) * tm
    np.testing.assert_allclose(got[:live], want[:live], rtol=1e-4, atol=1e-4)
    # Each held pair's row lies in its expert's tiles.
    e_of_row = np.repeat(np.asarray(tile_expert), tm)
    held = np.asarray((local >= 0) & (local < G))
    assert (e_of_row[np.asarray(row)[held]] == np.asarray(local)[held]).all()
    assert (np.asarray(row)[~held] == src.shape[0]).all()


@pytest.mark.parametrize("cols", [1, 2, 8])
@pytest.mark.parametrize("seed", [0, 1, 2])
def test_gmm_walk_reads_an_expert_once(cols, seed):
    """The grid step -> (row tile, column block) map over layouts with
    experts of 0, 1, 2 and 6 tiles and dead tiles behind: every block of
    the output once, every weight block fetched once a call."""
    from starway_tpu.models.moe import group_rows
    from starway_tpu.ops.pallas_gmm import tile_walk

    rng = np.random.default_rng(seed)
    tm = 8
    sizes = rng.permutation([0, 0, 3, 8, 9, 16, 41, 48, 1])
    local = _pairs_of(rng, sizes, 29)
    _, _, tile_expert, n_live, _ = group_rows(local, len(sizes), tm)
    tile_expert, n_live = np.asarray(tile_expert), int(n_live)
    n_tiles = tile_expert.shape[0]
    assert n_live == 1 + 1 + 2 + 2 + 6 + 6 + 1 and n_tiles > n_live + 3
    expert, tile, col = (np.asarray(a) for a in tile_walk(
        jnp.asarray(tile_expert), jnp.int32(n_live), cols))
    assert expert.shape == (n_tiles * cols,)
    assert (expert == tile_expert[tile]).all()
    steps = n_live * cols
    # Every (live tile, column block) exactly once, in the live steps.
    assert sorted(zip(tile[:steps], col[:steps])) == [
        (i, j) for i in range(n_live) for j in range(cols)]
    # A dead step repeats the last live step's indices: nothing moves.
    assert (tile[steps:] == tile[steps - 1]).all()
    assert (col[steps:] == col[steps - 1]).all()
    assert (tile[steps - 1], col[steps - 1]) == (n_live - 1, cols - 1)
    # The weight block (expert, column block) changes once a touched
    # expert and column block over the whole grid, its first fetch counted.
    block = np.stack([expert, col], 1)
    fetches = 1 + int((block[1:] != block[:-1]).any(1).sum())
    assert fetches == int((sizes > 0).sum()) * cols
    # A run of one tile walks as it always did: its x tile held, its
    # columns in order; so does everything where there is one column block.
    live_experts = tile_expert[:n_live]
    alone = np.isin(live_experts, [e for e in range(len(sizes))
                                   if 0 < sizes[e] <= tm])
    for s in range(steps):
        if cols == 1 or alone[s // cols]:
            assert (tile[s], col[s]) == (s // cols, s % cols)
    # A run of several tiles: column block outer, its tiles inner.
    six = int(np.flatnonzero(sizes == 48)[0])
    run = np.flatnonzero(tile_expert[tile[:steps]] == six)
    r0 = int(np.flatnonzero(live_experts == six)[0])
    assert (run == np.arange(r0 * cols, (r0 + 6) * cols)).all()
    assert (col[run] == np.repeat(np.arange(cols), 6)).all()
    assert (tile[run] == r0 + np.tile(np.arange(6), cols)).all()


def test_routed_experts_pallas_path_matches_lax(runner, force_kernels):
    """The whole routed layer with the grouped matmul on each side."""
    from starway_tpu.models.moe import routed_experts, sigmoid_route

    w = W.layer_weights(W.base_key(SEED), 1, W.dims(
        dict(TINY, n_routed_experts=4, expert_share=2)), True)["routed"]
    x = jax.random.normal(jax.random.PRNGKey(17), (21, 64))
    idx, g = sigmoid_route(x, w["router"], w["bias"], 4, 2.827)
    force_kernels(True)
    a, sa = routed_experts(x, idx, g, w, 8)
    force_kernels(False)
    b, sb = routed_experts(x, idx, g, w, 8)
    np.testing.assert_allclose(a, b, rtol=1e-4, atol=1e-4)
    np.testing.assert_array_equal(sa, sb)


def _hf_state(params, cfg):
    """A program tree written out under the published checkpoint's keys
    ([out, in] matrices, interleaved rope rows): the inverse of the map
    ``hf_convert`` applies."""
    la, r, H = cfg.latent, cfg.routed, cfg.n_heads
    order = np.concatenate([np.arange(0, la.rope_dim, 2),
                            np.arange(1, la.rope_dim, 2)])
    back = np.argsort(order)

    def interleave(w, start):
        w = w.copy()
        w[start:start + la.rope_dim] = w[start + back]
        return w

    state = {"model.embed_tokens.weight": np.asarray(params["embed"]),
             "model.norm.weight": np.asarray(params["final_norm"]),
             "lm_head.weight": np.asarray(params["lm_head"]).T}
    i = 0
    for seg in params["layers"]:
        for j in range(seg["wo"].shape[0]):
            lp = jax.tree_util.tree_map(lambda a: np.asarray(a[j]), seg)
            pre = f"model.layers.{i}."
            wq_b = lp["wq_b"].T
            for h in range(H):
                wq_b = interleave(wq_b, h * (la.nope_dim + la.rope_dim) + la.nope_dim)
            state.update({
                pre + "input_layernorm.weight": lp["attn_norm"],
                pre + "post_attention_layernorm.weight": lp["mlp_norm"],
                pre + "self_attn.q_a_proj.weight": lp["wq_a"].T,
                pre + "self_attn.q_a_layernorm.weight": lp["q_norm"],
                pre + "self_attn.q_b_proj.weight": wq_b,
                pre + "self_attn.kv_a_proj_with_mqa.weight":
                    interleave(lp["wkv_a"].T, la.kv_rank),
                pre + "self_attn.kv_a_layernorm.weight": lp["kv_norm"],
                pre + "self_attn.kv_b_proj.weight": lp["wkv_b"].T,
                pre + "self_attn.o_proj.weight": lp["wo"].T})

            def mlp(name, w):
                for ours, theirs in (("w_gate", "gate_proj"), ("w_up", "up_proj"),
                                     ("w_down", "down_proj")):
                    state[pre + f"{name}.{theirs}.weight"] = w[ours].T

            if "routed" in lp:
                rp = lp["routed"]
                state[pre + "mlp.gate.weight"] = rp["router"].T
                state[pre + "mlp.gate.e_score_correction_bias"] = rp["bias"]
                mlp("mlp.shared_experts", rp["shared"])
                for e in range(r.n_held):
                    mlp(f"mlp.experts.{r.first_held + e}",
                        {n: rp[n][e] for n in ("w_gate", "w_up", "w_down")})
            else:
                mlp("mlp", lp)
            i += 1
    return state


def test_hf_key_map_round_trip(runner):
    """``kimi_k2`` keys -> the segmented tree and back, the held share's
    experts only, with the rope rows' interleaved-to-halves permutation."""
    from types import SimpleNamespace

    from starway_tpu.models import config_from_hf, params_from_hf
    from starway_tpu.models.hf_convert import rope_rows_to_halves

    config = dict(TINY, n_routed_experts=4, expert_share=2)
    params, cfg = _model(runner, config)
    hf = SimpleNamespace(**dict(
        TINY, model_type="kimi_k2", scoring_func="sigmoid", n_group=1,
        topk_group=1, norm_topk_prob=True, moe_layer_freq=1,
        max_position_embeddings=64, hidden_act="silu"))
    got_cfg = config_from_hf(hf, held_experts=(8, 4), dtype="float32")
    assert got_cfg == cfg
    back = params_from_hf(_hf_state(params, cfg), got_cfg)
    assert jax.tree_util.tree_structure(back) == jax.tree_util.tree_structure(params)
    for a, b in zip(jax.tree_util.tree_leaves(back),
                    jax.tree_util.tree_leaves(params)):
        assert a.dtype == b.dtype
        np.testing.assert_array_equal(a, b)
    # What the permutation is for: the published rotation of interleaved
    # pairs, then put into halves, is this package's split-half rotation
    # of the permuted rows.
    x = np.arange(8.0)[:, None] + 1
    c, s = np.cos(0.3 * np.arange(4)), np.sin(0.3 * np.arange(4))
    pairs = np.stack([x[0::2, 0] * c - x[1::2, 0] * s,
                      x[0::2, 0] * s + x[1::2, 0] * c])        # [2, 4]
    halves = rope_rows_to_halves(x, 0, 8)[:, 0]
    ours = np.concatenate([halves[:4] * c - halves[4:] * s,
                           halves[:4] * s + halves[4:] * c])
    np.testing.assert_allclose(ours, pairs.reshape(-1))


def test_paged_server_refuses_a_latent_model(runner):
    from starway_tpu.models import PagedSlotServer

    params, cfg = _model(runner)
    with pytest.raises(NotImplementedError, match="latent page kind"):
        PagedSlotServer(params, cfg, n_slots=2, max_len=64, page=16)


def test_prefix_admission_reads_the_latent_cache(ref, runner):
    """A registered prefix and a suffix ingested through the chunk step
    (C > 1 absorbed queries) serve what the whole prompt serves."""
    from starway_tpu.models import SlotServer

    params, cfg = _model(runner)
    rng = np.random.default_rng(23)
    prefix = rng.integers(1, 128, 20).astype(np.int32)
    suffix = rng.integers(1, 128, 7).astype(np.int32)
    srv = SlotServer(params, cfg, n_slots=2, max_len=96, chunk=4)
    pid = srv.register_prefix(prefix)
    rid = srv.submit(suffix, 6, prefix=pid)
    got = srv.run()[rid]
    gaps = ref.served_gaps(TINY, SEED, [(np.concatenate([prefix, suffix]), got)],
                           96, 8)
    assert gaps["gap_max"] < 1e-5, gaps


# ------------------- a direct q projection and no rotation (Kimi-Linear's)


def test_nope_latent_with_a_direct_q_matches_its_reference():
    """``q_rank=None`` (one ``wq``, no low-rank pair) and a layer that does
    not rotate (``cos`` None): the expanded form equals the kimi-linear
    reference's latent attention, the absorbed form over the cached rows
    equals it too, and the cached ``k_pe`` is the projection itself."""
    from benchmark.harness import weights_kda_mla_moe as WK
    from starway_tpu.models import mla
    from starway_tpu.models.llama import cfg_rope_tables, rmsnorm
    from starway_tpu.ops import latent_attention, self_attention

    from test_kda import TINY as KL

    ref = S.load_reference("kimi-linear")
    cfg = S.load_runner("serve_kda_mla_moe").model_config(KL)
    d = WK.dims(KL)
    lp = WK.layer_weights(WK.base_key(SEED), 3, d, False, True)
    assert "wq" in lp and "wq_a" not in lp and cfg.latent.q_rank is None
    x = jax.random.normal(jax.random.PRNGKey(2), (1, 21, 64))
    want = ref._latent(x[0], lp, d, None)

    q, k, v, rows = mla.project_expanded(x, lp, cfg, None, None)
    o = self_attention(q, k, v, sm_scale=cfg.latent.sm_scale)
    got = o.transpose(0, 2, 1, 3).reshape(1, 21, -1) @ lp["wo"]
    np.testing.assert_allclose(got[0], want, rtol=2e-4, atol=2e-4)
    # The row cached is [norm(c_kv) | k_pe] as projected, zeros above.
    kv = x @ lp["wkv_a"]
    np.testing.assert_allclose(rows[0, 0, :, 32:40], kv[0, :, 32:], rtol=1e-6)
    np.testing.assert_allclose(
        rows[0, 0, :, :32], rmsnorm(kv[0, :, :32], lp["kv_norm"], cfg.norm_eps),
        rtol=1e-5, atol=1e-6)
    assert not np.asarray(rows[..., 40:]).any()
    # With tables the same layer would cache another k_pe: NoPE is a choice.
    turned = mla.latent_rows(x, lp, cfg, *cfg_rope_tables(cfg, 21))
    assert np.abs(np.asarray(turned - rows))[0, 0, 1:, 32:40].max() > 1e-3

    cache = jnp.pad(rows, ((0, 0), (0, 0), (0, 32 - 21), (0, 0)))[None]
    for t in (0, 7, 20):
        q_abs, row = mla.project_absorbed(x[:, t:t + 1], lp, cfg, None, None)
        np.testing.assert_allclose(row, rows[:, :, t:t + 1], rtol=1e-6, atol=1e-6)
        o_lat = latent_attention(q_abs, cache, jnp.asarray([t]), rank=32,
                                 sm_scale=cfg.latent.sm_scale, layer=0)
        o = mla.expand_values(o_lat, lp, cfg)
        got = o.transpose(0, 2, 1, 3).reshape(1, 1, -1) @ lp["wo"]
        np.testing.assert_allclose(got[0, 0], want[t], rtol=2e-4, atol=2e-4)


def test_a_latent_model_with_a_direct_q_serves_like_the_low_rank_one(runner):
    """The marker of a latent layer is ``wkv_a``: a tree without ``wq_a``
    prefills, decodes and matches its own forward."""
    import dataclasses

    from starway_tpu.models import forward, init_params
    from starway_tpu.models.generate import decode_step, prefill

    cfg = runner.model_config(TINY)
    cfg = dataclasses.replace(cfg, latent=dataclasses.replace(
        cfg.latent, q_rank=None))
    params = init_params(jax.random.PRNGKey(3), cfg)
    assert all("wq_a" not in seg and "wq" in seg and "wkv_a" in seg
               for seg in params["layers"])
    toks = _tokens(2, 14, seed=4)
    want = forward(params, jnp.asarray(toks), cfg)
    logits, cache = prefill(params, cfg, jnp.asarray(toks[:, :6]), 32)
    np.testing.assert_allclose(logits, want[:, 5], rtol=2e-4, atol=2e-4)
    step = jax.jit(lambda cache, tok, t: decode_step(params, cache, tok, t, cfg))
    for t in range(6, 14):
        logits, cache = step(cache, jnp.asarray(toks[:, t]), jnp.int32(t))
        np.testing.assert_allclose(logits, want[:, t], rtol=2e-4, atol=2e-4)
