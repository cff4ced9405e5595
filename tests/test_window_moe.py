"""Layers of different attention kinds in one model (``cfg.kinds``: full NoPE
layers beside window RoPE layers, the full layers' rows and the window
layers' rings side by side in one cache) and the softmax-routed ReGLU expert
layer whose router reads the block's input (the SmallThinker block), against
the benchmark's plain reference (benchmark/configs/
smallthinker-21b_reference.py: float32, full attention matrices with the
window mask, every expert on every token), at tiny widths with seeded
weights: period [full NoPE, window, window, window] x 2, window 8."""

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from benchmark.harness import spec as S
from benchmark.harness import weights_window_moe as W

TINY = {
    "hidden_size": 64, "num_attention_heads": 4, "num_key_value_heads": 2,
    "head_dim": 16, "moe_ffn_hidden_size": 32, "moe_num_primary_experts": 8,
    "moe_num_active_primary_experts": 3,
    "moe_primary_router_apply_softmax": True, "norm_topk_prob": True,
    "vocab_size": 128, "num_hidden_layers": 8, "rms_norm_eps": 1e-6,
    "rope_theta": 1500000, "rope_scaling": None, "sliding_window_size": 8,
    "sliding_window_layout": [0, 1, 1, 1] * 3, "rope_layout": [0, 1, 1, 1] * 3,
    "torch_dtype": "float32",
}
SEED = 4321
WINDOW = 8


@pytest.fixture(scope="module")
def ref():
    return S.load_reference("smallthinker-21b")


@pytest.fixture(scope="module")
def runner():
    return S.load_runner("serve_window_moe")


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


def _jit_step(params, cfg):
    """``decode_step`` as ONE compiled program (called eagerly it builds
    its layer scans anew at every position)."""
    from starway_tpu.models.generate import decode_step

    return jax.jit(lambda cache, tok, pos: decode_step(
        params, cache, tok, pos, cfg))


# ------------------------------------------------------- the configuration


def test_the_file_keys_give_one_group_a_kind(runner):
    cfg = runner.model_config(TINY)
    assert cfg.kinds.windows == (None, 8, 8, 8)
    assert cfg.kinds.rope == (False, True, True, True)
    assert cfg.kinds.window == 8 and cfg.sliding_window is None
    # (window, rotates, linear): no layer of this model is linear.
    assert [cfg.layer_kind(i) for i in (0, 1, 4, 7)] == [
        (None, False, False), (8, True, False)] * 2
    assert cfg.kinds.linear == (False,) * 4 and cfg.linear is None
    assert cfg.kind_runs() == [(0, 1), (1, 3), (4, 1), (5, 3)]
    assert cfg.segment_plan() == [(0, 1, True), (1, 3, True), (4, 1, True),
                                  (5, 3, True)]
    r = cfg.routed
    assert (r.score, r.act, r.router_in, r.n_shared) == (
        "softmax", "relu", "attn_norm", 0)


@pytest.mark.parametrize("kw", [
    dict(windows=(None, 8), rope=(True,)),          # lengths differ
    dict(windows=(8, 8), rope=(True, True)),        # no full layer
    dict(windows=(None, None), rope=(True, True)),  # no window layer
    dict(windows=(None, 8, 16), rope=(True,) * 3),  # two window lengths
])
def test_layer_kinds_refuses_what_it_cannot_hold(kw):
    from starway_tpu.models.llama import LayerKinds

    with pytest.raises(ValueError):
        LayerKinds(**kw)


@pytest.mark.parametrize("kw", [dict(sliding_window=8),
                                dict(kv_quant="int8")])
def test_kinds_goes_with_no_whole_model_window_and_no_int8_cache(kw):
    from starway_tpu.models.llama import LayerKinds, LlamaConfig

    with pytest.raises(ValueError):
        LlamaConfig.preset("debug", kinds=LayerKinds(
            windows=(None, 8), rope=(False, True)), **kw)


@pytest.mark.parametrize("field,value", [("score", "tanh"), ("act", "gelu"),
                                         ("router_in", "embedding")])
def test_routed_ffn_refuses_an_unknown_rule(field, value):
    from starway_tpu.models.llama import RoutedFFN

    with pytest.raises(ValueError):
        RoutedFFN(n_experts=8, top_k=2, d_expert=16, n_held=8, **{field: value})


def test_init_params_stacks_one_segment_a_run_of_a_kind(runner):
    from starway_tpu.models import init_params
    from starway_tpu.models.llama import layer_segments, segment_kind

    cfg = runner.model_config(TINY)
    params = init_params(jax.random.PRNGKey(0), cfg)
    segs = layer_segments(params["layers"])
    assert [(first, seg["wq"].shape[0]) for seg, first in segs] == cfg.kind_runs()
    assert [segment_kind(cfg, seg, first) for seg, first in segs] == [
        (None, False, False), (8, True, False)] * 2
    assert "bias" not in segs[0][0]["routed"]
    assert "shared" not in segs[0][0]["routed"]
    one = jax.tree_util.tree_map(lambda *xs: jnp.concatenate(xs),
                                 *params["layers"])
    with pytest.raises(ValueError, match="one segment"):
        segment_kind(cfg, one, 0)


# ------------------------------------- (a), (b): against the plain reference


def test_forward_matches_reference(ref, runner):
    from starway_tpu.models import forward

    params, cfg = _model(runner)
    toks = _tokens(2, 40)
    got = forward(params, jnp.asarray(toks), cfg)
    want = ref.full_logits(TINY, SEED, toks)
    np.testing.assert_allclose(got, want, rtol=2e-4, atol=2e-4)


def test_forward_returns_each_kinds_entries_under_its_own_names(runner):
    from starway_tpu.models import forward

    params, cfg = _model(runner)
    _logits, kv = forward(params, jnp.asarray(_tokens(2, 12)), cfg,
                          return_kv=True)
    assert {n: x.shape for n, x in kv.items()} == {
        "k": (2, 2, 2, 12, 16), "v": (2, 2, 2, 12, 16),
        "k_ring": (6, 2, 2, 12, 16), "v_ring": (6, 2, 2, 12, 16)}
    with pytest.raises(ValueError, match="attn_fn must be None"):
        forward(params, jnp.asarray(_tokens(1, 4)), cfg,
                attn_fn=lambda q, k, v: q)


@pytest.mark.parametrize("p0", [5, 11, 19])
def test_prefill_then_decode_through_both_caches_matches_reference(
        ref, runner, p0):
    """A prompt shorter than the window, longer, and longer than two: the
    prefill fills the full layers' rows and folds the window layers' last
    8 positions into their rings; decode steps then write both kinds at
    their own cursors, past two more wraps of the ring, and every step's
    logits equal the reference's one-pass forward."""
    from starway_tpu.models.generate import prefill

    params, cfg = _model(runner)
    toks = _tokens(2, 40, seed=1)
    want = np.asarray(ref.full_logits(TINY, SEED, toks))
    logits, cache = prefill(params, cfg, jnp.asarray(toks[:, :p0]), 48)
    assert {n: x.shape for n, x in cache.items()} == {
        "k": (2, 2, 2, 48, 16), "v": (2, 2, 2, 48, 16),
        "k_ring": (6, 2, 2, WINDOW, 16), "v_ring": (6, 2, 2, WINDOW, 16)}
    np.testing.assert_allclose(logits, want[:, p0 - 1], rtol=2e-4, atol=2e-4)
    step = _jit_step(params, cfg)
    for p in range(p0, toks.shape[1]):
        logits, cache = step(cache, jnp.asarray(toks[:, p]),
                             jnp.full((2,), p, jnp.int32))
        np.testing.assert_allclose(logits, want[:, p], rtol=2e-4, atol=2e-4)


def test_ragged_prefill_folds_each_rows_own_window(ref, runner):
    from starway_tpu.models.generate import prefill

    params, cfg = _model(runner)
    toks = _tokens(2, 30, seed=2)
    want = np.asarray(ref.full_logits(TINY, SEED, toks))
    lens = np.asarray([6, 21])
    logits, cache = prefill(params, cfg, jnp.asarray(toks[:, :24]), 32,
                            logit_positions=jnp.asarray(lens - 1))
    for b in range(2):
        np.testing.assert_allclose(logits[b], want[b, lens[b] - 1],
                                   rtol=2e-4, atol=2e-4)
    pos = jnp.asarray(lens, jnp.int32)
    step = _jit_step(params, cfg)
    for i in range(6):
        tok = jnp.asarray([toks[b, lens[b] + i] for b in range(2)])
        logits, cache = step(cache, tok, pos + i)
        for b in range(2):
            np.testing.assert_allclose(logits[b], want[b, lens[b] + i],
                                       rtol=2e-4, atol=2e-4)


def test_ring_fold_keeps_the_last_window_at_its_residues():
    from starway_tpu.models.cache import ring_fold

    a = jnp.arange(2 * 20, dtype=jnp.float32).reshape(1, 2, 1, 20, 1)
    out = np.asarray(ring_fold(a, jnp.asarray([5, 19]), 8))[0, :, 0, :, 0]
    # Row 0 (5 real positions): slots 0..4 hold themselves, the rest junk.
    np.testing.assert_array_equal(out[0, :5], np.arange(5))
    # Row 1 (19 real): positions 11..18, each at p % 8.
    want = np.zeros(8)
    for p in range(11, 19):
        want[p % 8] = 20 + p
    np.testing.assert_array_equal(out[1], want)


# ----------------------------------------------- (c), (f): the slot server


def _served(runner, prompts, wants, **kw):
    from starway_tpu.models import SlotServer

    params, cfg = _model(runner)
    srv = SlotServer(params, cfg, **kw)
    rids = [srv.submit(p, m) for p, m in zip(prompts, wants)]
    done = srv.run()
    return params, cfg, srv, [done[r] for r in rids]


def test_slot_server_tokens_are_generates_and_the_references(ref, runner):
    """Ragged prompts shorter and longer than the window, more requests
    than slots (slots reused, their rings overwritten): every request gets
    ``generate()``'s tokens, and each is the reference's best at its
    position (gap 0 up to float32 rounding)."""
    from starway_tpu.models import generate

    rng = np.random.default_rng(3)
    prompts = [rng.integers(1, 128, n).astype(np.int32)
               for n in (5, 17, 9, 30, 3, 12)]
    wants = [6, 11, 4, 9, 13, 21]
    params, cfg, srv, got = _served(runner, prompts, wants, n_slots=2,
                                    max_len=64, chunk=4)
    assert srv._widths == () and not srv.rolling
    assert set(srv.cache) == {"k", "v", "k_ring", "v_ring"}
    for p, m, g in zip(prompts, wants, got):
        alone = np.asarray(generate(params, cfg, jnp.asarray(p[None]), m))
        np.testing.assert_array_equal(g, alone[0, len(p):])
    gaps = ref.served_gaps(TINY, SEED, list(zip(prompts, got)), 64, 24)
    assert gaps["finite"] and gaps["gap_max"] < 1e-5, gaps


def test_step_log_counts_the_positions_each_kind_attends(runner):
    """``kv_rows_full`` / ``kv_rows_window`` against a hand count: two
    slots, prompts of 5 and 12, a window of 8, chunks of 4."""
    from starway_tpu.models import serving

    prompts = [np.arange(1, 6, dtype=np.int32), np.arange(1, 13, dtype=np.int32)]
    _p, _c, srv, _got = _served(runner, prompts, [10, 6], n_slots=2,
                                max_len=32, chunk=4)
    rows = [r for r in serving.step_log() if r["server"] == srv.server_id]
    # Cursors at each chunk's first step: the prompt's length, then + 4 a
    # chunk while the request lives (the second ends inside chunk 2).
    hand = [((5, 12)), ((9, 16)), ((13,))]
    assert [(r["kv_rows_full"], r["kv_rows_window"]) for r in rows] == [
        (sum(p + 1 for p in at), sum(min(p + 1, WINDOW) for p in at))
        for at in hand]
    assert all(r["moe_assign"] == 2 * 3 * 8 * 4 for r in rows)


def test_a_model_of_one_kind_logs_no_kv_rows():
    from starway_tpu.models import LlamaConfig, SlotServer, init_params, serving

    cfg = LlamaConfig.preset("debug")
    srv = SlotServer(init_params(jax.random.PRNGKey(0), cfg), cfg, n_slots=2,
                     max_len=32, chunk=4)
    srv.submit([1, 2, 3], 3)
    srv.run()
    rows = [r for r in serving.step_log() if r["server"] == srv.server_id]
    assert rows and all("kv_rows_full" not in r for r in rows)


@pytest.mark.parametrize("what", ["prefix", "paged", "beam", "chunk_verify"])
def test_paths_that_cannot_hold_rings_refuse_them(runner, what):
    from starway_tpu.models import PagedSlotServer, SlotServer, generate_beam
    from starway_tpu.models.cache import init_cache
    from starway_tpu.models.llama import cfg_rope_tables
    from starway_tpu.models.speculative import chunk_decode_step

    params, cfg = _model(runner)
    if what == "prefix":
        srv = SlotServer(params, cfg, n_slots=2, max_len=64)
        with pytest.raises(ValueError, match="prefix caching"):
            srv.register_prefix([1, 2, 3])
    elif what == "paged":
        with pytest.raises(NotImplementedError, match="window"):
            PagedSlotServer(params, cfg, n_slots=2, max_len=64, page=16)
    elif what == "beam":
        with pytest.raises(ValueError, match="full caches"):
            generate_beam(params, cfg, jnp.asarray(_tokens(1, 4)), 3, beams=2)
    else:
        with pytest.raises(ValueError, match="rings"):
            chunk_decode_step(params, init_cache(cfg, 1, 32),
                              jnp.asarray(_tokens(1, 4)), jnp.zeros((1,), jnp.int32),
                              cfg, cfg_rope_tables(cfg, 32))


# ------------------------ (g): a whole-model window still serves as before


def test_a_whole_model_window_still_serves_through_rolling_slots():
    """``cfg.sliding_window`` stays beside ``cfg.kinds``: every layer's
    cache is a ring under ``k`` / ``v``, admission is the chunked
    ``prefill_rolling``, and the tokens are the rolling primitives' own
    and the full-cache model's."""
    from conftest import rolling_primitive_oracle

    from starway_tpu.models import LlamaConfig, SlotServer, generate, init_params

    cfg = LlamaConfig.preset("debug", sliding_window=WINDOW)
    params = init_params(jax.random.PRNGKey(5), cfg)
    srv = SlotServer(params, cfg, n_slots=2, max_len=64, chunk=4)
    assert srv.rolling and srv._widths == ()
    assert {n: x.shape[3] for n, x in srv.cache.items()} == {
        "k": WINDOW, "v": WINDOW}
    rng = np.random.default_rng(9)
    prompts = [rng.integers(1, 512, n).astype(np.int32) for n in (5, 19, 11)]
    rids = [srv.submit(p, 12) for p in prompts]
    done = srv.run()
    oracle = rolling_primitive_oracle(params, cfg)
    for p, r in zip(prompts, rids):
        np.testing.assert_array_equal(done[r], oracle(p, 12, 64))
        full = np.asarray(generate(params, cfg, jnp.asarray(p[None]), 12))
        np.testing.assert_array_equal(done[r], full[0, len(p):])


@pytest.mark.parametrize("p", [5, 8, 19])
def test_generates_rolling_cache_is_the_ring_fold(p):
    """``generate()`` on a whole-model window folds its prefill into the
    ring with the fold the window layers of a ``cfg.kinds`` model take:
    the same tokens as a full cache with the window mask."""
    from starway_tpu.models import LlamaConfig, generate, init_params
    from starway_tpu.models.generate import prefill

    cfg = LlamaConfig.preset("debug", sliding_window=WINDOW)
    params = init_params(jax.random.PRNGKey(6), cfg)
    prompt = jnp.asarray(_tokens(2, p, seed=p) % 512)
    out = np.asarray(generate(params, cfg, prompt, 14))
    logits, cache = prefill(params, cfg, prompt, 40)   # whole rows, masked
    toks = []
    step = _jit_step(params, cfg)
    for i in range(14):
        tok = jnp.argmax(logits, -1).astype(jnp.int32)
        toks.append(np.asarray(tok))
        logits, cache = step(cache, tok, jnp.full((2,), p + i, jnp.int32))
    np.testing.assert_array_equal(out[:, p:], np.stack(toks, 1))


# --------------------------------- (d), (e): the routed layer and its kernel


def test_softmax_relu_routed_ffn_is_a_dense_loop_over_experts(ref, runner):
    """``routed_ffn`` with softmax scoring, ReLU, no shared expert and the
    router on another input than the experts', against every expert on
    every token weighted by its gate (the reference)."""
    from starway_tpu.models.llama import ffn_block

    cfg = runner.model_config(TINY)
    w = W.layer_weights(W.base_key(SEED), 2, W.dims(TINY))
    ky, kr = jax.random.split(jax.random.PRNGKey(7))
    y, r = (jax.random.normal(k, (2, 9, 64)) for k in (ky, kr))
    got, _aux, sizes = ffn_block(y, w, cfg, attn_in=r)
    want = ref.routed_part(y.reshape(-1, 64), r.reshape(-1, 64), w["routed"],
                           W.dims(TINY))
    np.testing.assert_allclose(got.reshape(-1, 64), want, rtol=1e-4, atol=1e-4)
    assert int(sizes.sum()) == 2 * 9 * 3
    # The router's input decides the choice: with the experts' own input
    # in its place other experts are chosen.
    other, _aux, _sizes = ffn_block(y, w, cfg, attn_in=y)
    assert not np.allclose(other, got, atol=1e-3)


def test_softmax_gates_are_the_softmax_over_the_chosen_logits():
    from starway_tpu.models.moe import softmax_route

    x = jax.random.normal(jax.random.PRNGKey(3), (7, 16))
    router = jax.random.normal(jax.random.PRNGKey(4), (16, 10))
    idx, gates = softmax_route(x, router, 4, 1.0)
    logits = np.asarray(x @ router)
    for t in range(7):
        top = np.argsort(-logits[t])[:4]
        assert set(top) == set(np.asarray(idx[t]))
        z = np.exp(logits[t, np.asarray(idx[t])] - logits[t, top[0]])
        np.testing.assert_allclose(gates[t], z / z.sum(), rtol=1e-5)
    np.testing.assert_allclose(gates.sum(-1), 1.0, rtol=1e-6)


def test_routed_experts_pallas_path_matches_lax_with_relu(force_kernels):
    """The whole routed layer with the grouped matmul on each side."""
    from starway_tpu.models.moe import routed_experts, softmax_route

    w = W.layer_weights(W.base_key(SEED), 1, W.dims(
        dict(TINY, experts_held=4, expert_share=1)))["routed"]
    x = jax.random.normal(jax.random.PRNGKey(17), (21, 64))
    idx, g = softmax_route(x, w["router"], 3, 1.0)
    force_kernels(True)
    a, sa = routed_experts(x, idx, g, w, 4, act="relu")
    force_kernels(False)
    b, sb = routed_experts(x, idx, g, w, 4, act="relu")
    np.testing.assert_allclose(a, b, rtol=1e-4, atol=1e-4)
    np.testing.assert_array_equal(sa, sb)
    c, _sc = routed_experts(x, idx, g, w, 4, act="silu")
    assert not np.allclose(b, c, atol=1e-3)


# ------------------------------------------- the decode kernel over a ring


@pytest.mark.parametrize("pos", [[0, 3, 7], [8, 21, 300]])
def test_ring_attention_is_window_attention_over_whole_rows(pos):
    """A ring written at ``pos % T`` and attended whole equals the window
    mask over whole rows, before and after the ring wraps."""
    from starway_tpu.ops import cached_attention

    T, L, B, H, D = 8, 2, 3, 2, 16
    ks = jax.random.split(jax.random.PRNGKey(21), 3)
    full_k = jax.random.normal(ks[0], (L, B, H, 304, D))
    full_v = jax.random.normal(ks[1], (L, B, H, 304, D))
    q = jax.random.normal(ks[2], (B, 2 * H, 1, D))
    pos = jnp.asarray(pos, jnp.int32)
    # Ring slot s holds the latest position p <= pos with p % T == s.
    src = pos[:, None] - (pos[:, None] - jnp.arange(T)[None, :]) % T
    fold = lambda a: jnp.take_along_axis(
        a, jnp.clip(src, 0)[None, :, None, :, None], axis=3)
    want = cached_attention(q, full_k, full_v, pos, layer=1, window=T)
    got = cached_attention(q, fold(full_k), fold(full_v), pos, layer=1,
                           ring=True)
    np.testing.assert_allclose(got, want, rtol=1e-5, atol=1e-5)
    # A ring LONGER than its window (LayerKinds.slack) is read under the
    # masks of the positions its slots hold: here 12 slots for a window of
    # 8, and a second query a row (a draft beside the pending token).
    R = 12
    top = pos[:, None] + 1
    src = top - (top - jnp.arange(R)[None, :]) % R
    fold = lambda a: jnp.take_along_axis(
        a, jnp.clip(src, 0)[None, :, None, :, None], axis=3)
    q2 = jax.random.normal(ks[2], (B, 2 * H, 2, D))
    want = cached_attention(q2, full_k, full_v, pos, layer=1, window=T)
    got = cached_attention(q2, fold(full_k), fold(full_v), pos, layer=1,
                           ring=True, window=T)
    np.testing.assert_allclose(got, want, rtol=1e-5, atol=1e-5)
    with pytest.raises(ValueError, match="longer than its window"):
        cached_attention(q, full_k[..., :T, :], full_v[..., :T, :], pos,
                         layer=1, window=T, ring=True)
    with pytest.raises(ValueError, match="one position a step"):
        cached_attention(q2, full_k[..., :T, :], full_v[..., :T, :], pos,
                         layer=1, ring=True)


def test_ring_decode_kernel_matches_lax(force_kernels):
    """The kernel (interpreted) over a ring, cursors before and far past
    its length; its name in a compiled program is tests/test_aot_tpu.py's."""
    from starway_tpu.ops import cached_attention

    T, L, B, Hkv, Hq, D = 256, 2, 3, 2, 14, 128
    ks = jax.random.split(jax.random.PRNGKey(22), 3)
    k = jax.random.normal(ks[0], (L, B, Hkv, T, D))
    v = jax.random.normal(ks[1], (L, B, Hkv, T, D))
    q = jax.random.normal(ks[2], (B, Hq, 1, D))
    pos = jnp.asarray([5, 255, 9000], jnp.int32)
    force_kernels(False)
    want = cached_attention(q, k, v, pos, layer=1, ring=True)
    force_kernels(True)
    fn = jax.jit(lambda q, k, v, pos: cached_attention(q, k, v, pos, layer=1,
                                                       ring=True))
    np.testing.assert_allclose(fn(q, k, v, pos), want, rtol=2e-5, atol=2e-5)
    # The same ring as one LONGER than a window of 128, two queries a row:
    # every slot under the mask of the position it holds, cold slots out.
    q2 = jax.random.normal(ks[2], (B, Hq, 2, D))
    masked = lambda q, k, v, pos: cached_attention(
        q, k, v, pos, layer=1, ring=True, window=128)
    force_kernels(False)
    want = masked(q2, k, v, pos)
    force_kernels(True)
    np.testing.assert_allclose(jax.jit(masked)(q2, k, v, pos), want,
                               rtol=2e-5, atol=2e-5)


def test_both_cache_kinds_on_the_kernels_side(runner, force_kernels):
    """The whole program on the kernels' side (interpreted): windowed flash
    prefill, the grouped matmul with ReLU, the in-place write of both kinds
    of leaves and the decode kernel over rows and over rings give the lax
    side's logits."""
    from starway_tpu.models.generate import prefill

    params, cfg = _model(runner)
    toks = _tokens(2, 22, seed=5)

    def run():
        logits, cache = prefill(params, cfg, jnp.asarray(toks[:, :13]), 32)
        out, step = [logits], _jit_step(params, cfg)
        for p in range(13, 22):
            logits, cache = step(cache, jnp.asarray(toks[:, p]),
                                 jnp.full((2,), p, jnp.int32))
            out.append(logits)
        return np.stack(out)

    force_kernels(False)
    want = run()
    force_kernels(True)
    np.testing.assert_allclose(run(), want, rtol=2e-4, atol=2e-4)
