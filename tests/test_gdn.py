"""Gated DeltaNet layers (models/kda.py's second parameterisation: one
log-decay a value head, key heads fewer than value heads, a full SiLU
output gate) beside gated grouped-query attention with partly rotated
heads, zero-centred norms and a gated shared expert, in one model and one
``SlotServer`` (the Qwen3-Next block), against the benchmark's plain
reference (benchmark/configs/qwen3-next_reference.py: float32, highest
precision, the recurrence token by token, plain causal attention, every
held expert on every token), at tiny widths with seeded weights; the
chunked prefill form with a decay a head against the token-by-token
recurrence; what a padded bucket leaves behind; and the kernels in
interpret mode against their lax twins.  KDA's own tests are
tests/test_kda.py."""

import dataclasses
import functools

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from benchmark.harness import spec as S
from benchmark.harness import weights_gdn_gqa_moe as W

TINY = {
    "hidden_size": 64, "num_attention_heads": 4, "num_key_value_heads": 2,
    "head_dim": 32, "partial_rotary_factor": 0.25,
    "full_attention_interval": 4, "linear_num_key_heads": 2,
    "linear_num_value_heads": 4, "linear_key_head_dim": 16,
    "linear_value_head_dim": 16, "linear_conv_kernel_dim": 4,
    "intermediate_size": 96, "moe_intermediate_size": 32,
    "shared_expert_intermediate_size": 32, "num_experts": 16,
    "num_experts_published": 16, "num_experts_per_tok": 4,
    "decoder_sparse_step": 1, "mlp_only_layers": [], "norm_topk_prob": True,
    "vocab_size": 128, "num_hidden_layers": 8, "rms_norm_eps": 1e-6,
    "rope_theta": 10000000, "rope_scaling": None, "torch_dtype": "float32",
}
SEED = 4321
# A float32 program against a float32 reference: what is left is the order
# of the sums (the chunked form's triangular solve against one token at a
# time; a blocked softmax against a whole one; tokens sorted by expert
# against a dense loop), a few 1e-5 on logits that reach 4.
TOL = dict(rtol=5e-4, atol=5e-4)


@pytest.fixture(scope="module")
def ref():
    return S.load_reference("qwen3-next")


@pytest.fixture(scope="module")
def runner():
    return S.load_runner("serve_gdn_gqa_moe")


@pytest.fixture(autouse=True)
def _highest():
    with jax.default_matmul_precision("highest"):
        yield


@functools.cache
def _model(runner):
    """The tiny model's weights and configuration, made once a module."""
    return (runner.program_tree(W.make_model(SEED, W.dims(TINY))),
            runner.model_config(TINY))


def _tokens(n, s, seed=0):
    return np.random.default_rng(seed).integers(
        1, TINY["vocab_size"], (n, s)).astype(np.int32)


# ------------------------------------------------------------- the config


def test_the_interval_gives_each_layer_its_kind(runner):
    """Which layers attend is computed from ``full_attention_interval``;
    every field the model forced is set from the file's own keys."""
    cfg = runner.model_config(TINY)
    assert cfg.kinds.linear == (True, True, True, False)
    assert cfg.kinds.rope == (False, False, False, True)
    assert cfg.kinds.window is None and cfg.latent is None
    la = cfg.linear
    assert (la.n_heads, la.key_heads, la.head_dim, la.decay) == (4, 2, 16, "head")
    assert (la.width, la.key_width, la.conv_width) == (64, 32, 128)
    assert (cfg.head_dim, cfg.rotary_dim, cfg.rope_dim) == (32, 8, 8)
    assert cfg.attn_gate and cfg.qk_norm and cfg.norm_zero_centred
    r = cfg.routed
    assert (r.score, r.shared_gate, r.first_dense, r.scale) == (
        "softmax", True, 0, 1.0)
    assert cfg.segment_plan() == [(0, 3, True), (3, 1, True), (4, 3, True),
                                  (7, 1, True)]
    assert [cfg.cache_kind(i) for i in range(4)] == ["linear"] * 3 + ["full"]
    other = runner.model_config(dict(TINY, full_attention_interval=2))
    assert other.kinds.linear == (True, False)


@pytest.mark.parametrize("kw,match", [
    (dict(decay="scalar"), "decay"),
    (dict(n_k_heads=3), "evenly"),
])
def test_linear_attn_refuses_what_it_cannot_hold(kw, match):
    from starway_tpu.models.llama import LinearAttn

    with pytest.raises(ValueError, match=match):
        LinearAttn(n_heads=4, head_dim=16, **kw)


@pytest.mark.parametrize("kw,match", [
    (dict(rotary_dim=7), "rotary_dim"),
    (dict(rotary_dim=64), "rotary_dim"),
])
def test_config_refuses_a_rotation_no_head_can_take(kw, match):
    from starway_tpu.models import LlamaConfig

    with pytest.raises(ValueError, match=match):
        LlamaConfig.preset("debug", head_dim_override=32, **kw)


def test_gate_and_partial_rotation_are_grouped_query_attentions():
    from starway_tpu.models import LlamaConfig
    from starway_tpu.models.llama import LatentAttn, RoutedFFN

    with pytest.raises(ValueError, match="grouped-query"):
        LlamaConfig.preset("debug", attn_gate=True,
                           latent=LatentAttn(24, 32, 16, 8, 16, 24 ** -0.5))
    with pytest.raises(ValueError, match="shared_gate"):
        RoutedFFN(n_experts=8, top_k=2, d_expert=16, n_held=8, n_shared=0,
                  shared_gate=True)


def test_init_params_draws_each_kinds_own_leaves(runner):
    from starway_tpu.models import init_params

    cfg = runner.model_config(TINY)
    params = init_params(jax.random.PRNGKey(0), cfg)
    assert len(params["layers"]) == 4
    lin, attn = params["layers"][0], params["layers"][1]
    assert set(lin["kda"]) == {"w_qkvz", "conv", "w_ba", "dt_bias", "a_log",
                               "o_norm"}
    assert lin["kda"]["w_qkvz"].shape == (3, 64, 2 * 32 + 2 * 64)
    assert lin["kda"]["conv"].shape == (3, 4, 128)
    assert lin["kda"]["dt_bias"].shape == lin["kda"]["a_log"].shape == (3, 4)
    assert lin["wo"].shape == (3, 64, 64) and "wq" not in lin
    assert attn["wq"].shape == (1, 64, 2 * 4 * 32)      # a head: q | gate
    assert attn["wk"].shape == (1, 64, 2 * 32) and "kda" not in attn
    assert lin["routed"]["shared_gate"].shape == (3, 64, 1)
    # A fresh model's norms multiply by one: zero-centred gains are zeros,
    # the DeltaNet's own output norm's ones.
    assert not np.asarray(lin["attn_norm"]).any()
    assert not np.asarray(attn["q_head_norm"]).any()
    assert not np.asarray(params["final_norm"]).any()
    assert (np.asarray(lin["kda"]["o_norm"]) == 1).all()
    shapes = jax.tree_util.tree_map(
        lambda a: a.shape, runner.program_tree(W.make_model(0, W.dims(TINY))))
    assert shapes == jax.tree_util.tree_map(lambda a: a.shape, params)


# ----------------------------------------------- the model on the normal path


def test_forward_matches_reference(ref, runner):
    from starway_tpu.models import forward

    params, cfg = _model(runner)
    toks = _tokens(2, 37)
    got = forward(params, jnp.asarray(toks), cfg)
    want = ref.full_logits(TINY, SEED, toks)
    np.testing.assert_allclose(got, want, **TOL)


@pytest.mark.parametrize("p0", [1, 9, 20])
def test_prefill_then_decode_through_state_and_rows_matches_reference(
        ref, runner, p0):
    """The chunked prefill leaves state, tails and k / v rows; the decode
    steps carry them: every step's logits equal the reference's one-pass
    forward."""
    from starway_tpu.models.generate import decode_step, prefill

    params, cfg = _model(runner)
    toks = _tokens(2, 26, seed=p0)
    want = np.asarray(ref.full_logits(TINY, SEED, toks))
    logits, cache = prefill(params, cfg, jnp.asarray(toks[:, :p0]), 32)
    assert {k: v.shape for k, v in cache.items()} == {
        "k": (2, 2, 2, 32, 32), "v": (2, 2, 2, 32, 32),
        "kda_state": (6, 2, 4, 16, 16), "kda_conv": (6, 2, 3, 128)}
    assert cache["kda_state"].dtype == jnp.float32
    np.testing.assert_allclose(logits, want[:, p0 - 1], **TOL)
    step = jax.jit(lambda cache, tok, t: decode_step(params, cache, tok, t, cfg))
    for t in range(p0, 26):
        logits, cache = step(cache, jnp.asarray(toks[:, t]), jnp.int32(t))
        np.testing.assert_allclose(logits, want[:, t], **TOL)


def test_init_cache_sizes_the_attention_layers_alone(runner):
    from starway_tpu.models.cache import cache_len, init_cache

    cfg = runner.model_config(TINY)
    small, large = init_cache(cfg, 3, 32), init_cache(cfg, 3, 256)
    assert small["kda_state"].shape == large["kda_state"].shape == (6, 3, 4, 16, 16)
    assert small["kda_conv"].shape == large["kda_conv"].shape == (6, 3, 3, 128)
    assert large["k"].shape == large["v"].shape == (2, 3, 2, 256, 32)
    assert set(large) == {"k", "v", "kda_state", "kda_conv"}
    assert cache_len(large) == 256


def _one_layer(ref, runner, i, s=21, seed=3):
    """(program output, reference output) of layer ``i`` alone on random
    rows ``h [1, s, D]``."""
    from starway_tpu.models.llama import (cfg_rope_tables, decoder_layer,
                                          resolve_attn_fn)

    d, cfg = W.dims(TINY), runner.model_config(TINY)
    w = W.layer_weights(W.base_key(SEED), i, d, d["linear"][i])
    h = jax.random.normal(jax.random.PRNGKey(seed), (1, s, 64))
    tables = (None, None) if d["linear"][i] else cfg_rope_tables(cfg, s)
    got, _aux, kv, _stats = decoder_layer(w, h, cfg, *tables,
                                          resolve_attn_fn(cfg, None))
    return got[0], ref._layer_one(h[0], w, d, None), kv


def test_a_deltanet_layer_alone_matches_the_reference(ref, runner):
    """Grouped key heads, one decay a head, the SiLU gate inside the norm."""
    got, want, kv = _one_layer(ref, runner, 1)
    np.testing.assert_allclose(got, want, rtol=2e-4, atol=2e-4)
    assert set(kv) == {"kda_state", "kda_conv"}


def test_a_gated_attention_layer_alone_matches_the_reference(ref, runner):
    """``rotary_dim < head_dim``: a quarter of each head turns; zero-centred
    head norms; the output gate before ``wo``."""
    got, want, kv = _one_layer(ref, runner, 3)
    np.testing.assert_allclose(got, want, rtol=2e-4, atol=2e-4)
    assert set(kv) == {"k", "v"}


def test_apply_rope_turns_only_the_tables_width():
    from starway_tpu.models.llama import apply_rope, rope_tables

    x = jax.random.normal(jax.random.PRNGKey(0), (2, 3, 5, 32))
    cos, sin = rope_tables(5, 8, 1e7)
    got = apply_rope(x, cos, sin)
    np.testing.assert_array_equal(got[..., 8:], x[..., 8:])
    np.testing.assert_allclose(got[..., :8], apply_rope(x[..., :8], cos, sin))
    assert np.abs(np.asarray(got[:, :, 1:, :8] - x[:, :, 1:, :8])).max() > 1e-3
    # Whole-width tables: as before.
    cos, sin = rope_tables(5, 32, 1e7)
    assert np.abs(np.asarray(apply_rope(x, cos, sin) - x))[:, :, 1:, 16:].max() > 1e-3


@pytest.mark.parametrize("zero_centred", [False, True])
def test_rmsnorm_in_both_forms(zero_centred):
    from starway_tpu.models.llama import rmsnorm

    x = jax.random.normal(jax.random.PRNGKey(1), (3, 16))
    w = 0.1 * jax.random.normal(jax.random.PRNGKey(2), (16,))
    unit = x / np.sqrt(np.mean(np.square(x), -1, keepdims=True) + 1e-6)
    want = unit * (1 + w) if zero_centred else unit * w
    np.testing.assert_allclose(rmsnorm(x, w, 1e-6, zero_centred), want,
                               rtol=1e-5, atol=1e-6)


def test_generate_greedy_is_the_references_argmax(ref, runner):
    from starway_tpu.models import generate

    params, cfg = _model(runner)
    prompt = _tokens(2, 7, seed=5)
    out = np.asarray(generate(params, cfg, jnp.asarray(prompt), 9))
    want = np.asarray(ref.full_logits(TINY, SEED, out[:, :-1]))
    np.testing.assert_array_equal(out[:, 7:], want[:, 6:].argmax(-1))


def test_slot_server_tokens_are_generates_and_the_references(ref, runner):
    """Ragged prompts through padded buckets, slots reused: every request's
    tokens are ``generate()``'s bit for bit and the reference's best; the
    step log carries the state's slots AND the full rows."""
    from starway_tpu.models import SlotServer, generate
    from starway_tpu.models import serving

    params, cfg = _model(runner)
    srv = SlotServer(params, cfg, n_slots=2, max_len=96, chunk=4,
                     prompt_buckets=(16, 32, 64))
    assert srv._widths == ()          # admit programs: no piece rides a chunk
    assert set(srv.cache) == {"k", "v", "kda_state", "kda_conv"}
    rng = np.random.default_rng(3)
    reqs = [(rng.integers(1, 128, n).astype(np.int32), m)
            for n, m in [(40, 9), (5, 12), (17, 6), (33, 5)]]
    rids = [srv.submit(p, m) for p, m in reqs]
    done = srv.run()
    for rid, (p, m) in zip(rids, reqs):
        want = np.asarray(generate(params, cfg, jnp.asarray(p[None]), m))[0, len(p):]
        np.testing.assert_array_equal(done[rid], want)
    for rid, (p, m) in list(zip(rids, reqs))[1:3]:    # a reused slot's too
        seq = np.concatenate([p, done[rid]])[None]
        best = np.asarray(ref.full_logits(TINY, SEED, seq[:, :-1]))[0].argmax(-1)
        np.testing.assert_array_equal(done[rid], best[len(p) - 1:])
    rows = [r for r in serving.step_log() if r["server"] == srv.server_id]
    assert all("state_slots" in r and "kv_rows_full" in r and "moe_assign" in r
               and "kv_rows_latent" not in r and "kv_rows_window" not in r
               for r in rows)
    assert max(r["state_slots"] for r in rows) == 2
    first = rows[0]      # both slots seated at their prompts' ends
    assert first["kv_rows_full"] == sum(len(p) + 1 for p, _m in reqs[:2])


def test_the_servers_cache_gives_the_references_logits(ref, runner):
    """Prefill through a padded admit bucket and the seat of the slot's
    state and rows, then decode steps over the SERVER's cache: by logits,
    the reference's full forward over the same tokens."""
    from starway_tpu.models import SlotServer
    from starway_tpu.models.generate import decode_step

    params, cfg = _model(runner)
    srv = SlotServer(params, cfg, n_slots=2, max_len=64, chunk=4,
                     prompt_buckets=(16, 32))
    toks = _tokens(1, 17, seed=21)[0]
    n = 11
    rid = srv.submit(toks[:n], 1)      # one token: seated, never decoded
    first = srv.run()[rid]
    want = np.asarray(ref.full_logits(TINY, SEED, toks[None]))[0]
    assert int(first[0]) == int(want[n - 1].argmax())
    step = jax.jit(lambda c, t, p: decode_step(params, c, t, p, cfg))
    cache = srv.cache
    for t in range(n, 17):             # slot 0 holds the request; slot 1 idles
        logits, cache = step(cache, jnp.asarray([toks[t], 0], jnp.int32),
                             jnp.asarray([t, 0], jnp.int32))
        np.testing.assert_allclose(logits[0], want[t], **TOL)


# ------------------------------- what a bucket's pads and an old request leave


@pytest.mark.parametrize("length", [1, 3, 4, 63, 64, 65])
def test_a_padded_admission_gives_the_unpadded_state_and_tails(runner, length):
    """A prompt right-padded to its bucket leaves the state and the
    convolution's tails of the prompt alone: the pads stand still."""
    from starway_tpu.models.generate import prefill

    params, cfg = _model(runner)
    toks = _tokens(1, length, seed=length)
    bucket = 128
    padded = np.concatenate([toks, _tokens(1, bucket - length, seed=99)], 1)
    _l, want = prefill(params, cfg, jnp.asarray(toks), length)
    run = test_a_padded_admission_gives_the_unpadded_state_and_tails.__dict__.setdefault(
        "run", jax.jit(lambda params, padded, at: prefill(
            params, cfg, padded, padded.shape[1], logit_positions=at)))
    logits, got = run(params, jnp.asarray(padded), jnp.asarray([length - 1]))
    for name in ("kda_state", "kda_conv"):
        np.testing.assert_allclose(got[name], want[name], rtol=2e-5, atol=2e-5)
    np.testing.assert_allclose(logits, _l, rtol=1e-4, atol=1e-4)
    np.testing.assert_allclose(got["k"][:, :, :, :length], want["k"],
                               rtol=2e-5, atol=2e-5)
    if length < 3:   # shorter than the taps: zeros, then the prompt's inputs
        assert not np.asarray(got["kda_conv"])[:, :, :3 - length].any()
    _l, whole = run(params, jnp.asarray(padded), jnp.asarray([bucket - 1]))
    assert np.abs(np.asarray(whole["kda_state"] - want["kda_state"])).max() > 1e-3


@pytest.mark.parametrize("what", ["prefix", "paged", "beam", "chunk_verify",
                                  "param_specs", "mtp"])
def test_paths_that_cannot_hold_a_state_refuse_it(runner, what):
    from starway_tpu.models import PagedSlotServer, SlotServer, generate_beam
    from starway_tpu.models.cache import init_cache
    from starway_tpu.models.llama import cfg_rope_tables, param_specs
    from starway_tpu.models.speculative import chunk_decode_step

    params, cfg = _model(runner)
    if what == "prefix":
        srv = SlotServer(params, cfg, n_slots=2, max_len=64)
        with pytest.raises(ValueError, match="snapshot"):
            srv.register_prefix([1, 2, 3])
    elif what == "paged":
        with pytest.raises(NotImplementedError, match="nothing to page"):
            PagedSlotServer(params, cfg, n_slots=2, max_len=64, page=16)
    elif what == "beam":
        with pytest.raises(ValueError, match="linear-attention"):
            generate_beam(params, cfg, jnp.asarray(_tokens(1, 4)), 3, beams=2)
    elif what == "chunk_verify":
        with pytest.raises(ValueError, match="linear-attention"):
            chunk_decode_step(params, init_cache(cfg, 1, 32),
                              jnp.asarray(_tokens(1, 4)), jnp.zeros((1,), jnp.int32),
                              cfg, cfg_rope_tables(cfg, 32))
    elif what == "param_specs":
        with pytest.raises(NotImplementedError, match="linear layers"):
            param_specs(cfg)
    else:
        with pytest.raises(ValueError, match="MTP block"):
            dataclasses.replace(cfg, mtp=1)


# ----------------------------------------------------------- the operations


def _recurrence(q, k, v, g, beta):
    """The gated delta rule with ONE decay a head, token by token: the
    definition."""
    def token(s, x):
        q, k, v, g, b = x
        s = s * jnp.exp(g)[..., None, None]
        u = b[..., None] * (v - jnp.einsum("bhkv,bhk->bhv", s, k))
        s = s + k[..., None] * u[..., None, :]
        return s, jnp.einsum("bhkv,bhk->bhv", s, q)

    b, _s, h, d = q.shape
    s, o = jax.lax.scan(token, jnp.zeros((b, h, d, v.shape[-1])), tuple(
        jnp.moveaxis(x, 1, 0) for x in (q, k, v, g, beta)))
    return jnp.moveaxis(o, 0, 1), s


def _operands(s, d=16, b=2, h=3, decay=3.0, seed=0):
    ks = jax.random.split(jax.random.PRNGKey(seed), 5)
    q, k, v = (jax.random.normal(x, (b, s, h, d)) for x in ks[:3])
    k = k / jnp.linalg.norm(k, axis=-1, keepdims=True)
    g = -jnp.abs(jax.random.normal(ks[3], (b, s, h))) * decay
    return q, k, v, g, jax.nn.sigmoid(jax.random.normal(ks[4], (b, s, h)))


@pytest.mark.parametrize("length,chunk", [(1, 64), (63, 64), (64, 64),
                                          (65, 64), (150, 64), (37, 8)])
def test_the_chunked_form_with_a_decay_a_head_is_the_recurrence(length, chunk):
    """At lengths that are no whole chunks too: the rest is padded with
    positions that stand still."""
    from starway_tpu.ops import kda_chunk

    x = _operands(length, seed=length)
    o, s = kda_chunk(*x, chunk=chunk)
    want_o, want_s = _recurrence(*x)
    np.testing.assert_allclose(o, want_o, rtol=2e-4, atol=2e-4)
    np.testing.assert_allclose(s, want_s, rtol=2e-4, atol=2e-4)


def test_a_decay_a_head_is_a_decay_a_channel_every_channel_alike():
    """The two forms of the chunked prefill agree where they must."""
    from starway_tpu.ops import kda_chunk

    q, k, v, g, beta = _operands(100, seed=4)
    o, s = kda_chunk(q, k, v, g, beta)
    want_o, want_s = kda_chunk(q, k, v, jnp.broadcast_to(g[..., None], q.shape),
                               beta)
    np.testing.assert_allclose(o, want_o, rtol=2e-4, atol=2e-4)
    np.testing.assert_allclose(s, want_s, rtol=2e-4, atol=2e-4)


def test_the_head_form_survives_a_decay_that_would_overflow_factored():
    """A log-decay of -40 a token: exp(-sum g) over a chunk is past
    float32, the decay BETWEEN two positions never is."""
    from starway_tpu.ops import kda_chunk

    x = _operands(130, decay=40.0, seed=7)
    o, s = kda_chunk(*x)
    want_o, want_s = _recurrence(*x)
    assert np.isfinite(np.asarray(o)).all()
    np.testing.assert_allclose(o, want_o, rtol=2e-4, atol=2e-4)
    np.testing.assert_allclose(s, want_s, rtol=2e-4, atol=2e-4)


def test_standing_positions_do_not_move_the_state():
    from starway_tpu.ops import kda_chunk

    q, k, v, g, beta = _operands(70, seed=3)
    real = jnp.arange(70) < 41
    _o, s = kda_chunk(q, k, v, jnp.where(real[:, None], g, 0.0),
                      jnp.where(real[:, None], beta, 0.0))
    _o, want = kda_chunk(q[:, :41], k[:, :41], v[:, :41], g[:, :41],
                         beta[:, :41])
    np.testing.assert_allclose(s, want, rtol=1e-5, atol=1e-5)


def test_the_decode_kernel_takes_a_heads_decay_as_a_channels():
    """``sw_kda_step`` (interpreted) fed one decay a head broadcast over a
    head's channels, key heads repeated: one token of the recurrence."""
    from starway_tpu.ops.pallas_kda import kda_step_kernel

    q, k, v, g, beta = _operands(4, h=4, seed=13)
    state = jnp.zeros((2, 2, 4, 16, 16))
    outs = []
    for t in range(4):
        o, state = kda_step_kernel(
            state, q[:, t], k[:, t], v[:, t],
            jnp.broadcast_to(g[:, t, :, None], (2, 4, 16)),
            beta[:, t], layer=jnp.int32(1), interpret=True)
        outs.append(o)
    want_o, want_s = _recurrence(q, k, v, g, beta)
    np.testing.assert_allclose(jnp.stack(outs, 1), want_o, rtol=1e-5, atol=1e-5)
    np.testing.assert_allclose(state[1], want_s, rtol=1e-5, atol=1e-5)
    assert not np.asarray(state[0]).any()


def test_the_whole_model_on_the_kernels_side(runner, force_kernels):
    """Prefill and decode with every operation on its Pallas kernel
    (interpreted): the logits of the lax side."""
    from starway_tpu.models.generate import decode_step, prefill

    params, cfg = _model(runner)
    toks = _tokens(1, 12, seed=8)

    def run():
        logits, cache = prefill(params, cfg, jnp.asarray(toks[:, :9]), 128)
        out = [logits]
        step = jax.jit(lambda cache, tok, t: decode_step(params, cache, tok, t, cfg))
        for t in range(9, 12):
            logits, cache = step(cache, jnp.asarray(toks[:, t]), jnp.int32(t))
            out.append(logits)
        return np.stack(out)

    force_kernels(False)
    want = run()
    force_kernels(True)
    jax.clear_caches()
    np.testing.assert_allclose(run(), want, rtol=2e-4, atol=2e-4)


# -------------------------------------------------- the share and the model


def test_four_shares_add_up_to_the_uncut_layer(ref, runner):
    """model-configs guide, section 4: the routed parts the 4 shares give
    (four experts each here), with the shared expert UNDER ITS GATE counted
    once, add up to what the uncut reference gives for the whole layer.
    Program and reference alike."""
    from starway_tpu.models.llama import ffn_block

    x = jax.random.normal(jax.random.PRNGKey(7), (2, 9, 64))
    flat = x.reshape(-1, 64)

    def part(config):
        d, cfg = W.dims(config), runner.model_config(config)
        w = W.layer_weights(W.base_key(SEED), 1, d, True)
        shared = ref.shared_part(flat, w["routed"])
        y, _aux, sizes = ffn_block(x, w, cfg)
        return (ref.routed_part(flat, w["routed"], d),
                y.reshape(-1, 64) - shared, shared, int(sizes.sum()), w)

    whole_ref, whole_prog, shared, pairs, whole_w = part(TINY)
    assert pairs == 2 * 9 * 4
    np.testing.assert_allclose(whole_prog, whole_ref, rtol=1e-4, atol=1e-4)
    # The gate is worked: the shared part is not the ungated expert.
    assert np.abs(np.asarray(
        shared - ref._swiglu(flat, whole_w["routed"]["shared"], None))).max() > 1e-3
    total_ref = total_prog = 0.0
    held = 0
    for share in range(4):
        r, p, sh, n, w = part(dict(TINY, num_experts=4, expert_share=share))
        np.testing.assert_array_equal(
            w["routed"]["w_up"], whole_w["routed"]["w_up"][4 * share:4 * share + 4])
        np.testing.assert_array_equal(sh, shared)      # every chip alike
        total_ref, total_prog, held = total_ref + r, total_prog + p, held + n
    assert held == 2 * 9 * 4        # every (token, choice) pair landed once
    np.testing.assert_allclose(total_ref + shared, whole_ref + shared,
                               rtol=1e-5, atol=1e-5)
    np.testing.assert_allclose(total_prog + shared, whole_ref + shared,
                               rtol=1e-4, atol=1e-4)
