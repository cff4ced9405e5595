"""Linear-attention layers (Kimi Delta Attention, models/kda.py) beside NoPE
latent layers in one model and one ``SlotServer`` (the Kimi-Linear block)
against the benchmark's plain reference
(benchmark/configs/kimi-linear_reference.py: float32, highest precision,
the recurrence token by token, expanded attention, every held expert on
every token), at tiny widths with seeded weights; the chunked prefill form
against the token-by-token recurrence; what a padded bucket and a reused
slot leave behind; and the kernels in interpret mode against their lax
twins."""

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from benchmark.harness import spec as S
from benchmark.harness import weights_kda_mla_moe as W

TINY = {
    "hidden_size": 64, "num_attention_heads": 4, "num_key_value_heads": 4,
    "q_lora_rank": None, "kv_lora_rank": 32, "qk_nope_head_dim": 16,
    "qk_rope_head_dim": 8, "v_head_dim": 16, "intermediate_size": 96,
    "moe_intermediate_size": 32, "num_experts": 16,
    "num_experts_published": 16, "num_experts_per_token": 4,
    "num_shared_experts": 1, "routed_scaling_factor": 2.446,
    "first_k_dense_replace": 1, "vocab_size": 128, "num_hidden_layers": 8,
    "rms_norm_eps": 1e-5, "rope_theta": 10000, "rope_scaling": None,
    "mla_use_nope": True,
    "linear_attn_config": {
        "full_attn_layers": [4, 8, 12], "kda_layers": [1, 2, 3, 5, 6, 7, 9, 10, 11],
        "head_dim": 16, "num_heads": 4, "short_conv_kernel_size": 4},
    "torch_dtype": "float32",
}
SEED = 4321
# A float32 program against a float32 reference: what is left is the order
# of the sums (the chunked form's triangular solve and pairwise decays
# against one token at a time; absorbed against expanded attention; tokens
# sorted by expert against a dense loop), a few 1e-5 on logits that reach 4.
TOL = dict(rtol=5e-4, atol=5e-4)


@pytest.fixture(scope="module")
def ref():
    return S.load_reference("kimi-linear")


@pytest.fixture(scope="module")
def runner():
    return S.load_runner("serve_kda_mla_moe")


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


# ------------------------------------------------------------- the config


def test_the_published_lists_give_each_layer_its_kind(runner):
    """Which layers are KDA and which latent is read from
    ``linear_attn_config``, not written down: another list, another
    model."""
    from starway_tpu.models.llama import LatentAttn, LinearAttn

    cfg = runner.model_config(TINY)
    assert cfg.kinds.linear == (True, True, True, False)
    assert cfg.kinds.rope == (False,) * 4 and cfg.kinds.window is None
    assert cfg.latent == LatentAttn(None, 32, 16, 8, 16, 24 ** -0.5)
    assert cfg.linear == LinearAttn(4, 16, 4)
    assert [cfg.cache_kind(i) for i in range(8)] == ["linear"] * 3 + ["full"] + [
        "linear"] * 3 + ["full"]
    assert cfg.layer_kind(3) == (None, False, False)
    # The leading dense layer is a segment of its own.
    assert cfg.segment_plan() == [(0, 1, False), (1, 2, True), (3, 1, True),
                                  (4, 3, True), (7, 1, True)]
    other = dict(TINY, linear_attn_config=dict(
        TINY["linear_attn_config"], kda_layers=[1, 3, 5, 7],
        full_attn_layers=[2, 4, 6, 8]))
    assert runner.model_config(other).kinds.linear == (True, False)
    with pytest.raises(ValueError, match="every layer once"):
        W.dims(dict(TINY, linear_attn_config=dict(
            TINY["linear_attn_config"], kda_layers=[1, 2, 3])))


@pytest.mark.parametrize("kw", [
    dict(windows=(None, None), rope=(False, False), linear=(True, True)),
    dict(windows=(None, 8), rope=(False, True), linear=(True, False)),
    dict(windows=(None, None), rope=(False, False), linear=(True,)),
])
def test_layer_kinds_refuses_linear_layers_it_cannot_hold(kw):
    """All layers linear, a window beside linear layers, a flag missing."""
    from starway_tpu.models.llama import LayerKinds

    with pytest.raises(ValueError):
        LayerKinds(**kw)


@pytest.mark.parametrize("kw", [
    dict(linear=None),                                   # which, not what
    dict(kinds=None),                                    # what, not which
    dict(kv_quant="int8"),
])
def test_linear_goes_with_kinds_and_a_plain_cache(kw, runner):
    import dataclasses

    cfg = runner.model_config(TINY)
    with pytest.raises(ValueError):
        dataclasses.replace(cfg, **kw)


def test_init_params_draws_each_kinds_own_leaves(runner):
    from starway_tpu.models.llama import init_params, layer_segments

    cfg = runner.model_config(TINY)
    segs = layer_segments(init_params(jax.random.PRNGKey(0), cfg)["layers"])
    assert [first for _seg, first in segs] == [0, 1, 3, 4, 7]
    for seg, first in segs:
        linear = cfg.layer_kind(first)[2]
        assert ("kda" in seg) == linear and ("wkv_a" in seg) == (not linear)
        assert "wq_a" not in seg and ("wq" in seg) == (not linear)
        assert not linear or seg["kda"]["wqkv"].shape[1:] == (64, 3 * 64)
        assert ("routed" in seg) == (first >= 1)
    kda = segs[0][0]["kda"]
    assert kda["conv"].shape == (1, 4, 3 * 64) and kda["a_log"].shape == (1, 4)
    assert segs[0][0]["wo"].shape == (1, 64, 64)


# ----------------------------------------------- the model on the normal path


def test_forward_matches_reference(ref, runner):
    from starway_tpu.models import forward

    params, cfg = _model(runner)
    toks = _tokens(2, 37)
    got = forward(params, jnp.asarray(toks), cfg)
    want = ref.full_logits(TINY, SEED, toks)
    np.testing.assert_allclose(got, want, **TOL)


@pytest.mark.parametrize("p0", [1, 9, 20])
def test_prefill_then_decode_through_all_three_leaves_matches_reference(
        ref, runner, p0):
    """The chunked prefill leaves state, tails and latent rows; the decode
    steps carry them: every step's logits equal the reference's one-pass
    forward."""
    from starway_tpu.models.generate import decode_step, prefill

    params, cfg = _model(runner)
    toks = _tokens(2, 26, seed=p0)
    want = np.asarray(ref.full_logits(TINY, SEED, toks))
    logits, cache = prefill(params, cfg, jnp.asarray(toks[:, :p0]), 32)
    assert {k: v.shape for k, v in cache.items()} == {
        "ckv": (2, 2, 1, 32, 128), "kda_state": (6, 2, 4, 16, 16),
        "kda_conv": (6, 2, 3, 3 * 64)}
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
    assert small["kda_conv"].shape == large["kda_conv"].shape == (6, 3, 3, 192)
    assert large["ckv"].shape == (2, 3, 1, 256, 128) and cache_len(large) == 256


def test_generate_greedy_is_the_references_argmax(ref, runner):
    from starway_tpu.models import generate

    params, cfg = _model(runner)
    prompt = _tokens(2, 7, seed=5)
    out = np.asarray(generate(params, cfg, jnp.asarray(prompt), 9))
    want = np.asarray(ref.full_logits(TINY, SEED, out[:, :-1]))
    np.testing.assert_array_equal(out[:, 7:], want[:, 6:].argmax(-1))


def test_slot_server_tokens_are_generates_and_the_references(ref, runner):
    """Ragged prompts through padded buckets, slots reused: every request's
    tokens are ``generate()``'s bit for bit and the reference's best."""
    from starway_tpu.models import SlotServer, generate
    from starway_tpu.models import serving

    params, cfg = _model(runner)
    srv = SlotServer(params, cfg, n_slots=2, max_len=96, chunk=4,
                     prompt_buckets=(16, 32, 64))
    assert srv._widths == ()          # admit programs: no piece rides a chunk
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
    assert all("state_slots" in r and "kv_rows_latent" in r and "moe_assign" in r
               and "kv_rows_full" not in r for r in rows)
    assert max(r["state_slots"] for r in rows) == 2
    first = rows[0]      # both slots seated at their prompts' ends
    assert first["kv_rows_latent"] == sum(len(p) + 1 for p, _m in reqs[:2])


def test_a_model_without_linear_layers_logs_no_state_fields():
    from starway_tpu.models import LlamaConfig, SlotServer, init_params, serving

    cfg = LlamaConfig.preset("debug")
    srv = SlotServer(init_params(jax.random.PRNGKey(0), cfg), cfg, n_slots=2,
                     max_len=32)
    srv.submit([1, 2, 3], 3)
    srv.run()
    assert all("state_slots" not in r for r in serving.step_log()
               if r["server"] == srv.server_id)


# ------------------------------- what a bucket's pads and an old request leave


def _padded_prefill(params, cfg, padded, at):
    """One compiled program for every length: the bucket is the shape."""
    from starway_tpu.models.generate import prefill

    run = _padded_prefill.__dict__.setdefault("run", jax.jit(
        lambda params, padded, at: prefill(params, cfg, padded, padded.shape[1],
                                           logit_positions=at)))
    return run(params, padded, at)


@pytest.mark.parametrize("length", [1, 3, 4, 63, 64, 65])
def test_a_padded_admission_gives_the_unpadded_state_and_tails(runner, length):
    """A prompt right-padded to its bucket leaves the state and the
    convolutions' tails of the prompt alone: the pads stand still."""
    from starway_tpu.models.generate import prefill

    params, cfg = _model(runner)
    toks = _tokens(1, length, seed=length)
    bucket = 128
    padded = np.concatenate([toks, _tokens(1, bucket - length, seed=99)], 1)
    _l, want = prefill(params, cfg, jnp.asarray(toks), length)
    logits, got = _padded_prefill(params, cfg, jnp.asarray(padded),
                                  jnp.asarray([length - 1]))
    for name in ("kda_state", "kda_conv"):
        np.testing.assert_allclose(got[name], want[name], rtol=2e-5, atol=2e-5)
    np.testing.assert_allclose(logits, _l, rtol=1e-4, atol=1e-4)
    # Shorter than the taps: the tails are zeros, then the prompt's inputs.
    if length < 3:
        assert not np.asarray(got["kda_conv"])[:, :, :3 - length].any()
    # And the pads really were something: the unmasked state differs.
    _l, whole = _padded_prefill(params, cfg, jnp.asarray(padded),
                                jnp.asarray([bucket - 1]))
    assert np.abs(np.asarray(whole["kda_state"] - want["kda_state"])).max() > 1e-3


def test_a_reused_slot_carries_nothing_of_the_request_before(runner):
    """One slot: a long request, then a short one.  The second's tokens are
    what it gets alone in a fresh server, and the slot's state after its
    admission is its own prompt's."""
    from starway_tpu.models import SlotServer
    from starway_tpu.models.generate import prefill

    params, cfg = _model(runner)
    kw = dict(n_slots=1, max_len=96, chunk=4, prompt_buckets=(16, 64))
    long, short = _tokens(1, 50, seed=1)[0], _tokens(1, 6, seed=2)[0]
    srv = SlotServer(params, cfg, **kw)
    srv.submit(long, 20)
    srv.run()
    before = np.asarray(srv.cache["kda_state"])
    assert np.abs(before).max() > 0
    rid = srv.submit(short, 1)          # one token: seated, never decoded
    got = srv.run()[rid]
    _l, own = prefill(params, cfg, jnp.asarray(short[None]), 6)
    np.testing.assert_allclose(srv.cache["kda_state"], own["kda_state"],
                               rtol=2e-5, atol=2e-5)
    np.testing.assert_allclose(srv.cache["kda_conv"], own["kda_conv"],
                               rtol=2e-5, atol=2e-5)
    rid = srv.submit(short, 8)
    fresh = SlotServer(params, cfg, **kw)
    alone = fresh.submit(short, 8)
    np.testing.assert_array_equal(srv.run()[rid], fresh.run()[alone])
    assert len(got) == 1


@pytest.mark.parametrize("what", ["prefix", "paged", "beam", "chunk_verify",
                                  "param_specs"])
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
    else:
        with pytest.raises(NotImplementedError, match="linear layers"):
            param_specs(cfg)


# ----------------------------------------------------------- the operations


def _recurrence(q, k, v, g, beta):
    """The gated delta rule token by token: the definition."""
    def token(s, x):
        q, k, v, g, b = x
        s = s * jnp.exp(g)[..., None]
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
    g = -jnp.abs(jax.random.normal(ks[3], (b, s, h, d))) * decay
    return q, k, v, g, jax.nn.sigmoid(jax.random.normal(ks[4], (b, s, h)))


@pytest.mark.parametrize("length,chunk", [(1, 64), (63, 64), (64, 64),
                                          (65, 64), (150, 64), (37, 8)])
def test_kda_chunk_is_the_token_by_token_recurrence(length, chunk):
    """At lengths that are no whole chunks too: the rest is padded with
    positions that stand still."""
    from starway_tpu.ops import kda_chunk

    x = _operands(length, seed=length)
    o, s = kda_chunk(*x, chunk=chunk)
    want_o, want_s = _recurrence(*x)
    np.testing.assert_allclose(o, want_o, rtol=2e-4, atol=2e-4)
    np.testing.assert_allclose(s, want_s, rtol=2e-4, atol=2e-4)


def test_kda_chunk_survives_a_decay_that_would_overflow_in_factored_form():
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
    _o, s = kda_chunk(q, k, v, jnp.where(real[:, None, None], g, 0.0),
                      jnp.where(real[:, None], beta, 0.0))
    _o, want = kda_chunk(q[:, :41], k[:, :41], v[:, :41], g[:, :41],
                         beta[:, :41])
    np.testing.assert_allclose(s, want, rtol=1e-5, atol=1e-5)


def test_kda_step_kernel_matches_lax():
    from starway_tpu.ops.pallas_kda import kda_step_kernel, kda_step_lax

    ks = jax.random.split(jax.random.PRNGKey(0), 6)
    state = jax.random.normal(ks[0], (3, 2, 4, 16, 16))
    q, k, v = (jax.random.normal(x, (2, 4, 16)) for x in ks[1:4])
    g = -jnp.abs(jax.random.normal(ks[4], (2, 4, 16)))
    beta = jax.nn.sigmoid(jax.random.normal(ks[5], (2, 4)))
    for layer in (0, 2):
        want_o, want_s = kda_step_lax(state, q, k, v, g, beta, layer=layer)
        o, s = kda_step_kernel(state, q, k, v, g, beta, layer=jnp.int32(layer),
                               interpret=True)
        np.testing.assert_allclose(o, want_o, rtol=1e-5, atol=1e-5)
        np.testing.assert_allclose(s, want_s, rtol=1e-5, atol=1e-5)
        # The other layers' states are left as they were.
        keep = [i for i in range(3) if i != layer]
        np.testing.assert_array_equal(np.asarray(s)[keep], np.asarray(state)[keep])


def test_kda_step_is_one_token_of_the_recurrence():
    from starway_tpu.ops.pallas_kda import kda_step_lax

    q, k, v, g, beta = _operands(5, seed=11)
    state = jnp.zeros((1, 2, 3, 16, 16))
    outs = []
    for t in range(5):
        o, state = kda_step_lax(state, q[:, t], k[:, t], v[:, t], g[:, t],
                                beta[:, t], layer=0)
        outs.append(o)
    want_o, want_s = _recurrence(q, k, v, g, beta)
    np.testing.assert_allclose(jnp.stack(outs, 1), want_o, rtol=1e-5, atol=1e-5)
    np.testing.assert_allclose(state[0], want_s, rtol=1e-5, atol=1e-5)


def _wide_operands(s, by_head, kind="plain", d=128, seed=0):
    """Operands at a width the fused kernel takes (a head a lane block of
    128), two value heads over one key head where the decay is a head's:
    ``plain``, ``overflow`` (a log-decay of -40 a token) or ``hard`` (beta
    near 1 and a chunk's keys nearly alike: ``A`` is nearly all ones under
    its diagonal, the solve's worst case)."""
    ks = jax.random.split(jax.random.PRNGKey(seed), 6)
    h, hk = 2, 1 if by_head else 2
    q = jax.random.normal(ks[0], (1, s, hk, d)) * d**-0.5
    k = jax.random.normal(ks[1], (1, s, hk, d))
    v = jax.random.normal(ks[2], (1, s, h, d))
    decay, shift = {"plain": (0.5, 0.0), "overflow": (40.0, 0.0),
                    "hard": (0.01, 6.0)}[kind]
    if kind == "hard":
        k = jax.random.normal(ks[5], (1, 1, hk, d)) + 0.02 * k
    k = k / jnp.linalg.norm(k, axis=-1, keepdims=True)
    g = -decay * jnp.abs(jax.random.normal(
        ks[3], (1, s, h) if by_head else (1, s, h, d)))
    return q, k, v, g, jax.nn.sigmoid(shift + jax.random.normal(ks[4], (1, s, h)))


def _on_both_sides(force_kernels, x):
    """``kda_chunk(*x)`` on the fused kernel (interpreted) and on its lax
    twin."""
    from starway_tpu.ops import kda_chunk

    force_kernels(True)
    got = kda_chunk(*x)
    force_kernels(False)
    return got, kda_chunk(*x)


DECAYS = pytest.mark.parametrize("by_head", [False, True],
                                 ids=["a_channel", "a_head"])


@DECAYS
@pytest.mark.parametrize("length", [1, 63, 64, 65, 200])
def test_fused_chunk_kernel_is_its_lax_twin(force_kernels, length, by_head):
    """``sw_kda_chunk`` builds the pairwise decays, solves and carries in
    one cell: the twin's outputs and state at lengths around a chunk's
    edge (the rest of a chunk stands still)."""
    (o, s), (want_o, want_s) = _on_both_sides(
        force_kernels, _wide_operands(length, by_head, seed=length))
    assert o.shape == want_o.shape == (1, length, 2, 128)
    np.testing.assert_allclose(o, want_o, rtol=2e-4, atol=2e-4)
    np.testing.assert_allclose(s, want_s, rtol=2e-4, atol=2e-4)


@DECAYS
def test_fused_chunk_kernel_survives_a_decay_that_would_overflow(
        force_kernels, by_head):
    """Every exponent the kernel takes is a sum of log-decays (``_sums``):
    -40 a token overflows nothing, and the twin's numbers come out."""
    x = _wide_operands(130, by_head, "overflow", seed=7)
    (o, s), (want_o, want_s) = _on_both_sides(force_kernels, x)
    assert np.isfinite(np.asarray(o)).all()
    np.testing.assert_allclose(o, want_o, rtol=2e-4, atol=2e-4)
    np.testing.assert_allclose(s, want_s, rtol=2e-4, atol=2e-4)


@DECAYS
def test_fused_chunk_kernel_solves_near_equal_keys_under_beta_near_one(
        force_kernels, by_head):
    """The solve's hard case: the kernel's block-diagonal inverses and
    block substitution (finite products of [C, C] matmuls) stay as close
    to the token-by-token recurrence as the twin's do."""
    x = _wide_operands(128, by_head, "hard", seed=2)
    (o, s), (twin_o, twin_s) = _on_both_sides(force_kernels, x)
    q, k, v, g, beta = x
    rep = v.shape[2] // q.shape[2]
    want_o, want_s = _recurrence(
        jnp.repeat(q, rep, 2), jnp.repeat(k, rep, 2), v,
        jnp.broadcast_to(g[..., None], v.shape) if by_head else g, beta)
    for got, twin, want in ((o, twin_o, want_o), (s, twin_s, want_s)):
        np.testing.assert_allclose(got, twin, rtol=2e-3, atol=2e-3)
        np.testing.assert_allclose(got, want, rtol=2e-3, atol=2e-3)


@DECAYS
def test_fused_chunk_kernel_leaves_the_unpadded_state_under_pads(
        force_kernels, by_head):
    """A bucket's pads (``g = 0``, ``beta = 0``) stand still inside the
    kernel too: the state of the 41 real positions alone."""
    from starway_tpu.ops import kda_chunk

    q, k, v, g, beta = _wide_operands(128, by_head, seed=3)
    real = jnp.arange(128)[None, :, None] < 41
    force_kernels(True)
    _o, s = kda_chunk(q, k, v, jnp.where(real if by_head else real[..., None],
                                         g, 0.0), jnp.where(real, beta, 0.0))
    _o, want = kda_chunk(q[:, :41], k[:, :41], v[:, :41], g[:, :41],
                         beta[:, :41])
    np.testing.assert_allclose(s, want, rtol=1e-5, atol=1e-5)


def test_fused_chunk_kernel_keeps_to_shapes_it_tiles(force_kernels):
    """A head narrower than a lane block, or a chunk that does not halve
    down to sublane tiles, takes the lax twin whatever the decision says."""
    from starway_tpu.ops import pallas_kda

    assert pallas_kda._kernel_takes(128, 128, 64)
    assert pallas_kda._kernel_takes(256, 128, 8)
    assert not pallas_kda._kernel_takes(16, 128, 64)
    assert not pallas_kda._kernel_takes(128, 128, 48)
    assert not pallas_kda._kernel_takes(128, 128, 4)
    force_kernels(True)
    x = _operands(70, seed=5)           # d = 16
    o, s = pallas_kda.kda_chunk(*x)
    want_o, want_s = _recurrence(*x)
    np.testing.assert_allclose(o, want_o, rtol=2e-4, atol=2e-4)
    np.testing.assert_allclose(s, want_s, rtol=2e-4, atol=2e-4)


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


def test_sixteen_shares_add_up_to_the_uncut_layer(ref, runner):
    """model-configs guide, section 4: the routed parts the 16 shares give
    (one expert each here), with the shared expert counted once, add up to
    what the uncut reference gives for the whole layer.  Program and
    reference alike."""
    from starway_tpu.models.llama import ffn_block

    x = jax.random.normal(jax.random.PRNGKey(7), (2, 9, 64))
    flat = x.reshape(-1, 64)

    def part(config):
        d, cfg = W.dims(config), runner.model_config(config)
        w = W.layer_weights(W.base_key(SEED), 1, d, True, True)
        shared = ref._swiglu(flat, w["routed"]["shared"], None)
        y, _aux, sizes = ffn_block(x, w, cfg)
        return (ref.routed_part(flat, w["routed"], d),
                y.reshape(-1, 64) - shared, shared, int(sizes.sum()), w)

    whole_ref, whole_prog, shared, pairs, whole_w = part(TINY)
    assert pairs == 2 * 9 * 4
    np.testing.assert_allclose(whole_prog, whole_ref, rtol=1e-4, atol=1e-4)
    total_ref = total_prog = 0.0
    held = 0
    for share in range(16):
        r, p, _shared, n, w = part(dict(TINY, num_experts=1, expert_share=share))
        np.testing.assert_array_equal(
            w["routed"]["w_up"][0], whole_w["routed"]["w_up"][share])
        total_ref, total_prog, held = total_ref + r, total_prog + p, held + n
    assert held == 2 * 9 * 4        # every (token, choice) pair landed once
    np.testing.assert_allclose(total_ref + shared, whole_ref + shared,
                               rtol=1e-5, atol=1e-5)
    np.testing.assert_allclose(total_prog + shared, whole_ref + shared,
                               rtol=1e-4, atol=1e-4)
