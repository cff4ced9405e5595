"""KV-cache decode correctness: cached single-token steps must reproduce the
training forward's logits exactly (teacher forcing), and generation runs
end-to-end for dense and MoE configs."""

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from jax import lax

from starway_tpu.models import LlamaConfig, forward, init_params
from starway_tpu.models.cache import init_cache
from starway_tpu.models.generate import _filter_logits, decode_step, generate
from starway_tpu.ops.attention import NEG_BIG
from starway_tpu.models.llama import rope_tables


@pytest.fixture(scope="module")
def cfg():
    return LlamaConfig.preset("debug")


@pytest.fixture(scope="module")
def params(cfg):
    return init_params(jax.random.PRNGKey(0), cfg)


def test_cached_decode_matches_forward(cfg, params):
    B, S = 2, 12
    tokens = jnp.asarray(
        np.random.default_rng(0).integers(0, cfg.vocab_size, (B, S), dtype=np.int32)
    )
    full = forward(params, tokens, cfg)  # [B, S, V]

    cache = init_cache(cfg, B, S)
    rope = rope_tables(S, cfg.head_dim, cfg.rope_theta)
    for i in range(S):
        logits, cache = decode_step(params, cache, tokens[:, i], i, cfg, rope)
        np.testing.assert_allclose(
            np.asarray(logits), np.asarray(full[:, i, :]), atol=2e-4, rtol=2e-4
        )


def test_generate_greedy_deterministic(cfg, params):
    prompt = jnp.asarray([[1, 2, 3], [4, 5, 6]], dtype=jnp.int32)
    out1 = generate(params, cfg, prompt, max_new_tokens=5)
    out2 = generate(params, cfg, prompt, max_new_tokens=5)
    assert out1.shape == (2, 8)
    np.testing.assert_array_equal(np.asarray(out1), np.asarray(out2))
    assert int(out1.max()) < cfg.vocab_size


def test_generate_sampling_runs(cfg, params):
    prompt = jnp.asarray([[7, 8]], dtype=jnp.int32)
    out = generate(params, cfg, prompt, max_new_tokens=4, temperature=0.8,
                   key=jax.random.PRNGKey(1))
    assert out.shape == (1, 6)


def test_prefill_matches_stepwise(cfg, params):
    """One-pass flash prefill == P cached decode steps: same last-position
    logits, same cache contents."""
    from starway_tpu.models.generate import prefill

    B, P, max_len = 2, 9, 14
    tokens = jnp.asarray(
        np.random.default_rng(3).integers(0, cfg.vocab_size, (B, P), dtype=np.int32)
    )
    logits_pre, cache_pre = prefill(params, cfg, tokens, max_len)

    cache = init_cache(cfg, B, max_len)
    rope = rope_tables(max_len, cfg.head_dim, cfg.rope_theta)
    logits = None
    for i in range(P):
        logits, cache = decode_step(params, cache, tokens[:, i], i, cfg, rope)

    np.testing.assert_allclose(np.asarray(logits_pre), np.asarray(logits),
                               atol=2e-4, rtol=2e-4)
    for name in ("k", "v"):
        np.testing.assert_allclose(np.asarray(cache_pre[name]),
                                   np.asarray(cache[name]),
                                   atol=2e-5, rtol=2e-5)


def test_generate_topk1_equals_greedy(cfg, params):
    """top_k=1 sampling collapses to greedy regardless of temperature/key."""
    prompt = jnp.asarray([[1, 2, 3]], dtype=jnp.int32)
    greedy = generate(params, cfg, prompt, max_new_tokens=5)
    k1 = generate(params, cfg, prompt, max_new_tokens=5, temperature=1.3,
                  top_k=1, key=jax.random.PRNGKey(9))
    np.testing.assert_array_equal(np.asarray(greedy), np.asarray(k1))


def test_generate_top_p(cfg, params):
    """Nucleus sampling runs and tiny top_p collapses to greedy (the first
    sorted token is always kept)."""
    prompt = jnp.asarray([[4, 5]], dtype=jnp.int32)
    out = generate(params, cfg, prompt, max_new_tokens=4, temperature=0.9,
                   top_p=0.8, key=jax.random.PRNGKey(2))
    assert out.shape == (1, 6)
    greedy = generate(params, cfg, prompt, max_new_tokens=4)
    tiny = generate(params, cfg, prompt, max_new_tokens=4, temperature=1.0,
                    top_p=1e-9, key=jax.random.PRNGKey(5))
    np.testing.assert_array_equal(np.asarray(greedy), np.asarray(tiny))


def _sorted_nucleus(l, top_p):
    """The nucleus by a full sort: the form the program had before it
    searched the logits' ordered bits, kept here as the reference.  Same
    definition: exclusive prefix mass ``< top_p``, ties at the threshold
    all kept, the maximum always."""
    srt = jnp.sort(l, axis=-1)[..., ::-1]
    probs = jax.nn.softmax(srt, axis=-1)
    cum = jnp.cumsum(probs, axis=-1)
    keep = (cum - probs) < top_p
    thresh = jnp.min(jnp.where(keep, srt, jnp.inf), axis=-1, keepdims=True)
    return jnp.where(l < thresh, NEG_BIG, l)


def _nucleus_case(name):
    """``(logits, temperature, top_k, top_p)`` of one case."""
    B, V = 96, 19200
    key = jax.random.PRNGKey(38)
    normal = jax.random.normal(key, (B, V), jnp.float32)
    if name == "normal_1":
        return normal, 1.0, None, 0.95
    if name == "normal_3":
        return 3.0 * normal, 1.0, None, 0.95
    if name == "quarters":  # ties abound, at the threshold too
        return jnp.round(8.0 * normal) / 4.0, 1.0, None, 0.95
    if name == "constant":
        return jnp.full((4, V), 1.25, jnp.float32), 0.7, None, 0.3
    if name == "dominant":
        return normal[:8].at[:, 17].set(40.0), 1.0, None, 0.5
    if name == "after_top_k":
        return 2.0 * normal[:16], 0.8, 50, 0.9
    if name == "negative_only":
        return -jnp.abs(2.0 * normal[:16]) - 1.0, 1.3, None, 0.95
    if name == "signed_zeros":  # -0.0 and +0.0 are ONE value, as in a sort
        row = jnp.asarray([0.0, -0.0, 1.0, -0.0, 0.0, -1.0, -2.0], jnp.float32)
        return jnp.tile(row, (3, 1)), 1.0, None, 0.6
    if name == "top_p_one":
        return normal[:4], 1.0, None, 1.0
    if name == "top_p_none":
        return normal[:4], 1.0, None, None
    if name == "rows_of_a_verify":  # [B, 1, V], as accept_rule passes it
        return 1.5 * normal[:16, None, :], 1.0, None, 0.95
    raise AssertionError(name)


@pytest.mark.parametrize("name", [
    "normal_1", "normal_3", "quarters", "constant", "dominant",
    "after_top_k", "negative_only", "signed_zeros", "top_p_one",
    "top_p_none", "rows_of_a_verify"])
def test_filter_logits_nucleus_matches_sorted_form(name):
    """``_filter_logits`` finds top-p's threshold by a search over the
    logits' ordered bits; the sorted form is the SAME definition at the
    same precision.  The kept sets are equal, except that the one value
    at a row's nucleus boundary may fall on the other side where its own
    mass above, ``S(v)``, is within 1e-5 of ``top_p`` (the two forms add
    the same float32 probabilities in a different order): such values are
    counted, and a row may have one."""
    logits, temperature, top_k, top_p = _nucleus_case(name)
    got = np.asarray(jax.jit(
        lambda x: _filter_logits(x, temperature, top_k, top_p))(logits))
    assert got.shape == logits.shape and got.dtype == np.float32

    @jax.jit  # as the program scales: a compiled division is not eager's
    def scaled(l):
        l = l / temperature
        if top_k is not None:
            l = jnp.where(l < lax.top_k(l, top_k)[0][..., -1:], NEG_BIG, l)
        return l

    l = scaled(logits)
    if top_p is None or top_p >= 1.0:
        np.testing.assert_array_equal(got, np.asarray(l))  # no mask at all
        return
    ref = np.asarray(_sorted_nucleus(l, top_p))
    l = np.asarray(l)
    V = l.shape[-1]
    kept, kept_ref = got != NEG_BIG, ref != NEG_BIG
    # Kept entries pass through untouched; the maximum is always kept.
    np.testing.assert_array_equal(got[kept], l[kept])
    assert kept.reshape(-1, V)[np.arange(l.size // V),
                               l.reshape(-1, V).argmax(-1)].all()

    for row, k, kr in zip(l.reshape(-1, V), kept.reshape(-1, V),
                          kept_ref.reshape(-1, V)):
        values = np.unique(row[k != kr])
        assert len(values) <= 1, (name, values)
        for v in values:
            p = np.exp(row.astype(np.float64) - row.max())
            mass_above = p[row > v].sum() / p.sum()
            assert abs(mass_above - top_p) < 1e-5, (name, v, mass_above)

    n_kept = kept.reshape(-1, V).sum(-1)
    if name == "constant":
        assert (n_kept == V).all()
    if name == "dominant":
        assert (n_kept == 1).all() and kept[:, 17].all()
    if name == "after_top_k":
        assert (n_kept <= top_k).all() and not kept[l == NEG_BIG].any()
    if name == "signed_zeros":
        # S(0) = p(1.0) = 0.38 < 0.6: every zero stays, of either sign.
        assert (n_kept == 5).all()
    if name == "quarters":
        # The threshold's tie group is kept whole.
        for row, k in zip(l, kept):
            assert not np.isin(row[~k], row[k]).any()


def test_generate_tp_sharded(cfg, params):
    """Tensor-parallel inference is pure GSPMD: the same compiled generate
    over tp-sharded params produces the greedy tokens of the unsharded
    run (XLA inserts the head-dim collectives)."""
    from jax.sharding import NamedSharding

    from starway_tpu.models import param_specs
    from starway_tpu.parallel import make_mesh

    from starway_tpu.models.generate import prefill

    prompt = jnp.asarray([[3, 1, 4, 1]], dtype=jnp.int32)
    ref = generate(params, cfg, prompt, max_new_tokens=6)

    mesh = make_mesh({"tp": 2})
    sharded = jax.tree_util.tree_map(
        lambda x, s: jax.device_put(x, NamedSharding(mesh, s)),
        params, param_specs(cfg))

    # Robust property: the sharded logits match within reduction-order
    # noise (collectives reassociate the contraction over tp).
    logits_ref, _ = jax.jit(lambda p: prefill(p, cfg, prompt))(params)
    logits_tp, _ = jax.jit(lambda p: prefill(p, cfg, prompt))(sharded)
    np.testing.assert_allclose(np.asarray(logits_tp), np.asarray(logits_ref),
                               atol=1e-4, rtol=1e-3)

    # On the deterministic CPU mesh the greedy tokens also agree exactly
    # (argmax could legitimately flip on hardware where a top-2 logit gap
    # sits inside that noise; the logit check above is the contract).
    out = generate(sharded, cfg, prompt, max_new_tokens=6)
    np.testing.assert_array_equal(np.asarray(out), np.asarray(ref))


def test_generate_logprobs(cfg, params):
    """return_logprobs: each emitted token's logprob equals the
    teacher-forced log-softmax at its position (unfiltered, regardless
    of sampling settings); eos-fill positions report 0.0."""
    prompt = jnp.asarray(np.random.default_rng(3).integers(
        1, cfg.vocab_size, (2, 6), dtype=np.int32))
    P = prompt.shape[1]
    for kw in ({}, {"temperature": 0.9, "top_k": 8,
                    "key": jax.random.PRNGKey(4)}):
        out, lps = generate(params, cfg, prompt, 7, return_logprobs=True,
                            **kw)
        assert lps.shape == (2, 7)
        lp_ref = jax.nn.log_softmax(forward(params, out[:, :-1], cfg), -1)
        want = jnp.take_along_axis(
            lp_ref[:, P - 1:], out[:, P:, None], -1)[..., 0]
        np.testing.assert_allclose(np.asarray(lps), np.asarray(want),
                                   atol=2e-4, rtol=2e-4)

    # eos-fill rows report 0.0 after their first eos.
    free = generate(params, cfg, prompt, 7)
    eos = int(free[0, P + 1])
    out, lps = generate(params, cfg, prompt, 7, eos_id=eos,
                        return_logprobs=True)
    row = list(np.asarray(out[0, P:]))
    i = row.index(eos)
    assert bool((np.asarray(lps[0, i + 1:]) == 0.0).all())
    assert float(lps[0, i]) != 0.0  # the sampled eos itself is a model event


def test_generate_eos_fill(cfg, params):
    """Once a row emits eos_id it keeps emitting it; other rows continue
    unaffected (greedy tokens identical to the eos-free run up to the
    first eos)."""
    prompt = jnp.asarray([[1, 2, 3], [4, 5, 6]], dtype=jnp.int32)
    free = generate(params, cfg, prompt, max_new_tokens=6)
    # Use row 0's second greedy token as the eos: the run must match the
    # free run through that token, then fill with it.
    eos = int(free[0, prompt.shape[1] + 1])
    out = generate(params, cfg, prompt, max_new_tokens=6, eos_id=eos)
    new = np.asarray(out[:, prompt.shape[1]:])
    ref = np.asarray(free[:, prompt.shape[1]:])
    row0 = list(ref[0])
    cut = row0.index(eos)
    np.testing.assert_array_equal(new[0, :cut + 1], ref[0, :cut + 1])
    assert (new[0, cut:] == eos).all()
    # Row 1: identical until (if ever) it hits eos itself.
    if eos in list(ref[1]):
        c1 = list(ref[1]).index(eos)
        np.testing.assert_array_equal(new[1, :c1 + 1], ref[1, :c1 + 1])
        assert (new[1, c1:] == eos).all()
    else:
        np.testing.assert_array_equal(new[1], ref[1])


def test_generate_ragged_matches_per_row(cfg, params):
    """Ragged batch (right-padded, per-row lengths) must produce, for every
    row, exactly the tokens of a standalone unpadded generation of that
    row's prompt — pinning per-row positions through rope, cache writes,
    and the masked attention window."""
    rows = [[5, 1, 7, 2, 9], [3, 8], [6, 4, 2]]
    max_new = 4
    P = max(len(r) for r in rows)
    padded = jnp.asarray([r + [0] * (P - len(r)) for r in rows], jnp.int32)
    lengths = jnp.asarray([len(r) for r in rows], jnp.int32)

    got = generate(params, cfg, padded, max_new, prompt_lengths=lengths)
    assert got.shape == (len(rows), max_new)

    for b, r in enumerate(rows):
        solo = generate(params, cfg, jnp.asarray([r], jnp.int32), max_new)
        np.testing.assert_array_equal(np.asarray(got[b]),
                                      np.asarray(solo[0, len(r):]),
                                      err_msg=f"row {b}")

    with pytest.raises(ValueError):
        generate(params, cfg, padded, max_new, prompt_lengths=lengths[:2])
    with pytest.raises(ValueError, match=r"in \[1,"):
        generate(params, cfg, padded, max_new,
                 prompt_lengths=jnp.asarray([0, 2, P + 1], jnp.int32))

    # Droppy MoE refuses ragged batches: shared expert capacity means pad
    # tokens could perturb real rows' routing (provably-dropless capacity,
    # cf >= E, is the exception — tests/test_hf_convert.py's Mixtral
    # ragged pin).
    moe_cfg = LlamaConfig.preset("debug", n_experts=4)
    with pytest.raises(ValueError, match="dropless"):
        generate(init_params(jax.random.PRNGKey(1), moe_cfg), moe_cfg,
                 padded, max_new, prompt_lengths=lengths)

    # Ragged generate validates lengths on the host; under jit that would
    # silently clamp, so it must refuse traced lengths loudly.
    with pytest.raises(ValueError, match="outside jit"):
        jax.jit(lambda l: generate(params, cfg, padded, max_new,
                                   prompt_lengths=l))(lengths)


def test_generate_rejects_nonpositive_max_new(cfg, params):
    prompt = jnp.asarray([[1, 2, 3]], dtype=jnp.int32)
    with pytest.raises(ValueError, match="max_new_tokens"):
        generate(params, cfg, prompt, max_new_tokens=0)


def test_generate_moe():
    cfg = LlamaConfig.preset("debug", n_experts=4)
    params = init_params(jax.random.PRNGKey(2), cfg)
    prompt = jnp.asarray([[1, 2]], dtype=jnp.int32)
    out = generate(params, cfg, prompt, max_new_tokens=3)
    assert out.shape == (1, 5)


def test_sliding_window_cached_decode_matches_forward():
    """Windowed model end-to-end: stepping tokens through the cached decode
    path reproduces the windowed forward's logits (teacher forcing), and
    generation runs."""
    cfg = LlamaConfig.preset("debug", sliding_window=5)
    params = init_params(jax.random.PRNGKey(4), cfg)
    B, S = 2, 12
    tokens = jnp.asarray(
        np.random.default_rng(2).integers(0, cfg.vocab_size, (B, S), dtype=np.int32))
    full = forward(params, tokens, cfg)

    cache = init_cache(cfg, B, S)
    rope = rope_tables(S, cfg.head_dim, cfg.rope_theta)
    for i in range(S):
        logits, cache = decode_step(params, cache, tokens[:, i], i, cfg, rope)
        np.testing.assert_allclose(
            np.asarray(logits), np.asarray(full[:, i, :]), atol=2e-4,
            rtol=2e-4, err_msg=f"pos {i}")

    out = generate(params, cfg, tokens[:, :4], max_new_tokens=5)
    assert out.shape == (B, 9)

    # A custom attn_fn that doesn't declare window support is rejected
    # (silent full-causal on a windowed config would be a different model).
    with pytest.raises(ValueError, match="handles_window"):
        forward(params, tokens, cfg, attn_fn=lambda q, k, v: q)


def test_rolling_cache_matches_full_model():
    """Rolling O(window) decode must reproduce the windowed model exactly:
    greedy generation equals the full re-forward oracle at every step
    (prompt longer AND shorter than the window), and rolling teacher
    forcing matches forward logits past the wrap point."""
    from starway_tpu.models.cache import init_rolling_cache

    cfg = LlamaConfig.preset("debug", sliding_window=5)
    params = init_params(jax.random.PRNGKey(6), cfg)

    for P in (3, 9):  # straddles W=5
        prompt = jnp.asarray(
            np.random.default_rng(P).integers(0, cfg.vocab_size, (2, P),
                                              dtype=np.int32))
        max_new = 7
        out = generate(params, cfg, prompt, max_new)  # rolling auto-engages
        # Oracle: re-run the full windowed forward for every next token.
        toks = prompt
        for _ in range(max_new):
            logits = forward(params, toks, cfg)[:, -1]
            toks = jnp.concatenate(
                [toks, jnp.argmax(logits, -1)[:, None].astype(jnp.int32)], 1)
        np.testing.assert_array_equal(np.asarray(out), np.asarray(toks),
                                      err_msg=f"P={P}")

    # Teacher forcing through the wrap: rolling decode logits == forward.
    B, S = 2, 14
    tokens = jnp.asarray(np.random.default_rng(0).integers(
        0, cfg.vocab_size, (B, S), dtype=np.int32))
    full = forward(params, tokens, cfg)
    cache = init_rolling_cache(cfg, B)
    rope = rope_tables(S, cfg.head_dim, cfg.rope_theta)
    for i in range(S):
        logits, cache = decode_step(params, cache, tokens[:, i], i, cfg,
                                    rope, rolling=True)
        np.testing.assert_allclose(np.asarray(logits),
                                   np.asarray(full[:, i, :]),
                                   atol=2e-4, rtol=2e-4, err_msg=f"pos {i}")
    assert cache["k"].shape[3] == 5  # O(window), not O(S)

    # The COMPILED generate path must actually engage the rolling cache: its
    # lowering carries the [L, B, Hkv, W, hd] = [2, 2, 4, 5, 16] cache and
    # no full-length [.., 16, 16] cache (P=9 + max_new=7 -> max_len=16).
    # Token equality alone cannot catch the gate silently regressing to the
    # O(max_len) path.
    from starway_tpu.models.generate import _compiled_generate

    run = _compiled_generate(cfg, 2, 9, 7, 16, 0.0, None, None, False, None)
    prompt = jnp.zeros((2, 9), jnp.int32)
    txt = run.lower(params, prompt, jax.random.PRNGKey(0),
                    jnp.zeros((2,), jnp.int32)).as_text()
    assert "2x2x4x5x16" in txt, "rolling cache did not engage"
    assert "2x2x4x16x16" not in txt, "full-length cache still materialised"

    with pytest.raises(ValueError):
        init_rolling_cache(LlamaConfig.preset("debug"), 1)
    with pytest.raises(ValueError):
        decode_step(params, init_cache(cfg, B, 9), tokens[:, 0], 0, cfg,
                    rope, rolling=True)  # cache size != window


def test_prefill_rolling_matches_full():
    """Chunked O(window) prefill == the one-pass windowed prefill: same
    last-position logits, same rolling cache contents, and decoding onward
    from it reproduces full generate()."""
    from starway_tpu.models.generate import prefill, prefill_rolling

    cfg = LlamaConfig.preset("debug", sliding_window=5)
    params = init_params(jax.random.PRNGKey(8), cfg)
    B, P, W = 2, 13, 5
    prompt = jnp.asarray(np.random.default_rng(4).integers(
        0, cfg.vocab_size, (B, P), dtype=np.int32))

    logits_r, cache_r = prefill_rolling(params, cfg, prompt, chunk=4)
    assert cache_r["k"].shape[3] == W

    # Oracle cache: one-pass prefill gathered into rolling layout.
    logits_f, cache_f = prefill(params, cfg, prompt, P)
    src = (P - W) + ((jnp.arange(W) - (P - W)) % W)
    np.testing.assert_allclose(np.asarray(logits_r), np.asarray(logits_f),
                               atol=2e-4, rtol=2e-4)
    for name in ("k", "v"):
        np.testing.assert_allclose(
            np.asarray(cache_r[name]),
            np.asarray(jnp.take(cache_f[name], src, axis=3)),
            atol=2e-5, rtol=2e-5, err_msg=name)

    # Decode onward: same greedy continuation as full generate().
    full = generate(params, cfg, prompt, max_new_tokens=4)
    rope = rope_tables(P + 4, cfg.head_dim, cfg.rope_theta)
    cache, logits = cache_r, logits_r
    toks = []
    for i in range(4):
        nxt = jnp.argmax(logits, -1).astype(jnp.int32)
        toks.append(nxt)
        logits, cache = decode_step(params, cache, nxt, P + i, cfg, rope,
                                    rolling=True)
    np.testing.assert_array_equal(
        np.stack(toks, 1), np.asarray(full[:, P:]))

    # Short prompt (single cold chunk) also agrees.
    short = prompt[:, :3]
    lr, cr = prefill_rolling(params, cfg, short)
    lf, cf = prefill(params, cfg, short, W)
    np.testing.assert_allclose(np.asarray(lr), np.asarray(lf),
                               atol=2e-4, rtol=2e-4)
    for name in ("k", "v"):
        np.testing.assert_allclose(np.asarray(cr[name]),
                                   np.asarray(cf[name]),
                                   atol=2e-5, rtol=2e-5)

    with pytest.raises(ValueError):
        prefill_rolling(params, LlamaConfig.preset("debug"), prompt)
