"""A decoder-hybrid-decoder (SambaY with differential attention: the
Phi-4-mini-flash-reasoning block) in one model and one ``SlotServer``:
Mamba state, window rings and ONE full layer's rows in one cache, gated
memory units and cross-attention layers that keep nothing, LayerNorm with a
bias and a tied head, against the benchmark's plain reference
(benchmark/configs/phi4-mini-flash_reference.py: float32, highest
precision, the recurrence token by token, the two softmaxes of a pair on
64-wide heads, every layer over every position), at a tenth of the depth
with the published layout (``[ssm, window] x 2, ssm, full, [gmu, cross] x
2``, window 8) and seeded weights."""

import json

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from benchmark.harness import spec as S
from benchmark.harness import weights_ssm_yoco as W

SEED = 4321
# A float32 program against a float32 reference: what is left is the order
# of the sums (paired 128-wide rows against 64-wide heads, a scan against
# one token at a time), a few 1e-5 on logits that reach 4.
TOL = dict(rtol=5e-4, atol=5e-4)


def _tiny() -> dict:
    config = S.load_config(S.load_spec(), "phi4-mini-flash")
    with open(S.BENCH / "tests" / "data" / "rehearsal_ssm_yoco.json") as f:
        config.update(json.load(f)["config"])
    return config


TINY = _tiny()


@pytest.fixture(scope="module")
def ref():
    return S.load_reference("phi4-mini-flash")


@pytest.fixture(scope="module")
def runner():
    return S.load_runner("serve_ssm_yoco")


@pytest.fixture(autouse=True)
def _highest():
    with jax.default_matmul_precision("highest"):
        yield


@pytest.fixture(scope="module")
def model(runner):
    return (runner.program_tree(W.make_model(SEED, W.dims(TINY))),
            runner.model_config(TINY))


def _tokens(n, s, seed=0):
    return np.random.default_rng(seed).integers(
        1, TINY["vocab_size"], (n, s)).astype(np.int32)


# ------------------------------------------------------ kinds and the cache


def test_kinds_in_runs_say_what_every_layer_is(model):
    from starway_tpu.models.cache import cache_spec
    from starway_tpu.models.generate import early_exit_at
    from starway_tpu.models.llama import layer_segments

    params, cfg = model
    assert cfg.kinds.mixers == ("ssm", "window") * 2 + ("ssm", "full") + (
        "gmu", "cross") * 2
    assert [cfg.cache_kind(i) for i in range(10)] == [
        "ssm", "ring", "ssm", "ring", "ssm", "full", None, None, None, None]
    assert cfg.segment_plan() == [(0, 4, False), (4, 1, False), (5, 1, False),
                                  (6, 4, False)]
    assert [(first, len(seg)) for seg, first in layer_segments(
        params["layers"])] == [(0, 2), (4, 1), (5, 1), (6, 2)]
    # a cross layer reads the full layer's rows; that layer its own
    assert [cfg.rows_layer(i) for i in (5, 7, 9)] == [0, 0, 0]
    assert early_exit_at(cfg) == 5
    spec = cache_spec(cfg, 32)
    assert {leaf.name: (leaf.layers,) + leaf.shape for leaf in spec.leaves} == {
        "k": (1, 2, 32, 16), "v": (1, 2, 32, 16),
        "k_ring": (2, 2, 8, 16), "v_ring": (2, 2, 8, 16),
        "ssm_state": (3, 8, 128), "ssm_conv": (3, 3, 128)}
    assert spec.state and spec.ring == 8 and not spec.piecewise
    assert spec.readers == 3
    assert spec.step_rows(np.array([3, 20])) == {
        "state_slots": 2, "kv_rows_full": 25, "kv_rows_window": 12,
        "kv_full_readers": 3}


@pytest.mark.parametrize("runs,match", [
    (((("cross", "full"), 1),), "full layer before any cross"),
    (((("gmu", "ssm", "full"), 1),), "ssm layer before any gmu"),
    (((("ssm", "window"), 2),), "runs names one kind"),          # no full layer
    (((("ssm", "mamba2", "full"), 1),), "runs names one kind"),
])
def test_layer_kinds_refuses_runs_it_cannot_hold(runs, match):
    from starway_tpu.models.llama import LayerKinds

    with pytest.raises(ValueError, match=match):
        LayerKinds.in_runs(runs, window=8)


@pytest.mark.parametrize("kw,match", [
    (dict(ssm=None), "ssm"),
    (dict(diff_attn=False), "diff_attn"),
    (dict(n_layers=8), "kinds.runs lays out"),
    (dict(norm="batch"), "norm must be"),
    (dict(n_kv_heads=3, n_heads=6), "diff_attn pairs"),
])
def test_config_refuses_what_goes_together(model, kw, match):
    import dataclasses

    with pytest.raises(ValueError, match=match):
        dataclasses.replace(model[1], **kw)


def test_init_params_draws_each_kinds_own_leaves(model):
    from starway_tpu.models import init_params
    from starway_tpu.models.llama import diff_lambda_init

    params, cfg = model
    fresh = init_params(jax.random.PRNGKey(0), cfg)
    assert "lm_head" not in fresh                       # tied: ONE table
    shapes = lambda t: jax.tree_util.tree_map(lambda a: (a.shape, a.dtype), t)
    assert shapes(fresh) == shapes(params)              # the benchmark's tree
    np.testing.assert_allclose(fresh["layers"][3][1]["lam0"],
                               diff_lambda_init([7, 9]))
    assert fresh["layers"][0][0]["attn_norm"].shape == (2, 2, 64)


# --------------------------------------------------- differential attention


def test_paired_layout_equals_the_four_softmax_form(model, ref):
    """One window layer's attention: two 128-wide rows a pair over a cached
    PAIR against the two softmaxes written out on 64-wide heads."""
    from starway_tpu.models.llama import (diff_combine, diff_kv, diff_q,
                                          matmul_w, resolve_attn_fn)

    params, cfg = model
    d = W.dims(TINY)
    lp = jax.tree_util.tree_map(lambda a: a[1], params["layers"][0][1])  # layer 3
    x = jax.random.normal(jax.random.PRNGKey(5), (1, 24, cfg.d_model))
    for window in (8, None):
        attend = resolve_attn_fn(cfg, None)
        if window:
            attend = jax.tree_util.Partial(attend, window=window)
        k, v = diff_kv(x, lp, cfg)
        assert k.shape == (1, 2, 24, 16)                 # pairs of 8-wide heads
        q = diff_q(x, lp, cfg)
        assert q.shape == (1, 8, 24, 16)
        np.testing.assert_array_equal(q[0, 0, :, 8:], 0)  # [q_0 | 0]
        np.testing.assert_array_equal(q[0, 1, :, :8], 0)  # [0 | q_1]
        got = matmul_w(diff_combine(attend(q, k, v), lp, cfg), lp["wo"]) + lp["bo"]
        rk, rv = ref._project_kv(x[0], lp, d, None)
        want = ref._diff_attention(x[0], lp, 3, rk, rv, d, None, window=window)
        np.testing.assert_allclose(got[0], want, **TOL)


def test_tied_head_is_the_table_transposed(model):
    from starway_tpu.models.llama import lm_head_matmul

    params, _cfg = model
    x = jax.random.normal(jax.random.PRNGKey(2), (3, 5, 64))
    np.testing.assert_allclose(lm_head_matmul(x, params),
                               x @ params["embed"].T, rtol=1e-5, atol=1e-5)
    assert "lm_head" not in params


# ------------------------------------------------- against the reference


def test_forward_matches_the_reference_on_logits(model, ref):
    from starway_tpu.models import forward

    params, cfg = model
    toks = _tokens(2, 28)
    got = forward(params, jnp.asarray(toks), cfg)
    np.testing.assert_allclose(got, ref.full_logits(TINY, SEED, toks), **TOL)


@pytest.mark.parametrize("early_exit", [True, False])
def test_prefill_then_decode_matches_the_reference(model, ref, early_exit):
    """Ragged rows through the cache: a padded bucket, then decode steps
    past one window (8)."""
    from starway_tpu.models.generate import decode_step, prefill

    params, cfg = model
    toks = _tokens(2, 30, seed=1)
    want = ref.full_logits(TINY, SEED, toks)
    lengths = np.array([11, 5])
    padded = np.where(np.arange(16)[None] < lengths[:, None], toks[:, :16], 0)
    logits, cache = prefill(params, cfg, jnp.asarray(padded), 32,
                            logit_positions=jnp.asarray(lengths - 1),
                            early_exit=early_exit)
    for b, n in enumerate(lengths):
        np.testing.assert_allclose(logits[b], want[b, n - 1], **TOL)
    pos = jnp.asarray(lengths)
    for t in range(14):
        tok = jnp.asarray([toks[b, lengths[b] + t] for b in range(2)])
        logits, cache = decode_step(params, cache, tok, pos + t, cfg)
        for b, n in enumerate(lengths):
            np.testing.assert_allclose(logits[b], want[b, n + t], **TOL)


def test_early_exit_admit_seats_what_every_layer_over_the_bucket_seats(model):
    from starway_tpu.models.generate import prefill

    params, cfg = model
    toks = jnp.asarray(_tokens(2, 16, seed=2))
    at = jnp.asarray([12, 6])
    early = prefill(params, cfg, toks, 32, logit_positions=at)
    whole = prefill(params, cfg, toks, 32, logit_positions=at, early_exit=False)
    np.testing.assert_allclose(early[0], whole[0], rtol=1e-5, atol=1e-5)
    assert set(early[1]) == set(whole[1])
    for name in whole[1]:
        np.testing.assert_allclose(early[1][name], whole[1][name],
                                   rtol=1e-6, atol=1e-6, err_msg=name)


def test_slot_server_staggered_admissions_match_the_reference(model, ref):
    """Requests admitted at different steps into a running batch, each past
    one window, slots reused: every served token is the reference's greedy
    token of ITS OWN sequence (within the tolerance's reach of a tie)."""
    from starway_tpu.models import SlotServer
    from starway_tpu.models import serving

    params, cfg = model
    srv = SlotServer(params, cfg, n_slots=2, max_len=64, chunk=3,
                     prompt_buckets=(8, 16, 32))
    rng = np.random.default_rng(3)
    prompts = [rng.integers(1, 128, n).astype(np.int32) for n in (5, 13, 9, 20)]
    new = [14, 9, 12, 10]
    rids = [srv.submit(prompts[0], new[0])]
    done = dict(srv.step())
    rids.append(srv.submit(prompts[1], new[1]))
    done.update(srv.step())
    rids += [srv.submit(p, n) for p, n in zip(prompts[2:], new[2:])]
    done.update(srv.run())
    for rid, prompt, n in zip(rids, prompts, new):
        out = np.asarray(done[rid])
        assert len(out) == n
        seq = np.concatenate([prompt, out])
        logits = np.asarray(ref.full_logits(TINY, SEED, seq[None]))[0]
        at = len(prompt) - 1 + np.arange(n)
        gap = logits[at].max(-1) - logits[at, out]
        assert gap.max() < 2e-3, (rid, gap.max())
    steps = [r for r in serving.step_log() if r["server"] == srv.server_id]
    assert all(r["kv_full_readers"] == 3 for r in steps if "state_slots" in r)
    assert all({"state_slots", "kv_rows_full", "kv_rows_window"} <= set(r)
               for r in steps if r["live"])
    admitted = [r for r in steps if r["admits"]]
    assert sum(r["admit_rows_self"] for r in admitted) == 8 + 16 + 16 + 32
    assert sum(r["admit_rows_cross"] for r in admitted) == 4   # a row a prompt


def test_generate_is_the_slot_servers_oracle(model):
    from starway_tpu.models import SlotServer, generate

    params, cfg = model
    prompt = _tokens(1, 9, seed=4)
    want = np.asarray(generate(params, cfg, jnp.asarray(prompt), 12))[0, 9:]
    srv = SlotServer(params, cfg, n_slots=2, max_len=32, prompt_buckets=(16,))
    rid = srv.submit(prompt[0], 12)
    np.testing.assert_array_equal(np.asarray(srv.run()[rid]), want)


def test_kernels_side_serves_what_the_twins_serve(model, force_kernels):
    """The whole chunk and admit programs on the kernels' side (interpret
    mode: ``sw_ssm_step``, ``sw_ssm_scan`` and the attention kernels where
    they tile) against the lax twins."""
    from starway_tpu.models import SlotServer

    params, cfg = model
    prompt = _tokens(1, 12, seed=6)[0]

    def served(on):
        force_kernels(on)
        jax.clear_caches()
        srv = SlotServer(params, cfg, n_slots=2, max_len=32, chunk=2,
                         prompt_buckets=(16,))
        rid = srv.submit(prompt, 5)
        return np.asarray(srv.run()[rid])

    np.testing.assert_array_equal(served(True), served(False))


# ------------------------------------------------------------ the refusals


@pytest.mark.parametrize("what", ["prefix", "paged", "beam", "chunk_verify",
                                  "param_specs", "ingest"])
def test_paths_that_cannot_hold_a_state_refuse_it(model, what):
    from starway_tpu.models import PagedSlotServer, SlotServer, generate_beam
    from starway_tpu.models.cache import init_cache
    from starway_tpu.models.llama import cfg_rope_tables, param_specs
    from starway_tpu.models.speculative import chunk_decode_step

    params, cfg = model
    if what == "prefix":
        srv = SlotServer(params, cfg, n_slots=2, max_len=64)
        with pytest.raises(ValueError, match="prefix caching"):
            srv.register_prefix([1, 2, 3])
    elif what == "paged":
        with pytest.raises(NotImplementedError, match="nothing to page"):
            PagedSlotServer(params, cfg, n_slots=2, max_len=64, page=16)
    elif what == "beam":
        with pytest.raises(ValueError, match="beam search"):
            generate_beam(params, cfg, jnp.asarray(_tokens(1, 4)), 3, beams=2)
    elif what == "chunk_verify":
        with pytest.raises(ValueError, match="cannot be taken"):
            chunk_decode_step(params, init_cache(cfg, 1, 32),
                              jnp.asarray(_tokens(1, 4)),
                              jnp.zeros((1,), jnp.int32), cfg,
                              cfg_rope_tables(cfg, 32))
    elif what == "param_specs":
        with pytest.raises(NotImplementedError, match="no sharding rules"):
            param_specs(cfg)
    else:   # a state cannot ride the decode chunk: admit programs only
        assert SlotServer(params, cfg, n_slots=2, max_len=64)._widths == ()
