"""A model with a multi-token-prediction block (``cfg.mtp``) served by
``SlotServer``: the block drafts and the model verifies inside the decode
chunk, so a step yields one or two tokens a slot, over rings longer than
their window (read under position masks) beside full rows, with the block's
own row a slot.  CPU, tiny sizes, float32, seeded weights; the model is
K-EXAONE's (benchmark/configs/k-exaone.json) cut small, and the plain
reference is the benchmark's (benchmark/configs/k-exaone_reference.py).
"""

import dataclasses
import json

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from benchmark.harness import spec as S
from starway_tpu.models import (LlamaConfig, PagedSlotServer, SlotServer,
                                generate_beam, init_cache, init_params)
from starway_tpu.models.llama import (LayerKinds, RoutedFFN, cfg_rope_tables,
                                      forward)
from starway_tpu.models.serving import request_log, step_log
from starway_tpu.models.speculative import chunk_decode_step

with open(S.BENCH / "tests" / "data" / "rehearsal_window_moe_mtp.json") as f:
    SMALL = json.load(f)["config"]


def small_config(**over) -> dict:
    """The cell's configuration at the rehearsal's tiny sizes."""
    config = S.load_config(S.load_spec(), "k-exaone")
    config.update(SMALL)
    config.update(over)
    return config


def tiny_cfg(vocab=16, mtp=1, kinds=True, **over) -> LlamaConfig:
    return LlamaConfig(
        vocab_size=vocab, d_model=32, n_layers=8 if kinds else 2, n_heads=4,
        n_kv_heads=2, d_ff=64, dtype="float32", head_dim_override=8,
        qk_norm=True, mtp=mtp,
        kinds=LayerKinds(windows=(4, 4, 4, None), rope=(1, 1, 1, 0),
                         slack=4) if kinds else None,
        routed=RoutedFFN(n_experts=8, top_k=2, d_expert=16, n_held=8,
                         first_dense=1, scale=2.5), **over)


def without_block(params, cfg):
    return ({k: v for k, v in params.items() if k != "mtp"},
            dataclasses.replace(cfg, mtp=0))


REQUESTS = [(5, 20), (9, 7), (3, 1), (12, 2), (7, 3), (30, 9), (2, 25)]


def serve(params, cfg, requests=REQUESTS, n_slots=3, max_len=64, seed=0,
          **kw):
    """{request index: tokens} and the server's drafts heard on the way."""
    heard = {}
    if cfg.mtp:
        kw.setdefault("on_logprobs", lambda rid, logp, drafts: heard.setdefault(
            rid, []).append((logp, drafts)))
    srv = SlotServer(params, cfg, n_slots=n_slots, max_len=max_len, chunk=4,
                     **kw)
    rng = np.random.default_rng(seed)
    rids = [srv.submit(rng.integers(0, cfg.vocab_size, n), m)
            for n, m in requests]
    out = srv.run()
    return [out[r] for r in rids], [heard.get(r, []) for r in rids]


# ---------------------------------------------------- against the reference


def test_served_logprobs_match_the_plain_reference():
    """Prefill, then speculative decoding through rows, masked rings that
    wrap (window 8 + 8) and the MTP block's row, sampled at temperature 1 /
    top-p 0.95: at EVERY emitted position the main model's log-probability
    as served, and every draft's ``log q``, agree with the float32
    reference's full forward over the served sequence."""
    config = small_config()
    runner = S.load_runner(config["runner"])
    from benchmark.harness import weights_k_exaone as W

    cfg = runner.model_config(config)
    assert cfg.kinds.ring == 16 and cfg.mtp == 1 and cfg.qk_norm
    params = runner.program_tree(W.make_model(11, W.dims(config)))
    requests = [(20, 30), (7, 44), (33, 12)]
    rng = np.random.default_rng(2)
    prompts = [rng.integers(1, cfg.vocab_size, n).astype(np.int32)
               for n, _m in requests]
    heard = {}
    srv = SlotServer(params, cfg, n_slots=2, max_len=96, chunk=4,
                     temperature=1.0, top_p=0.95, seed=5,
                     prompt_buckets=(16, 32, 64),
                     on_logprobs=lambda rid, lp, dr: heard.setdefault(
                         rid, []).append((lp, dr)))
    rids = [srv.submit(p, m) for p, (_n, m) in zip(prompts, requests)]
    out = srv.run()
    samples, accepted = [], 0
    for rid, prompt in zip(rids, prompts):
        logp, drafts = [], []
        for lp, dr in heard[rid]:
            drafts += [(len(logp) + at, tok, lq) for at, tok, lq, _ok in dr]
            accepted += sum(ok for *_x, ok in dr)
            logp += lp
        assert len(logp) == len(out[rid])
        # an accepted draft IS the token it stood for
        for (j, tok, _lq), ok in zip(drafts, [ok for _lp, dr in heard[rid]
                                              for *_x, ok in dr]):
            assert (out[rid][j] == tok) or not ok
        samples.append({"prompt": prompt, "tokens": out[rid], "logp": logp,
                        "drafts": drafts})
    drafted = sum(len(s["drafts"]) for s in samples)
    assert 0 < accepted < drafted          # both branches of the rule ran
    ref = S.load_reference("k-exaone")
    got = ref.served_logps(config, 11, samples, 96, 44)
    assert got["finite"] and got["tokens"] == 86 and got["drafts"] == drafted
    assert got["logp_gap_max"] < 1e-4, got
    assert got["draft_logp_gap_mean"] < 1e-4, got
    # and the int8 control is told apart by the same numbers
    low = ref.control_logps(config, 11, samples, 96, 44, "int8")
    assert low["logp_gap_mean"] > 100 * got["logp_gap_mean"], (low, got)


# ------------------------------------------------------------ greedy identity


@pytest.mark.parametrize("kinds", [True, False], ids=["rings", "rows"])
def test_greedy_speculation_emits_what_the_plain_server_emits(kinds):
    """Greedy, a 16-row vocabulary (chance acceptance runs both branches):
    the server with the block emits token for token what the same server
    emits with the block absent; slots are reused (7 requests on 3), and
    the books follow the device's count."""
    cfg = tiny_cfg(kinds=kinds)
    params = init_params(jax.random.PRNGKey(0), cfg)
    n0 = len(step_log())
    got, heard = serve(params, cfg)
    steps = [r for r in step_log()[n0:] if "spec_drafted" in r]
    want, _ = serve(*without_block(params, cfg))
    for g, w, (_n, m) in zip(got, want, REQUESTS):
        assert g.tolist() == w.tolist() and len(g) == m
    drafted = sum(r["spec_drafted"] for r in steps)
    accepted = sum(r["spec_accepted"] for r in steps)
    emitted = sum(r["spec_emitted"] for r in steps)
    assert 0 < accepted < drafted
    # every token but a request's first came out of a step
    assert emitted == sum(m - 1 for _n, m in REQUESTS)
    assert drafted + accepted >= emitted      # a budget may clip a bonus
    rows = [r for r in request_log() if "spec_accepted" in r][-len(REQUESTS):]
    assert sum(r["spec_accepted"] for r in rows) == accepted
    assert sum(ok for h in heard for _lp, dr in h for *_x, ok in dr) == accepted
    if kinds:
        assert all("kv_rows_window" in r for r in steps)


@pytest.mark.parametrize("budget", [1, 2, 3])
def test_budgets_are_kept_to_the_token(budget):
    """A request never gets more than it asked for, whatever a step
    yields: a 4-row vocabulary accepts about every second draft."""
    cfg = tiny_cfg(vocab=4, kinds=False)
    params = init_params(jax.random.PRNGKey(1), cfg)
    requests = [(3 + i, budget) for i in range(6)]
    got, heard = serve(params, cfg, requests)
    want, _ = serve(*without_block(params, cfg), requests)
    assert [g.tolist() for g in got] == [w.tolist() for w in want]
    assert all(len(g) == budget for g in got)


def test_eos_as_the_bonus_token_ends_the_request_there():
    """The bonus token of an accepted draft is the request's eos: the
    request ends with it, as the plain server's does."""
    cfg = tiny_cfg(vocab=8, kinds=False)
    params = init_params(jax.random.PRNGKey(1), cfg)
    for seed in range(12):     # a request whose accepted draft's bonus is fresh
        requests = [(4 + seed % 3, 24)]
        (tokens,), (heard,) = serve(params, cfg, requests, seed=seed)
        n, at0 = 0, None
        for logp, drafts in heard:
            for at, _tok, _lq, ok in drafts:
                j = n + at + 1          # the bonus behind an accepted draft
                if ok and at0 is None and j < len(tokens) and (
                        tokens[j] not in tokens[:j]):
                    at0 = j
            n += len(logp)
        if at0 is not None:
            break
    assert at0 is not None, "no accepted draft with a fresh bonus token"
    eos = int(tokens[at0])
    (got,), _ = serve(params, cfg, requests, seed=seed, eos_id=eos)
    (want,), _ = serve(*without_block(params, cfg), requests, seed=seed,
                       eos_id=eos)
    assert got.tolist() == want.tolist() == tokens[:at0 + 1].tolist()


def test_a_slot_at_the_caches_end_yields_one_token():
    """``pos + 2 == max_len``: the verify writes the cache's last two
    positions and the step emits one token, the request's last."""
    cfg = tiny_cfg(vocab=4)
    params = init_params(jax.random.PRNGKey(2), cfg)
    requests = [(20, 12), (5, 27)]            # prompt + budget == max_len
    got, _ = serve(params, cfg, requests, n_slots=2, max_len=32)
    want, _ = serve(*without_block(params, cfg), requests, n_slots=2,
                    max_len=32)
    assert [g.tolist() for g in got] == [w.tolist() for w in want]
    assert [len(g) for g in got] == [12, 27]


# --------------------------------------------------------- sampled: the law


def test_sampled_speculation_preserves_the_target_distribution():
    """After ``test_sampled_speculative_preserves_target_distribution``:
    the marginal of a request's THIRD token (two steps behind the
    admission's) over 4,096 slots equals the main model's own, computed
    exactly, and not the draft's."""
    V = 16
    cfg = tiny_cfg(vocab=V, kinds=False)
    params = init_params(jax.random.PRNGKey(3), cfg)
    p0, cfg0 = without_block(params, cfg)
    prompt = np.asarray([3, 7, 1, 9], np.int32)

    def marginal(depth):
        """P(token at prompt + depth) under the main model, exactly."""
        seqs, w = prompt[None], np.ones((1,))
        for _ in range(depth):
            q = np.asarray(jax.nn.softmax(
                forward(p0, jnp.asarray(seqs), cfg0)[:, -1], -1))
            w = (w[:, None] * q).reshape(-1)
            seqs = np.concatenate([np.repeat(seqs, V, 0),
                                   np.tile(np.arange(V), len(seqs))[:, None]], 1)
        q = np.asarray(jax.nn.softmax(
            forward(p0, jnp.asarray(seqs), cfg0)[:, -1], -1))
        return w @ q

    target = marginal(2)
    B = 4096
    srv = SlotServer(params, cfg, n_slots=B, max_len=16, chunk=2,
                     temperature=1.0, seed=7, prompt_buckets=(4,))
    rids = [srv.submit(prompt, 3) for _ in range(B)]
    out = srv.run()
    emp = np.bincount([out[r][2] for r in rids], minlength=V) / B
    tvd = 0.5 * np.abs(emp - target).sum()
    assert tvd < 0.06, f"TVD to the target {tvd:.3f}"
    steps = [r for r in step_log() if r["server"] == srv.server_id
             and "spec_drafted" in r]
    rate = (sum(r["spec_accepted"] for r in steps)
            / sum(r["spec_drafted"] for r in steps))
    assert 0.2 < rate < 0.95, rate            # both branches ran, often


def test_a_sampled_chunk_finds_its_nucleus_without_a_sort():
    """A sampled draft-and-verify chunk (temperature 1.0, top-p 0.95, as
    ``k-exaone.think_closed`` serves) filters three distributions a step
    (two under the accept rule, one under the draft); none of them sorts
    its vocabulary: the compiled program holds no ``sort`` over ``[..,
    V]``.  The routed FFN's ``argsort`` of a step's pairs by expert stays,
    and is another width."""
    from conftest import sorts_over
    from starway_tpu.models.serving import _compiled_chunk

    V, n, max_len = 48, 3, 64
    cfg = tiny_cfg(vocab=V)
    assert V not in (2 * n * cfg.routed.top_k, n * cfg.routed.top_k)
    params = jax.eval_shape(lambda: init_params(jax.random.PRNGKey(0), cfg))
    cache = jax.eval_shape(lambda: init_cache(cfg, n, max_len))
    i32 = jax.ShapeDtypeStruct((n,), jnp.int32)
    f32 = jax.ShapeDtypeStruct((n,), jnp.float32)
    state = (i32, i32, jax.ShapeDtypeStruct((n,), bool), i32,
             jax.eval_shape(jax.random.PRNGKey, 0))
    draft = (i32, jax.ShapeDtypeStruct((n, V), jnp.float32), f32)
    run = _compiled_chunk(cfg, n, max_len, 4, 1.0, None, 0.95, None,
                          logprobs=True)
    text = run.lower(params, cache, *state, draft).compile().as_text()
    assert " sort(" in text          # the pairs' argsort: the text shows sorts
    assert sorts_over(text, V) == []
    # The reading is not blind: the sorted form's text is caught.
    sorted_text = jax.jit(lambda l: jnp.sort(l, axis=-1)).lower(
        draft[1]).compile().as_text()
    assert len(sorts_over(sorted_text, V)) == 1


# ------------------------------------------------------------------ the ring


def test_masked_ring_crosses_its_wrap_under_accepted_and_rejected_drafts():
    """``chunk_decode_step`` over rings of window 4 + 4: a sequence is
    verified two positions a step, the second either its true next token
    (accepted: the cursor moves by two) or junk (rejected: by one, and the
    junk's entry is overwritten), past two wraps of the ring; every real
    position's logits equal whole-length masked attention's
    (``forward``)."""
    cfg = tiny_cfg(mtp=0)
    assert cfg.kinds.ring == 8
    params = init_params(jax.random.PRNGKey(4), cfg)
    rng = np.random.default_rng(0)
    T = 40
    seq = rng.integers(0, cfg.vocab_size, (2, T)).astype(np.int32)
    want = np.asarray(forward(params, jnp.asarray(seq), cfg))
    cache = init_cache(cfg, 2, 64)
    rope = cfg_rope_tables(cfg, 64)
    step = jax.jit(lambda c, t, p: chunk_decode_step(params, c, t, p, cfg, rope))
    pos, seen = np.zeros((2,), np.int32), 0
    while (pos < T - 2).all():
        accept = rng.random(2) < 0.5
        second = np.where(accept, seq[np.arange(2), pos + 1],
                          (seq[np.arange(2), pos + 1] + 1) % cfg.vocab_size)
        tokens = np.stack([seq[np.arange(2), pos], second], 1)
        logits, cache = step(cache, jnp.asarray(tokens), jnp.asarray(pos))
        logits = np.asarray(logits)
        for b in range(2):
            np.testing.assert_allclose(logits[b, 0], want[b, pos[b]],
                                       atol=2e-4, rtol=2e-4)
            if accept[b]:
                np.testing.assert_allclose(logits[b, 1], want[b, pos[b] + 1],
                                           atol=2e-4, rtol=2e-4)
                seen += 1
        pos = pos + 1 + accept
    assert seen > 8 and pos.min() > 3 * cfg.kinds.ring


def test_a_ring_of_one_window_refuses_a_chunk():
    cfg = dataclasses.replace(
        tiny_cfg(mtp=0), kinds=LayerKinds((4, 4, 4, None), (1, 1, 1, 0)))
    params = init_params(jax.random.PRNGKey(4), cfg)
    with pytest.raises(ValueError, match="window \\+ C - 1"):
        chunk_decode_step(params, init_cache(cfg, 1, 16),
                          jnp.zeros((1, 2), jnp.int32), jnp.zeros((1,), jnp.int32),
                          cfg, cfg_rope_tables(cfg, 16))


# ---------------------------------------------------------- the chip's share


def test_the_sixteen_expert_shares_add_up_to_the_uncut_layer():
    """One chip of 16 holds 1 of 16 experts here: the routed parts of all
    16 shares plus the shared expert, counted once, are the uncut layer;
    and the program's layer for one share is the reference's."""
    ref = S.load_reference("k-exaone")
    from benchmark.harness import weights_k_exaone as W
    from starway_tpu.models.moe import routed_ffn

    config = small_config(num_experts=1)
    whole = small_config(num_experts=16)
    key = W.base_key(3)
    y = jax.random.normal(jax.random.PRNGKey(9), (12, config["hidden_size"]))
    with jax.default_matmul_precision("highest"):
        dw = W.dims(whole)
        uncut = ref.ffn(y, W.layer_weights(key, 2, dw, True), dw)
        shared = ref._mlp(y, W.layer_weights(key, 2, dw, True)["routed"]["shared"],
                          None)
        parts = 0
        for share in range(16):
            d = W.dims(dict(config, expert_share=share))
            w = W.layer_weights(key, 2, d, True)
            part = ref.routed_part(y, w["routed"], d)
            parts = parts + part
            if share == 5:   # the program's layer, told which expert it holds
                cfg = S.load_runner(config["runner"]).model_config(
                    dict(config, expert_share=share))
                got, sizes = routed_ffn(y[None], w["routed"], cfg.routed)
                np.testing.assert_allclose(got[0], part + shared, atol=1e-5)
                assert int(sizes.sum()) == int((ref.route(
                    y, w["routed"], d)[0] == share).sum())
    np.testing.assert_allclose(parts + shared, uncut, atol=1e-5)


# -------------------------------------------------------------- the refusals


def _mtp_params(**over):
    cfg = tiny_cfg(**over)
    return init_params(jax.random.PRNGKey(0), cfg), cfg


@pytest.mark.parametrize("what", [
    "paged", "prefix", "beam", "kv_quant", "latent", "linear", "rolling",
    "two_drafts", "logprobs_without_block", "ingest", "short_ring"])
def test_what_speculation_refuses(what):
    if what == "paged":
        with pytest.raises(NotImplementedError, match="MTP"):
            PagedSlotServer(*_mtp_params(kinds=False), n_slots=2, max_len=32)
    elif what == "prefix":
        srv = SlotServer(*_mtp_params(kinds=False), n_slots=2, max_len=64)
        with pytest.raises(ValueError, match="MTP"):
            srv.register_prefix([1, 2, 3])
    elif what == "beam":
        params, cfg = _mtp_params(kinds=False)
        with pytest.raises(ValueError, match="MTP"):
            generate_beam(params, cfg, jnp.zeros((1, 4), jnp.int32), 4, beams=2)
    elif what == "kv_quant":
        with pytest.raises(ValueError, match="MTP"):
            tiny_cfg(kinds=False, kv_quant="int8")
    elif what == "latent":
        from starway_tpu.models.llama import LatentAttn

        with pytest.raises(ValueError, match="MTP"):
            tiny_cfg(kinds=False, latent=LatentAttn(24, 32, 16, 8, 16, 24 ** -0.5))
    elif what == "linear":
        from starway_tpu.models.llama import LinearAttn

        with pytest.raises(ValueError, match="MTP"):
            LlamaConfig(vocab_size=16, d_model=32, n_layers=4, n_heads=4,
                        n_kv_heads=2, d_ff=64, mtp=1,
                        linear=LinearAttn(2, 8), kinds=LayerKinds(
                            (None,) * 4, (0,) * 4, (1, 1, 1, 0)))
    elif what == "rolling":
        with pytest.raises(ValueError, match="MTP"):
            LlamaConfig.preset("debug", mtp=1, sliding_window=8)
    elif what == "two_drafts":
        with pytest.raises(ValueError, match="0 or 1"):
            LlamaConfig.preset("debug", mtp=2)
    elif what == "logprobs_without_block":
        params, cfg = _mtp_params(kinds=False, mtp=0)
        with pytest.raises(ValueError, match="MTP block"):
            SlotServer(params, cfg, on_logprobs=lambda *a: None)
    elif what == "ingest":
        # dense rows, and still no prompt rides the decode chunk
        srv = SlotServer(*_mtp_params(kinds=False), n_slots=2, max_len=64)
        assert srv._ingest_widths() == ()
        params, cfg = without_block(*_mtp_params(kinds=False))
        assert SlotServer(params, cfg, n_slots=2, max_len=64)._ingest_widths()
    elif what == "short_ring":
        params, cfg = _mtp_params()
        cfg = dataclasses.replace(cfg, kinds=dataclasses.replace(
            cfg.kinds, slack=0))
        srv = SlotServer(params, cfg, n_slots=2, max_len=32)
        srv.submit([1, 2, 3], 4)
        with pytest.raises(ValueError, match="slack"):
            srv.run()
