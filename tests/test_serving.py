"""Continuous batching (SlotServer): greedy outputs bit-identical to the
standalone generate() oracle for every request under slot reuse, queuing,
eos, and mixed lengths; sampled mode sanity; input validation."""

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from starway_tpu.models import LlamaConfig, SlotServer, init_params
from starway_tpu.models.generate import generate


@pytest.fixture(scope="module")
def cfg():
    return LlamaConfig.preset("debug")


@pytest.fixture(scope="module")
def params(cfg):
    return init_params(jax.random.PRNGKey(0), cfg)


def _oracle(params, cfg, prompt, max_new, eos_id=None):
    out = generate(params, cfg, jnp.asarray([prompt], jnp.int32), max_new,
                   eos_id=eos_id)
    toks = np.asarray(out[0, len(prompt):])
    if eos_id is not None and eos_id in toks:
        toks = toks[: list(toks).index(eos_id) + 1]  # server stops at eos
    return toks


def test_continuous_batching_matches_generate(cfg, params):
    """More requests than slots, mixed prompt lengths and budgets: every
    request's greedy continuation equals its standalone generate() run —
    slot cohabitation and reuse must not leak between requests."""
    rng = np.random.default_rng(0)
    reqs = [(list(rng.integers(1, cfg.vocab_size, n)), m)
            for n, m in [(3, 6), (7, 4), (12, 9), (5, 1), (2, 11), (9, 3)]]

    srv = SlotServer(params, cfg, n_slots=2, max_len=64, chunk=4)
    rids = [srv.submit(p, m) for p, m in reqs]
    done = srv.run()

    assert sorted(done) == sorted(rids)
    for rid, (prompt, max_new) in zip(rids, reqs):
        want = _oracle(params, cfg, prompt, max_new)
        np.testing.assert_array_equal(
            done[rid], want, err_msg=f"request {rid} (P={len(prompt)}, "
                                     f"N={max_new})")


def test_continuous_batching_eos(cfg, params):
    """eos-terminated requests free their slot early; outputs match the
    oracle's eos-truncated stream (terminating eos included)."""
    prompt = [5, 1, 7, 2, 9]
    free = _oracle(params, cfg, prompt, 8)
    eos = int(free[1])  # force an early stop on the second token

    srv = SlotServer(params, cfg, n_slots=2, max_len=64, chunk=4, eos_id=eos)
    rid_a = srv.submit(prompt, 8)
    rid_b = srv.submit([3, 8, 6], 5)
    done = srv.run()

    want = _oracle(params, cfg, prompt, 8, eos_id=eos)
    np.testing.assert_array_equal(done[rid_a], want)
    assert done[rid_a][-1] == eos and len(done[rid_a]) <= 8
    np.testing.assert_array_equal(
        done[rid_b], _oracle(params, cfg, [3, 8, 6], 5, eos_id=eos))


def test_staggered_admission_matches_generate(cfg, params):
    """Requests submitted BETWEEN decode chunks (the continuous part):
    late arrivals join mid-flight and still match their oracle."""
    srv = SlotServer(params, cfg, n_slots=2, max_len=64, chunk=3)
    r0 = srv.submit([4, 2, 8, 1], 9)
    srv.step()  # r0 is now mid-generation
    r1 = srv.submit([6, 6, 3], 7)  # joins while r0 decodes
    done = srv.run()
    np.testing.assert_array_equal(done[r0],
                                  _oracle(params, cfg, [4, 2, 8, 1], 9))
    np.testing.assert_array_equal(done[r1],
                                  _oracle(params, cfg, [6, 6, 3], 7))


def test_sampled_serving_is_wellformed(cfg, params):
    """Sampled mode: tokens in-vocab, budgets respected (sampling keys
    differ from generate()'s chain, so only shape/validity is pinned)."""
    srv = SlotServer(params, cfg, n_slots=2, max_len=64, chunk=4,
                     temperature=0.8, top_k=16, top_p=0.9, seed=3)
    rids = [srv.submit([1, 2, 3], 6), srv.submit([9, 9], 4)]
    done = srv.run()
    assert len(done[rids[0]]) == 6 and len(done[rids[1]]) == 4
    for toks in done.values():
        assert ((toks >= 0) & (toks < cfg.vocab_size)).all()


def test_long_prompt_uses_top_bucket(cfg, params):
    """A prompt in (max_len/2, max_len - max_new] must be servable: the
    default buckets cover the full cache (regression: prompts past the
    last power-of-two bucket were accepted by submit then crashed at
    admission, losing the request)."""
    prompt = list(np.random.default_rng(4).integers(1, cfg.vocab_size, 40))
    srv = SlotServer(params, cfg, n_slots=1, max_len=64, chunk=4)
    rid = srv.submit(prompt, 5)
    done = srv.run()
    np.testing.assert_array_equal(done[rid], _oracle(params, cfg, prompt, 5))


def test_serving_validation(cfg, params):
    srv = SlotServer(params, cfg, n_slots=1, max_len=32)
    with pytest.raises(ValueError, match="max_new"):
        srv.submit([1, 2], 0)
    with pytest.raises(ValueError, match="empty"):
        srv.submit([], 3)
    with pytest.raises(ValueError, match="exceeds"):
        srv.submit(list(range(1, 30)), 10)
    with pytest.raises(ValueError, match="n_slots"):
        SlotServer(params, cfg, n_slots=0)
    with pytest.raises(ValueError, match="chunk"):
        SlotServer(params, cfg, chunk=0)
    moe_cfg = LlamaConfig.preset("debug", n_experts=4)  # default cf 1.25:
    with pytest.raises(ValueError, match="dropless"):   # droppy -> refuse
        SlotServer(init_params(jax.random.PRNGKey(1), moe_cfg), moe_cfg)


def test_rolling_continuous_batching(cfg, params):
    """Sliding-window continuous batching: per-slot rolling caches, no
    prompt bucketing.  Oracle = a single-request loop over the SAME
    primitives (prefill_rolling + rolling decode_step + greedy sample) —
    bit-exact, so any cross-slot leak or cursor slip shows.  A second
    sanity bound: outputs match generate()'s aligned rolling path up to
    its (documented) bit-close-not-bit-equal chunked-prefill algebra."""
    from conftest import rolling_primitive_oracle

    wcfg = LlamaConfig.preset("debug", sliding_window=8)
    wparams = init_params(jax.random.PRNGKey(2), wcfg)
    oracle = rolling_primitive_oracle(wparams, wcfg)

    # Admission math sanity: the chunk+stepper state builder agrees with
    # one-shot prefill_rolling (bit-close; their partial-merge orders
    # differ) on next-token logits.
    from starway_tpu.models.generate import prefill_rolling
    from starway_tpu.models.serving import _rolling_prefill_state

    probe = np.asarray([5, 1, 7, 2, 9, 4, 3, 8, 6], np.int32)
    l_hybrid, _ = _rolling_prefill_state(wparams, wcfg, probe)
    l_oneshot, _ = prefill_rolling(wparams, wcfg, jnp.asarray(probe[None]))
    np.testing.assert_allclose(np.asarray(l_hybrid), np.asarray(l_oneshot),
                               atol=1e-4, rtol=1e-3)

    # Prompts straddle the window (longer and shorter than W=8).
    reqs = [([5, 1, 7, 2, 9, 4, 3, 8, 6, 2, 7], 6), ([3, 8], 9),
            ([1, 2, 3, 4, 5, 6, 7, 8, 9, 1, 2, 3, 4], 4)]
    srv = SlotServer(wparams, wcfg, n_slots=2, max_len=64, chunk=4)
    rids = [srv.submit(p, m) for p, m in reqs]
    done = srv.run()
    for rid, (prompt, max_new) in zip(rids, reqs):
        np.testing.assert_array_equal(
            done[rid], oracle(prompt, max_new, 64),
            err_msg=f"request {rid} (P={len(prompt)})")


def test_prefix_caching_matches_generate(cfg, params):
    """Prefix caching: requests sharing a registered prefix must generate
    exactly what standalone generate(prefix + suffix) produces — the
    prefix rows are written once, suffixes ingest through the slot's own
    cache at decode-path semantics, and cohabiting requests (with and
    without prefixes, different prefixes) never leak."""
    rng = np.random.default_rng(7)
    pre_a = list(rng.integers(1, cfg.vocab_size, 9))
    pre_b = list(rng.integers(1, cfg.vocab_size, 4))
    reqs = [  # (suffix, max_new, which prefix)
        (list(rng.integers(1, cfg.vocab_size, 3)), 6, "a"),
        (list(rng.integers(1, cfg.vocab_size, 7)), 4, "a"),
        (list(rng.integers(1, cfg.vocab_size, 2)), 8, "b"),
        (list(rng.integers(1, cfg.vocab_size, 5)), 5, None),
        (list(rng.integers(1, cfg.vocab_size, 1)), 7, "a"),
    ]

    srv = SlotServer(params, cfg, n_slots=2, max_len=64, chunk=4)
    pids = {"a": srv.register_prefix(pre_a),
            "b": srv.register_prefix(pre_b), None: None}
    pres = {"a": pre_a, "b": pre_b, None: []}
    rids = [srv.submit(s, m, prefix=pids[w]) for s, m, w in reqs]
    done = srv.run()

    assert sorted(done) == sorted(rids)
    for rid, (suffix, max_new, which) in zip(rids, reqs):
        want = _oracle(params, cfg, pres[which] + suffix, max_new)
        np.testing.assert_array_equal(
            done[rid], want,
            err_msg=f"request {rid} (prefix={which}, S={len(suffix)})")


def test_prefix_caching_int8_cache(params):
    """Prefix rows, suffix ingest, and decode all ride the int8 cache
    format (scale leaves share the T-axis-at-3 layout the masked prefix
    write relies on)."""
    cfg8 = LlamaConfig.preset("debug", kv_quant="int8")
    rng = np.random.default_rng(8)
    pre = list(rng.integers(1, cfg8.vocab_size, 6))
    suf = list(rng.integers(1, cfg8.vocab_size, 3))

    srv = SlotServer(params, cfg8, n_slots=2, max_len=64, chunk=4)
    pid = srv.register_prefix(pre)
    rid = srv.submit(suf, 6, prefix=pid)
    done = srv.run()
    want = _oracle(params, cfg8, pre + suf, 6)
    np.testing.assert_array_equal(done[rid], want)


def test_prefix_validation(cfg, params):
    srv = SlotServer(params, cfg, n_slots=1, max_len=64, chunk=2)
    with pytest.raises(KeyError):
        srv.submit([1, 2], 4, prefix=99)
    pid = srv.register_prefix([1, 2, 3])
    with pytest.raises(ValueError, match="exceeds"):
        srv.submit([1] * 40, 30, prefix=pid)  # prefix + suffix + new > 64
    with pytest.raises(ValueError, match="smallest suffix bucket"):
        # A prefix no submit() could ever use refuses at registration,
        # before its prefill is burned.
        srv.register_prefix([1] * 62)
    # Dropping under a QUEUED request refuses (mid-step failure would
    # destroy that step's harvested results); after it runs, drop works.
    rid = srv.submit([4, 5], 3, prefix=pid)
    with pytest.raises(ValueError, match="referenced"):
        srv.drop_prefix(pid)
    assert rid in srv.run()
    srv.drop_prefix(pid)
    with pytest.raises(KeyError):
        srv.submit([1, 2], 4, prefix=pid)
    rolling = SlotServer(params,
                         LlamaConfig.preset("debug", sliding_window=8),
                         n_slots=1, max_len=32, chunk=2)
    with pytest.raises(ValueError, match="rolling"):
        rolling.register_prefix([1, 2, 3])


def test_moe_continuous_batching_dropless():
    """Provably-dropless MoE (Mixtral-style) serves through continuous
    batching: cohabiting slots cannot perturb each other's routing, so
    every request matches its solo generate() oracle; a droppy capacity
    still refuses."""
    mcfg = LlamaConfig.preset("debug", n_experts=4, moe_top_k=2,
                              moe_swiglu=True, moe_capacity_factor=4.0)
    mparams = init_params(jax.random.PRNGKey(2), mcfg)
    rng = np.random.default_rng(9)
    reqs = [(list(rng.integers(1, mcfg.vocab_size, n)), m)
            for n, m in [(3, 5), (6, 4), (2, 6)]]

    srv = SlotServer(mparams, mcfg, n_slots=2, max_len=64, chunk=4)
    rids = [srv.submit(p, m) for p, m in reqs]
    done = srv.run()
    for rid, (prompt, max_new) in zip(rids, reqs):
        np.testing.assert_array_equal(
            done[rid], _oracle(mparams, mcfg, prompt, max_new),
            err_msg=f"request {rid}")

    droppy = LlamaConfig.preset("debug", n_experts=4,
                                moe_capacity_factor=1.25)
    with pytest.raises(ValueError, match="dropless"):
        SlotServer(init_params(jax.random.PRNGKey(3), droppy), droppy,
                   n_slots=2, max_len=64)


@pytest.mark.parametrize("flavour", ["qwen2", "gemma"])
def test_family_configs_serve_continuously(flavour):
    """The family knobs (Qwen2 projection biases; Gemma GeGLU + scaled
    embeddings) flow through the slot server's admit/decode programs:
    every request matches its solo generate() oracle."""
    kw = (dict(attn_bias=True) if flavour == "qwen2"
          else dict(mlp_act="gelu_tanh", scaled_embed=True))
    fcfg = LlamaConfig.preset("debug", **kw)
    fparams = init_params(jax.random.PRNGKey(5), fcfg)
    if flavour == "qwen2":
        # Zero-init biases would make the flag a no-op; randomise.
        fparams["layers"]["bq"] = 0.3 * jax.random.normal(
            jax.random.PRNGKey(6), fparams["layers"]["bq"].shape)
    rng = np.random.default_rng(10)
    reqs = [(list(rng.integers(1, fcfg.vocab_size, n)), m)
            for n, m in [(4, 5), (7, 3)]]
    srv = SlotServer(fparams, fcfg, n_slots=2, max_len=64, chunk=4)
    rids = [srv.submit(p, m) for p, m in reqs]
    done = srv.run()
    for rid, (prompt, max_new) in zip(rids, reqs):
        np.testing.assert_array_equal(
            done[rid], _oracle(fparams, fcfg, prompt, max_new),
            err_msg=f"{flavour} request {rid}")


def test_fuzz_request_stream_with_prefixes(cfg, params):
    """Randomised stream: random lengths/budgets, random prefix reuse,
    staggered submission between steps — every request still matches its
    generate(prefix + suffix) oracle (the serving analogue of the engine
    fuzz tests)."""
    rng = np.random.default_rng(1234)
    srv = SlotServer(params, cfg, n_slots=3, max_len=64, chunk=3)
    pres = [list(rng.integers(1, cfg.vocab_size, int(n)))
            for n in rng.integers(2, 12, 3)]
    pids = [srv.register_prefix(p) for p in pres]

    want, done = {}, {}
    for i in range(14):
        which = int(rng.integers(-1, 3))  # -1 = no prefix
        suffix = list(rng.integers(1, cfg.vocab_size, int(rng.integers(1, 8))))
        max_new = int(rng.integers(1, 9))
        pre = [] if which < 0 else pres[which]
        rid = srv.submit(suffix, max_new,
                         prefix=None if which < 0 else pids[which])
        want[rid] = (pre + suffix, max_new)
        if rng.random() < 0.5:
            done.update(srv.step())  # stagger admissions mid-flight
    done.update(srv.run())

    assert sorted(done) == sorted(want)
    for rid, (full, max_new) in want.items():
        np.testing.assert_array_equal(
            done[rid], _oracle(params, cfg, full, max_new),
            err_msg=f"request {rid} (P={len(full)}, N={max_new})")


def test_cancel_pending_and_inflight(cfg, params):
    """cancel() de-queues a pending request, kills an in-flight one's
    slot (freed for waiting work on the next step), and neither is
    reported by run(); survivors still match their oracle."""
    srv = SlotServer(params, cfg, n_slots=1, max_len=64, chunk=3)
    r0 = srv.submit([4, 2, 8, 1], 20)   # will occupy the only slot
    r1 = srv.submit([6, 6, 3], 7)       # pending behind it
    r2 = srv.submit([9, 1, 5], 6)       # pending behind that
    srv.step()  # r0 mid-generation
    assert srv.cancel(r1) is True       # pending: de-queued
    assert srv.cancel(r0) is True       # in-flight: slot killed
    assert srv.cancel(r0) is False      # already gone
    assert srv.cancel(12345) is False   # unknown
    done = srv.run()
    assert sorted(done) == [r2]
    np.testing.assert_array_equal(done[r2], _oracle(params, cfg,
                                                    [9, 1, 5], 6))


def test_cancel_emits_no_done_event(cfg, params):
    """A cancelled request never fires the on_tokens done event (the
    caller declared the stream dead); survivors still do."""
    events = []
    srv = SlotServer(params, cfg, n_slots=2, max_len=64, chunk=3,
                     on_tokens=lambda rid, toks, done: events.append(
                         (rid, list(toks), done)))
    r0 = srv.submit([4, 2, 8], 12)
    r1 = srv.submit([7, 7], 5)
    srv.step()
    srv.cancel(r0)
    srv.run()
    dones = [rid for rid, _t, d in events if d]
    assert dones == [r1]


def test_cancel_reentrant_from_on_tokens(cfg, params):
    """cancel() called from inside the on_tokens callback (a stream
    consumer declaring another stream dead mid-step) must not crash the
    step and must take effect."""
    state = {}

    def hook(rid, toks, done):
        # First emission from r0 kills r1.
        if "r1" in state and rid == state["r0"] and not state.get("done"):
            state["done"] = True
            assert state["srv"].cancel(state["r1"]) is True

    srv = SlotServer(params, cfg, n_slots=2, max_len=64, chunk=3,
                     on_tokens=hook)
    state["srv"] = srv
    state["r0"] = srv.submit([4, 2, 8], 9)
    state["r1"] = srv.submit([7, 7], 9)
    done = srv.run()
    assert sorted(done) == [state["r0"]]
    np.testing.assert_array_equal(done[state["r0"]],
                                  _oracle(params, cfg, [4, 2, 8], 9))


def test_cancel_own_request_from_admit_callback(cfg, params):
    """cancel() from the admit-time first-token callback must not leave
    a zombie slot: the slot frees immediately and the next request
    admits into it, matching its oracle."""
    state = {}

    def hook(rid, toks, done):
        if rid == state.get("victim") and not done:
            state["srv"].cancel(rid)

    srv = SlotServer(params, cfg, n_slots=1, max_len=64, chunk=3,
                     on_tokens=hook)
    state["srv"] = srv
    state["victim"] = srv.submit([4, 2, 8, 1], 20)
    r1 = srv.submit([9, 1, 5], 6)
    done = srv.run()
    assert sorted(done) == [r1]
    np.testing.assert_array_equal(done[r1], _oracle(params, cfg,
                                                    [9, 1, 5], 6))
    assert not srv.busy and not srv._slot_rid


# ---------------------------------------------------------- the serve scope
#
# DESIGN.md §13: request_log() / step_log(), the serve.* phases on the
# swtrace spine, the stable program names.


def _rows_of(srv):
    from starway_tpu.models import serving

    return {r["rid"]: r for r in serving.request_log()
            if r["side"] == "server" and r["server"] == srv.server_id}


def _finished(srv, cfg, params):
    rid = srv.submit([5, 1, 7, 2, 9], 6)
    done = srv.run()
    return rid, "done", len(done[rid]), True


def _one_token(srv, cfg, params):
    rid = srv.submit([3, 8, 6], 1)
    done = srv.run()
    return rid, "done", 1, True


def _eos_at_admission(srv, cfg, params):
    prompt = [5, 1, 7, 2, 9]
    srv.eos_id = int(_oracle(params, cfg, prompt, 1)[0])
    rid = srv.submit(prompt, 8)
    done = srv.run()
    assert list(done[rid]) == [srv.eos_id]
    return rid, "done", 1, True


def _cancelled_while_queued(srv, cfg, params):
    srv.submit([4, 2, 8, 1], 12)        # takes the only slot
    rid = srv.submit([6, 6, 3], 7)      # waits behind it
    srv.step()
    assert srv.cancel(rid)
    srv.run()
    return rid, "cancelled", 0, False


def _cancelled_in_slot(srv, cfg, params):
    rid = srv.submit([4, 2, 8, 1], 20)
    srv.step()      # ingested in the chunk's first step, decoded in two
    assert srv.cancel(rid)
    srv.run()
    return rid, "cancelled", 1 + 2, True


@pytest.mark.parametrize("scenario", [
    _finished, _one_token, _eos_at_admission, _cancelled_while_queued,
    _cancelled_in_slot], ids=lambda f: f.__name__.lstrip("_"))
def test_request_log_stamps(cfg, params, scenario):
    """Every way a request ends leaves one complete row: the stamps it
    reached are ordered, the ones it never reached stay None, and the
    server forgets the row (the module's log keeps it)."""
    srv = SlotServer(params, cfg, n_slots=1, max_len=64, chunk=3)
    rid, status, n_out, admitted = scenario(srv, cfg, params)
    row = _rows_of(srv)[rid]
    assert row["status"] == status and row["n_out"] == n_out
    assert row["n_prompt"] in (3, 4, 5)
    if admitted:
        assert (row["t_submit"] <= row["t_admit0"] <= row["t_first"]
                <= row["t_done"])
        # No admit program on the ingest path, so no bucket.
        assert row["bucket"] == 0 and row["steps"] >= 1
    else:
        assert row["t_admit0"] is None and row["t_first"] is None
        assert row["t_submit"] <= row["t_done"] and row["steps"] == 0
    assert not srv._rows          # nothing open is left behind


def test_a_step_admits_the_queues_first_the_longest_budget_first(cfg, params):
    """WHO is admitted is the queue's order; among those a step admits,
    the request with most tokens to produce goes first (each admission
    stalls those before it: PERF.md, PR 26).  Every request's tokens are
    what it would have got alone."""
    srv = SlotServer(params, cfg, n_slots=2, max_len=64, chunk=3)
    a = srv.submit([4, 2, 8], 3)
    b = srv.submit([7, 7], 9)
    c = srv.submit([5, 1], 30)      # the longest, and not of this step's two
    d = srv.submit([9, 1, 5], 9)    # a tie with b: the earlier goes first
    done = srv.step()
    rows = _rows_of(srv)
    assert rows[b]["t_admit0"] < rows[a]["t_admit0"]
    assert rows[c]["t_admit0"] is None and rows[d]["t_admit0"] is None
    done.update(srv.run())
    rows = _rows_of(srv)
    assert rows[c]["t_admit0"] < rows[d]["t_admit0"]
    for rid, prompt, n in ((a, [4, 2, 8], 3), (b, [7, 7], 9),
                           (c, [5, 1], 30), (d, [9, 1, 5], 9)):
        np.testing.assert_array_equal(done[rid], _oracle(params, cfg, prompt, n))


def test_request_log_rejected_request(cfg, params):
    from starway_tpu.models import serving

    srv = SlotServer(params, cfg, n_slots=1, max_len=64)
    with pytest.raises(ValueError):
        srv.submit(list(range(1, 70)), 4)
    row = [r for r in serving.request_log()
           if r["side"] == "server" and r["server"] == srv.server_id][-1]
    assert row["status"] == "rejected" and row["rid"] is None
    assert row["n_prompt"] == 69 and row["t_done"] == row["t_submit"]


def test_request_log_paged_server(cfg, params):
    """The paged server overrides _admit and _run_chunk; the stamps sit in
    the paths it inherits, so its rows are as complete -- including a
    request its pool made wait."""
    from starway_tpu.models import PagedSlotServer

    srv = PagedSlotServer(params, cfg, n_slots=2, max_len=64, page=16,
                          n_pages=2, chunk=4)
    rids = [srv.submit([7, 3, 9, 1, 4], 6), srv.submit([2, 5, 8], 5)]
    done = srv.run()
    rows = _rows_of(srv)
    for rid in rids:
        row = rows[rid]
        assert row["status"] == "done" and row["n_out"] == len(done[rid])
        assert row["bucket"] == 16
        assert (row["t_submit"] <= row["t_admit0"] <= row["t_first"]
                <= row["t_done"])
    # One usable page: the second request's admission was refused until the
    # first one's page came back, and its stamp is the attempt that held.
    assert rows[rids[1]]["t_admit0"] >= rows[rids[0]]["t_done"]
    assert not srv._rows


def test_step_log_rows_add_up(cfg, params):
    from starway_tpu.models import serving

    srv = SlotServer(params, cfg, n_slots=2, max_len=64, chunk=4)
    for prompt, n in [([5, 1, 7], 9), ([3, 8, 6, 2], 5), ([9, 9], 7)]:
        srv.submit(prompt, n)
    srv.run()
    steps = [r for r in serving.step_log() if r["server"] == srv.server_id]
    assert len(steps) == srv._n_steps >= 3
    assert sum(r["admits"] for r in steps) == 3
    assert steps[0]["queued"] == 1 and steps[0]["live"] == 2
    for r in steps:
        # The dense server ingests its prompts inside the chunk: no lane
        # ever stands still for an admission, and a step reads once.
        parts = r["dispatch_s"] + r["wait_s"] + r["harvest_s"]
        assert 0.0 <= parts <= r["t1"] - r["t0"]
        assert r["admit_s"] == 0.0 and r["fetches"] == 1
        assert r["n_slots"] == 2 and 0 <= r["live"] <= 2
        assert (r["ingest_width"] > 0) == (r["ingest_tokens"] > 0)
    assert sum(r["ingest_tokens"] for r in steps) == 3 + 4 + 2
    assert all(a["t1"] <= b["t0"] for a, b in zip(steps, steps[1:]))
    # The server's own accumulator saw the same phases.
    snap = srv.stage_scope.snapshot()
    assert snap["serve.step"]["count"] == len(steps)
    assert snap["serve.admit"]["count"] == 3
    assert {"serve.chunk_dispatch", "serve.chunk_wait",
            "serve.harvest"} <= set(snap)


def test_serve_logs_stay_bounded(cfg, params):
    from starway_tpu.models import serving

    srv = SlotServer(params, cfg, n_slots=1, max_len=64)
    for _ in range(serving.LOG_ROWS + 10):
        with pytest.raises(ValueError):
            srv.submit([], 1)           # a rejected row each
    assert len(serving.request_log()) == serving.LOG_ROWS
    serving._step_log.extend({"server": 0} for _ in range(serving.LOG_ROWS + 1))
    assert len(serving.step_log()) == serving.LOG_ROWS
    serving._step_log.clear()


def test_serve_clock_is_the_monotonic_clock():
    """The logs stamp with time.perf_counter, swtrace's clock; a caller
    windows them with time.monotonic.  On Linux both are CLOCK_MONOTONIC."""
    import time

    perf, mono = (time.get_clock_info(n)
                  for n in ("perf_counter", "monotonic"))
    assert perf.implementation == mono.implementation
    assert perf.monotonic and abs(time.perf_counter() - time.monotonic()) < 0.01


def test_serve_spans_in_chrome_export(cfg, params, monkeypatch):
    """STARWAY_TRACE=1: the server registers a ring of its own and the
    trace CLI's export draws the serve.* phases, admissions carrying the
    rid; with the variable unset it has no ring at all."""
    from starway_tpu import trace
    from starway_tpu.core import swtrace

    monkeypatch.delenv("STARWAY_TRACE", raising=False)
    monkeypatch.delenv("STARWAY_FLIGHT_DIR", raising=False)
    assert SlotServer(params, cfg, n_slots=1,
                      max_len=64).stage_scope.ring is None

    monkeypatch.setenv("STARWAY_TRACE", "1")
    swtrace.reset()
    try:
        srv = SlotServer(params, cfg, n_slots=1, max_len=64, chunk=3)
        rids = [srv.submit([5, 1, 7], 4), srv.submit([3, 8], 5)]
        srv.run()
        live = [d for d in swtrace.dump_all() if d["worker"] == srv.trace_label]
        assert len(live) == 1
        srv.close()                    # retired: the events outlive it
        label = srv.trace_label
        del srv
        dumps = [d for d in swtrace.dump_all() if d["worker"] == label]
        assert len(dumps) == 1 and dumps[0]["events"] == live[0]["events"]
        events = trace.to_chrome(dumps)["traceEvents"]
    finally:
        swtrace.reset()
    spans = [e for e in events if e.get("cat") == "stage"]
    names = {e["name"] for e in spans}
    assert {"serve.step", "serve.admit", "serve.chunk_dispatch",
            "serve.chunk_wait", "serve.harvest"} <= names
    admits = [e for e in spans if e["name"] == "serve.admit"]
    assert [e["args"]["tag"] for e in admits] == rids
    assert all(e["ph"] == "X" and e["dur"] > 0 for e in admits)


def test_serving_program_names_are_stable(cfg, params):
    """The profiler names a program after the jitted function: each
    serving program carries a name of its own (a trace reduction takes a
    program's device time by name), not ``run``."""
    from starway_tpu.models import paged, serving

    sampling = (0.0, None, None)
    progs = {
        "serve_admit_32": serving._compiled_admit(cfg, 32, *sampling),
        "serve_prefix_register_64": serving._compiled_prefix_register(cfg, 64),
        "serve_prefix_admit_64_32": serving._compiled_prefix_admit(
            cfg, 64, 32, 128, *sampling),
        "serve_rolling_admit": serving._compiled_rolling_admit(cfg, *sampling),
        "serve_decode_chunk": serving._compiled_chunk(
            cfg, 2, 64, 4, *sampling, None),
        "serve_paged_admit_32": paged._compiled_paged_admit(
            cfg, 32, 16, *sampling),
        "serve_paged_decode_chunk": paged._compiled_paged_chunk(
            cfg, 64, 4, *sampling, None),
        "serve_paged_prefix_register_32": paged._compiled_paged_prefix_write(
            cfg, 32, 16, 1),
        "serve_paged_prefix_admit_32": paged._compiled_paged_prefix_admit(
            cfg, 32, 16, 4, False, *sampling),
    }
    progs["serve_seat"] = serving._seat
    for name, prog in progs.items():
        assert prog.__name__ == name
    # Only the prefills are ``serve_admit*``: a trace reduction takes the
    # device time of an admission from the programs of that name.
    assert not serving._seat.__name__.startswith("serve_admit")
    # ... and that is the name the compiled module carries.
    srv = SlotServer(params, cfg, n_slots=2, max_len=64, chunk=4)
    lowered = progs["serve_decode_chunk"].lower(
        params, srv.cache, srv.token, srv.pos, srv.live, srv.remaining,
        srv.key)
    assert "jit_serve_decode_chunk" in lowered.as_text()[:400]


# ------------------------------------------------ a step queues, then fetches


def check_step_queues_then_fetches(srv, requests, k, monkeypatch):
    """One ``step()`` that admits ``k`` requests (``requests``: (prompt,
    max_new, prefix) triples, cycled): its device programs are queued with
    nothing from the host between them and it reads the device twice.

    * ``step_log()`` counts ``fetches <= 2`` beside ``admits == k``;
    * the slot state reaches the chunk program through ``serve_seat``
      alone: ``k`` calls, each taking the arrays the one before returned,
      the last one's outputs being what the chunk is launched on (object
      identity: any other program writing a slot's state, such as a
      ``.at[slot].set`` from the host, would have put new arrays there);
    * every first token is handed out before any chunk token.

    Shared by tests/test_paged.py and tests/test_mla_moe.py."""
    from starway_tpu.models import serving

    srv.submit(*requests[0])
    srv.run()                      # every program of the step is compiled
    events = []
    srv.on_tokens = lambda rid, toks, done: events.append((rid, len(toks), done))
    rids = [srv.submit(*requests[i % len(requests)]) for i in range(k)]

    seats, at_chunk = [], []
    seat = serving._seat

    def spy_seat(*args):
        out = seat(*args)
        seats.append((args[:4], out))
        return out

    launch = srv._launch_chunk

    def spy_launch(sub):
        at_chunk.append((srv.token, srv.pos, srv.live, srv.remaining))
        return launch(sub)

    monkeypatch.setattr(serving, "_seat", spy_seat)
    srv._launch_chunk = spy_launch
    before = (srv.token, srv.pos, srv.live, srv.remaining)
    srv.step()
    monkeypatch.undo()
    srv._launch_chunk = launch

    row = [r for r in serving.step_log() if r["server"] == srv.server_id][-1]
    assert row["admits"] == k and row["fetches"] == 2 and row["admit_s"] > 0
    assert len(seats) == k and len(at_chunk) == 1
    state = before
    for took, gave in seats:
        assert all(a is b for a, b in zip(took, state))
        state = gave
    assert all(a is b for a, b in zip(at_chunk[0], state))
    firsts = events[:k]
    assert sorted(e[0] for e in firsts) == sorted(rids)
    assert all(n == 1 and not done for _rid, n, done in firsts)
    assert all(rid in rids for rid, _n, _d in events)
    log = {r["rid"]: r for r in serving.request_log()
           if r["side"] == "server" and r["server"] == srv.server_id}
    assert len({log[rid]["t_first"] for rid in rids}) == 1
    assert log[rids[0]]["t_first"] <= row["t0"] + row["admit_s"]
    done = srv.run()
    srv.on_tokens = None
    return rids, done


def check_step_ingests_and_fetches_once(srv, requests, k, monkeypatch):
    """The dense server's counterpart of
    :func:`check_step_queues_then_fetches`: one ``step()`` that takes
    ``k`` requests launches ONE program (the mixed chunk: no admit
    program, no ``serve_seat``), reads the device once, and hands each
    request its first token before its chunk tokens."""
    from starway_tpu.models import serving

    srv.submit(*requests[0])
    srv.run()
    events = []
    srv.on_tokens = lambda rid, toks, done: events.append((rid, len(toks), done))
    rids = [srv.submit(*requests[i % len(requests)]) for i in range(k)]
    launched = []
    monkeypatch.setattr(serving, "_seat", lambda *a: launched.append("seat"))
    monkeypatch.setattr(serving, "_compiled_admit",
                        lambda *a: launched.append("admit"))
    launch = srv._launch_chunk
    srv._launch_chunk = lambda sub: launched.append("chunk") or launch(sub)
    srv.step()
    monkeypatch.undo()
    srv._launch_chunk = launch
    row = [r for r in serving.step_log() if r["server"] == srv.server_id][-1]
    assert launched == ["chunk"]
    assert row["admits"] == k and row["fetches"] == 1 and row["admit_s"] == 0
    asked = [requests[i % len(requests)] for i in range(k)]
    # A piece a step, the longest budget first: the rest ride the next chunk.
    order = sorted(range(k), key=lambda i: (-asked[i][1], i))[:srv.chunk]
    assert row["ingest_tokens"] == sum(len(asked[i][0]) for i in order)
    seated = [rids[i] for i in order]
    for rid in seated:
        mine = [n for r, n, _d in events if r == rid]
        assert mine[0] == 1 and len(mine) <= 2
    assert not [e for e in events if e[0] not in seated]
    done = srv.run()
    srv.on_tokens = None
    return rids, done


def _dense(cfg, params):
    srv = SlotServer(params, cfg, n_slots=4, max_len=64, chunk=3)
    return srv, [([5, 1, 7, 2, 9], 7, None), ([3, 8, 6], 5, None),
                 ([4, 2, 8, 1, 6, 6, 3], 9, None)]


def _prefix(cfg, params):
    srv = SlotServer(params, cfg, n_slots=4, max_len=64, chunk=3)
    pid = srv.register_prefix([7, 3, 9, 1, 4, 4, 2])
    return srv, [([5, 1, 7], 7, pid), ([3, 8], 5, pid), ([4, 2, 8, 1], 9, pid)]


def _rolling(cfg, params):
    rcfg = LlamaConfig.preset("debug", sliding_window=8)
    srv = SlotServer(params, rcfg, n_slots=4, max_len=64, chunk=3)
    return srv, [([5, 1, 7, 2, 9], 7, None), ([3, 8, 6], 5, None),
                 (list(range(1, 14)), 9, None)]


@pytest.mark.parametrize("k", [1, 3, 4])
@pytest.mark.parametrize("kind", [_dense, _prefix, _rolling],
                         ids=lambda f: f.__name__.lstrip("_"))
def test_a_step_queues_its_programs_and_fetches_twice(cfg, params, kind, k,
                                                      monkeypatch):
    srv, requests = kind(cfg, params)
    check = (check_step_ingests_and_fetches_once if kind is _dense
             else check_step_queues_then_fetches)
    rids, done = check(srv, requests, k, monkeypatch)
    assert sorted(done) == sorted(rids)
    for i, rid in enumerate(rids):
        assert len(done[rid]) == requests[i % len(requests)][1]


def check_first_token_endings(srv, oracle, fetches=2):
    """A one-token request and one whose first token is its eos, admitted
    in one step beside two long requests: each ends in that very step with
    exactly one token and one done event after it, its slot is free again,
    and the step still read the device ``fetches`` times (twice behind
    admit programs, once where the chunk ingests the prompts: its steps
    must then hold all four).  ``srv``: 4 slots, eos unset;
    ``oracle(prompt, max_new, eos_id)``.  Shared by tests/test_paged.py."""
    from starway_tpu.models import serving

    instant = [5, 1, 7, 2, 9]
    srv.eos_id = int(oracle(instant, 1, None)[0])
    events = []
    srv.on_tokens = lambda rid, toks, done: events.append(
        (rid, list(toks), done))
    asked = {srv.submit([3, 8, 6], 9): ([3, 8, 6], 9),
             srv.submit([4, 2, 8, 1], 1): ([4, 2, 8, 1], 1),
             srv.submit(instant, 8): (instant, 8),
             srv.submit([9, 1, 5], 6): ([9, 1, 5], 6)}
    _long_a, one, eos_first, _long_b = asked
    done = srv.step()
    row = [r for r in serving.step_log() if r["server"] == srv.server_id][-1]
    assert row["admits"] == 4 and row["fetches"] == fetches
    assert sorted(done) == [one, eos_first]
    for rid in (one, eos_first):
        assert len(done[rid]) == 1
        assert [e[1:] for e in events if e[0] == rid] == [
            ([int(done[rid][0])], False), ([], True)]
    assert done[eos_first][0] == srv.eos_id
    assert sorted(srv._slot_rid.values()) == [r for r in asked
                                              if r not in done]
    done.update(srv.run())
    for rid, (prompt, n) in asked.items():
        np.testing.assert_array_equal(done[rid], oracle(prompt, n, srv.eos_id))
    assert sum(1 for e in events if e[2]) == 4 and not srv.busy


def test_requests_ending_at_their_first_token_beside_long_ones(cfg, params):
    srv = SlotServer(params, cfg, n_slots=4, max_len=64, chunk=4)
    check_first_token_endings(
        srv, lambda p, n, eos: _oracle(params, cfg, p, n, eos_id=eos),
        fetches=1)


def test_only_one_token_requests_need_no_chunk(cfg, params):
    """A step whose every occupied slot holds a request the host knows to
    end at its first token launches no chunk: one fetch, no key split.
    (On a server whose prompts come in by admit programs, here the rolling
    one; the dense server's chunk is what ingests a prompt.)"""
    from starway_tpu.models import serving

    srv = SlotServer(params, LlamaConfig.preset("debug", sliding_window=8),
                     n_slots=2, max_len=64, chunk=3)
    rids = [srv.submit([3, 8, 6], 1), srv.submit([4, 2], 1)]
    key = srv.key
    done = srv.step()
    row = [r for r in serving.step_log() if r["server"] == srv.server_id][-1]
    assert row["admits"] == 2 and row["fetches"] == 1
    assert row["dispatch_s"] == row["wait_s"] == 0.0
    assert sorted(done) == rids and not srv.busy
    # Two admissions split the key twice; no chunk split it a third time.
    want = jax.random.split(jax.random.split(key)[0])[0]
    np.testing.assert_array_equal(jax.random.key_data(srv.key),
                                  jax.random.key_data(want))


# ------------------------------------------- prompts ride the decode chunk
#
# The dense server (k/v leaves as computed, no int8 scales, no window)
# launches no admit program: a prompt comes in piece by piece inside the decode chunk
# (serving._compiled_ingest_chunk), at the smallest of INGEST_WIDTHS that
# brings what waits in within a chunk (all of it; the longest prompt while
# requests queue for slots).  Small widths here; the constant's own below.


def _ingest_server(monkeypatch, params, cfg, widths=(8,), **kw):
    from starway_tpu.models import serving

    monkeypatch.setattr(serving, "INGEST_WIDTHS", tuple(widths))
    kw = {"n_slots": 2, "max_len": 64, "chunk": 3, **kw}
    srv = SlotServer(params, cfg, **kw)
    assert srv._widths == tuple(widths)[:len(srv._widths)] and srv._widths
    return srv


def _prompt(n, seed=0, vocab=512):
    return [int(t) for t in
            np.random.default_rng([seed, n]).integers(1, vocab, n)]


def _served_as_generate(srv, params, cfg, reqs, oracle=None):
    oracle = oracle or (lambda p, n: _oracle(params, cfg, p, n,
                                             eos_id=srv.eos_id))
    rids = [srv.submit(p, n) for p, n in reqs]
    done = srv.run()
    assert sorted(done) == sorted(rids)
    for rid, (p, n) in zip(rids, reqs):
        np.testing.assert_array_equal(
            done[rid], oracle(p, n), err_msg=f"request {rid} (P={len(p)})")


def _ingest_length(n):
    """One prompt of ``n`` tokens at width 8, chunk 3, beside a short one."""
    def case(monkeypatch, params, cfg):
        srv = _ingest_server(monkeypatch, params, cfg)
        _served_as_generate(srv, params, cfg, [(_prompt(n), 6),
                                               (_prompt(3, 1), 9)])
    case.__name__ = f"length_{n}"
    return case


def _ingest_arrivals(monkeypatch, params, cfg):
    """Requests arriving while other slots decode, more than there are
    slots, widths chosen by the backlog (4, 8 and 16 all in play)."""
    srv = _ingest_server(monkeypatch, params, cfg, widths=(4, 8, 16),
                         n_slots=3, chunk=4)
    reqs = [(_prompt(n, 2), m) for n, m in
            [(5, 12), (19, 4), (2, 7), (33, 9), (8, 3), (13, 11), (40, 5)]]
    rids, done = [], {}
    for p, m in reqs:
        rids.append(srv.submit(p, m))
        done.update(srv.step())      # the next arrives one chunk later
    done.update(srv.run())
    for rid, (p, m) in zip(rids, reqs):
        np.testing.assert_array_equal(done[rid], _oracle(params, cfg, p, m))
    from starway_tpu.models import serving

    used = {r["ingest_width"] for r in serving.step_log()
            if r["server"] == srv.server_id}
    assert {4, 8, 16} <= used


def _ingest_width_follows_the_queue(queued):
    """Two prompts of 3 pieces each at width 4, chunk 4: with no request
    waiting for a slot the chunk takes width 8, at which both come in
    within it; with one waiting, width 4, at which the longer one does."""
    def case(monkeypatch, params, cfg):
        from starway_tpu.models import serving

        srv = _ingest_server(monkeypatch, params, cfg, widths=(4, 8),
                             chunk=4)
        reqs = [(_prompt(11, 7), 5), (_prompt(10, 8), 5)]
        reqs += [(_prompt(2, 9), 4)] * queued
        _served_as_generate(srv, params, cfg, reqs)
        first = next(r for r in serving.step_log()
                     if r["server"] == srv.server_id)
        assert first["queued"] == queued
        assert first["ingest_width"] == (4 if queued else 8)
        assert first["ingest_tokens"] == (15 if queued else 21)
    case.__name__ = f"width_with_{queued}_queued"
    return case


def _ingest_int8(monkeypatch, params, cfg):
    """An int8 cache is NOT ingested: a piece would attend over the
    cache's quantized entries where ``generate()``'s prefill reads the
    prompt's k/v exact, so that kind keeps its admit programs, and
    ``generate()``'s tokens exactly."""
    cfg8 = LlamaConfig.preset("debug", kv_quant="int8")
    srv = SlotServer(params, cfg8, n_slots=2, max_len=64, chunk=3)
    assert srv._widths == ()
    _served_as_generate(srv, params, cfg8,
                        [(_prompt(21, 4), 6), (_prompt(9, 5), 5),
                         (_prompt(3, 6), 8)])


def _ingest_one_token(monkeypatch, params, cfg):
    """``max_new_tokens == 1``: seated dead, one token, its slot free."""
    srv = _ingest_server(monkeypatch, params, cfg)
    _served_as_generate(srv, params, cfg, [(_prompt(11), 1), (_prompt(4), 6),
                                           (_prompt(17), 1)])
    assert not srv.busy


def _ingest_eos_first(monkeypatch, params, cfg):
    """eos as the first token: the request ends where it is seated."""
    prompt = _prompt(13)
    srv = _ingest_server(monkeypatch, params, cfg)
    srv.eos_id = int(_oracle(params, cfg, prompt, 1)[0])
    events = []
    srv.on_tokens = lambda rid, toks, done: events.append(
        (rid, list(toks), done))
    rid = srv.submit(prompt, 8)
    other = srv.submit(_prompt(5, 1), 7)
    done = srv.run()
    assert list(done[rid]) == [srv.eos_id]
    assert [e[1:] for e in events if e[0] == rid] == [([srv.eos_id], False),
                                                      ([], True)]
    np.testing.assert_array_equal(
        done[other], _oracle(params, cfg, _prompt(5, 1), 7, eos_id=srv.eos_id))


def _ingest_cancel_half_way(monkeypatch, params, cfg):
    """``cancel()`` of a request half ingested (40 tokens, 24 a chunk):
    nothing is delivered, its slot is free at the next step, and the
    request that takes the slot gets its own tokens (the prompt half
    written there is overwritten before anything reads it)."""
    srv = _ingest_server(monkeypatch, params, cfg, n_slots=1)
    events = []
    srv.on_tokens = lambda rid, toks, done: events.append(rid)
    victim = srv.submit(_prompt(40), 6)
    srv.step()
    assert srv._ingest and not events       # 24 of its 40 tokens are in
    assert srv.cancel(victim) and not srv._ingest and not srv._slot_rid
    nxt = srv.submit(_prompt(7, 3), 9)
    done = srv.run()
    assert sorted(done) == [nxt] and victim not in events
    np.testing.assert_array_equal(done[nxt],
                                  _oracle(params, cfg, _prompt(7, 3), 9))


def _ingest_kernels(monkeypatch, params, cfg):
    """The whole mixed chunk on the kernels' side (interpreted):
    ``sw_kv_write`` with a count and ``sw_ingest_attn`` through a row
    index, a 125-token prompt on a 128-position cache."""
    from starway_tpu.models import serving

    monkeypatch.setattr("starway_tpu.ops.dispatch.use_kernels", lambda: True)
    srv = _ingest_server(monkeypatch, params, cfg, max_len=128)
    reqs = [(_prompt(3, 7), 6), (_prompt(21, 7), 4), (_prompt(125, 7), 2)]
    rids = [srv.submit(p, n) for p, n in reqs]
    try:
        done = srv.run()
    finally:  # programs traced on this side must not outlive the case
        serving._compiled_ingest_chunk.cache_clear()
        serving._compiled_chunk.cache_clear()
    monkeypatch.undo()
    for rid, (p, n) in zip(rids, reqs):
        np.testing.assert_array_equal(done[rid], _oracle(params, cfg, p, n))


def _ingest_width_of_the_constant(width):
    """The constant's own widths (128, 256) on a 520-position cache, chunk
    2: a prompt that needs exactly this width to come in within one chunk,
    so the plan picks it; one of 515 tokens comes in at 256 and, its last
    3 tokens, at 128, a piece whose pads reach past the cache's end."""
    def case(monkeypatch, params, cfg):
        from starway_tpu.models import serving

        assert serving.INGEST_WIDTHS == (128, 256)
        srv = SlotServer(params, cfg, n_slots=2, max_len=520, chunk=2)
        assert srv._widths == serving.INGEST_WIDTHS
        n = {128: 200, 256: 400}[width]
        _served_as_generate(srv, params, cfg, [
            (_prompt(n), 4), (_prompt(3, 1), 5), (_prompt(515, 2), 3)])
        used = [r["ingest_width"] for r in serving.step_log()
                if r["server"] == srv.server_id and r["ingest_width"]]
        assert used[0] == width and used[-2:] == [256, 128]
    case.__name__ = f"width_{width}"
    return case


@pytest.mark.parametrize("case", [
    *(_ingest_length(n) for n in (1, 7, 8, 9, 3 * 8 + 5, 40)),
    _ingest_arrivals, _ingest_int8, _ingest_one_token, _ingest_eos_first,
    _ingest_cancel_half_way, _ingest_kernels,
    *(_ingest_width_of_the_constant(w) for w in (128, 256)),
    *(_ingest_width_follows_the_queue(q) for q in (0, 1))],
    ids=lambda f: f.__name__.lstrip("_"))
def test_ingest_path_returns_generates_tokens(cfg, params, monkeypatch, case):
    """A server on the ingest path returns ``generate()``'s tokens exactly
    (float32, greedy): prompt lengths 1, W - 1, W, W + 1, 3W + 5 and one
    longer than ``chunk x W`` (W = 8, chunk 3); arrivals while other slots
    decode; an int8 cache (which keeps its admit programs for the sake of
    exactly this); one-token requests; eos as the first token; a request
    cancelled half ingested; the kernels' side of the program
    (interpreted); each width of the constant."""
    case(monkeypatch, params, cfg)


def _kind_dense(cfg, params):
    return SlotServer(params, cfg, n_slots=2, max_len=64, chunk=4), None, 0


def _kind_dense_int8(cfg, params):
    return SlotServer(params, LlamaConfig.preset("debug", kv_quant="int8"),
                      n_slots=2, max_len=64, chunk=4), None, 3


def _kind_prefix(cfg, params):
    srv = SlotServer(params, cfg, n_slots=2, max_len=64, chunk=4)
    return srv, srv.register_prefix([7, 3, 9, 1, 4, 4, 2]), 3


def _kind_rolling(cfg, params):
    rcfg = LlamaConfig.preset("debug", sliding_window=8)
    return SlotServer(params, rcfg, n_slots=2, max_len=64, chunk=4), None, 3


def _kind_paged(cfg, params):
    from starway_tpu.models import PagedSlotServer

    return PagedSlotServer(params, cfg, n_slots=2, max_len=64, page=16,
                           chunk=4), None, 3


def _kind_latent(cfg, params):
    from benchmark.harness import spec as S
    from tests import test_mla_moe as latent

    lparams, lcfg = latent._model(S.load_runner("serve_mla_moe"))
    assert "ckv" in SlotServer(lparams, lcfg, n_slots=1, max_len=64).cache
    return SlotServer(lparams, lcfg, n_slots=2, max_len=64, chunk=4), None, 3


@pytest.mark.parametrize("kind", [
    _kind_dense, _kind_dense_int8, _kind_prefix, _kind_rolling, _kind_paged,
    _kind_latent], ids=lambda f: f.__name__[6:])
def test_which_requests_ingest_and_which_keep_admit_programs(cfg, params,
                                                             kind,
                                                             monkeypatch):
    """``step_log()`` pinned for both paths, chosen from the cache kind
    alone.  Dense k/v as computed: every step ``fetches == 1`` and
    ``admit_s == 0``, no ``serve_seat`` runs, and ``ingest_tokens`` sums to
    the prompts' lengths.  A ``prefix=`` request (on that same dense
    server), an int8 cache, a rolling window, the page pool and a latent
    cache still launch their admit programs (``serve_seat`` behind each, ``admit_s >
    0`` in a step that admits) and ingest nothing."""
    from starway_tpu.models import serving

    srv, prefix, admitted = kind(cfg, params)
    seats = []
    seat = serving._seat
    monkeypatch.setattr(serving, "_seat",
                        lambda *a: seats.append(1) or seat(*a))
    prompts = [[5, 1, 7, 2, 9], [3, 8, 6], [4, 2, 8, 1, 6, 6, 3]]
    for p in prompts:
        srv.submit(p, 5, prefix)
    done = srv.run()
    assert len(done) == 3 and all(len(t) == 5 for t in done.values())
    steps = [r for r in serving.step_log() if r["server"] == srv.server_id]
    assert sum(r["admits"] for r in steps) == 3 and len(seats) == admitted
    if admitted:
        assert srv._widths == () or prefix is not None
        assert all((r["admit_s"] > 0) == (r["admits"] > 0) for r in steps)
        assert all(r["ingest_tokens"] == r["ingest_rows"]
                   == r["ingest_width"] == 0 for r in steps)
        assert max(r["fetches"] for r in steps) == 2
    else:
        assert srv._widths == (128,)      # the first that holds a prompt
        assert all(r["fetches"] == 1 and r["admit_s"] == 0.0 for r in steps)
        assert sum(r["ingest_tokens"] for r in steps) == sum(map(len, prompts))
        assert sum(r["ingest_rows"] for r in steps) == 3 * 128
