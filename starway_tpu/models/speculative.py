"""Speculative decoding: draft-model proposal + single-dispatch chunk verify.

Decode is HBM-bandwidth-bound — every generated token streams the whole KV
cache once.  Speculative decoding (Leviathan et
al. 2023 / Chen et al. 2023, public algorithm) breaks the one-token-per-
stream limit: a cheap DRAFT model proposes ``gamma - 1`` tokens
autoregressively, then the TARGET model scores the whole proposed chunk in
ONE forward pass — the target's cache streams once per ``a + 1`` accepted
tokens instead of once per token, and the rejection rule keeps the output
distribution EXACTLY the target model's (greedy case: identical tokens up
to bf16 argmax near-ties between the chunk and stepwise forwards — the two
compute the same logits through different summation orders; pinned exactly
on the CPU mesh by tests/test_speculative.py, and the chunk-vs-stepwise
logit gap is pinned on hardware by ``kernel_bench --kernels check``'s
``check_spec_chunk_onchip`` row).

TPU-first construction, mirroring models/generate.py's discipline:

* the whole generation is one ``lax.while_loop`` dispatch — draft scan,
  chunk verify, acceptance, and output writes are all on-device (no host
  round trip per macro step);
* static shapes throughout: every macro step drafts exactly ``gamma - 1``
  tokens and verifies a ``gamma`` chunk; per-row cursors absorb the
  variable acceptance length (rows advance 1..gamma tokens per step);
* cache rollback is FREE: rejected positions sit beyond the row's cursor,
  where position masking hides them and later writes overwrite them — no
  copy, no checkpoint (the same invariant ragged decode relies on).

No reference counterpart (/root/reference is a transport library); this is
the TPU build's serving-stack extension implementing the public algorithm.
"""

from __future__ import annotations

import dataclasses
import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax import lax

from .cache import (_write_cached, attend_cache, require_chunk_rows,
                    taken_for_rolling)
from .generate import _filter_logits, _sample, cached_layer_scan, prefill
from .llama import LlamaConfig, cfg_rope_tables, embed_tokens, head_logits


def chunk_decode_step(params, cache, tokens, pos, cfg: LlamaConfig, rope):
    """``C`` tokens in, ``C`` next-token logits out — the multi-token
    generalisation of :func:`~starway_tpu.models.generate.decode_step`
    (C=1 reduces to it, pinned by tests).

    tokens: [B, C] int32 at ABSOLUTE positions ``pos .. pos + C - 1``
    (``pos`` scalar or per-row [B]).  Returns ``(logits [B, C, V] f32,
    updated cache)``.  Write-then-attend: the chunk's k/v (quantized when
    the cache is int8) land in the cache first, then the chunk attends
    through it with per-row global-position masks — in-chunk causality
    falls out of the positions.  This is the speculative VERIFY step, and
    generally useful for multi-token ingestion (teacher forcing, cache
    warm-up) at decode-path semantics.  Dense FFN and MoE follow
    decode_step.

    A ``cfg.kinds`` cache's RINGS take a chunk when they are longer than
    their window by at least ``C - 1`` positions (``LayerKinds.slack``):
    position ``p`` is written at ``p % T`` and every slot is read under
    the mask of the position it holds, so the chunk's last write reaches
    no entry its first query attends, and a position written and then
    given up (a rejected draft) is overwritten before any query reaches it
    (the invariant of the full rows).  A ring of exactly one window
    refuses.  The whole-model rolling cache is not supported (speculative
    decoding targets the full-cache path) — a window-sized cache raises
    rather than silently writing absolute positions into a modular window.
    The check is a shape heuristic (``cache.taken_for_rolling``: rolling
    and full caches share a layout), so a FULL cache allocated with
    max_len exactly == sliding_window is rejected too; allocate max_len =
    window + C for ingestion — positions past the window are masked out of
    attention anyway, so the extra slots change nothing.
    """
    h, out, _ = chunk_decode_hidden(params, cache, tokens, pos, cfg, rope)
    return head_logits(h, params["final_norm"], params["lm_head"],
                       cfg.norm_eps, cfg.norm_zero_centred), out


def chunk_decode_hidden(params, cache, tokens, pos, cfg: LlamaConfig, rope):
    """:func:`chunk_decode_step` up to the last layer's output: ``(h [B,
    C, D]`` before the final norm, the updated cache, the routed layers'
    pair counts of :func:`~starway_tpu.models.generate.cached_layer_scan``)``.
    What a server that drafts with an MTP block verifies with: the block
    reads ``h`` (models/mtp.py)."""
    B, C = tokens.shape
    require_chunk_rows(cfg, cache, C)
    cos, sin = rope
    pos = jnp.asarray(pos, jnp.int32)
    pos_b = pos if pos.ndim == 1 else jnp.broadcast_to(pos, (B,))
    pos_bc = pos_b[:, None] + jnp.arange(C)[None, :]  # [B, C]
    cos_p = cos[pos_bc][:, None]  # [B, 1, C, hd/2]
    sin_p = sin[pos_bc][:, None]

    def write(cache, new, layer, ring=False):
        # C contiguous entries at each row's cursor (a ring: modulo).
        return _write_cached(cache, new, layer, pos_b, ring=ring)

    def attend(q, cache, layer, ring=False):
        # The SAME grouped-stream attention decode_step uses, at C query
        # positions: on TPU the pallas kernel packs C x n_rep rows into
        # one per-(batch, kv head) matmul over the narrow (int8-capable)
        # cache stream — the verify costs one decode step's bytes.
        return attend_cache(q, cache, pos_b, layer, cfg, ring=ring)

    h = embed_tokens(params, tokens, cfg)  # [B, C, D]
    return cached_layer_scan(params, cache, h, cos_p, sin_p, cfg, write,
                             attend)


# ------------------------------------------------------------- the driver


def accept_rule(drafts, pd, t_logits, key, *, greedy: bool, probs_of):
    """THE acceptance rule of speculative decoding, once, for every
    driver (model-draft, prompt-lookup, and the serving step that drafts
    with an MTP block: models/serving.py).

    drafts [B, G-1], pd [B, G-1, V] (the PROPOSAL distributions — one-hot
    for deterministic drafters; unread when ``greedy``), t_logits [B, G,
    V] from the chunk verify; ``probs_of(logits)``: the distribution the
    target samples from (temperature, then top-k / nucleus).  Returns
    ``(a [B]`` the leading-accept count in ``[0, G-1]``, ``c [B]`` the
    correction (a rejection's draw from ``norm(max(p - q, 0))``) or bonus
    token at ``pos + a + 1``, ``key)``."""
    G = t_logits.shape[1]
    if greedy:
        tgt = jnp.argmax(t_logits[:, :-1], -1)  # [B, G-1]
        ok = drafts == tgt
    else:
        qt = probs_of(t_logits[:, :-1])  # [B, G-1, V]
        key, akey = jax.random.split(key)
        u = jax.random.uniform(akey, drafts.shape)
        take = jnp.take_along_axis
        qt_d = take(qt, drafts[..., None], -1)[..., 0]
        pd_d = take(pd, drafts[..., None], -1)[..., 0]
        # STRICT inequality: u == 0 with qt_d == 0 (draft proposed
        # outside the target's top-k/top-p support) must reject —
        # plain generate() can never emit that token.
        ok = u * pd_d < qt_d
    # a = leading-accept count in [0, G-1].
    a = jnp.sum(jnp.cumprod(ok.astype(jnp.int32), axis=1), axis=1)

    # The correction/bonus token at pos + a + 1.
    la = jnp.take_along_axis(t_logits, a[:, None, None], axis=1)[:, 0]
    key, ckey = jax.random.split(key)
    if greedy:
        # Rejected d was != argmax, so the correction IS argmax; full
        # acceptance's bonus is argmax of the last logits.
        c = jnp.argmax(la, -1).astype(jnp.int32)
    else:
        qa = probs_of(la)
        # Residual only where a rejection happened (a < G-1); full
        # acceptance samples the bonus from q_T directly.
        pa = jnp.take_along_axis(
            jnp.pad(pd, ((0, 0), (0, 1), (0, 0))),
            a[:, None, None], axis=1)[:, 0]
        res = jnp.maximum(qa - pa, 0.0)
        res_sum = jnp.sum(res, -1, keepdims=True)
        # Degenerate residual (q_T <= p_D everywhere it was sampled-able
        # can leave ~0 mass after float error): fall back to q_T.
        use_res = (a[:, None] < G - 1) & (res_sum > 1e-9)
        dist = jnp.where(use_res, res / jnp.maximum(res_sum, 1e-30), qa)
        c = jax.random.categorical(
            ckey, jnp.log(jnp.maximum(dist, 1e-30)), axis=-1
        ).astype(jnp.int32)
    return a, c, key


def _accept_emit(drafts, pd, t_logits, key, out, n_out, t_pend, pos, stats,
                 *, greedy: bool, G: int, B: int, max_new: int, probs_of):
    """:func:`accept_rule` + the output bookkeeping the generation drivers
    share (model-draft and prompt-lookup): per-row emit at the cursor,
    and the freeze/clamp logic that keeps every position inside max_len.

    drafts [B, G-1], pd [B, G-1, V], t_logits [B, G, V] as the rule takes
    them.  Returns ``(out, n_out, t_pend, pos, key, stats, emit)``;
    ``emit [B, G]`` is the written token vector ([d_1..d_a, c, junk]) so
    a caller maintaining its own sequence buffer can mirror the write.
    """
    idx = jnp.arange(G - 1)[None, :]
    a, c, key = accept_rule(drafts, pd, t_logits, key, greedy=greedy,
                            probs_of=probs_of)

    # Emit d_1..d_a then c: a+1 tokens at each row's cursor.
    emit = jnp.where(idx < a[:, None], drafts, 0)
    emit = jnp.concatenate([emit, jnp.zeros((B, 1), jnp.int32)], 1)
    emit = emit.at[jnp.arange(B), a].set(c)  # [B, G]
    out = jax.vmap(
        lambda row, w, s: lax.dynamic_update_slice(row, w, (s,))
    )(out, emit, n_out)
    # Finished rows freeze (cursor, position, pending token): they keep
    # re-running the same macro step while slower rows catch up.  The
    # advance is CLAMPED to the remaining budget so the invariant
    # pos == P + n_out - 1 holds exactly — pos never exceeds
    # P + max_new - 1, keeping every rope gather and cache write
    # (<= pos + G - 1) inside max_len even on the finishing step; a
    # clamped row keeps its stale pending token, which is never read
    # into the returned slice.
    done = n_out >= max_new
    adv = jnp.where(done, 0, jnp.minimum(a + 1, max_new - n_out))
    n_out = n_out + adv
    live = (~done).astype(jnp.int32)
    # ``accepted`` counts accepted draft tokens actually EMITTED: normally
    # ``a`` (adv = a + 1), but a finishing row clamps its advance, and the
    # budget-truncated write is all drafts (the correction never lands) —
    # min(a, adv) — so accepted + macro_steps never exceeds emitted tokens.
    stats = stats + jnp.stack([live, live * jnp.minimum(a, adv)], axis=1)
    return (out, n_out, jnp.where(adv == a + 1, c, t_pend), pos + adv, key,
            stats, emit)


def draft_from_truncation(params: dict, cfg: LlamaConfig, n_layers: int):
    """A FREE draft model: the target's first ``n_layers`` decoder layers
    with the same embedding, final norm, and head — no second checkpoint,
    no training.  The stacked-layer parameter tree makes this a slice:
    every ``layers`` leaf leads with the layer axis.

    Truncated ("early-exit") drafts are a standard speculative-decoding
    baseline: early layers already predict easy tokens, and easy tokens
    are where acceptance pays.  Returns ``(draft_params, draft_cfg)``
    ready for :func:`generate_speculative`.  Memory: the non-layer leaves
    (embed, final norm, head) are SHARED with the target; the sliced
    ``layers`` leaves are materialised by jax at call time (~n_layers /
    cfg.n_layers of the stacked weights) — budget for that extra HBM on
    a tightly packed chip.
    """
    if not 1 <= n_layers < cfg.n_layers:
        raise ValueError(
            f"n_layers must be in [1, {cfg.n_layers - 1}], got {n_layers}")
    draft_params = dict(params)
    draft_params["layers"] = jax.tree_util.tree_map(
        lambda a: a[:n_layers], params["layers"])
    return draft_params, dataclasses.replace(cfg, n_layers=n_layers)


def _lookup_propose(seq, pos, *, ngram: int, gamma: int):
    """Prompt-lookup proposal: continue the most recent earlier occurrence
    of the sequence's current ``ngram``-gram.

    seq: [B, L] token buffer, valid through index ``pos`` (per-row [B]);
    the current n-gram is ``seq[pos-ngram+1 .. pos]``.  Finds the largest
    j < pos with ``seq[j-ngram+1 .. j]`` equal to it and proposes
    ``seq[j+1 .. j+gamma-1]``.  No match: j falls back to ``ngram - 1``
    (a harmless in-bounds span — the verify rejects bad proposals, it
    never needs them to be good).  Returns ``[B, gamma-1]`` int32.

    Pure gather/compare ops — no model, no host: the drafter is free, so
    any acceptance at all is profit (repetitive text — code, extraction,
    summarisation — accepts a lot; the public "prompt lookup decoding"
    trick used by mainstream serving engines).
    """
    B, L = seq.shape
    idx = jnp.arange(L)[None, :]
    match = jnp.ones((B, L), bool)
    for k in range(ngram):
        # seq[j - k] == seq[pos - k], masked where j - k < 0.  The key
        # gather clamps at 0: when pos < ngram the n-gram does not exist
        # and any (verified-anyway) proposal is acceptable.
        shifted = jnp.pad(seq, ((0, 0), (k, 0)))[:, :L]
        want = jnp.take_along_axis(
            seq, jnp.maximum(pos[:, None] - k, 0), axis=1)
        match = match & (shifted == want) & (idx >= k)
    match = match & (idx < pos[:, None]) & (idx >= ngram - 1)
    j = jnp.max(jnp.where(match, idx, ngram - 1), axis=1)  # [B]
    return jax.vmap(
        lambda row, s: lax.dynamic_slice(row, (s + 1,), (gamma - 1,))
    )(seq, j)


@functools.cache
def _compiled_lookup(cfg: LlamaConfig, B: int, P: int, max_new: int,
                     max_len: int, gamma: int, ngram: int,
                     temperature: float, top_k: Optional[int],
                     top_p: Optional[float], ragged: bool = False):
    """jit'd prompt-lookup speculative generation: the model-draft driver
    with the draft scan replaced by :func:`_lookup_propose` over a
    sequence buffer — ONE model (the target) runs at all, so every
    accepted token saves a whole decode step."""
    rope = cfg_rope_tables(cfg, max_len)
    greedy = temperature == 0.0
    G = gamma

    def probs_of(logits):
        return jax.nn.softmax(_filter_logits(logits, temperature, top_k,
                                             top_p), axis=-1)

    def run(params, prompt, key, lengths):
        lp = (lengths - 1) if ragged else None
        t_logits, t_cache = prefill(params, cfg, prompt, max_len,
                                    logit_positions=lp)
        key, sub = jax.random.split(key)
        t0 = _sample(t_logits, sub, temperature, top_k, top_p)
        pos0 = lengths if ragged else jnp.full((B,), P, jnp.int32)

        # Sequence buffer: prompt, then every emitted token at its
        # absolute position (the lookup corpus grows as generation runs).
        # Ragged rows carry right-pad junk at lengths..P-1, but matching
        # only scans j < pos and emits overwrite from lengths upward, so
        # junk is never a lookup key or a copied span before it is
        # replaced.
        seq = jnp.zeros((B, max_len), jnp.int32)
        seq = lax.dynamic_update_slice(seq, prompt, (0, 0))
        seq = seq.at[jnp.arange(B), pos0].set(t0)

        out = jnp.zeros((B, max_new + G), jnp.int32)
        out = out.at[:, 0].set(t0)
        n_out = jnp.ones((B,), jnp.int32)
        stats0 = jnp.zeros((B, 2), jnp.int32)

        def macro(carry):
            t_cache, seq, out, n_out, t_pend, pos, key, stats = carry
            old_pos = pos

            drafts = _lookup_propose(seq, pos, ngram=ngram, gamma=G)
            pd = jax.nn.one_hot(drafts, cfg.vocab_size, dtype=jnp.float32)

            chunk = jnp.concatenate([t_pend[:, None], drafts], axis=1)
            t_logits, t_cache = chunk_decode_step(params, t_cache, chunk,
                                                  pos, cfg, rope)

            out, n_out, t_pend, pos, key, stats, emit = _accept_emit(
                drafts, pd, t_logits, key, out, n_out, t_pend, pos, stats,
                greedy=greedy, G=G, B=B, max_new=max_new,
                probs_of=probs_of)
            # Mirror the emit into the lookup corpus at the PRE-advance
            # position + 1 (emit holds [d_1..d_a, c, junk]; junk gets
            # overwritten by the next mirror — the same covering argument
            # as the caches).
            seq = jax.vmap(
                lambda row, w, s: lax.dynamic_update_slice(row, w, (s,))
            )(seq, emit, old_pos + 1)
            return (t_cache, seq, out, n_out, t_pend, pos, key, stats)

        def cond(carry):
            return jnp.any(carry[3] < max_new)

        carry = (t_cache, seq, out, n_out, t0, pos0, key, stats0)
        _, _, out, _, _, _, _, stats = lax.while_loop(cond, macro, carry)
        return out[:, :max_new], stats

    return jax.jit(run)


@functools.cache
def _compiled_speculative(cfg: LlamaConfig, draft_cfg: LlamaConfig, B: int,
                          P: int, max_new: int, max_len: int, gamma: int,
                          temperature: float, top_k: Optional[int],
                          top_p: Optional[float], ragged: bool = False):
    """jit'd speculative generation for one (shape, sampling) signature.

    One dispatch: target+draft prefill, then a ``lax.while_loop`` of macro
    steps — draft scan (``gamma - 1`` proposals), one ``gamma``-wide
    target chunk verify, the acceptance rule, per-row output writes.
    Rows advance 1..gamma tokens per macro step behind per-row cursors;
    the loop runs until every row has ``max_new`` tokens (bounded by
    ``max_new`` iterations: every step advances every row by >= 1).
    """
    from .generate import decode_step

    rope = cfg_rope_tables(cfg, max_len)
    greedy = temperature == 0.0
    G = gamma

    def probs_of(logits):
        """The SAME distribution _sample draws from, as probabilities."""
        return jax.nn.softmax(_filter_logits(logits, temperature, top_k,
                                             top_p), axis=-1)

    def run(params, draft_params, prompt, key, lengths):
        # Ragged: right-padded prompts, per-row cursors from the start
        # (the per-row position plumbing is the same machinery the
        # variable-acceptance advance uses anyway).
        lp = (lengths - 1) if ragged else None
        t_logits, t_cache = prefill(params, cfg, prompt, max_len,
                                    logit_positions=lp)
        _, d_cache = prefill(draft_params, draft_cfg, prompt, max_len,
                             logit_positions=lp)

        key, sub = jax.random.split(key)
        t0 = _sample(t_logits, sub, temperature, top_k, top_p)  # [B]

        out = jnp.zeros((B, max_new + G), jnp.int32)
        out = out.at[:, 0].set(t0)
        n_out = jnp.ones((B,), jnp.int32)
        pos0 = lengths if ragged else jnp.full((B,), P, jnp.int32)
        stats0 = jnp.zeros((B, 2), jnp.int32)  # [macro steps, accepted]

        def macro(carry):
            t_cache, d_cache, out, n_out, t_pend, pos, key, stats = carry

            # --- draft phase: G-1 proposals from the draft's own cache.
            # The scan feeds ALL G chunk tokens (t, d_1 .. d_{G-1}) — the
            # last step produces no proposal, it only writes d_{G-1}'s kv,
            # so after a FULL acceptance the draft cache has no hole at
            # pos+G-1 when the next macro step decodes past it (a zero
            # entry there would poison every later proposal).
            def draft_step(dcache, tok, p, k):
                logits, dcache = decode_step(draft_params, dcache, tok, p,
                                             draft_cfg, rope)
                if greedy:
                    nxt = jnp.argmax(logits, -1).astype(jnp.int32)
                    pd = jax.nn.one_hot(nxt, logits.shape[-1],
                                        dtype=jnp.float32)
                else:
                    nxt = _sample(logits, k, temperature, top_k, top_p)
                    pd = probs_of(logits)
                return dcache, nxt, pd

            def draft_scan(dcache, t_pend, pos, key):
                toks, pds = [], []
                tok = t_pend
                for i in range(G - 1):
                    key, sub = jax.random.split(key)
                    dcache, tok, pd = draft_step(dcache, tok, pos + i, sub)
                    toks.append(tok)
                    pds.append(pd)
                # Cache-write-only step for the last proposal's kv.
                _, dcache = decode_step(draft_params, dcache, tok,
                                        pos + G - 1, draft_cfg, rope)
                return dcache, jnp.stack(toks, 1), jnp.stack(pds, 1)

            key, dkey = jax.random.split(key)
            d_cache, drafts, pd = draft_scan(d_cache, t_pend, pos, dkey)
            # drafts: [B, G-1] proposals d_1..d_{G-1}; pd their proposal
            # distributions [B, G-1, V].

            # --- verify: ONE target forward over [t, d_1..d_{G-1}].
            chunk = jnp.concatenate([t_pend[:, None], drafts], axis=1)
            t_logits, t_cache = chunk_decode_step(params, t_cache, chunk,
                                                  pos, cfg, rope)
            # t_logits[:, i] = p_T(x at pos+i+1 | ..., chunk[:i+1]).

            out, n_out, t_pend, pos, key, stats, _emit = _accept_emit(
                drafts, pd, t_logits, key, out, n_out, t_pend, pos, stats,
                greedy=greedy, G=G, B=B, max_new=max_new,
                probs_of=probs_of)
            return (t_cache, d_cache, out, n_out, t_pend, pos, key, stats)

        def cond(carry):
            return jnp.any(carry[3] < max_new)

        carry = (t_cache, d_cache, out, n_out, t0, pos0, key, stats0)
        _, _, out, _, _, _, _, stats = lax.while_loop(cond, macro, carry)
        return out[:, :max_new], stats

    return jax.jit(run)


def generate_speculative(params: dict, cfg: LlamaConfig, draft_params: dict,
                         draft_cfg: LlamaConfig, prompt,
                         max_new_tokens: int, *, gamma: int = 4,
                         temperature: float = 0.0,
                         key: Optional[jax.Array] = None,
                         top_k: Optional[int] = None,
                         top_p: Optional[float] = None,
                         eos_id: Optional[int] = None,
                         prompt_lengths=None,
                         return_stats: bool = False):
    """Speculative generation: the TARGET model's output at a fraction of
    its decode steps.  prompt: [B, P] int32; returns ``[B, P +
    max_new_tokens]`` (prompt + continuation), the aligned
    :func:`~starway_tpu.models.generate.generate` contract.

    ``gamma``: macro-step width — the draft proposes ``gamma - 1`` tokens
    and the target verifies them (plus samples one more) in ONE forward.
    Per macro step a row advances ``a + 1`` tokens where ``a`` is its
    leading-accept count, so the target streams its cache once per
    ``a + 1`` tokens instead of once per token — the speedup is the
    draft's acceptance rate times that amortisation, minus the draft's
    own cost.

    Greedy (``temperature == 0``) output matches
    ``generate(params, cfg, ...)`` token for token up to bf16 argmax
    near-ties: the chunk verify and the stepwise decode compute the same
    logits through different summation orders, so a near-tied argmax can
    resolve differently in low precision (exact-match pinned on the CPU
    mesh by tests/test_speculative.py; the chunk-vs-stepwise logit gap
    on-chip by kernel_bench's ``check_spec_chunk_onchip``).  The draft
    only changes how fast
    tokens appear.  Sampling uses the standard speculative
    rejection rule against exactly the filtered distribution ``generate``
    samples from, so the per-token output distribution is the target
    model's (statistically pinned).  ``eos_id``: conventional eos-fill,
    applied to the finished buffer.

    ``return_stats``: additionally return an acceptance-health dict (the
    serving analogue of the MoE router stats): per-row ``macro_steps``
    and ``accepted`` counts — their ratio is the realised mean accept
    length ``a``, making the amortisation ``a + 1`` visible so a cold
    draft is distinguishable from a working one without timings.

    ``prompt_lengths`` ([B] ints, RIGHT-padded prompt): ragged batches —
    every row speculates from its own cursor; returns only the NEW
    tokens ``[B, max_new_tokens]`` (the ragged ``generate`` contract).

    Requirements: same vocab on both models; dense FFNs or
    provably-dropless MoE (``moe_capacity_factor >= n_experts``, the
    Mixtral conversion default — shape-invariant routing makes the chunk
    verify route exactly like stepwise decode; droppy capacities
    refuse).  Sliding-window models speculate through FULL caches with
    window masking (the O(window) rolling layout is the one thing not
    wired).
    """
    B, P = prompt.shape
    _validate_spec_args(max_new_tokens, gamma, (cfg, "target"),
                        (draft_cfg, "draft"))
    if cfg.vocab_size != draft_cfg.vocab_size:
        raise ValueError(
            f"target and draft must share a vocab: {cfg.vocab_size} != "
            f"{draft_cfg.vocab_size}")
    lengths = _validate_lengths(prompt_lengths, B, P)
    if key is None:
        key = jax.random.PRNGKey(0)
    # LongRoPE regime resolves at the LOGICAL horizon (prompt + budget),
    # BEFORE the gamma scratch headroom below — spec decode's contract is
    # output-equivalence with generate() at the same request, and
    # generate() resolves at this horizon (llama.resolve_longrope).
    from .llama import resolve_longrope

    cfg = resolve_longrope(cfg, P + max_new_tokens)
    draft_cfg = resolve_longrope(draft_cfg, P + max_new_tokens)
    # Cache headroom: a macro step may write up to gamma - 1 positions
    # past the last kept token before the row's budget check stops it.
    max_len = P + max_new_tokens + gamma
    if taken_for_rolling(cfg, max_len):
        # Dodge chunk_decode_step's rolling-cache shape heuristic (a FULL
        # cache of exactly window slots is indistinguishable from the
        # rolling layout); the extra slot is masked out of attention.
        max_len += 1
    run = _compiled_speculative(cfg, draft_cfg, B, P, max_new_tokens,
                                max_len, int(gamma), float(temperature),
                                top_k, top_p,
                                ragged=prompt_lengths is not None)
    toks, stats = run(params, draft_params, prompt, key, lengths)
    return _finish_spec(prompt, toks, stats, eos_id, return_stats,
                        ragged=prompt_lengths is not None)


def _validate_spec_args(max_new_tokens: int, gamma: int, *cfgs):
    """The restrictions both speculative entry points share."""
    if max_new_tokens < 1:
        raise ValueError(
            f"max_new_tokens must be >= 1, got {max_new_tokens}")
    if gamma < 2:
        raise ValueError(f"gamma must be >= 2 (got {gamma}); gamma=1 is "
                         f"plain decode — use generate()")
    from .moe import require_dropless

    for c, who in cfgs:
        if who == "target":
            # Only the TARGET's routing must be shape-invariant (the
            # chunk verify vs stepwise decode); a droppy DRAFT merely
            # proposes worse — the rejection rule keeps the output the
            # target's regardless of how the draft routes.
            require_dropless(c, f"speculative decoding ({who})")
        # Sliding-window configs run fine: the drivers allocate FULL
        # caches (max_len = P + max_new + gamma) and both the draft's
        # decode_step and the chunk verify mask by cfg.sliding_window —
        # only the O(window) ROLLING cache layout is unsupported, and
        # these entry points never allocate one.


def _validate_lengths(prompt_lengths, B: int, P: int):
    """generate()'s ragged-lengths contract (one shared implementation:
    generate.py:validate_prompt_lengths), with a zero placeholder for
    aligned batches so the compiled signature is uniform."""
    if prompt_lengths is None:
        return jnp.zeros((B,), jnp.int32)
    from .generate import validate_prompt_lengths

    return validate_prompt_lengths(prompt_lengths, B, P)


def _finish_spec(prompt, toks, stats, eos_id, return_stats, ragged=False):
    """Shared tail: conventional eos-fill on the finished buffer, prompt
    concat (aligned batches; ragged returns only the new tokens, the
    generate() contract), optional acceptance-stats dict."""
    if eos_id is not None:
        # Everything after a row's first eos becomes eos.
        seen = jnp.cumsum((toks == eos_id).astype(jnp.int32), axis=1)
        fill = (seen - (toks == eos_id).astype(jnp.int32)) > 0
        toks = jnp.where(fill, jnp.int32(eos_id), toks)
    out = toks if ragged else jnp.concatenate([prompt, toks], axis=1)
    if return_stats:
        return out, {"macro_steps": stats[:, 0], "accepted": stats[:, 1]}
    return out


def generate_lookup(params: dict, cfg: LlamaConfig, prompt,
                    max_new_tokens: int, *, gamma: int = 4, ngram: int = 2,
                    temperature: float = 0.0,
                    key: Optional[jax.Array] = None,
                    top_k: Optional[int] = None,
                    top_p: Optional[float] = None,
                    eos_id: Optional[int] = None,
                    prompt_lengths=None,
                    return_stats: bool = False):
    """Prompt-lookup speculative generation: no draft model — proposals
    are copied from the sequence's own history (continue the latest
    earlier occurrence of the current ``ngram``-gram,
    :func:`_lookup_propose`) and verified by the target's chunk forward.
    The drafter costs a few gathers, so ANY acceptance is pure profit;
    repetitive workloads (code, extraction, quoting) accept a lot.  Same
    guarantees as :func:`generate_speculative`: greedy output matches
    ``generate()`` up to bf16 argmax near-ties between the chunk and
    stepwise forwards; sampling preserves the target
    distribution (deterministic proposals are the ``p_D = one-hot``
    special case of the same rejection rule).  Same contract and
    restrictions otherwise (aligned or ragged ``prompt_lengths``
    batches; dense or provably-dropless MoE; sliding-window models run
    through full caches).
    """
    B, P = prompt.shape
    _validate_spec_args(max_new_tokens, gamma, (cfg, "target"))
    if ngram < 1:
        raise ValueError(f"ngram must be >= 1, got {ngram}")
    lengths = _validate_lengths(prompt_lengths, B, P)
    if key is None:
        key = jax.random.PRNGKey(0)
    from .llama import resolve_longrope

    cfg = resolve_longrope(cfg, P + max_new_tokens)  # logical horizon,
    # matching generate()'s regime for the same request (spec decode's
    # output-equivalence contract); the gamma headroom below is scratch.
    max_len = P + max_new_tokens + gamma
    if taken_for_rolling(cfg, max_len):
        # Dodge chunk_decode_step's rolling-cache shape heuristic (a FULL
        # cache of exactly window slots is indistinguishable from the
        # rolling layout); the extra slot is masked out of attention.
        max_len += 1
    run = _compiled_lookup(cfg, B, P, max_new_tokens, max_len, int(gamma),
                           int(ngram), float(temperature), top_k, top_p,
                           ragged=prompt_lengths is not None)
    toks, stats = run(params, prompt, key, lengths)
    return _finish_spec(prompt, toks, stats, eos_id, return_stats,
                        ragged=prompt_lengths is not None)
