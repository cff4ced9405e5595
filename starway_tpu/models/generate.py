"""KV-cache inference for the Llama family: prefill + single-token decode.

Static-shape, jit-compiled decode: the cache holds ``max_len`` slots per
layer and attention masks by position, so one compiled step serves the whole
generation (``lax.scan`` over steps; no retracing, no dynamic shapes -- the
XLA-friendly decode loop).

What the cache holds, and how it is written, attended and filled, is
models/cache.py's; :func:`cached_layer_scan` is the one place here that
names a cache leaf, because it is the layer body that PRODUCES a layer's
new entries.
"""

from __future__ import annotations

import contextlib
import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax import lax

from .cache import (_write_cached, attend_cache, attend_piece, cache_len,
                    cache_spec, from_forward, init_rolling_cache,
                    quantize_rows, ring_fold, ring_in_order,
                    taken_for_rolling)
from .llama import (LlamaConfig, apply_rope, cfg_rmsnorm, cfg_rope_tables,
                    diff_combine, diff_kv, diff_q, embed_tokens, ffn_block,
                    forward, gate_heads, gated_memory, layer_segments,
                    lm_head_matmul, matmul_w, mixer_out, model_logits,
                    qkv_proj, scan_segment, segment_kind, segment_layers)
from ..ops.attention import NEG_BIG, repeat_kv


def decode_step(params: dict, cache: dict, token, pos, cfg: LlamaConfig,
                rope=None, rolling: bool = False):
    """One token in, next-token logits out: ``(logits [B, V], updated
    cache)`` of :func:`decode_step_counted`, which documents the rest."""
    return decode_step_counted(params, cache, token, pos, cfg, rope,
                               rolling)[:2]


def decode_step_counted(params: dict, cache: dict, token, pos,
                        cfg: LlamaConfig, rope=None, rolling: bool = False):
    """token: [B] int32; pos: the ABSOLUTE position of ``token`` — a
    scalar (aligned batch) or a per-row [B] vector (ragged batch: every
    row sits at its own cursor).  Returns (logits [B, V], updated cache,
    the third value of :func:`cached_layer_scan`).

    ``rolling``: EVERY layer's cache is a ring of exactly
    ``cfg.sliding_window`` slots (``init_rolling_cache``) — writes go to
    ``pos % window``, and attention covers every warm slot with no window
    re-mask (the residents ARE the window; keys carry their absolute RoPE,
    and attention is permutation-invariant over keys, so slot order never
    matters).  Cache memory is O(window) for any generation length.  A
    ``cfg.kinds`` model's window layers keep such rings beside its full
    layers' rows (``init_cache``) and :func:`cached_layer_scan` says which
    a layer has: the same write and the same attention."""
    T = cache_len(cache)
    if rolling and not taken_for_rolling(cfg, T):
        raise ValueError(
            f"rolling decode needs a cache of exactly sliding_window="
            f"{cfg.sliding_window} slots, got {T}")
    if rope is None:
        if rolling:
            # Absolute positions exceed the cache size; the caller knows the
            # true horizon, we don't.
            raise ValueError("rolling decode requires explicit rope tables")
        rope = cfg_rope_tables(cfg, T)
    cos, sin = rope
    pos = jnp.asarray(pos, jnp.int32)
    per_row = pos.ndim == 1
    if per_row:
        # [B, 1, 1, hd/2]: one rotation angle per row, broadcast over heads.
        cos_p = cos[pos][:, None, None, :]
        sin_p = sin[pos][:, None, None, :]
    else:
        cos_p = lax.dynamic_slice_in_dim(cos, pos, 1, axis=0)
        sin_p = lax.dynamic_slice_in_dim(sin, pos, 1, axis=0)

    h = embed_tokens(params, token, cfg)[:, None, :]  # [B, 1, D]

    def write(cache, new, layer, ring=rolling):
        return _write_cached(cache, new, layer, pos, ring=ring)

    def attend(q, cache, layer, ring=rolling):
        return attend_cache(q, cache, pos, layer, cfg, ring=ring)

    h, out, counts = cached_layer_scan(params, cache, h, cos_p, sin_p, cfg,
                                       write, attend)
    h = cfg_rmsnorm(h, params["final_norm"], cfg)
    logits = lm_head_matmul(h[:, 0, :], params).astype(jnp.float32)
    return logits, out, counts


def ingest_decode_step(params: dict, cache: dict, token, pos, piece,
                       cfg: LlamaConfig, rope):
    """One decode step of the ``B`` cache rows AND up to ``W`` prompt
    tokens of one request, as ONE batch of ``B + W`` rows: embedding,
    projections, ``wo``, FFN and norms read every weight once for both.
    ``token``, ``pos``: ``[B]``, as :func:`decode_step_counted` takes them.
    ``piece = (ids [W], slot, first, valid)``: the request's prompt tokens
    at positions ``first .. first + valid - 1`` (the rest of ``ids`` is
    padding), to be ingested into cache row ``slot``; ``valid == 0`` is a
    step with nothing to ingest (its W rows compute and write nothing).

    Only ``write`` and ``attend`` of :func:`cached_layer_scan` tell the
    rows apart.  The decode rows write at their cursors and attend their
    own rows, as ever.  The piece is written to its slot (only its valid
    positions: a last piece's pads may reach past the cache) AFTER the
    decode rows' write -- the slot's own decode row is dead and writes
    junk at its frozen cursor, which the caller keeps at the piece's end,
    where the next piece or the request's first decode step overwrites it
    before anything reads it -- and attends that one row at ``W`` query
    positions, write-then-attend (:func:`~starway_tpu.models.speculative.
    chunk_decode_step`'s semantics; ``ops.ingest_attention``).  A dense
    k/v cache without a window.  (An int8 cache's scale leaves go through
    the same lines, but its pieces would attend over quantized entries
    where a prefill reads them exact, so no server sends one here:
    ``cache.CacheSpec.piecewise``.)

    Returns ``(logits [B, V], cache, piece_logits [V], counts)``:
    ``piece_logits`` are the next-token logits of the piece's last valid
    position (the request's first token, when the piece ends its prompt);
    the head runs on ``B + 1`` rows."""
    ids, slot, first, valid = piece
    B, W = token.shape[0], ids.shape[0]
    cos, sin = rope
    pos = jnp.broadcast_to(jnp.asarray(pos, jnp.int32), (B,))
    one = lambda x: jnp.asarray(x, jnp.int32).reshape(1)
    at = jnp.minimum(jnp.concatenate([pos, first + jnp.arange(W)]),
                     cos.shape[0] - 1)
    cos_p, sin_p = cos[at][:, None, None, :], sin[at][:, None, None, :]
    h = embed_tokens(params, jnp.concatenate([token, ids]), cfg)[:, None, :]

    def write(cache, new, layer):
        cache = _write_cached(cache, {name: x[:B] for name, x in new.items()},
                              layer, pos)
        # [W, Hkv, 1(, D)] -> [1, Hkv, W(, D)]: one cache row's W positions.
        mine = {name: jnp.swapaxes(x[B:], 0, 2) for name, x in new.items()}
        return _write_cached(cache, mine, layer, one(first), rows=one(slot),
                             count=one(valid))

    def attend(q, cache, layer):
        mine = attend_piece(jnp.swapaxes(q[B:], 0, 2), cache, one(first),
                            one(slot), layer)
        return jnp.concatenate([attend_cache(q[:B], cache, pos, layer, cfg),
                                jnp.swapaxes(mine, 0, 2)])

    h, out, counts = cached_layer_scan(params, cache, h, cos_p, sin_p, cfg,
                                       write, attend)
    last = lax.dynamic_slice_in_dim(h[:, 0], B + jnp.maximum(valid, 1) - 1, 1)
    rows = cfg_rmsnorm(jnp.concatenate([h[:B, 0], last]),
                       params["final_norm"], cfg)
    logits = matmul_w(rows, params["lm_head"]).astype(jnp.float32)
    return logits[:B], out, logits[B], counts


def cached_layer_scan(params, cache, h, cos_p, sin_p, cfg: LlamaConfig,
                      write, attend, *, first_layer: int = 0, mem=None):
    """The ONE per-layer body of every cached decode path — decode_step's
    C=1, the speculative chunk verify's C>1
    (models/speculative.py:chunk_decode_step), the paged pool's
    (models/paged.py) and the serving step that carries a prompt piece
    beside its decode rows (:func:`ingest_decode_step`) run exactly this:
    the attention kind's projection and RoPE, quantize-on-write when the
    cache is int8, ``write`` at the
    caller's cursor(s), ``attend``, the FFN kind
    (:func:`~starway_tpu.models.llama.ffn_block`).  Sharing it is what
    keeps the pinned chunk==stepwise parity a tautology instead of a
    maintenance contract.

    The stacked cache arrays (``k``, ``v`` and, int8, ``k_scale`` /
    ``v_scale``: [L, B, Hkv, T(, D)]; latent attention: ``ckv`` [L, B, 1,
    T, W]) ride the scan's CARRY beside ``h``, through every segment's
    scan in turn; ``xs`` is a segment's stacked layer weights and the
    layers' indices in the whole cache.  As scan inputs and outputs they
    could not share a buffer: every layer would be sliced out, updated as
    a slice and stored into a second stacked array, and the caller's step
    scan would copy that array into its own carry — half the device time
    of a serving step (PERF.md, PR 25).

    What a cache kind provides: its leaves all have the layer at axis 0,
    the row at 1 and the position at 3; ``write(cache, new, layer) ->
    cache`` places ``new`` (the same keys, [B, Hkv, C(, D)] each) at the
    caller's cursor(s) of layer ``layer`` (:func:`_write_cached`);
    ``attend(q, cache, layer)`` returns [B, Hq, C, hd] (latent: absorbed
    queries in, ``P c_kv`` [B, H, C, kv_rank] out; write-then-attend: it
    sees the entries just written; :func:`attend_cache`).  A cache of TWO
    kinds of leaves (a ``cfg.kinds`` model's: full rows under ``k`` /
    ``v``, the window layers' rings under ``k_ring`` / ``v_ring``, each
    stacked over its own layers) rides the carry whole; a window layer's
    hooks are called with ``ring=True`` and ``layer`` counting the ring
    leaves' layers, a full layer's as ever with ``layer`` counting the
    full ones (a caller whose hooks take no ``ring`` serves no such
    model).  A linear layer's state leaves (``kda_state`` / ``kda_conv``)
    ride the carry too and NO hook is called for them: a state has no
    cursor, the layer moves it on by one token itself (models/kda.py
    ``kda_decode``, C = 1 only).  ``cos_p`` None: no layer rotates (the
    MTP block's one full layer, models/mtp.py).  Returns ``(h [B, C, D], cache, counts)``: the pairs each held
    expert of each routed layer got, ``[routed layers, n_held]`` int32
    (None for a model with no routed layer).

    A model laid out in ``LayerKinds.runs`` takes :func:`_mixer_scan`: one
    scan body a run of whole periods, state, rings and full rows in one
    carry, and beside them ``mem``, the last state-space layer's read-out
    of the SAME token, for the gated memory units behind it.
    ``first_layer`` / ``mem``: begin at that layer (the first of a run)
    with that memory, which is how an admission runs the cross-decoder on
    a prompt's last row alone (:func:`prefill`).
    """
    if cfg.kinds is not None and cfg.kinds.runs:
        return _mixer_scan(params, cache, h, cfg, write, attend,
                           first_layer, mem)
    B, C = h.shape[0], h.shape[1]
    quant = "k_scale" in cache  # int8 cache (init_cache's format marker)

    def layer(carry, lp, li, *, rope=True, ring=False):
        kind = {"ring": True} if ring else {}
        h, cache = carry
        gate = None
        x = cfg_rmsnorm(h, lp["attn_norm"], cfg)
        if "kda" in lp:
            from .kda import kda_decode

            if C != 1:
                raise ValueError(
                    "a linear-attention layer's state moves one token a "
                    "step: it cannot verify or ingest a chunk of C > 1")
            # No cursor and no hook: the state has no position to write at.
            o, cache = kda_decode(x, lp["kda"], cfg, cache, li)
        elif "wkv_a" in lp:
            from .mla import expand_values, project_absorbed

            q, rows = project_absorbed(x, lp, cfg,
                                       *((cos_p, sin_p) if rope
                                         else (None, None)))
            cache = write(cache, {"ckv": rows}, li)
            o = expand_values(attend(q, cache, li), lp, cfg)
        else:
            q, k, v, gate = qkv_proj(x, lp, cfg)
            if rope and cos_p is not None:
                q = apply_rope(q, cos_p, sin_p)
                k = apply_rope(k, cos_p, sin_p)
            new = {"k": k, "v": v}
            if quant:
                from ..ops.quantize import quantize_kv

                # Quantize-on-write: the cache never holds a wide entry.
                new["k"], new["k_scale"] = quantize_kv(k)
                new["v"], new["v_scale"] = quantize_kv(v)
            cache = write(cache, new, li, **kind)
            o = attend(q, cache, li, **kind)
        o = gate_heads(o.transpose(0, 2, 1, 3).reshape(B, C, -1), gate)
        h = h + matmul_w(o, lp["wo"])
        y, _aux, stats = ffn_block(cfg_rmsnorm(h, lp["mlp_norm"], cfg),
                                   lp, cfg, attn_in=x)
        return (h + y, cache), (stats if "routed" in lp else None)

    carry, counts = (h, dict(cache)), []
    for seg, first in layer_segments(params["layers"]):
        n = jax.tree_util.tree_leaves(seg)[0].shape[0]
        body = layer
        if cfg.kinds is not None:
            # The layers of this segment among those of their cache kind.
            window, rope, _linear = segment_kind(cfg, seg, first)
            first = cfg.kind_layers(cfg.cache_kind(first), first)
            body = functools.partial(layer, rope=rope, ring=window is not None)
        carry, ys = scan_segment(
            body, carry, seg, first + jnp.arange(n, dtype=jnp.int32))
        if ys is not None:
            counts.append(ys)
    return (*carry, jnp.concatenate(counts) if counts else None)


def _mixer_scan(params, cache, h, cfg: LlamaConfig, write, attend,
                first_layer: int = 0, mem=None):
    """:func:`cached_layer_scan` of a model laid out in ``LayerKinds.runs``.
    A state-space layer moves its state on itself (``ssm_decode``, C = 1)
    and hands its read-out on as ``mem``; a gated memory unit multiplies
    it and touches no leaf; a window or full layer writes its pair rows
    and attends them; a cross layer writes nothing and attends the rows of
    the full layer before it (``attend``'s ``layer`` is then THAT layer's
    index, ``cfg.rows_layer``).  The layers behind the last one that keeps
    anything run under the scope ``sw_cross_decoder``."""
    from .ssm import ssm_decode

    B, C = h.shape[0], h.shape[1]

    def one(carry, lp, li, kind: str):
        h, cache, mem = carry
        x = cfg_rmsnorm(h, lp["attn_norm"], cfg)
        if kind == "ssm":
            if C != 1:
                raise ValueError(
                    "a state-space layer's state moves one token a step: "
                    "it cannot verify or ingest a chunk of C > 1")
            o, cache, mem = ssm_decode(x, lp["ssm"], cfg, cache, li)
        elif kind == "gmu":
            o = gated_memory(x, lp, mem)
        else:
            ring = {"ring": True} if kind == "window" else {}
            if kind != "cross":
                k, v = diff_kv(x, lp, cfg)
                cache = write(cache, {"k": k, "v": v}, li, **ring)
            o = diff_combine(attend(diff_q(x, lp, cfg), cache, li, **ring),
                             lp, cfg)
        return mixer_out(h, o, lp, cfg), cache, mem

    def leaf_index(i: int) -> int:
        kind = cfg.mixer(i)
        if kind in ("cross", "full"):
            return cfg.rows_layer(i)
        return 0 if kind == "gmu" else cfg.kind_layers(cfg.cache_kind(i), i)

    if mem is None:
        wide = cfg.ssm.d_inner if cfg.ssm is not None else 0
        mem = jnp.zeros((B, C, wide), cfg.compute_dtype)
    carry = (h, dict(cache), mem)
    keeps = [i for i in range(cfg.n_layers) if cfg.cache_kind(i) is not None]
    for seg, first in layer_segments(params["layers"]):
        if first < first_layer:
            continue
        kinds, p = cfg.kinds.mixers[first:first + len(seg)], len(seg)
        index = tuple(jnp.asarray(
            [leaf_index(first + r * p + j)
             for r in range(segment_layers(seg) // p)], jnp.int32)
            for j in range(p))

        def period(carry, xs, kinds=kinds):
            for kind, lp, li in zip(kinds, *xs):
                carry = one(carry, lp, li, kind)
            return carry, None

        behind = first > keeps[-1]     # the layers that keep nothing
        with (jax.named_scope("sw_cross_decoder") if behind
              else contextlib.nullcontext()):
            carry, _ = lax.scan(period, carry, (seg, index))
    return carry[0], carry[1], None


@functools.cache
def early_exit_at(cfg: LlamaConfig) -> Optional[int]:
    """Where an admission may leave the prompt's rows behind: the index of
    the model's LAST layer that keeps anything, if that is a full layer in
    a run of its own and every layer behind it a gated memory unit or a
    cross layer (they write no cache, so an admission needs them at the
    prompt's last row only); None for every other model."""
    if cfg.kinds is None or "cross" not in cfg.kinds.mixers:
        return None
    kept = [i for i in range(cfg.n_layers) if cfg.cache_kind(i) is not None]
    last = kept[-1]
    alone = (last, 1, ("full",)) in cfg.kinds.segments()
    return last if alone and last + 1 < cfg.n_layers else None


def _prefill_early_exit(params, cfg: LlamaConfig, prompt, max_len: int,
                        last, full_at: int):
    """:func:`prefill` of a model whose layers behind ``full_at`` keep
    nothing (:func:`early_exit_at`): the layers below it over every row;
    layer ``full_at``'s k and v of every row and the rest of it for each
    prompt's LAST row (``last [B]``); the layers behind it for that one
    row, with the memory of that row, through the decode path's own body
    over the rows just made.  The logits are the whole forward's."""
    take = lambda a: jnp.take_along_axis(a, last[:, None, None], axis=1)
    lengths = last + 1
    h, mem, kv = forward(params, prompt, cfg, return_kv=True, lengths=lengths,
                         stop_at=full_at)
    seg = next(seg for seg, first in layer_segments(params["layers"])
               if first == full_at)
    lp = jax.tree_util.tree_map(lambda a: a[0], seg[0])
    x = cfg_rmsnorm(h, lp["attn_norm"], cfg)
    k, v = diff_kv(x, lp, cfg)
    rows = {"k": k[None], "v": v[None]}        # a cache of this one layer

    def attend(q, cache, layer, **_kind):
        return attend_cache(q, cache, last, 0, cfg)

    o = diff_combine(attend(diff_q(take(x), lp, cfg), rows, 0), lp, cfg)
    h = mixer_out(take(h), o, lp, cfg)
    h, _rows, _counts = cached_layer_scan(
        params, rows, h, None, None, cfg, None, attend,
        first_layer=full_at + 1, mem=take(mem))
    return (model_logits(params, h, cfg)[:, 0], from_forward(
        cache_spec(cfg, max_len), {**kv, **rows}, lengths))


def prefill(params: dict, cfg: LlamaConfig, prompt,
            max_len: Optional[int] = None, attn_fn=None,
            logit_positions=None, return_hidden: bool = False,
            early_exit: bool = True):
    """One parallel forward pass over the whole prompt -> the decode state.

    Returns ``(next_logits [B, V], cache)`` where the cache holds the
    post-RoPE grouped k/v (latent attention: the latent rows) of positions
    ``0..P-1`` (zero-padded to ``max_len``).  This is the flash-attention path over the prompt — one
    MXU-shaped dispatch instead of P bandwidth-bound cached decode steps,
    and bit-identical to stepping the prompt through ``decode_step``
    (pinned by tests/test_generate.py::test_prefill_matches_stepwise).

    ``logit_positions`` ([B] ints, ragged right-padded batches): the
    returned logits come from each row's own position instead of the last
    column (no [B, P, V] tensor is built either way).

    A ``cfg.kinds`` model's window layers come back as RINGS
    (:func:`ring_fold`: each row's last ``window`` real positions at their
    residues, a row's length being ``logit_positions + 1``, else P), its
    full layers padded to ``max_len``: :func:`init_cache`'s two kinds.  Its
    linear layers (``cfg.linear``) come back as the STATE after each row's
    own last token, ``logit_positions + 1`` long: the positions behind it
    do not move the state and stay out of the convolutions' tails, so a
    padded bucket leaves what the unpadded prompt leaves.  State-space
    layers (``cfg.ssm``) likewise.

    ``early_exit``: a model whose last layers keep nothing
    (:func:`early_exit_at`) runs them for each row's last position only
    (:func:`_prefill_early_exit`); off, every layer sees every row, and the
    logits and every leaf are the same.
    """
    B, P = prompt.shape
    if max_len is None:
        max_len = P
    elif max_len < P:
        raise ValueError(f"max_len={max_len} is smaller than the prompt ({P})")
    full_at = early_exit_at(cfg) if early_exit and not return_hidden else None
    if full_at is not None and attn_fn is None:
        last = (jnp.full((B,), P - 1, jnp.int32) if logit_positions is None
                else jnp.asarray(logit_positions, jnp.int32))
        return _prefill_early_exit(params, cfg, prompt, max_len, last,
                                   full_at)
    logits, _aux, kv, *hidden = forward(
        params, prompt, cfg, attn_fn, return_aux=True, return_kv=True,
        last_only=logit_positions is None, logit_positions=logit_positions,
        lengths=None if logit_positions is None else logit_positions + 1,
        return_hidden=return_hidden,
    )
    lengths = (jnp.full((B,), P, jnp.int32) if logit_positions is None
               else logit_positions + 1)
    return (logits[:, 0],
            from_forward(cache_spec(cfg, max_len), kv, lengths), *hidden)


def prefill_rolling(params: dict, cfg: LlamaConfig, prompt, *,
                    chunk: Optional[int] = None, attn_fn=None,
                    widths=None):
    """Long-prompt prefill in O(window) memory: chunks of at most
    ``sliding_window`` tokens stream through the transformer, each chunk
    attending to the rolling cache (its own window's past) plus itself,
    merged with the online-softmax partial algebra
    (ops/attention.py::merge_partials).  Peak activation memory scales
    with ``chunk + window``, never the prompt — the missing piece between
    the O(window) decode cache and an O(S) full-prompt prefill.

    Returns ``(last_logits [B, V], rolling_cache)``; continue with
    ``decode_step(..., pos=P, rolling=True)`` (or hand both to a serving
    loop).  Matches the one-pass windowed prefill bit-close (pinned by
    tests/test_generate.py).  The chunk body is the same
    :func:`~starway_tpu.models.llama.decoder_layer` every other path uses
    (``attn_fn`` must be None: the chunk step owns its attention).

    ``widths`` (else ``chunk``): a DENOMINATION schedule, e.g. (64, 8, 1)
    — the prompt is covered greedily by these chunk widths (each capped at
    the window), so the set of compiled chunk programs is bounded by
    ``len(widths)`` for ANY prompt length.  The default single-``chunk``
    plan compiles one extra program per distinct final-partial width —
    fine for batch jobs, a compile explosion for serving admission
    (models/serving.py passes denominations).
    """
    from .llama import head_logits

    W = cfg.sliding_window
    if W is None:
        raise ValueError("prefill_rolling requires cfg.sliding_window")
    if attn_fn is not None:
        raise ValueError("prefill_rolling owns its attention; attn_fn must be None")
    B, P = prompt.shape
    cos, sin = cfg_rope_tables(cfg, P)
    cache = init_rolling_cache(cfg, B)

    # Host-side chunk plan.
    plan = []
    c0 = 0
    if widths is None:
        C = min(chunk or W, W, P)
        while c0 < P:
            plan.append(min(C, P - c0))
            c0 += plan[-1]
    else:
        for width in widths:
            width = min(int(width), W)
            while P - c0 >= width:
                plan.append(width)
                c0 += width
        if c0 != P:
            raise ValueError(
                f"widths={tuple(widths)} cannot cover prompt length {P} "
                f"(include 1 as the smallest denomination)")

    # Jitted chunk step (module-level compile cache keyed on cfg; jit's own
    # cache keys one shape per distinct plan width).  Eager per-op dispatch
    # here would cost O(P/C * n_layers) host round trips.
    run_chunk = _compiled_prefill_chunk(cfg)

    h_last = None
    c0 = 0
    for Cc in plan:
        # Rope slices are cut on the host so the compiled signature sees
        # [Cc, ...] — independent of P (a full-table argument would
        # recompile the chunk program for every distinct prompt length).
        h_last, cache = run_chunk(params, cache, prompt[:, c0:c0 + Cc],
                                  jnp.asarray(c0, jnp.int32),
                                  cos[c0:c0 + Cc], sin[c0:c0 + Cc])
        c0 += Cc
    logits = head_logits(h_last[:, -1:], params["final_norm"],
                         params["lm_head"], cfg.norm_eps,
                         cfg.norm_zero_centred)
    return logits[:, 0], cache


@functools.cache
def _compiled_prefill_chunk(cfg: LlamaConfig):
    """jit'd single-chunk body of :func:`prefill_rolling` for one config.

    ``c0`` (the chunk's global start) is traced, so every full-size chunk
    reuses ONE compiled program; only the final partial chunk (different
    width) triggers a second trace."""
    from ..ops.attention import (finalize_partial, merge_partials,
                                 partial_attention)
    from .llama import decoder_layer

    W = cfg.sliding_window
    n_rep = cfg.n_heads // cfg.n_kv_heads

    quant = cache_spec(cfg, W, rolling=True).int8

    def run_chunk(params, cache, tokens_c, c0, cos_c, sin_c):
        """One chunk through every layer; returns (h, new cache)."""
        Cc = tokens_c.shape[1]
        slots = (c0 + jnp.arange(Cc)) % W
        # Reorder the cache by absolute position: slot s holds the latest
        # p < c0 with p % W == s; gathering positions c0-W..c0-1 in order
        # lets partial_attention mask in plain global coordinates.
        order = (c0 - W + jnp.arange(W)) % W
        h = embed_tokens(params, tokens_c, cfg)  # [B, Cc, D]

        def chunk_attn(mine):
            """attn_fn for decoder_layer: past (the layer's leaves of the
            rolling cache, ``mine``, in position order) + present (the
            chunk itself, causal) as two mergeable online-softmax partials.
            int8 caches dequantize the gathered window up front — an
            O(window) transient per layer, matching the path's O(chunk +
            window) memory contract."""
            def attn(q, k, v):
                kco, vco = ring_in_order(mine, order, q.dtype)
                past = partial_attention(
                    q, repeat_kv(kco, n_rep), repeat_kv(vco, n_rep),
                    q_offset=c0, kv_offset=c0 - W, causal=True, window=W,
                    kv_min=0)
                here = partial_attention(
                    q, repeat_kv(k, n_rep), repeat_kv(v, n_rep),
                    q_offset=c0, kv_offset=c0, causal=True, window=W)
                return finalize_partial(*merge_partials(past, here),
                                        out_dtype=q.dtype)

            return attn

        # Python loop over layers (stacked tree sliced per layer): the one
        # decoder_layer body the scan forward uses, with a per-layer
        # cache-aware attn_fn; the returned post-RoPE grouped k/v feed the
        # circular slot write.
        new = {name: [] for name in cache}
        for li in range(cfg.n_layers):
            lp = jax.tree_util.tree_map(lambda a: a[li], params["layers"])
            mine = {name: a[li] for name, a in cache.items()}
            h, _aux, kv, _stats = decoder_layer(lp, h, cfg, cos_c, sin_c,
                                                chunk_attn(mine))
            kv = quantize_rows(kv) if quant else kv
            for name in cache:
                new[name].append(mine[name].at[:, :, slots].set(kv[name]))
        return h, {name: jnp.stack(v) for name, v in new.items()}

    # The caller rebinds its cache to the returned one each chunk, so the
    # input cache can be donated: the update happens in place instead of
    # holding two full O(window) caches live per dispatch.
    return jax.jit(run_chunk, donate_argnums=(1,))


def validate_prompt_lengths(prompt_lengths, B: int, P: int):
    """The ragged-batch lengths contract shared by every generation entry
    point (generate, generate_speculative, generate_lookup): concrete
    [B] int values in [1, P].  Under jit the downstream gathers would
    clamp and return wrong continuations silently, so tracers are
    rejected — ragged generation must be called outside jit (the entry
    points compile their own prefill+decode programs internally).
    Returns the [B] int32 lengths."""
    lengths = jnp.asarray(prompt_lengths, jnp.int32)
    if lengths.shape != (B,):
        raise ValueError(f"prompt_lengths must be [{B}], got {lengths.shape}")
    if isinstance(lengths, jax.core.Tracer):
        raise ValueError(
            "ragged generation (prompt_lengths) must be called outside "
            "jit: length validation needs concrete values")
    if bool((lengths < 1).any()) or bool((lengths > P).any()):
        raise ValueError(
            f"prompt_lengths must be in [1, {P}]; got {lengths.tolist()}")
    return lengths


def _nucleus_key(l):
    """float32 -> uint32 in the same order: a non-negative's sign bit is
    set, a negative's bits are negated (so -0.0 meets +0.0, as it does
    in a float comparison)."""
    bits = lax.bitcast_convert_type(l, jnp.uint32)
    return jnp.where(bits >> 31 == 1, -bits, bits | jnp.uint32(1 << 31))


def _filter_logits(logits, temperature: float, top_k: Optional[int],
                   top_p: Optional[float]):
    """The sampling distribution's logits: temperature-scaled, then top-k /
    nucleus masked (NEG_BIG outside the kept set).  ``softmax`` of the
    result IS the distribution :func:`_sample` draws from — speculative
    decoding's acceptance rule needs exactly it (models/speculative.py).
    Only meaningful for ``temperature > 0``.

    The nucleus, with ``l = logits / temperature`` (after top-k's mask)
    and ``p = softmax(l)``: the kept set is ``{i : l_i >= t}``, ``t`` the
    smallest entry ``v`` of the row whose mass strictly above it, ``S(v) =
    sum of p_j over l_j > v``, is ``< top_p``.  So ties at the threshold
    are all kept, and the row's maximum always is (``S(max) = 0``).

    ``S`` falls as ``v`` rises, so ``t`` needs no sort: it is the largest
    ``T``, over the logits' ordered 32-bit keys (:func:`_nucleus_key`),
    whose mass AT OR ABOVE it, ``sum of e_j over key_j >= T`` with ``e =
    exp(l - max)``, still reaches ``top_p * sum(e)``: built bit by bit from
    the top, 32 passes over the row in float32, every one of them needed
    (no data value lies between that ``T`` and ``t``)."""
    l = logits / temperature
    if top_k is not None and top_k < l.shape[-1]:
        kth = lax.top_k(l, top_k)[0][..., -1:]
        l = jnp.where(l < kth, NEG_BIG, l)
    if top_p is not None and top_p < 1.0:
        # Rows by vocabulary, whatever the caller's leading axes: a
        # ``[B, 1, V]`` operand would ride the loop one sublane a tile.
        rows = l.reshape(-1, l.shape[-1])
        f32 = rows.astype(jnp.float32)
        key = _nucleus_key(f32)
        e = jnp.exp(f32 - jnp.max(f32, axis=-1, keepdims=True))
        budget = top_p * jnp.sum(e, axis=-1, keepdims=True)

        def take_bit(i, T):
            cand = T | (jnp.uint32(1 << 31) >> i.astype(jnp.uint32))
            mass = jnp.sum(jnp.where(key >= cand, e, 0.0), axis=-1,
                           keepdims=True)
            return jnp.where(mass >= budget, cand, T)

        thresh = lax.fori_loop(0, 32, take_bit,
                               jnp.zeros(budget.shape, jnp.uint32))
        l = jnp.where(key < thresh, NEG_BIG, rows).reshape(l.shape)
    return l


def _sample(logits, key, temperature: float, top_k: Optional[int],
            top_p: Optional[float]):
    """One sampled token id per row of ``logits [B, V]``.  Static Python
    ``temperature``/``top_k``/``top_p`` (baked into the compiled step):
    temperature 0 = greedy; top-k keeps the k largest logits; top-p keeps
    every token whose logit is at least the nucleus threshold of
    :func:`_filter_logits` (the tokens with less than ``top_p`` of the
    mass strictly above them: ties at the threshold all stay, and the
    most likely token always does)."""
    if temperature == 0.0:
        return jnp.argmax(logits, axis=-1).astype(jnp.int32)
    l = _filter_logits(logits, temperature, top_k, top_p)
    return jax.random.categorical(key, l, axis=-1).astype(jnp.int32)


@functools.cache
def _compiled_generate(cfg: LlamaConfig, B: int, P: int, max_new: int,
                       max_len: int, temperature: float,
                       top_k: Optional[int], top_p: Optional[float],
                       ragged: bool = False, eos_id: Optional[int] = None,
                       want_logprobs: bool = False):
    """jit'd prefill + decode scan for one (shape, sampling) signature.

    The whole generation is ONE dispatch: flash prefill, then a
    ``lax.scan`` of sample->decode steps — no per-token host round trip
    (the XLA-friendly decode loop).

    ``ragged``: the compiled fn takes per-row prompt lengths; every row
    decodes from its own cursor (see :func:`generate`'s contract).

    Sliding-window configs on the aligned path decode through a ROLLING
    cache of ``sliding_window`` slots whenever that is smaller than
    ``max_len`` — cache memory is O(window) however long the generation
    runs, and the tokens are bit-identical to the full-cache path (pinned
    by tests/test_generate.py).
    """
    rope = cfg_rope_tables(cfg, max_len)
    W = cfg.sliding_window
    rolling = (not ragged) and W is not None and W < max_len

    def run(params, prompt, key, lengths):
        if rolling:
            # Every layer's last W positions, each at its slot p % W.
            logits, cache = prefill(params, cfg, prompt, P)  # unpadded
            cache = jax.tree_util.tree_map(
                lambda a: ring_fold(a, jnp.full((B,), P), W), cache)
            pos0 = jnp.asarray(P, jnp.int32)
        elif ragged:
            # Right-padded prompts: causal attention already confines every
            # real position to real prefixes (pad positions only corrupt
            # their OWN states, which are never read — hence the dense-only
            # restriction: MoE capacity is shared batch-wide), so the same
            # prefill fills the cache, gathering each row's next-token
            # logits from its own length-1 position.
            logits, cache = prefill(params, cfg, prompt, max_len,
                                    logit_positions=lengths - 1)
            pos0 = lengths
        else:
            logits, cache = prefill(params, cfg, prompt, max_len)
            pos0 = jnp.asarray(P, jnp.int32)

        done0 = jnp.zeros((B,), bool)

        def emit(logits, sub, done):
            """Sample one token per row (+, when asked, its UNFILTERED
            model logprob — the serving-API convention); rows already
            done emit eos at logprob 0 (the fill is mechanical, not a
            model event).  ``want_logprobs`` is in the compile key, so
            the default path keeps its logprob-free graph."""
            tok = _sample(logits, sub, temperature, top_k, top_p)
            if want_logprobs:
                lp = jnp.take_along_axis(
                    jax.nn.log_softmax(logits, -1), tok[:, None], -1)[:, 0]
            else:
                lp = jnp.zeros((B,), jnp.float32)
            if eos_id is not None:
                tok = jnp.where(done, jnp.int32(eos_id), tok)
                lp = jnp.where(done, 0.0, lp)
                done = done | (tok == eos_id)
            return tok, lp, done

        def step(carry, _):
            cache, logits, key, pos, done = carry
            key, sub = jax.random.split(key)
            tok, lp, done = emit(logits, sub, done)
            logits, cache = decode_step(params, cache, tok, pos, cfg, rope,
                                        rolling=rolling)
            return (cache, logits, key, pos + 1, done), (tok, lp)

        # Scan max_new - 1 sample->decode pairs, then sample the final token
        # outside the scan: its decode_step would compute logits nothing
        # ever reads.
        init = (cache, logits, key, pos0, done0)
        (cache, logits, key, _, done), (toks, lps) = lax.scan(
            step, init, None, length=max_new - 1)
        key, sub = jax.random.split(key)
        last, last_lp, _ = emit(logits, sub, done)
        toks = jnp.concatenate([toks, last[None]], axis=0)
        lps = jnp.concatenate([lps, last_lp[None]], axis=0)
        return toks.T, lps.T  # [B, max_new] each

    return jax.jit(run)


def generate(params: dict, cfg: LlamaConfig, prompt, max_new_tokens: int,
             *, temperature: float = 0.0, key: Optional[jax.Array] = None,
             max_len: Optional[int] = None, top_k: Optional[int] = None,
             top_p: Optional[float] = None, prompt_lengths=None,
             eos_id: Optional[int] = None, return_logprobs: bool = False):
    """Autoregressive generation.  prompt: [B, P] int32.

    Aligned batch (default): returns ``[B, P + max_new_tokens]`` (prompt +
    continuation).  temperature=0 -> greedy; otherwise softmax sampling
    with ``key``, optionally truncated by ``top_k`` and/or nucleus
    ``top_p``.  ``eos_id``: rows that emit it keep emitting it for the
    rest of the scan (the conventional eos-fill; the compiled step count
    stays static).

    Ragged batch: pass ``prompt_lengths`` ([B] ints, RIGHT-padded prompt)
    and every row decodes from its own length — one compiled scan serves
    mixed prompt sizes.  Returns only the NEW tokens ``[B,
    max_new_tokens]`` (row b's continuation of ``prompt[b, :lengths[b]]``;
    the caller stitches ragged rows).

    ``return_logprobs``: additionally return ``[B, max_new_tokens]`` f32 —
    each emitted token's UNFILTERED model logprob (log-softmax of the raw
    logits at its position, the serving-API convention, regardless of
    temperature/top-k/top-p), with eos-fill positions at 0.0 (the fill is
    mechanical, not a model event).  Pinned against teacher-forced
    recomputation by tests/test_generate.py.
    """
    B, P = prompt.shape
    if max_new_tokens < 1:
        # The compiled scan has length max_new_tokens - 1; a zero/negative
        # count would die deep inside tracing after paying a full prefill.
        raise ValueError(f"max_new_tokens must be >= 1, got {max_new_tokens}")
    total = P + max_new_tokens
    if max_len is None:
        max_len = total
    elif max_len < total:
        # Without this, dynamic_update_slice clamps every position >= max_len
        # onto the last cache slot and generation silently corrupts.
        raise ValueError(
            f"max_len={max_len} is smaller than prompt + max_new_tokens={total}"
        )
    if key is None:
        key = jax.random.PRNGKey(0)
    # LongRoPE: pin the factor regime to this run's horizon ONCE —
    # prefill and decode tables are built at different lengths and must
    # agree (llama.resolve_longrope).
    from .llama import resolve_longrope

    cfg = resolve_longrope(cfg, max_len)
    ragged = prompt_lengths is not None
    if ragged:
        from .moe import require_dropless

        # Pad tokens share the batch-wide expert capacity; only provable
        # droplessness keeps real rows untouched (moe.py, the single
        # source of the rule).
        require_dropless(cfg, "ragged generation")
        lengths = validate_prompt_lengths(prompt_lengths, B, P)
    else:
        lengths = jnp.zeros((B,), jnp.int32)  # unused placeholder
    run = _compiled_generate(cfg, B, P, max_new_tokens, max_len,
                             float(temperature), top_k, top_p, ragged,
                             None if eos_id is None else int(eos_id),
                             want_logprobs=bool(return_logprobs))
    toks, lps = run(params, prompt, key, lengths)
    out = toks if ragged else jnp.concatenate([prompt, toks], axis=1)
    if return_logprobs:
        return out, lps
    return out
