"""KV-cache inference for the Llama family: prefill + single-token decode.

Static-shape, jit-compiled decode: the cache holds ``max_len`` slots per
layer and attention masks by position, so one compiled step serves the whole
generation (``lax.scan`` over steps; no retracing, no dynamic shapes -- the
XLA-friendly decode loop).

The cache layout is scan-stacked like the parameters: ``k/v
[n_layers, B, Hkv, max_len, head_dim]``.  The stacked arrays ride the layer
scan's CARRY (:func:`cached_layer_scan`): they are never a scan input or
output and never sliced per layer, so one buffer serves the whole
generation (donate the cache under jit).  On the chip the new entries are
written in place by ``ops.cache_write`` and attention reads
the stacked array through a layer index; the decode step moves no cache
bytes but the ones attention reads.

A RING is a cache of exactly one window's positions, written at ``pos %
window`` and attended whole (``_write_cached`` / ``attend_cache`` with
``ring=True``: the one implementation).  A model whose every layer has
the window (``cfg.sliding_window``) may keep all its layers so
(``init_rolling_cache``, ``decode_step(rolling=True)``); a model whose
layers differ (``cfg.kinds``) keeps its window layers' rings under
``k_ring`` / ``v_ring`` BESIDE its full layers' rows under ``k`` / ``v``,
each stacked over the layers of its own kind (``init_cache``).

A STATE is what a linear-attention layer keeps (``cfg.linear``,
models/kda.py): ``kda_state`` / ``kda_conv``, a matrix a head and the
convolutions' last inputs, with NO position axis.  Nothing is written at a
cursor and nothing masks by one: a decode step moves the whole state on
(``ops.kda_step``, in place), a prefill hands back the state after each
row's own last token (:func:`prefill`'s ``logit_positions``), and whoever
seats a request replaces the row's state whole.
"""

from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax import lax

from .llama import (LlamaConfig, apply_rope, cfg_rmsnorm, cfg_rope_tables,
                    embed_tokens, ffn_block, forward, gate_heads,
                    layer_segments, matmul_w, qkv_proj, scan_segment,
                    segment_kind)
from ..ops import (cache_write, cached_attention, ingest_attention,
                   latent_attention)
from ..ops.attention import NEG_BIG, repeat_kv


def init_cache(cfg: LlamaConfig, batch: int, max_len: int) -> dict:
    """Decode cache: ``k/v [n_layers, B, Hkv, max_len, head_dim]``.

    ``cfg.kv_quant == "int8"`` stores k/v as int8 plus per-token f32 scales
    ``k_scale/v_scale [n_layers, B, Hkv, max_len]`` (ops/quantize.py) —
    half the HBM bytes on the bandwidth-bound decode stream.  The scale
    keys' presence IS the format marker every consumer dispatches on.

    Latent attention (``cfg.latent``, models/mla.py) caches ONE row a token
    for all heads: ``ckv [n_layers, B, 1, max_len, cache_width]`` (``kv_rank
    + rope_dim`` values in whole lane tiles), the same five axes with a
    single "head", so slot writes, padding and ``kv_write`` treat it as
    they treat ``k``.

    Layers of different kinds (``cfg.kinds``) keep TWO kinds of leaves:
    ``k`` / ``v [full layers, B, Hkv, max_len, head_dim]`` and the window
    layers' rings ``k_ring`` / ``v_ring [window layers, B, Hkv, window,
    head_dim]``, each stacked over its own layers in model order.  Linear
    layers (``cfg.linear``) add a third kind with NO position axis:
    ``kda_state [linear layers, B, H, d, d]`` float32 and ``kda_conv
    [linear layers, B, taps - 1, conv_width]`` (q, k and v side by side:
    ``3*H*d``, less where the key heads are fewer); ``max_len`` then
    sizes the attention layers alone, latent rows or grouped-query
    ``k`` / ``v`` as the model has them.  A ring holds ``cfg.kinds.ring``
    positions: the window and the slack a step of several positions needs.

    An MTP block (``cfg.mtp``, models/mtp.py) keeps a full row of its own
    a batch row, ``k_mtp`` / ``v_mtp [1, B, Hkv, max_len, head_dim]``,
    beside the model's leaves.
    """
    full = cfg.kind_layers("full")
    state = {}
    if cfg.mtp:
        state = {name: jnp.zeros(
            (cfg.mtp, batch, cfg.n_kv_heads, max_len, cfg.head_dim),
            cfg.compute_dtype) for name in ("k_mtp", "v_mtp")}
    if cfg.linear is not None:
        la, n = cfg.linear, cfg.kind_layers("linear")
        state = {
            "kda_state": jnp.zeros(
                (n, batch, la.n_heads, la.head_dim, la.head_dim), jnp.float32),
            "kda_conv": jnp.zeros((n, batch, la.conv - 1, la.conv_width),
                                  cfg.compute_dtype)}
    if cfg.latent is not None:
        return {"ckv": jnp.zeros(
            (full, batch, 1, max_len, cfg.latent.cache_width),
            cfg.compute_dtype), **state}
    hd = cfg.head_dim
    if cfg.kinds is not None:
        shapes = {"": (full, max_len)}
        if cfg.kinds.window is not None:
            shapes["_ring"] = (cfg.kind_layers("ring"), cfg.kinds.ring)
        return {**{name + kind: jnp.zeros((n, batch, cfg.n_kv_heads, t, hd),
                                          cfg.compute_dtype)
                   for kind, (n, t) in shapes.items() for name in ("k", "v")},
                **state}
    shape = (cfg.n_layers, batch, cfg.n_kv_heads, max_len, hd)
    if cfg.kv_quant == "int8":
        return {
            "k": jnp.zeros(shape, jnp.int8),
            "v": jnp.zeros(shape, jnp.int8),
            "k_scale": jnp.zeros(shape[:-1], jnp.float32),
            "v_scale": jnp.zeros(shape[:-1], jnp.float32),
        }
    return {
        "k": jnp.zeros(shape, cfg.compute_dtype),
        "v": jnp.zeros(shape, cfg.compute_dtype),
        **state,
    }


def init_rolling_cache(cfg: LlamaConfig, batch: int) -> dict:
    """O(window) cache for sliding-window models: ``sliding_window`` slots
    per layer, written modulo the window (see ``decode_step(rolling=True)``).
    Generation length no longer bounds cache memory."""
    if cfg.sliding_window is None:
        raise ValueError("rolling caches require cfg.sliding_window")
    return init_cache(cfg, batch, cfg.sliding_window)


def cache_len(cache: dict) -> int:
    """Positions a cache's rows hold: the T axis sits at index 3 of every
    leaf that has one (the ring leaves of a ``cfg.kinds`` cache hold a
    window's and a linear layer's state has none; this is the full
    layers' length)."""
    for name in ("k", "ckv"):
        if name in cache:
            return cache[name].shape[3]
    return next(iter(cache.values())).shape[3]


def is_state(name: str) -> bool:
    """Whether a cache leaf is a linear layer's state: no position axis,
    the whole of a row's entry is the request's (:func:`init_cache`)."""
    return name.startswith("kda_")


def ring_fold(a, lengths, window: int):
    """A ring out of whole rows: ``a [L, B, Hkv, S(, D)]`` holds positions
    ``0 .. S - 1`` of which row b's first ``lengths[b]`` are real; returns
    ``[L, B, Hkv, window(, D)]`` whose slot ``s`` holds row b's LATEST real
    position ``p`` with ``p % window == s``: the layout ``pos % window``
    writes leave behind (``window``: the RING's length, which a
    ``LayerKinds.slack`` makes longer than the attention window).  Slots
    no real position reached yet hold junk that the decode steps overwrite
    before the cursor lets them be read."""
    last = jnp.asarray(lengths, jnp.int32).reshape(-1, 1) - 1       # [B, 1]
    src = last - (last - jnp.arange(window, dtype=jnp.int32)[None, :]) % window
    src = jnp.clip(src, 0, a.shape[3] - 1)                          # [B, W]
    return jnp.take_along_axis(
        a, src.reshape((1, -1, 1, window) + (1,) * (a.ndim - 4)), axis=3)


def _ring_names(cache: dict) -> dict:
    """The leaves that hold rings, by the plain name of each: a cache with
    ``*_ring`` leaves keeps them there; else every leaf is one (a
    whole-model rolling cache, ``init_rolling_cache``)."""
    if "k_ring" in cache:
        return {"k": "k_ring", "v": "v_ring"}
    return {name: name for name in cache}


def mtp_rows(cache: dict) -> dict:
    """An MTP block's own rows as a cache of their own, under the plain
    names (``k`` / ``v``): what :func:`cached_layer_scan` and
    :func:`_write_cached` take."""
    return {"k": cache["k_mtp"], "v": cache["v_mtp"]}


def attend_cache(q, cache: dict, pos, layer, cfg: LlamaConfig,
                 ring: bool = False):
    """``attend`` of :func:`cached_layer_scan` over a cache of either kind,
    queries ``q [B, Hq, C, D]`` at ``pos[b] ..`` (C=1 is
    single-token decode; C>1 the speculative chunk verify, whose entries
    are already written: write-then-attend): grouped k/v
    (``ops.cached_attention``, windowed and int8-aware), or the latent
    rows of ``cfg.latent`` (absorbed queries in, ``P c_kv`` out;
    ``ops.latent_attention``).  ``ring``: the layer's entries lie in a
    ring (written at ``pos % T``).  A ring of exactly one window: its
    warm slots ARE the window, so every slot up to the clamped cursor is
    attended and nothing is masked again; cold slots (> pos) are masked by
    the clamped position.  A ring LONGER than its window
    (``LayerKinds.slack``): every slot is read under the mask of the
    position it holds, ``i - window < j <= i`` for the query at ``i``."""
    if "ckv" in cache:
        return latent_attention(q, cache["ckv"], pos,
                                rank=cfg.latent.kv_rank,
                                sm_scale=cfg.latent.sm_scale, layer=layer)
    if ring:
        at = _ring_names(cache)
        scales = {n: cache[at[n]] for n in ("k_scale", "v_scale") if n in at}
        window = cfg.kinds.window if "k_ring" in cache else cfg.sliding_window
        return cached_attention(
            q, cache[at["k"]], cache[at["v"]], pos, layer=layer, ring=True,
            window=None if cache[at["k"]].shape[3] == window else window,
            **scales)
    return cached_attention(q, cache["k"], cache["v"], pos, layer=layer,
                            window=cfg.sliding_window,
                            k_scale=cache.get("k_scale"),
                            v_scale=cache.get("v_scale"))


def _write_cached(cache: dict, new: dict, layer, pos, rows=None,
                  count=None, ring: bool = False) -> dict:
    """The C new positions of one layer into the stacked cache, every leaf
    (k, v and, int8, their scales): ``cache[name][layer, rows[b], :,
    pos[b] + c] = new[name][b, :, c]``.  ``new[name]``: [B, Hkv, C(, D)];
    ``pos``: scalar or per-row [B]; ``rows`` (default ``arange(B)``): the
    cache row each batch row owns — the paged pool passes page ids, with
    ``pos`` the offsets inside them.  A start above ``T - C`` is clamped,
    as ``lax.dynamic_update_slice`` does; with ``count`` ([B]) only each
    row's first ``count[b]`` positions are written and nothing is clamped
    (``ops.cache_write``).  ``ring``: the layer's entries lie in a ring
    and ``pos`` is the ABSOLUTE position: position ``pos + c`` goes to
    ``(pos + c) % T`` of the ring leaves, ``layer`` counting them (one
    write a position: two of a chunk may lie at the ring's two ends).

    The write itself is ``ops.cache_write``: on the chip in place, a tile
    a row.  Either XLA form (a scatter, or ``dynamic_update_slice`` per
    row) makes the chip's compiler re-lay the scan's carry for the write
    and copy the whole stacked cache back for the kernel, every layer."""
    B = next(iter(new.values())).shape[0]
    pos = jnp.broadcast_to(jnp.asarray(pos, jnp.int32).reshape(-1), (B,))
    rows = jnp.arange(B) if rows is None else rows
    layer = jnp.asarray(layer, jnp.int32)
    out = dict(cache)
    if ring:
        at = _ring_names(cache)
        T = cache[at["k"]].shape[3]
        groups = [(at["k"], at["v"])] + (
            [(at["k_scale"], at["v_scale"])] if "k_scale" in at else [])
        new = {at[name]: x for name, x in new.items()}
        C = next(iter(new.values())).shape[2]
        writes = [(new, lax.rem(pos, T))] if C == 1 else [
            ({n: x[:, :, c:c + 1] for n, x in new.items()},
             lax.rem(pos + c, T)) for c in range(C)]
    else:
        groups = ([("ckv",)] if "ckv" in cache else [("k", "v")] + [
            ("k_scale", "v_scale")] * ("k_scale" in cache))
        writes = [(new, pos)]
    for new, pos in writes:
        for names in groups:  # same-shaped leaves share one kernel call
            out.update(zip(names, cache_write(
                tuple(out[name] for name in names),
                tuple(new[name] for name in names), layer, rows, pos, count)))
    return out


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
    if rolling:
        if cfg.sliding_window is None or T != cfg.sliding_window:
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
    logits = matmul_w(h[:, 0, :], params["lm_head"]).astype(jnp.float32)
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
    ``serving.SlotServer._ingest_widths``.)

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
        mine = ingest_attention(
            jnp.swapaxes(q[B:], 0, 2), cache["k"], cache["v"], one(first),
            one(slot), layer=layer, k_scale=cache.get("k_scale"),
            v_scale=cache.get("v_scale"))
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
                      write, attend):
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
    """
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


def prefill(params: dict, cfg: LlamaConfig, prompt,
            max_len: Optional[int] = None, attn_fn=None,
            logit_positions=None, return_hidden: bool = False):
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
    padded bucket leaves what the unpadded prompt leaves.
    """
    B, P = prompt.shape
    if max_len is None:
        max_len = P
    elif max_len < P:
        raise ValueError(f"max_len={max_len} is smaller than the prompt ({P})")
    logits, _aux, kv, *hidden = forward(
        params, prompt, cfg, attn_fn, return_aux=True, return_kv=True,
        last_only=logit_positions is None, logit_positions=logit_positions,
        lengths=None if logit_positions is None else logit_positions + 1,
        return_hidden=return_hidden,
    )
    state = {name: kv.pop(name) for name in list(kv) if is_state(name)}
    cache = dict(kv)
    if cfg.kv_quant == "int8":
        from ..ops.quantize import quantize_kv

        cache["k"], cache["k_scale"] = quantize_kv(kv["k"])
        cache["v"], cache["v_scale"] = quantize_kv(kv["v"])
    rings = {}
    if cfg.kinds is not None:  # the window layers' whole rows -> rings
        lengths = (jnp.full((B,), P, jnp.int32) if logit_positions is None
                   else logit_positions + 1)
        rings = {name: ring_fold(cache.pop(name), lengths, cfg.kinds.ring)
                 for name in [n for n in cache if n.endswith("_ring")]}
    pad = max_len - P
    if pad:
        # Every leaf's T axis sits at index 3 (the scale arrays only drop
        # the trailing D dim) — same invariant the ring fold relies on.
        cache = jax.tree_util.tree_map(
            lambda a: jnp.pad(
                a, ((0, 0),) * 3 + ((0, pad),) + ((0, 0),) * (a.ndim - 4)),
            cache)
    return (logits[:, 0], {**cache, **rings, **state}, *hidden)


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

    quant = cfg.kv_quant == "int8"

    def run_chunk(params, cache, tokens_c, c0, cos_c, sin_c):
        """One chunk through every layer; returns (h, new cache)."""
        Cc = tokens_c.shape[1]
        slots = (c0 + jnp.arange(Cc)) % W
        # Reorder the cache by absolute position: slot s holds the latest
        # p < c0 with p % W == s; gathering positions c0-W..c0-1 in order
        # lets partial_attention mask in plain global coordinates.
        order = (c0 - W + jnp.arange(W)) % W
        h = embed_tokens(params, tokens_c, cfg)  # [B, Cc, D]

        def chunk_attn(kc, vc, ksc, vsc):
            """attn_fn for decoder_layer: past (the rolling cache, in
            position order) + present (the chunk itself, causal) as two
            mergeable online-softmax partials.  int8 caches dequantize the
            gathered window up front — an O(window) transient per layer,
            matching the path's O(chunk + window) memory contract."""
            def attn(q, k, v):
                kco = jnp.take(kc, order, axis=2)
                vco = jnp.take(vc, order, axis=2)
                if quant:
                    from ..ops.quantize import dequantize_kv

                    kco = dequantize_kv(kco, jnp.take(ksc, order, axis=2),
                                        q.dtype)
                    vco = dequantize_kv(vco, jnp.take(vsc, order, axis=2),
                                        q.dtype)
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
            kc, vc = cache["k"][li], cache["v"][li]
            ksc = cache["k_scale"][li] if quant else None
            vsc = cache["v_scale"][li] if quant else None
            h, _aux, kv, _stats = decoder_layer(lp, h, cfg, cos_c, sin_c,
                                                chunk_attn(kc, vc, ksc, vsc))
            k, v = kv["k"], kv["v"]
            if quant:
                from ..ops.quantize import quantize_kv

                k, k_s = quantize_kv(k)
                v, v_s = quantize_kv(v)
                new["k_scale"].append(ksc.at[:, :, slots].set(k_s))
                new["v_scale"].append(vsc.at[:, :, slots].set(v_s))
            new["k"].append(kc.at[:, :, slots, :].set(k))
            new["v"].append(vc.at[:, :, slots, :].set(v))
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
