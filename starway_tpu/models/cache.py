"""What a decode cache holds, and what that implies: said here, once.

A cache is a dict of stacked arrays whose kind the model's configuration
decides.  :func:`cache_spec` describes it (:class:`CacheSpec`: the leaves
and the facts every consumer asks for), :func:`init_cache` is zeros over
the description, and the functions below write, attend and fill a cache
of any kind.  Outside this module only the layers that PRODUCE or CONSUME
a leaf of their own name one (``generate.cached_layer_scan``, models/kda.py,
models/mtp.py, ``llama.forward``'s latent rows); the server, the page
pool, the chunk verify and beam search ask.

The layout is scan-stacked like the parameters: ``k/v [n_layers, B, Hkv,
max_len, head_dim]``, every leaf with the layer at axis 0, the row at 1
and (where it has one) the position at 3.  The stacked arrays ride the
layer scan's CARRY (``generate.cached_layer_scan``): they are never a scan
input or output and never sliced per layer, so one buffer serves the whole
generation (donate the cache under jit).  On the chip the new entries are
written in place by ``ops.cache_write`` and attention reads the stacked
array through a layer index; the decode step moves no cache bytes but the
ones attention reads.

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
row's own last token (``generate.prefill``'s ``logit_positions``), and
whoever seats a request replaces the row's state whole.  A state-space
layer (``cfg.ssm``, models/ssm.py) keeps the same kind under names of its
own, ``ssm_state`` / ``ssm_conv``, and a model laid out in
``LayerKinds.runs`` may keep state, rings AND full rows in one cache.  Its
``"gmu"`` and ``"cross"`` layers keep NOTHING: a cross layer reads the
rows of the full layer before it (``cfg.rows_layer``), so a row there has
several readers a step and one writer.
"""

from __future__ import annotations

import dataclasses
from typing import NamedTuple

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax

from .llama import LlamaConfig
from ..ops import (cache_write, cached_attention, ingest_attention,
                   latent_attention)


class Leaf(NamedTuple):
    """One array of a cache: ``[layers, B, *shape]`` of ``dtype``."""
    name: str
    layers: int     # the layers of the kind that keeps it
    shape: tuple    # one row's, behind the batch axis
    dtype: object


@dataclasses.dataclass(frozen=True)
class CacheSpec:
    """What a cache of one kind holds.  ``latent``: its rows are
    ``cfg.latent``'s (one a token for all heads), else grouped k / v;
    ``int8``: they are int8 beside per-token scales; ``length``: the
    positions a full row holds; ``ring``: the positions a ring holds (0:
    no ring) and ``rolling``: EVERY leaf is one (``length == ring``: the
    whole-model window), else the rings lie BESIDE full rows; ``state``:
    linear or state-space layers' leaves with no position axis; ``mtp``:
    MTP blocks, each with rows of its own; ``leaves``: the arrays, in the
    dict's order; ``readers``: the layers that read ONE full layer's rows
    a step (1, and more where cross layers read a full layer's)."""
    latent: bool
    int8: bool
    length: int
    ring: int
    rolling: bool
    state: bool
    mtp: int
    leaves: tuple
    readers: int = 1

    def zeros(self, batch: int) -> dict:
        """The cache itself, empty, of ``batch`` rows."""
        return {leaf.name: jnp.zeros((leaf.layers, batch) + leaf.shape,
                                     leaf.dtype) for leaf in self.leaves}

    @property
    def piecewise(self) -> bool:
        """Whether a server may ingest a prompt piece by piece inside its
        decode chunk (``ingest_decode_step``) and launch no admit program:
        true of ONE kind, rows of dense k / v of ``length`` positions that
        hold k and v as computed.  Every other kind keeps its admit
        programs: a latent cache; a linear layer's state beside latent or
        grouped-query rows (a piece would have to move the state on by W
        tokens inside a decode step); a rolling window, and rings beside
        the full rows (a piece would have to attend over a ring its own
        later tokens overwrite); an int8 cache (a piece attends over what
        the cache holds, quantized there, where a prefill reads the
        prompt's k/v exact: other tokens than ``generate()``'s); a model
        with an MTP block (its admission runs the block over the prompt
        and seats its row and the first draft)."""
        return not (self.latent or self.int8 or self.ring or self.state
                    or self.mtp)

    def step_rows(self, pos) -> dict:
        """The fields a kind adds to a serving step's ``step_log()`` row,
        from the cursors ``pos`` of the slots that decode in it: the
        positions their queries read in each kind of leaf.  A step attends
        from ``pos`` (a speculating step's verify from ``pos + 1`` too).
        Rings beside full rows: ``kv_rows_full``, and ``kv_rows_window``,
        a ring being read whole once warm.  State beside rows:
        ``state_slots``, and ``kv_rows_latent`` or ``kv_rows_full`` as the
        rows are.  All three together where a cache holds all three, and
        ``kv_full_readers`` where more layers than its own read a full
        layer's rows.  Every other kind adds nothing."""
        at = 1 + self.mtp + np.asarray(pos).astype(int)
        rings = self.ring and not self.rolling
        out = {"state_slots": len(at)} if self.state else {}
        if rings or self.state:
            out["kv_rows_latent" if self.latent else "kv_rows_full"] = int(
                at.sum())
        if rings:
            out["kv_rows_window"] = int(np.minimum(at, self.ring).sum())
        if self.readers > 1:
            out["kv_full_readers"] = self.readers
        return out


def cache_spec(cfg: LlamaConfig, max_len: int,
               rolling: bool = False) -> CacheSpec:
    """The cache ``cfg`` decodes through, full rows of ``max_len``
    positions: ``k/v [n_layers, B, Hkv, max_len, head_dim]``.  ``rolling``:
    every layer a ring of ``cfg.sliding_window`` slots instead, whatever
    ``max_len`` (:func:`init_rolling_cache`).

    ``cfg.kv_quant == "int8"`` stores k/v as int8 plus per-token f32 scales
    ``k_scale/v_scale [n_layers, B, Hkv, max_len]`` (ops/quantize.py) —
    half the HBM bytes on the bandwidth-bound decode stream.

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
    if rolling:
        if cfg.sliding_window is None:
            raise ValueError("rolling caches require cfg.sliding_window")
        max_len = cfg.sliding_window
    dt, full = cfg.compute_dtype, cfg.kind_layers("full")

    def pair(kind, layers, t, dtype=dt, d=(cfg.kv_cache_dim,)):
        return [Leaf(name + kind, layers, (cfg.kv_cache_heads, t) + d, dtype)
                for name in ("k", "v")]

    beside = pair("_mtp", cfg.mtp, max_len) if cfg.mtp else []
    if cfg.linear is not None:
        la, n = cfg.linear, cfg.kind_layers("linear")
        beside = [
            Leaf("kda_state", n, (la.n_heads, la.head_dim, la.head_dim),
                 jnp.float32),
            Leaf("kda_conv", n, (la.conv - 1, la.conv_width), dt)]
    if cfg.ssm is not None:
        sp, n = cfg.ssm, cfg.kind_layers("ssm")
        beside = [Leaf("ssm_state", n, (sp.d_state, sp.d_inner), jnp.float32),
                  Leaf("ssm_conv", n, (sp.conv - 1, sp.d_inner), dt)]
    ring, int8 = cfg.sliding_window if rolling else 0, False
    if cfg.latent is not None:
        rows = [Leaf("ckv", full, (1, max_len, cfg.latent.cache_width), dt)]
    elif cfg.kinds is not None:
        rows = pair("", full, max_len)
        if cfg.kinds.window is not None:
            ring = cfg.kinds.ring
            rows += pair("_ring", cfg.kind_layers("ring"), ring)
    elif cfg.kv_quant == "int8":
        int8 = True
        rows = (pair("", full, max_len, jnp.int8)
                + pair("_scale", full, max_len, jnp.float32, ()))
        beside = []
    else:
        rows = pair("", full, max_len)
    return CacheSpec(
        latent=cfg.latent is not None, int8=int8, length=max_len, ring=ring,
        rolling=rolling, state=(cfg.linear is not None
                                or cfg.ssm is not None), mtp=cfg.mtp,
        leaves=tuple(rows + beside),
        readers=1 + sum(cfg.mixer(i) == "cross"
                        for i in range(cfg.n_layers)))


def served_spec(cfg: LlamaConfig, max_len: int) -> CacheSpec:
    """The cache a slot server keeps for ``cfg``: a model whose every
    layer has the window serves through rolling slots, O(window) a slot
    however long a generation runs (``max_len`` is then its rope
    horizon)."""
    return cache_spec(cfg, max_len, rolling=cfg.sliding_window is not None)


def taken_for_rolling(cfg: LlamaConfig, length: int) -> bool:
    """THE one guess: rolling and full caches share a layout, so a cache of
    exactly ``cfg.sliding_window`` positions IS taken for a rolling one
    (:func:`init_rolling_cache`'s).  The decode step holds a caller's
    ``rolling=True`` to it, the chunk verify refuses by it
    (:func:`spec_of`), and who wants a FULL cache verified allocates
    another length (one more slot, which the window masks out of attention
    anyway)."""
    return cfg.sliding_window is not None and length == cfg.sliding_window


def spec_of(cfg: LlamaConfig, cache: dict) -> CacheSpec:
    """The description of a cache in hand, from ``cfg`` and the cache's own
    length (:func:`taken_for_rolling`)."""
    T = cache_len(cache)
    return cache_spec(cfg, T, rolling=taken_for_rolling(cfg, T))


def init_cache(cfg: LlamaConfig, batch: int, max_len: int) -> dict:
    """An empty decode cache of ``batch`` rows (:func:`cache_spec`)."""
    return cache_spec(cfg, max_len).zeros(batch)


def init_rolling_cache(cfg: LlamaConfig, batch: int) -> dict:
    """O(window) cache for sliding-window models: ``sliding_window`` slots
    per layer, written modulo the window (see ``decode_step(rolling=True)``).
    Generation length no longer bounds cache memory."""
    return cache_spec(cfg, 0, rolling=True).zeros(batch)


# What a consumer that needs rows by position is refused, by what it
# needs of them and by what the model keeps, in the order asked.
_REFUSED = {
    # the page pool: rows it may cut into pages (NotImplementedError)
    "page": (
        ("mtp",
         "the page pool's step writes one position a slot: an MTP "
         "block's draft beside it, and the block's own row, need "
         "pages that a rejection gives back; such a model serves "
         "through the dense SlotServer (ROADMAP M5)"),
        ("state",
         "the page pool holds rows a position: a linear-attention "
         "layer's state (cfg.linear) is one matrix a request, with "
         "nothing to page; such a model serves through the dense "
         "SlotServer (ROADMAP M4: state in the pool)"),
        ("window",
         "paged serving v1 is full-causal; sliding-window models "
         "already serve in O(window) via the rolling SlotServer, "
         "window layers beside full ones via the dense one "
         "(ROADMAP M1: a pool with window pages)"),
        ("int8",
         "int8 paged pools are not wired yet; use the dense "
         "SlotServer for kv_quant='int8'"),
        ("latent",
         "the page pool has no latent page kind yet (ROADMAP M3); "
         "latent-attention models serve through the dense SlotServer")),
    # beam search: rows it may reorder between steps
    "reorder": (
        ("mtp",
         "beam search scores one token a step; an MTP "
         "block's drafts are not wired into it (ROADMAP M5)"),
        ("state",
         "beam search reorders cache rows by position; a "
         "linear-attention layer's state (cfg.linear) has "
         "none and is not wired (ROADMAP M4)"),
        ("window",
         "beam search needs full caches; rolling-cache "
         "support is not wired")),
}


def require_rows(cfg: LlamaConfig, need: str, error=ValueError) -> None:
    """Raise ``error`` unless ``cfg``'s cache gives what ``need`` names
    (``_REFUSED``'s keys): full rows by position, every layer alike."""
    keeps = {"mtp": cfg.mtp,
             "state": cfg.linear is not None or cfg.ssm is not None,
             "window": (cfg.sliding_window is not None
                        or cfg.kinds is not None),
             "int8": cfg.kv_quant != "none", "latent": cfg.latent is not None}
    for kind, message in _REFUSED[need]:
        if keeps[kind]:
            raise error(message)


def require_chunk_rows(cfg: LlamaConfig, cache: dict, C: int) -> None:
    """Raise ValueError unless ``C`` positions a row may be written into
    ``cache`` at absolute positions and attended behind the write (the
    chunk verify, ``speculative.chunk_decode_step``)."""
    spec = spec_of(cfg, cache)
    if spec.state:
        raise ValueError(
            "chunk_decode_step does not support linear-attention layers "
            "(cfg.linear): a state moved on by C tokens cannot be taken "
            "back to where the accepted ones end (ROADMAP M4: snapshots)")
    if spec.ring and not spec.rolling and (spec.ring
                                           < cfg.kinds.window + C - 1):
        raise ValueError(
            f"chunk_decode_step needs rings of at least window + C - 1 = "
            f"{cfg.kinds.window + C - 1} positions, got "
            f"{spec.ring}: a chunk written into a ring of "
            f"one window overwrites entries its own earlier positions "
            f"attend (LayerKinds.slack lengthens the rings)")
    if spec.rolling:
        # A rolling cache's modular slots cannot be addressed by this
        # absolute-position write-then-attend: dynamic_update_slice would
        # clamp the write and the masks would lie.
        raise ValueError(
            f"chunk_decode_step does not support rolling caches: got a "
            f"{spec.length}-slot cache == cfg.sliding_window, which is "
            f"init_rolling_cache's layout; allocate a full cache "
            f"(init_cache with max_len != sliding_window — positions past "
            f"the window are masked anyway, so max_len = window + C costs "
            f"nothing) for chunk verify / multi-token ingestion")


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
    """Whether a cache leaf is a linear or state-space layer's state: no
    position axis, the whole of a row's entry is the request's
    (:func:`cache_spec`)."""
    return name.startswith(("kda_", "ssm_"))


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
    names (``k`` / ``v``): what ``generate.cached_layer_scan`` and
    :func:`_write_cached` take."""
    return {"k": cache["k_mtp"], "v": cache["v_mtp"]}


def quantize_rows(kv: dict) -> dict:
    """``k`` / ``v`` as an int8 cache holds them: int8 entries and, under
    ``k_scale`` / ``v_scale``, their per-token scales."""
    from ..ops.quantize import quantize_kv

    out = dict(kv)
    out["k"], out["k_scale"] = quantize_kv(kv["k"])
    out["v"], out["v_scale"] = quantize_kv(kv["v"])
    return out


def ring_in_order(layer: dict, order, dtype):
    """``(k, v)`` of ONE layer's ring (``layer``: the rolling cache's
    leaves at that layer, ``[B, Hkv, W(, D)]``) with the slots gathered in
    ``order`` and, int8, widened to ``dtype``: an O(window) transient."""
    k, v = (jnp.take(layer[name], order, axis=2) for name in ("k", "v"))
    if "k_scale" in layer:
        from ..ops.quantize import dequantize_kv

        k = dequantize_kv(k, jnp.take(layer["k_scale"], order, axis=2), dtype)
        v = dequantize_kv(v, jnp.take(layer["v_scale"], order, axis=2), dtype)
    return k, v


def from_forward(spec: CacheSpec, kv: dict, lengths) -> dict:
    """A forward pass's entries (``forward(return_kv=True)``: every
    position of the prompt, a window layer's under its ``*_ring`` name,
    and the linear layers' states) as a cache of ``spec``'s kind: int8 on
    write, each window layer's rows folded into its ring (:func:`ring_fold`:
    row b's last ``lengths[b]`` real positions at their residues), the
    full rows zero-padded to ``spec.length``, the states as they came."""
    state = {name: kv.pop(name) for name in list(kv) if is_state(name)}
    cache = quantize_rows(kv) if spec.int8 else dict(kv)
    rings = {name: ring_fold(cache.pop(name), lengths, spec.ring)
             for name in [n for n in cache if n.endswith("_ring")]}
    pad = spec.length - cache_len(cache)
    if pad:
        # Every leaf's T axis sits at index 3 (the scale arrays only drop
        # the trailing D dim) — same invariant the ring fold relies on.
        cache = jax.tree_util.tree_map(
            lambda a: jnp.pad(
                a, ((0, 0),) * 3 + ((0, pad),) + ((0, 0),) * (a.ndim - 4)),
            cache)
    return {**cache, **rings, **state}


def state_names(cache: dict) -> list:
    """The leaves of ``cache`` that are state: a request's entry is
    replaced whole, not written by position (:func:`is_state`)."""
    return [name for name in cache if is_state(name)]


def attend_cache(q, cache: dict, pos, layer, cfg: LlamaConfig,
                 ring: bool = False):
    """``attend`` of ``generate.cached_layer_scan`` over a cache of either
    kind, queries ``q [B, Hq, C, D]`` at ``pos[b] ..`` (C=1 is
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
    position it holds, ``i - window < j <= i`` for the query at ``i``.
    ``layer`` indexes the LEAF that is read, which is not always the
    attending layer's own: a cross layer, which keeps nothing, passes the
    index of the full layer whose rows it reads (``cfg.rows_layer``)."""
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
            **scales, **_scale_of(cfg))
    return cached_attention(q, cache["k"], cache["v"], pos, layer=layer,
                            window=cfg.sliding_window,
                            k_scale=cache.get("k_scale"),
                            v_scale=cache.get("v_scale"), **_scale_of(cfg))


def _scale_of(cfg: LlamaConfig) -> dict:
    """The scores' multiplier where it is not the cached head's own
    ``D ** -0.5`` (``cfg.attn_scale``: a differential pair's)."""
    return {} if cfg.attn_scale is None else {"sm_scale": cfg.attn_scale}


def attend_piece(q, cache: dict, first, slot, layer):
    """A prompt piece's ``W`` queries ``q [1, Hq, W, D]`` at positions
    ``first ..`` over the ONE cache row ``slot``, write-then-attend
    (``ops.ingest_attention``; dense k / v, an int8 cache's scales ride
    along): ``generate.ingest_decode_step``'s half of a mixed step."""
    return ingest_attention(q, cache["k"], cache["v"], first, slot,
                            layer=layer, k_scale=cache.get("k_scale"),
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
