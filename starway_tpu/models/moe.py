"""Mixture-of-experts FFN with expert parallelism and top-k routing.

Switch/GShard-style static-capacity routing built TPU-first:

* **Dispatch is scatter/gather, not a dense one-hot einsum.**  Each routed
  (token, choice) computes an integer slot ``expert * C + position`` and the
  token rows are scattered into an ``[E*C, D]`` send buffer (overflow goes to
  a trash row) -- O(T*k) index work plus the O(E*C*D) = O(T*cf*D) buffer the
  all-to-all needs anyway, instead of the O(T*E*C) dispatch tensor of the
  textbook formulation.  Shapes stay static so XLA can plan the collectives.
* **Top-k routing** (k=1 Switch, k=2 GShard/Mixtral): first choices take
  capacity priority over second choices; top-2 gates are renormalised over
  the chosen pair.
* Two views of the same math:
  :func:`switch_moe` -- global view; expert tables shard over the mesh "ep"
  axis via :func:`moe_specs` and GSPMD inserts the dispatch collectives.
  :func:`sharded_switch_moe` -- local (shard_map) view with an explicit
  ``lax.all_to_all`` over the "ep" axis, for when the collective schedule
  should be pinned rather than inferred; :func:`make_sharded_moe` wraps it
  for use as ``forward(..., moe_fn=...)``.

The load-balancing auxiliary loss is the standard
``E * sum_e(frac_first_choice_e * mean_router_prob_e)`` (Switch eq. 4;
reduces to GShard's aux for k>=2 with first-choice fractions).
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax import lax
from jax.sharding import PartitionSpec as P

from ..ops import GATE_ACTS, grouped_matmul


def init_moe_params(key, n_layers: int, n_experts: int, d_model: int,
                    d_ff: int, dtype, swiglu: bool = False) -> dict:
    k1, k2, k3, k4 = jax.random.split(key, 4)

    def norm(k, shape, scale):
        return (jax.random.normal(k, shape, jnp.float32) * scale).astype(dtype)

    out = {
        "router": norm(k1, (n_layers, d_model, n_experts), d_model**-0.5),
        "w_in": norm(k2, (n_layers, n_experts, d_model, d_ff), d_model**-0.5),
        "w_out": norm(k3, (n_layers, n_experts, d_ff, d_model), d_ff**-0.5),
    }
    if swiglu:
        # Mixtral-style SwiGLU experts; _expert_ffn keys off the leaf.
        out["w_gate"] = norm(k4, (n_layers, n_experts, d_model, d_ff),
                             d_model**-0.5)
    return out


def moe_specs(swiglu: bool = False) -> dict:
    """PartitionSpecs: experts shard over the "ep" mesh axis."""
    out = {
        "router": P(None, None, None),
        "w_in": P(None, "ep", None, None),
        "w_out": P(None, "ep", None, None),
    }
    if swiglu:
        out["w_gate"] = P(None, "ep", None, None)
    return out


def require_dropless(cfg, context: str) -> None:
    """Raise unless ``cfg`` is dense or PROVABLY dropless MoE
    (``moe_capacity_factor >= n_experts`` -> capacity >= T * k for any
    token count, :func:`moe_capacity`'s ceiling).  The single source of
    the rule every shape-sensitive entry point shares: ragged
    generation, continuous batching, and the speculative chunk verify
    all rely on routing being shape-invariant, which only droplessness
    guarantees.  The routed FFN (``cfg.routed``, :func:`routed_ffn`) has
    no capacity to exceed and passes: its ``n_experts`` here is 0."""
    if cfg.n_experts > 0 and cfg.moe_capacity_factor < cfg.n_experts:
        raise ValueError(
            f"{context} needs dense FFNs or provably-dropless MoE: expert "
            f"capacity is computed per forward, so routing could differ "
            f"across forward shapes; set moe_capacity_factor >= n_experts "
            f"(= {cfg.n_experts}) to make drops impossible (the Mixtral "
            f"conversion default)")


def moe_capacity(n_assignments: int, n_experts: int,
                 capacity_factor: float) -> int:
    """Static per-expert capacity for ``n_assignments`` routed (token,
    choice) pairs -- ``T * k``, not ``T`` (GShard scales capacity by k, or
    top-2 would drop second choices even under a balanced router).

    Ceiling, not truncation: ``capacity_factor >= n_experts`` must yield
    capacity ``>= n_assignments`` — PROVABLY dropless for any routing —
    because ragged MoE generation's pad-safety argument
    (models/generate.py) rests on exactly that guarantee; ``int()`` would
    lose it off float division for non-power-of-two expert counts."""
    import math

    return max(1, math.ceil(n_assignments * capacity_factor / n_experts
                            - 1e-9))


def _route(xt, router_w, k: int):
    """Router statistics for ``xt [T, D]``.

    Returns ``(expert_flat [T*k], gate_flat [T*k] f32, aux scalar f32)``
    in choice-major order (all first choices in token order, then all
    second choices, ...), so a cumsum over the flat order gives first
    choices capacity priority.
    """
    e = router_w.shape[-1]
    logits = (xt @ router_w).astype(jnp.float32)  # [T, E]
    probs = jax.nn.softmax(logits, axis=-1)
    top_p, top_i = lax.top_k(probs, k)  # [T, k]
    if k > 1:
        # Mixtral/GShard: renormalise the chosen gates over the pair.
        top_p = top_p / jnp.sum(top_p, axis=-1, keepdims=True)

    # Load-balancing aux loss from FIRST choices (Switch eq. 4).
    frac_tokens = jnp.mean(
        jax.nn.one_hot(top_i[:, 0], e, dtype=jnp.float32), axis=0)
    frac_probs = jnp.mean(probs, axis=0)
    aux = e * jnp.sum(frac_tokens * frac_probs)

    expert_flat = top_i.T.reshape(-1)  # choice-major
    gate_flat = top_p.T.reshape(-1)
    return expert_flat, gate_flat, aux


def _dispatch_slots(expert_flat, n_experts: int, capacity: int):
    """Slot index per routed (token, choice): ``expert * C + position``.

    ``position`` counts prior assignments to the same expert in flat order
    (choice-major -> first choices win capacity).  Overflow maps to the
    trash slot ``E*C``.  Returns ``(slot [T*k] int32, keep [T*k] bool,
    counts [E] int32)`` — counts is each expert's routed-assignment total,
    a byproduct of the capacity numbering that :func:`_routing_stats`
    reuses for free.
    """
    # int32 counting stays exact however many tokens are routed (an f32
    # cumsum would misnumber positions past 2^24 assignments).
    onehot = jax.nn.one_hot(expert_flat, n_experts, dtype=jnp.int32)
    pos = jnp.sum(jnp.cumsum(onehot, axis=0) * onehot, axis=-1) - 1
    keep = pos < capacity
    pos = jnp.clip(pos, 0, capacity - 1)
    slot = jnp.where(keep, expert_flat * capacity + pos,
                     n_experts * capacity)
    return slot.astype(jnp.int32), keep, jnp.sum(onehot, axis=0)


def _scatter_tokens(xt, slot, k: int, n_experts: int, capacity: int):
    """Gather routed token rows into the ``[E*C, D]`` send buffer."""
    t, d = xt.shape
    token_flat = jnp.tile(jnp.arange(t, dtype=jnp.int32), k)
    buf = jnp.zeros((n_experts * capacity + 1, d), xt.dtype)
    return buf.at[slot].set(xt[token_flat], mode="drop")[:-1]


def _combine_tokens(y_buf, slot, keep, gate_flat, k: int, t: int):
    """Inverse of :func:`_scatter_tokens`: gather each routed choice's
    expert output, weight by its gate, sum the k choices per token."""
    ec = y_buf.shape[0]
    y = y_buf[jnp.clip(slot, 0, ec - 1)]  # [T*k, D]
    w = (gate_flat * keep.astype(jnp.float32)).astype(y.dtype)
    return jnp.sum((y * w[:, None]).reshape(k, t, -1), axis=0)


def _routing_stats(expert_counts, keep):
    """Router-health metrics from quantities the dispatch already computed
    (``_dispatch_slots``' per-expert counts; no extra collective, no second
    one-hot): ``drop_fraction`` -- share of routed (token, choice) pairs
    that fell over capacity and were dropped to the residual path -- and
    ``expert_load [E]`` -- each expert's share of routed assignments (1/E
    everywhere = perfectly balanced; a collapsing router concentrates mass
    on few experts and shows a rising drop_fraction)."""
    load = expert_counts.astype(jnp.float32) / keep.shape[0]
    drop = 1.0 - jnp.mean(keep.astype(jnp.float32))
    return {"drop_fraction": drop, "expert_load": load}


def _expert_ffn(expert_in, w_in, w_out, w_gate=None):
    """``[E, C', D] -> [E, C', D]`` through each expert's MLP: gelu
    two-matrix (Switch-style) by default, or SwiGLU when ``w_gate``
    [E, D, F] is given (Mixtral-style:
    ``(silu(x @ w_gate) * (x @ w_in)) @ w_out``)."""
    cd = expert_in.dtype
    if w_gate is not None:
        g = jax.nn.silu(
            jnp.einsum("ecd,edf->ecf", expert_in, w_gate).astype(jnp.float32)
        ).astype(cd)
        h = g * jnp.einsum("ecd,edf->ecf", expert_in, w_in)
    else:
        h = jax.nn.gelu(
            jnp.einsum("ecd,edf->ecf", expert_in, w_in).astype(jnp.float32)
        ).astype(cd)
    return jnp.einsum("ecf,efd->ecd", h, w_out)


def switch_moe(x, router_w, w_in, w_out, *, capacity_factor: float = 1.25,
               k: int = 1, with_stats: bool = False, w_gate=None):
    """x: [B, S, D] -> (y: [B, S, D], aux_loss: scalar f32).  Global view.

    Tokens over capacity are dropped (their residual path carries them).
    Under a GSPMD mesh with ``moe_specs`` the expert dimension of the
    ``[E, C, D]`` buffers shards over "ep" and XLA inserts the all-to-alls.

    ``with_stats``: also return :func:`_routing_stats` (drop fraction +
    per-expert load) as a third element, so a collapsing router is visible
    from the training loop instead of silently dropping tokens.
    """
    b, s, d = x.shape
    e = router_w.shape[-1]
    t = b * s
    xt = x.reshape(t, d)
    capacity = moe_capacity(t * k, e, capacity_factor)

    expert_flat, gate_flat, aux = _route(xt, router_w, k)
    slot, keep, counts = _dispatch_slots(expert_flat, e, capacity)
    expert_in = _scatter_tokens(xt, slot, k, e, capacity).reshape(e, capacity, d)
    expert_out = _expert_ffn(expert_in, w_in, w_out, w_gate)
    y = _combine_tokens(expert_out.reshape(e * capacity, d), slot, keep,
                        gate_flat, k, t)
    y = y.reshape(b, s, d)
    if with_stats:
        return y, aux, _routing_stats(counts, keep)
    return y, aux


def sharded_switch_moe(x, router_w, w_in, w_out, axis_name: str, *,
                       capacity_factor: float = 1.25, k: int = 1,
                       with_stats: bool = False, w_gate=None):
    """Local (shard_map) view with an explicit expert all-to-all.

    ``x [B_loc, S_loc, D]``: this shard's tokens.  ``w_in/w_out
    [E_loc, D, F] / [E_loc, F, D]``: this shard's experts (E = E_loc * ep).
    Capacity is per (source shard, expert) from the LOCAL token count, so
    the all-to-all payload is O(T_loc * cf * D) per device.

    The aux loss is the pmean over the axis of per-shard aux statistics --
    statistically the global Switch aux (equal shard sizes) though not
    bit-identical to the global-view formula (mean of products vs product
    of means across shards).

    ``with_stats``: also return drop fraction + per-expert load (see
    :func:`_routing_stats`).  The stats ride the SAME pmean the aux loss
    already pays (stacked into one small vector) -- no new collective in
    the hot path.
    """
    ep = lax.axis_size(axis_name)
    b, s, d = x.shape
    e_loc = w_in.shape[0]
    e = e_loc * ep
    t = b * s
    xt = x.reshape(t, d)
    capacity = moe_capacity(t * k, e, capacity_factor)

    expert_flat, gate_flat, aux = _route(xt, router_w, k)
    slot, keep, counts = _dispatch_slots(expert_flat, e, capacity)
    send = _scatter_tokens(xt, slot, k, e, capacity)  # [E*C, D]

    # [ep, E_loc, C, D] -> all-to-all -> leading axis becomes source shard.
    send = send.reshape(ep, e_loc, capacity, d)
    recv = lax.all_to_all(send, axis_name, split_axis=0, concat_axis=0,
                          tiled=False)
    # Each local expert sees the rows every shard bucketed for it.
    expert_in = recv.transpose(1, 0, 2, 3).reshape(e_loc, ep * capacity, d)
    expert_out = _expert_ffn(expert_in, w_in, w_out, w_gate)
    back = expert_out.reshape(e_loc, ep, capacity, d).transpose(1, 0, 2, 3)
    got = lax.all_to_all(back, axis_name, split_axis=0, concat_axis=0,
                         tiled=False)

    y = _combine_tokens(got.reshape(e * capacity, d), slot, keep, gate_flat,
                        k, t)
    y = y.reshape(b, s, d)
    if with_stats:
        stats = _routing_stats(counts, keep)
        packed = jnp.concatenate(
            [jnp.stack([aux, stats["drop_fraction"]]), stats["expert_load"]])
        packed = lax.pmean(packed, axis_name)
        return y, packed[0], {"drop_fraction": packed[1],
                              "expert_load": packed[2:]}
    return y, lax.pmean(aux, axis_name)


def make_sharded_moe(mesh, *, ep_axis: str = "ep", dp_axis: str = "dp",
                     capacity_factor: float = 1.25, k: int = 1,
                     with_stats: bool = False, swiglu: bool = False):
    """Build a ``moe_fn(x, router_w, w_in, w_out[, w_gate]) -> (y, aux)``
    running :func:`sharded_switch_moe` under shard_map: tokens shard over
    (dp, ep) -- batch over dp, sequence over ep -- experts over ep, and the
    dispatch rides one explicit ``all_to_all`` pair over the ep axis.

    Plug into ``forward(..., moe_fn=...)`` /
    ``make_train_step(..., moe_fn=...)``.  ``with_stats``: the built fn
    returns ``(y, aux, stats)`` with router-health metrics (drop fraction,
    per-expert load) pmean'd over the mesh.  ``swiglu``: the tree carries
    Mixtral-style ``w_gate`` experts (decoder_layer passes it through).
    """
    from ..parallel.sharding import shard_map_fn

    other_axes = tuple(a for a in mesh.axis_names if a != ep_axis)

    def local(x, router_w, w_in, w_out, w_gate=None):
        out = sharded_switch_moe(
            x, router_w, w_in, w_out, ep_axis, w_gate=w_gate,
            capacity_factor=capacity_factor, k=k, with_stats=with_stats)
        y, aux = out[0], out[1]
        # aux/stats are ep-uniform already; replicate across the remaining
        # axes so the scalars can leave the shard_map with spec P().
        if other_axes:
            aux = lax.pmean(aux, other_axes)
        if with_stats:
            stats = out[2]
            if other_axes:
                stats = jax.tree_util.tree_map(
                    lambda v: lax.pmean(v, other_axes), stats)
            return y, aux, stats
        return y, aux

    x_spec = P(dp_axis if dp_axis in mesh.shape else None, ep_axis, None)
    out_specs = (x_spec, P())
    if with_stats:
        out_specs = (x_spec, P(),
                     {"drop_fraction": P(), "expert_load": P(None)})
    e_spec = P(ep_axis, None, None)
    in_specs = (x_spec, P(None, None), e_spec, e_spec) + (
        (e_spec,) if swiglu else ())
    mapped = shard_map_fn(mesh, local, in_specs=in_specs,
                          out_specs=out_specs)
    if not swiglu:
        return mapped

    def fn(x, router_w, w_in, w_out, w_gate=None):
        # decoder_layer passes w_gate by KEYWORD; shard_map takes
        # positional args only — adapt.
        return mapped(x, router_w, w_in, w_out, w_gate)

    return fn


# --------------------------------------------------- routed (dropless) FFN
#
# The routed expert layer (DeepSeek-V3 / Kimi-K2: sigmoid scores, a shared
# expert; SmallThinker: softmax over the chosen logits, ReGLU experts, no
# shared one, a router that reads the block's input) as ONE chip of an
# expert-parallel deployment runs it: the router scores all ``n_experts``,
# every token takes its ``top_k``, and this chip computes the part of the
# result that the experts it HOLDS give (plus the shared experts, which
# every chip holds).  No capacity: the (token, choice) pairs that landed here are
# sorted by expert and go through a grouped matmul (ops.grouped_matmul)
# that reads only the experts that got a token.


def sigmoid_route(xt, router_w, bias, top_k: int, scale: float):
    """``xt [T, D]`` -> ``(experts [T, k] int32, gates [T, k] f32)``.
    Scores are sigmoids in f32; the SELECTION adds ``bias`` (the
    checkpoint's ``e_score_correction_bias``), the gates are the chosen
    scores without it, divided by their sum and scaled."""
    s = jax.nn.sigmoid(jnp.dot(xt, router_w,
                               preferred_element_type=jnp.float32))
    _, idx = lax.top_k(s + bias.astype(jnp.float32), top_k)
    g = jnp.take_along_axis(s, idx, axis=-1)
    g = g / (jnp.sum(g, axis=-1, keepdims=True) + 1e-20) * scale
    return idx.astype(jnp.int32), g


def softmax_route(xt, router_w, top_k: int, scale: float):
    """``xt [T, D]`` -> ``(experts [T, k] int32, gates [T, k] f32)``: the
    ``top_k`` largest logits (f32), the gates their softmax (over the
    chosen alone: it sums to 1), scaled.  No selection bias."""
    logits = jnp.dot(xt, router_w, preferred_element_type=jnp.float32)
    top, idx = lax.top_k(logits, top_k)
    return idx.astype(jnp.int32), jax.nn.softmax(top, axis=-1) * scale


def row_tile(n_pairs: int) -> int:
    """Rows of a row tile for a call of ``n_pairs`` (token, choice) pairs:
    a decode step's few pairs an expert want small tiles, a prompt's
    hundreds the MXU's height."""
    return 16 if n_pairs <= 4096 else 128


def group_rows(local, n_held: int, tile_m: int):
    """The grouped matmul's row layout for ``local [M]`` (each pair's
    expert as an index into the held ones; anything outside ``[0,
    n_held)`` is not held here).  Held pairs are laid out expert by
    expert, each expert's rows padded to whole tiles of ``tile_m``.
    Returns ``(src [M_pad]`` the pair whose input each row holds, M for an
    empty row; ``row [M]`` each pair's row, M_pad where it is not held;
    ``tile_expert [M_pad / tile_m]``; ``n_live`` tiles that hold a pair;
    ``sizes [n_held]`` pairs an expert)."""
    m = local.shape[0]
    m_pad = -(-m // tile_m) * tile_m + n_held * tile_m
    held = (local >= 0) & (local < n_held)
    key = jnp.where(held, local, n_held)
    order = jnp.argsort(key, stable=True)
    sizes = jnp.zeros((n_held + 1,), jnp.int32).at[key].add(1)[:n_held]
    padded = -(-sizes // tile_m) * tile_m
    ends_p = jnp.cumsum(padded)
    starts = jnp.cumsum(sizes) - sizes
    skey = key[order]
    e = jnp.minimum(skey, n_held - 1)
    dest = jnp.where(skey < n_held,
                     (ends_p - padded)[e] + jnp.arange(m) - starts[e], m_pad)
    src = jnp.full((m_pad,), m, jnp.int32).at[dest].set(
        order.astype(jnp.int32), mode="drop")
    row = jnp.zeros((m,), jnp.int32).at[order].set(dest.astype(jnp.int32))
    tile_expert = jnp.minimum(
        jnp.searchsorted(ends_p, jnp.arange(m_pad // tile_m) * tile_m,
                         side="right"), n_held - 1).astype(jnp.int32)
    return src, row, tile_expert, ends_p[-1] // tile_m, sizes


def routed_experts(xt, experts, gates, w, first: int, act: str = "silu"):
    """The held experts' part of the layer for ``xt [T, D]``: ``experts`` /
    ``gates`` ``[T, k]`` from the router over ALL experts, ``w`` the held
    experts' stacked gated weights (``w_gate`` / ``w_up [G, D, F]``,
    ``w_down [G, F, D]``; ``act`` on the gate: a name of ``ops.GATE_ACTS``),
    which are experts ``first .. first + G - 1``;
    with ``w["layer"]`` (a traced scalar) the three are every layer's
    ``[L, G, ...]`` and the kernel picks the layer
    (:func:`~starway_tpu.models.llama.scan_segment`).  Returns ``(y [T,
    D], sizes [G])``: pairs that chose an expert held elsewhere add
    nothing here."""
    t, d = xt.shape
    k = experts.shape[1]
    layer = w.get("layer")
    g = w["w_gate"].shape[0 if layer is None else 1]
    tile_m = row_tile(t * k)
    src, row, tile_expert, n_live, sizes = group_rows(
        experts.reshape(-1) - first, g, tile_m)
    x_rows = jnp.concatenate([xt, jnp.zeros((1, d), xt.dtype)])[
        jnp.minimum(src // k, t)]
    run = functools.partial(grouped_matmul, tile_m=tile_m, layer=layer)
    hidden = run(x_rows, w["w_gate"], tile_expert, n_live, w2=w["w_up"],
                 act=act)
    out = run(hidden, w["w_down"], tile_expert, n_live)
    held = (row < out.shape[0]).reshape(t, k)
    picked = out[jnp.minimum(row, out.shape[0] - 1)].reshape(t, k, d)
    # Rows no live tile wrote are never summed: a held pair's row is live.
    y = jnp.sum(jnp.where(held[..., None], picked, 0).astype(jnp.float32)
                * gates[..., None], axis=1)
    return y.astype(xt.dtype), sizes


def routed_ffn(x, rp, routed, router_x=None):
    """One routed FFN layer on ``x [B, S, D]``: router over all experts,
    the held experts' share of the routed result, plus the shared experts.
    ``rp``: ``router [D, E]``, ``w_gate`` / ``w_up`` / ``w_down`` (held
    experts, stacked), ``bias [E]`` (sigmoid scoring) and ``shared`` (one
    dense gated MLP; absent with ``n_shared == 0``) and ``shared_gate [D,
    1]`` (where the shared expert has a gate of its own: its output times
    ``sigmoid(x . shared_gate)``, a number a token).  ``routed``: the
    configuration's :class:`~.llama.RoutedFFN`: its scoring rule and gate
    activation.  ``router_x`` (default ``x``): what the router scores,
    where that is not the experts' input.  Returns ``(y, sizes [G])``."""
    b, s, d = x.shape
    xt = x.reshape(b * s, d)
    rt = xt if router_x is None else router_x.reshape(b * s, d)
    if routed.score == "softmax":
        experts, gates = softmax_route(rt, rp["router"], routed.top_k,
                                       routed.scale)
    else:
        experts, gates = sigmoid_route(rt, rp["router"], rp["bias"],
                                       routed.top_k, routed.scale)
    y, sizes = routed_experts(xt, experts, gates, rp, routed.first_held,
                              routed.act)
    if "shared" in rp:
        sh = rp["shared"]
        gate = GATE_ACTS[routed.act](
            (xt @ sh["w_gate"]).astype(jnp.float32)).astype(xt.dtype)
        shared = (gate * (xt @ sh["w_up"])) @ sh["w_down"]
        if "shared_gate" in rp:
            shared = shared * jax.nn.sigmoid(
                (xt @ rp["shared_gate"]).astype(jnp.float32)).astype(xt.dtype)
        y = y + shared
    return y.reshape(b, s, d), sizes
