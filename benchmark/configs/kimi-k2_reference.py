"""Plain reference of the ``kimi-k2`` configuration: the DeepSeek-V3 block as
Kimi-K2 publishes it (RMSNorm, multi-head latent attention with low-rank q
and kv projections and one RoPE key head shared by all heads, YaRN-scaled
RoPE, a leading dense SwiGLU layer, then sigmoid-routed experts with a
bias-corrected top-k beside a shared expert, untied head) in straightforward
``jax.numpy`` float32 with ``precision=HIGHEST``: the EXPANDED attention at
every position, no cache, no kernel, no absorbed form, no sorting of tokens
by expert (every held expert runs on every token and is weighted by its
gate, zero where it was not chosen), nothing imported from the program.

Departures from the published code, each on purpose:

* RoPE rotates split halves (x[:d/2], x[d/2:]); the published code first
  de-interleaves the rope columns and then does the same, so its weights
  map onto these by that permutation (``models/hf_convert.py`` applies it).
* This chip's SHARE of the deployment: of the router's ``E`` experts only
  ``held`` live here.  The router, the top-k and the gates' normalisation
  run over all ``E``; chosen experts held elsewhere add nothing, and that
  partial result goes on to the next layer (model-configs guide, section
  4).  Embedding and head cover the held slice of the vocabulary.
* ``n_group = topk_group = 1``: the group cut of the published router is
  the identity and is left out.

It makes its own weights from the seed (``harness/weights_mla_moe.py``),
one layer at a time, after the program's state is freed; attention runs a
head at a time and the sequences one after another, so that it fits.

What it answers is what ``mistral7b_reference`` answers (``served_gaps``,
``control_gaps``: the gap of a token's reference logit below the
reference's best, as a share of max |logit|), plus ``router_flips``: the
share of (position, layer) pairs whose top-k SET changes when the router's
inputs are rounded to bfloat16, i.e. how often a near-tie flips an expert
between the served precision and this one.
"""

from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax

from benchmark.harness import weights_mla_moe as W

HI = lax.Precision.HIGHEST


def _round_to(x, axis: int, quant: str):
    """``x`` rounded to ``quant`` with one symmetric scale along ``axis``."""
    amax = jnp.max(jnp.abs(x), axis=axis, keepdims=True) + 1e-30
    if quant == "int8":
        s = amax / 127.0
        return jnp.clip(jnp.round(x / s), -127, 127) * s
    if quant == "fp8":
        s = amax / 448.0
        return (x / s).astype(jnp.float8_e4m3fn).astype(jnp.float32) * s
    raise ValueError(f"unknown control precision {quant!r}")


def _linear(x, w, quant):
    """x [S, in] @ w [in, out] in float32; the control rounds both."""
    w = w.astype(jnp.float32)
    if quant is not None:
        x, w = _round_to(x, -1, quant), _round_to(w, 0, quant)
    return jnp.dot(x, w, precision=HI)


def _rmsnorm(x, w, eps):
    return x * lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps) * w.astype(jnp.float32)


def _yarn_inv_freq(d: dict):
    """The published YaRN frequencies of the rope head."""
    dim, base, orig = d["rope"], d["theta"], d["yarn_orig"]
    freq = 1.0 / (base ** (np.arange(0, dim, 2, dtype=np.float64) / dim))

    def corr(rot):
        return dim * math.log(orig / (rot * 2 * math.pi)) / (2 * math.log(base))

    low = max(math.floor(corr(d["yarn_beta_fast"])), 0)
    high = min(math.ceil(corr(d["yarn_beta_slow"])), dim - 1)
    if low == high:
        high += 0.001   # as published: the ramp's two ends must differ
    ramp = np.clip((np.arange(dim // 2) - low) / (high - low), 0, 1)
    keep = 1.0 - ramp   # 1 where the frequency is left as it is
    return jnp.asarray(freq / d["yarn_factor"] * (1 - keep) + freq * keep,
                       jnp.float32)


def _rope(x, d):
    """x [..., S, rope], positions 0..S-1, split-half rotation."""
    half = d["rope"] // 2
    ang = jnp.arange(x.shape[-2], dtype=jnp.float32)[:, None] * _yarn_inv_freq(d)
    c, s = jnp.cos(ang) * d["rope_att"], jnp.sin(ang) * d["rope_att"]
    x1, x2 = x[..., :half], x[..., half:]
    return jnp.concatenate([x1 * c - x2 * s, x1 * s + x2 * c], -1)


def _attention(x, w, d, quant):
    """Expanded multi-head latent attention of one sequence x [S, D]."""
    S, H, nope, rope, r = x.shape[0], d["H"], d["nope"], d["rope"], d["kv_rank"]
    cq = _rmsnorm(_linear(x, w["wq_a"], quant), w["q_norm"], d["eps"])
    q = _linear(cq, w["wq_b"], quant).reshape(S, H, nope + rope).transpose(1, 0, 2)
    kv = _linear(x, w["wkv_a"], quant)
    c_kv = _rmsnorm(kv[:, :r], w["kv_norm"], d["eps"])
    k_pe = _rope(kv[:, r:], d)                                    # [S, rope]
    kvb = _linear(c_kv, w["wkv_b"], quant).reshape(S, H, nope + d["v"]).transpose(1, 0, 2)
    q = jnp.concatenate([q[..., :nope], _rope(q[..., nope:], d)], -1)
    causal = jnp.tril(jnp.ones((S, S), bool))

    def head(qkv):   # one head at a time: an [S, S] score matrix each
        qh, kh = qkv
        k = jnp.concatenate([kh[:, :nope], k_pe], -1)
        s = jnp.dot(qh, k.T, precision=HI) * d["sm_scale"]
        p = jax.nn.softmax(jnp.where(causal, s, -jnp.inf), -1)
        return jnp.dot(p, kh[:, nope:], precision=HI)

    o = lax.map(head, (q, kvb))                                   # [H, S, v]
    return _linear(o.transpose(1, 0, 2).reshape(S, -1), w["wo"], quant)


def _swiglu(x, w, quant):
    g = jax.nn.silu(_linear(x, w["w_gate"], quant)) * _linear(x, w["w_up"], quant)
    return _linear(g, w["w_down"], quant)


def route(x, rw, d, quant=None):
    """x [S, D] -> (scores [S, E], chosen [S, k], gates [S, k])."""
    s = jax.nn.sigmoid(_linear(x, rw["router"], quant))
    _, idx = lax.top_k(s + rw["bias"], d["top_k"])
    g = jnp.take_along_axis(s, idx, -1)
    return s, idx, g / (g.sum(-1, keepdims=True) + 1e-20) * d["route_scale"]


def routed_part(x, rw, d, quant=None):
    """The held experts' part of the routed result for x [S, D]: every held
    expert on every token, times the token's gate for it (0: not chosen)."""
    _s, idx, gates = route(x, rw, d, quant)

    def one(acc, ew_e):
        ew, e = ew_e
        gate = jnp.sum(jnp.where(idx == e, gates, 0.0), -1, keepdims=True)
        return acc + _swiglu(x, ew, quant) * gate, None

    held = d["first_held"] + jnp.arange(d["held"])
    return lax.scan(one, jnp.zeros_like(x), (
        {n: rw[n] for n in ("w_gate", "w_up", "w_down")}, held))[0]


def _layer_one(h, w, d, quant):
    """One block on one sequence h [S, D]."""
    h = h + _attention(_rmsnorm(h, w["attn_norm"], d["eps"]), w, d, quant)
    x = _rmsnorm(h, w["mlp_norm"], d["eps"])
    if "routed" not in w:
        return h + _swiglu(x, w, quant)
    return h + routed_part(x, w["routed"], d, quant) + _swiglu(
        x, w["routed"]["shared"], quant)


def _flips_one(h, w, d):
    """Top-k sets that differ when the router's inputs are bfloat16."""
    h = h + _attention(_rmsnorm(h, w["attn_norm"], d["eps"]), w, d, None)
    x = _rmsnorm(h, w["mlp_norm"], d["eps"])
    rw = w["routed"]
    _s, idx, _g = route(x, rw, d)
    low = jax.nn.sigmoid(jnp.dot(
        x.astype(jnp.bfloat16), rw["router"].astype(jnp.bfloat16),
        preferred_element_type=jnp.float32))
    _, idx_low = lax.top_k(low + rw["bias"], d["top_k"])
    return jnp.any(jnp.sort(idx, -1) != jnp.sort(idx_low, -1), -1)   # [S]


@functools.cache
def _programs(dkey: tuple, quant):
    d = dict(dkey)

    @jax.jit
    def embed(key, tokens):
        return W.outer_weights(key, d)["embed"].astype(jnp.float32)[tokens]

    @functools.partial(jax.jit, static_argnames="routed")
    def layer(key, i, h, routed):
        w = W.layer_weights(key, i, d, routed)
        # One sequence at a time, so that it fits.
        return lax.map(lambda hs: _layer_one(hs, w, d, quant), h)

    @jax.jit
    def flips(key, i, h):
        w = W.layer_weights(key, i, d, True)
        return lax.map(lambda hs: _flips_one(hs, w, d), h)

    @jax.jit
    def logits(key, h, at):
        o = W.outer_weights(key, d)
        rows = jnp.take_along_axis(h, at[:, :, None], 1)        # [n, P, D]
        x = _rmsnorm(rows, o["final_norm"], d["eps"])
        return lax.map(lambda r: _linear(r, o["lm_head"], quant), x)

    return embed, layer, logits, flips


@jax.jit
def _gap(ref_logits, chosen):
    """(best - logit of the chosen token) / max |logit|, per position."""
    got = jnp.take_along_axis(ref_logits, chosen[..., None], -1)[..., 0]
    return ((ref_logits.max(-1) - got) / jnp.abs(ref_logits).max(-1),
            jnp.isfinite(ref_logits).all())


def hidden_states(config, seed, tokens, quant=None, flips_at=None):
    """h [n, S, D] after the last block; with ``flips_at`` a list, also
    appends each routed layer's [n, S] flip mask to it."""
    d = W.dims(config)
    embed, layer, _logits, flips = _programs(tuple(sorted(d.items())), quant)
    key = W.base_key(seed)
    h = embed(key, tokens)
    for i in range(d["L"]):
        routed = i >= d["first_dense"]
        if routed and flips_at is not None:
            flips_at.append(flips(key, jnp.int32(i), h))
        h = layer(key, jnp.int32(i), h, routed=routed)
    return h


def _logits(config, seed, tokens, at, quant):
    d = W.dims(config)
    logits = _programs(tuple(sorted(d.items())), quant)[2]
    return logits(W.base_key(seed), hidden_states(config, seed, tokens, quant), at)


def full_logits(config, seed, tokens):
    """Logits [n, S, V] at every position: what the CPU tests compare."""
    at = jnp.broadcast_to(jnp.arange(tokens.shape[1]), tokens.shape)
    return _logits(config, seed, jnp.asarray(tokens), at, None)


def _pack(samples, pad_to: int, out_to: int):
    n = len(samples)
    tokens = np.zeros((n, pad_to), np.int32)
    at = np.zeros((n, out_to), np.int32)
    chosen = np.zeros((n, out_to), np.int32)
    counts = []
    for r, (prompt, served) in enumerate(samples):
        p, m = len(prompt), len(served)
        if p + m > pad_to or m > out_to:
            raise ValueError(f"sample of {p}+{m} tokens exceeds {pad_to}/{out_to}")
        tokens[r, :p], tokens[r, p:p + m] = prompt, served
        # Served token j is predicted at position p + j - 1.
        at[r, :m] = p - 1 + np.arange(m)
        at[r, m:] = p - 1
        chosen[r, :m], chosen[r, m:] = served, served[0]
        counts.append(m)
    return jnp.asarray(tokens), jnp.asarray(at), jnp.asarray(chosen), counts


def _reduce(gap, counts) -> dict:
    gap = np.asarray(gap)
    real = np.concatenate([gap[r, :m] for r, m in enumerate(counts)])
    return {"gap_max": float(real.max()), "gap_mean": float(real.mean()),
            "tokens": int(real.size), "sequences": len(counts)}


def served_gaps(config: dict, seed: int, samples, pad_to: int, out_to: int) -> dict:
    """``samples``: [(prompt ids, served ids)].  The widest and the mean gap
    of the served tokens under the float32 reference.

    ``config["correct"]["decide_control"]`` (set by the calibration and by
    the test, never by a cell's file) puts the lower-precision control's
    readings here instead, so that the harness's own decision, with its
    own limits, is seen to come out ``correct: false`` for them."""
    if config.get("correct", {}).get("decide_control"):
        return control_gaps(config, seed, samples, pad_to, out_to,
                            config["correct"]["control"])
    tokens, at, chosen, counts = _pack(samples, pad_to, out_to)
    gap, finite = _gap(_logits(config, seed, tokens, at, None), chosen)
    out = _reduce(gap, counts)
    out["finite"] = bool(finite)
    return out


def control_gaps(config: dict, seed: int, samples, pad_to: int, out_to: int,
                 quant: str) -> dict:
    """The same readings for the tokens the lower precision puts first."""
    tokens, at, _chosen, counts = _pack(samples, pad_to, out_to)
    ref = _logits(config, seed, tokens, at, None)
    low = _logits(config, seed, tokens, at, quant)
    gap, finite = _gap(ref, jnp.argmax(low, -1).astype(jnp.int32))
    out = _reduce(gap, counts)
    out["finite"] = bool(finite)
    return out


def router_flips(config: dict, seed: int, samples, pad_to: int, out_to: int) -> dict:
    """Share of (real position, routed layer) pairs whose top-k set differs
    between float32 and bfloat16 router inputs, at the reference's own
    hidden states."""
    tokens, _at, _chosen, counts = _pack(samples, pad_to, out_to)
    masks: list = []
    hidden_states(config, seed, tokens, None, flips_at=masks)
    flipped = np.stack([np.asarray(m) for m in masks])          # [layers, n, S]
    real = np.zeros(flipped.shape[1:], bool)
    for r, ((prompt, _served), m) in enumerate(zip(samples, counts)):
        real[r, :len(prompt) + m] = True
    return {"share": float(flipped[:, real].mean()),
            "positions": int(real.sum()), "layers": len(masks)}
