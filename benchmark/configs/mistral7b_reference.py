"""Plain reference of the ``mistral7b`` configuration: the Mistral decoder's
forward pass (RMSNorm, RoPE in the split-half convention of the published
code, grouped-query causal attention, SwiGLU, untied head) in straightforward
``jax.numpy`` float32 with ``precision=HIGHEST``: no kernel, no cache, no
batching tricks, nothing imported from the program.

It makes its own weights from the seed (``benchmark/harness/weights.py``),
one layer at a time, so it fits beside nothing else on a 16 GB chip and
runs after the program's state is freed.

What it answers, for a sample of served requests (prompt, served tokens):
at every served position, how far the reference's logit of the served
token lies below the reference's best logit, as a share of max |logit| at
that position.  0 where they agree, a rounding's worth at a near-tie.

The control (``quant``) is the same forward with every linear layer's
weights and inputs rounded to a lower precision (int8: per output channel
and per token, symmetric; fp8: e4m3 with the same scales).  It does not
decode: at each position of the same prompts and tokens it reads the gap
of the token the lower precision puts first.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax

from benchmark.harness import weights as W

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


def _rope(x, theta):
    """x [H, S, hd], positions 0..S-1, split-half rotation."""
    hd = x.shape[-1]
    inv = 1.0 / (theta ** (jnp.arange(0, hd, 2, dtype=jnp.float32) / hd))
    ang = jnp.arange(x.shape[1], dtype=jnp.float32)[:, None] * inv[None, :]
    c, s = jnp.cos(ang), jnp.sin(ang)
    x1, x2 = x[..., : hd // 2], x[..., hd // 2:]
    return jnp.concatenate([x1 * c - x2 * s, x1 * s + x2 * c], -1)


def _layer_one(h, w, d, quant):
    """One decoder layer on one sequence h [S, D]."""
    S = h.shape[0]
    x = _rmsnorm(h, w["attn_norm"], d["eps"])
    q = _linear(x, w["wq"], quant).reshape(S, d["Hq"], d["hd"]).transpose(1, 0, 2)
    k = _linear(x, w["wk"], quant).reshape(S, d["Hkv"], d["hd"]).transpose(1, 0, 2)
    v = _linear(x, w["wv"], quant).reshape(S, d["Hkv"], d["hd"]).transpose(1, 0, 2)
    q, k = _rope(q, d["theta"]), _rope(k, d["theta"])
    rep = d["Hq"] // d["Hkv"]
    k, v = jnp.repeat(k, rep, 0), jnp.repeat(v, rep, 0)
    s = jnp.einsum("hqd,hkd->hqk", q, k, precision=HI) * d["hd"] ** -0.5
    s = jnp.where(jnp.tril(jnp.ones((S, S), bool))[None], s, -jnp.inf)
    o = jnp.einsum("hqk,hkd->hqd", jax.nn.softmax(s, -1), v, precision=HI)
    h = h + _linear(o.transpose(1, 0, 2).reshape(S, -1), w["wo"], quant)
    x = _rmsnorm(h, w["mlp_norm"], d["eps"])
    g = jax.nn.silu(_linear(x, w["w_gate"], quant)) * _linear(x, w["w_up"], quant)
    return h + _linear(g, w["w_down"], quant)


@functools.cache
def _programs(dkey: tuple, quant):
    d = dict(dkey)

    @jax.jit
    def embed(key, tokens):
        return W.outer_weights(key, d)["embed"].astype(jnp.float32)[tokens]

    @jax.jit
    def layer(key, i, h):
        w = W.layer_weights(key, i, d)
        # One sequence at a time: blocks of rows, so that it fits.
        return lax.map(lambda hs: _layer_one(hs, w, d, quant), h)

    @jax.jit
    def logits(key, h, at):
        o = W.outer_weights(key, d)
        rows = jnp.take_along_axis(h, at[:, :, None], 1)        # [n, P, D]
        x = _rmsnorm(rows, o["final_norm"], d["eps"])
        return lax.map(lambda r: _linear(r, o["lm_head"], quant), x)

    return embed, layer, logits


@jax.jit
def _gap(ref_logits, chosen):
    """(best - logit of the chosen token) / max |logit|, per position."""
    got = jnp.take_along_axis(ref_logits, chosen[..., None], -1)[..., 0]
    return ((ref_logits.max(-1) - got) / jnp.abs(ref_logits).max(-1),
            jnp.isfinite(ref_logits).all())


def _logits(config, seed, tokens, at, quant):
    d = W.dims(config)
    embed, layer, logits = _programs(tuple(sorted(d.items())), quant)
    key = W.base_key(seed)
    h = embed(key, tokens)
    for i in range(d["L"]):
        h = layer(key, jnp.int32(i), h)
    return logits(key, h, at)


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
    of the served tokens under the float32 reference."""
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
