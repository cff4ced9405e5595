"""Plain reference of the ``smallthinker-21b`` configuration: the
SmallThinker-21BA3B-Instruct block as its ``config.json`` gives it, in
straightforward ``jax.numpy`` float32 under
``jax.default_matmul_precision("highest")``: RMSNorm, grouped-query
attention whose layers differ on a period (``sliding_window_layout`` /
``rope_layout``: a FULL layer attends [0, t] and does NOT rotate q and k, a
WINDOW layer attends (t - window, t] and rotates them), a router that scores
the block's normed INPUT (what attention reads) and so chooses a token's
experts before attention, applied after it to the normed state the FFN
reads; the gates are the softmax over the chosen logits alone; experts are
gated MLPs with ReLU on the gate (ReGLU), no shared expert; untied head.

Full [q block, S] attention matrices with the window mask, no cache, no
kernel, no sorting of tokens by expert (every held expert runs on every
token and is weighted by its gate, zero where it was not chosen), nothing
imported from the program.  It makes its own weights from the seed
(``harness/weights_window_moe.py``), one layer at a time, after the
program's state is freed; one sequence, one head and one block of queries
at a time, so that 16k tokens fit (5.7 s a 16,384-token sequence on the
v5e, every sample padded to that one shape; 20 s more where its programs
are not in the compile cache yet).

Departures from the published code, each on purpose:

* RoPE rotates split halves (x[:d/2], x[d/2:]), the Hugging Face layout of
  the published weights' family; no scaling (``rope_scaling`` null).
* ``described_as`` speaks of "secondary experts"; the config has no key
  for them and they are NOT modelled.
* The config has no ``hidden_act``; ReLU on the gate is ASSUMED from
  "sparse ReGLU" (the file lists it under ``assumed``).
* The router's weights are held in the served type (bfloat16) like every
  other matrix; its logits are float32.
* A chip's SHARE of the experts (``experts_held`` < 64 in a configuration,
  used by the share test only): the router, the top-k and the softmax run
  over all 64; chosen experts held elsewhere add nothing.

What it answers is what the other two references answer (``served_gaps``,
``control_gaps``: the gap of a token's reference logit below the
reference's best, as a share of max |logit|), plus ``router_flips``: the
share of (position, layer) pairs whose top-k SET changes when the router's
inputs are rounded to bfloat16.
"""

from __future__ import annotations

import functools
import types

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax

from benchmark.harness import weights_window_moe as W

Q_BLOCK = 2048     # queries a score matrix holds: [Q_BLOCK, S] float32
AT_BLOCK = 512     # positions a block of logits holds: [AT_BLOCK, V] float32


def _highest(fn):
    """Every matmul of the reference in full float32 (on a TPU a float32
    matmul is otherwise computed in bfloat16 passes)."""
    @functools.wraps(fn)
    def run(*args, **kwargs):
        with jax.default_matmul_precision("highest"):
            return fn(*args, **kwargs)
    return run


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
    return jnp.dot(x, w)


def _rmsnorm(x, w, eps):
    return x * lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps) * w.astype(jnp.float32)


def _rope(x, d):
    """x [..., S, hd], positions 0..S-1, split-half rotation."""
    half = d["hd"] // 2
    inv = 1.0 / (d["theta"] ** (jnp.arange(half, dtype=jnp.float32) / half))
    ang = jnp.arange(x.shape[-2], dtype=jnp.float32)[:, None] * inv
    c, s = jnp.cos(ang), jnp.sin(ang)
    x1, x2 = x[..., :half], x[..., half:]
    return jnp.concatenate([x1 * c - x2 * s, x1 * s + x2 * c], -1)


def _attention(x, w, d, window, rope, quant):
    """Grouped-query attention of one sequence x [S, D]: ``window`` None is
    full causal, else position t sees (t - window, t]; ``rope`` False
    leaves q and k unrotated."""
    S, Hq, Hkv, hd = x.shape[0], d["Hq"], d["Hkv"], d["hd"]
    q = _linear(x, w["wq"], quant).reshape(S, Hq, hd).transpose(1, 0, 2)
    k = _linear(x, w["wk"], quant).reshape(S, Hkv, hd).transpose(1, 0, 2)
    v = _linear(x, w["wv"], quant).reshape(S, Hkv, hd).transpose(1, 0, 2)
    if rope:
        q, k = _rope(q, d), _rope(k, d)
    blk = Q_BLOCK if S % Q_BLOCK == 0 else S
    kpos = jnp.arange(S)[None, :]

    def head(args):   # one head, one block of queries at a time
        qh, h = args
        kh, vh = k[h // (Hq // Hkv)], v[h // (Hq // Hkv)]

        def block(args):
            qb, b = args
            qpos = b * blk + jnp.arange(blk)[:, None]
            keep = kpos <= qpos
            if window is not None:
                keep = keep & (kpos > qpos - window)
            s = jnp.dot(qb, kh.T) * hd ** -0.5
            return jnp.dot(jax.nn.softmax(jnp.where(keep, s, -jnp.inf), -1), vh)

        return lax.map(block, (qh.reshape(S // blk, blk, hd),
                               jnp.arange(S // blk))).reshape(S, hd)

    o = lax.map(head, (q, jnp.arange(Hq)))                        # [Hq, S, hd]
    return _linear(o.transpose(1, 0, 2).reshape(S, -1), w["wo"], quant)


def _expert(y, ew, quant):
    g = jax.nn.relu(_linear(y, ew["w_gate"], quant)) * _linear(y, ew["w_up"], quant)
    return _linear(g, ew["w_down"], quant)


def route(r, rw, d, quant=None):
    """r [S, D], the router's input -> (chosen [S, k], gates [S, k]): the
    k largest logits, the gates their softmax (over the chosen alone)."""
    top, idx = lax.top_k(_linear(r, rw["router"], quant), d["top_k"])
    return idx, jax.nn.softmax(top, -1)


def routed_part(y, r, rw, d, quant=None):
    """The held experts' part of the routed result for y [S, D] with the
    router reading r [S, D]: every held expert on every token, times the
    token's gate for it (0: not chosen)."""
    idx, gates = route(r, rw, d, quant)

    def one(acc, ew_e):
        ew, e = ew_e
        gate = jnp.sum(jnp.where(idx == e, gates, 0.0), -1, keepdims=True)
        return acc + _expert(y, ew, quant) * gate, None

    held = d["first_held"] + jnp.arange(d["held"])
    return lax.scan(one, jnp.zeros_like(y), (
        {n: rw[n] for n in ("w_gate", "w_up", "w_down")}, held))[0]


def _layer_one(h, w, d, kind, quant):
    """One block on one sequence h [S, D]; ``kind`` = (window, rope)."""
    x = _rmsnorm(h, w["attn_norm"], d["eps"])       # attention's input AND the router's
    h = h + _attention(x, w, d, *kind, quant)
    y = _rmsnorm(h, w["mlp_norm"], d["eps"])
    return h + routed_part(y, x, w["routed"], d, quant)


def _flips_one(h, w, d):
    """Top-k sets that differ when the router's inputs are bfloat16."""
    x = _rmsnorm(h, w["attn_norm"], d["eps"])
    idx, _g = route(x, w["routed"], d)
    low = jnp.dot(x.astype(jnp.bfloat16), w["routed"]["router"].astype(jnp.bfloat16),
                  preferred_element_type=jnp.float32)
    _, idx_low = lax.top_k(low, d["top_k"])
    return jnp.any(jnp.sort(idx, -1) != jnp.sort(idx_low, -1), -1)   # [S]


@functools.cache
def _programs(dkey: tuple, quant):
    d = dict(dkey)

    @jax.jit
    def embed(key, tokens):
        return W.outer_weights(key, d)["embed"].astype(jnp.float32)[tokens]

    @functools.partial(jax.jit, static_argnames="kind")
    def layer(key, i, h, kind):
        return _layer_one(h, W.layer_weights(key, i, d), d, kind, quant)

    @jax.jit
    def flips(key, i, h):
        return _flips_one(h, W.layer_weights(key, i, d), d)

    @jax.jit
    def logits(key, h, at):
        """[len(at), V] at positions ``at`` of h [S, D]."""
        o = W.outer_weights(key, d)
        return _linear(_rmsnorm(h[at], o["final_norm"], d["eps"]),
                       o["lm_head"], quant)

    @jax.jit
    def gaps(key, h, h_choice, at, chosen):
        """Per position of ``at``: (best - logit of the chosen token) / max
        |logit| under h; ``chosen`` None: the token h_choice's logits (the
        control's, under ITS precision) put first.  Blocks of positions."""
        o = W.outer_weights(key, d)
        head = o["lm_head"].astype(jnp.float32)

        def block(args):
            a, c = args
            ref = jnp.dot(_rmsnorm(h[a], o["final_norm"], d["eps"]), head)
            if h_choice is not None:
                c = jnp.argmax(_linear(_rmsnorm(h_choice[a], o["final_norm"],
                                                d["eps"]), o["lm_head"], quant), -1)
            got = jnp.take_along_axis(ref, c[:, None], -1)[:, 0]
            return ((ref.max(-1) - got) / jnp.abs(ref).max(-1),
                    jnp.isfinite(ref).all())

        blk = AT_BLOCK if at.shape[0] % AT_BLOCK == 0 else at.shape[0]
        gap, finite = lax.map(block, (at.reshape(-1, blk), chosen.reshape(-1, blk)))
        return gap.reshape(-1), finite.all()

    return types.SimpleNamespace(embed=embed, layer=layer, logits=logits,
                                 flips=flips, gaps=gaps)


def _of(config, quant=None):
    d = W.dims(config)
    return d, _programs(tuple(sorted(d.items())), quant)


@_highest
def hidden_states(config, seed, tokens, quant=None, flips_at=None):
    """h [S, D] of ONE sequence after the last block; with ``flips_at`` a
    list, also appends each layer's [S] flip mask to it."""
    d, run = _of(config, quant)
    key = W.base_key(seed)
    h = run.embed(key, jnp.asarray(tokens))
    for i in range(d["L"]):
        if flips_at is not None:
            flips_at.append(run.flips(key, jnp.int32(i), h))
        h = run.layer(key, jnp.int32(i), h,
                      kind=(d["windows"][i], d["rope"][i]))
    return h


@_highest
def full_logits(config, seed, tokens):
    """Logits [n, S, V] at every position: what the CPU tests compare."""
    _d, run = _of(config)
    at = jnp.arange(np.shape(tokens)[1])
    return jnp.stack([run.logits(W.base_key(seed),
                                 hidden_states(config, seed, row), at)
                      for row in np.asarray(tokens)])


def _pack(prompt, served, pad_to: int, out_to: int):
    """One sample as the reference runs it: the sequence padded to
    ``pad_to`` where that is whole query blocks (the cell's ``max_len``:
    ONE shape for every sample, so nothing compiles anew for a sample of
    another length), else left at its own length (the CPU tests); and
    where served token j is predicted, position p + j - 1, padded to
    ``out_to`` rounded up to whole position blocks likewise."""
    p, m = len(prompt), len(served)
    if p + m > pad_to or m > out_to:
        raise ValueError(f"sample of {p}+{m} tokens exceeds {pad_to}/{out_to}")
    tokens = np.zeros((pad_to if pad_to % Q_BLOCK == 0 else p + m,), np.int32)
    tokens[:p], tokens[p:p + m] = prompt, served
    k = -(-out_to // AT_BLOCK) * AT_BLOCK if out_to >= AT_BLOCK else m
    at = np.full((k,), p - 1, np.int32)
    at[:m] = p - 1 + np.arange(m)
    chosen = np.full((k,), served[0], np.int32)
    chosen[:m] = served
    return tokens, jnp.asarray(at), jnp.asarray(chosen), m


def _reduce(gaps, finite) -> dict:
    real = np.concatenate([np.asarray(g) for g in gaps])
    return {"gap_max": float(real.max()), "gap_mean": float(real.mean()),
            "tokens": int(real.size), "sequences": len(gaps),
            "finite": bool(all(bool(f) for f in finite))}


@_highest
def _gaps(config, seed, samples, pad_to, out_to, quant) -> dict:
    """``quant`` None: the served tokens' gaps; else the gaps of the tokens
    that precision puts first, at the same positions."""
    run = _of(config, quant)[1]   # the control chooses under ITS precision
    key = W.base_key(seed)
    gaps, finite = [], []
    for prompt, served in samples:      # one sequence at a time: it fits
        tokens, at, chosen, m = _pack(prompt, served, pad_to, out_to)
        h = hidden_states(config, seed, tokens)
        low = None if quant is None else hidden_states(config, seed, tokens, quant)
        g, f = run.gaps(key, h, low, at, chosen)
        gaps.append(np.asarray(g)[:m])
        finite.append(f)
    return _reduce(gaps, finite)


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
    return _gaps(config, seed, samples, pad_to, out_to, None)


def control_gaps(config: dict, seed: int, samples, pad_to: int, out_to: int,
                 quant: str) -> dict:
    """The same readings for the tokens the lower precision puts first."""
    return _gaps(config, seed, samples, pad_to, out_to, quant)


def router_flips(config: dict, seed: int, samples, pad_to: int, out_to: int) -> dict:
    """Share of (real position, layer) pairs whose top-k set differs
    between float32 and bfloat16 router inputs, at the reference's own
    hidden states."""
    flipped, positions, layers = 0, 0, 0
    for prompt, served in samples:
        tokens, _at, _chosen, m = _pack(prompt, served, pad_to, out_to)
        masks: list = []
        hidden_states(config, seed, tokens, None, flips_at=masks)
        real = len(prompt) + m
        flipped += int(sum(np.asarray(mk)[:real].sum() for mk in masks))
        positions += real
        layers = len(masks)
    return {"share": flipped / max(positions * layers, 1),
            "positions": positions, "layers": layers}
