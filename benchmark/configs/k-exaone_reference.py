"""Plain reference of the ``k-exaone`` configuration: the K-EXAONE-236B-A23B
block (``model_type: exaone_moe``) as its ``config.json`` gives it, in
straightforward ``jax.numpy`` float32 under
``jax.default_matmul_precision("highest")``.  For layer ``l`` on ``x``::

    a = RMSNorm(x; g1);  q = a Wq (64 heads of 128), k = a Wk, v = a Wv (8)
    q <- RMSNorm_128(q; gq), k <- RMSNorm_128(k; gk)       each head alone
    sliding_attention: split-half RoPE on q, k; position t sees (t - 128, t]
    full_attention:    no rotation; position t sees [0, t]
    h = x + softmax(q k^T / sqrt 128) v Wo
    b = RMSNorm(h; g2)
    layer 0:   y = (SiLU(b Wg) * (b Wu)) Wd                       18,432 wide
    layers>=1: s = sigmoid(b Wr) float32; chosen = top-8 of s + bias;
               gates = 2.5 * s_chosen / sum(s_chosen);
               y = sum_chosen gate_e Expert_e(b) + Shared(b)   HELD experts only
    out = h + y;   logits = RMSNorm(out_last; gf) W_head

and the multi-token-prediction block (DeepSeek-V3's form) for position t,
from the main model's last hidden state ``h_t`` (before ``gf``) and the NEXT
token ``x_{t+1}``::

    u_t = [RMSNorm(Emb(x_{t+1}); ge) ; RMSNorm(h_t; gh)] W_eh
    m_t = one full_attention layer of the form above over u_0 .. u_t
    draft_logits_t = RMSNorm(m_t; gm) W_head                  over x_{t+2}

Full [q block, S] attention matrices with a causal or banded mask, no cache,
no ring, no kernel, no sorting of tokens by expert (every held expert runs
on every token and is weighted by its gate, zero where it was not chosen),
nothing imported from the program.  It makes its own weights from the seed
(``harness/weights_k_exaone.py``), one layer at a time, after the program's
state is freed; one sequence at a time.

What is ASSUMED (the configuration's file lists each): the head norms, RoPE
on the window layers only, the pre-norm residual form, the selection bias,
the MTP block's form and its sparse FFN.  A chip's SHARE of the experts
(``num_experts`` < ``num_experts_published``): the router, the top-8 and the
gates run over all 128; chosen experts held elsewhere add nothing.

What it answers: served tokens are SAMPLED here, so a token's rank says
nothing; the served path hands out the log-probability it computed for each
emitted token and for each draft, and :func:`served_logps` recomputes both
over the served sequence: ``logp_gap_mean`` / ``logp_gap_max`` (|served -
reference| of the main model's log-softmax at the served temperature, every
emitted token) and ``draft_logp_gap_mean`` (the same for ``log q(d)`` of
every drafted token, from the MTP forward).  The int8 control
(:func:`control_logps`) puts the int8 reference's log-probabilities in the
served ones' place.
"""

from __future__ import annotations

import functools
import types

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax

from benchmark.harness import weights_k_exaone as W

Q_BLOCK = 1024     # queries a score matrix holds: [Q_BLOCK, S] float32
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
    """Grouped-query attention of one sequence x [S, D] with the head
    norms: ``window`` None is full causal, else position t sees (t -
    window, t]; ``rope`` False leaves q and k unrotated."""
    S, Hq, Hkv, hd = x.shape[0], d["Hq"], d["Hkv"], d["hd"]
    q = _linear(x, w["wq"], quant).reshape(S, Hq, hd).transpose(1, 0, 2)
    k = _linear(x, w["wk"], quant).reshape(S, Hkv, hd).transpose(1, 0, 2)
    v = _linear(x, w["wv"], quant).reshape(S, Hkv, hd).transpose(1, 0, 2)
    q = _rmsnorm(q, w["q_head_norm"], d["eps"])
    k = _rmsnorm(k, w["k_head_norm"], d["eps"])
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


def _mlp(y, ew, quant):
    g = jax.nn.silu(_linear(y, ew["w_gate"], quant)) * _linear(y, ew["w_up"], quant)
    return _linear(g, ew["w_down"], quant)


def route(y, rw, d, quant=None):
    """y [S, D] -> (chosen [S, k], gates [S, k]): sigmoid scores, the k
    largest of score + bias, the gates the chosen scores over their sum,
    times the scaling factor."""
    s = jax.nn.sigmoid(_linear(y, rw["router"], quant))
    _, idx = lax.top_k(s + rw["bias"].astype(jnp.float32), d["top_k"])
    g = jnp.take_along_axis(s, idx, -1)
    return idx, g / (jnp.sum(g, -1, keepdims=True) + 1e-20) * d["scale"]


def routed_part(y, rw, d, quant=None):
    """The held experts' part of the routed result for y [S, D]: every
    held expert on every token, times the token's gate for it (0: not
    chosen).  Without the shared expert."""
    idx, gates = route(y, rw, d, quant)

    def one(acc, ew_e):
        ew, e = ew_e
        gate = jnp.sum(jnp.where(idx == e, gates, 0.0), -1, keepdims=True)
        return acc + _mlp(y, ew, quant) * gate, None

    held = d["first_held"] + jnp.arange(d["held"])
    return lax.scan(one, jnp.zeros_like(y), (
        {n: rw[n] for n in ("w_gate", "w_up", "w_down")}, held))[0]


def ffn(y, w, d, quant=None):
    """The FFN of one block on its normed input: dense, or the held
    experts' share plus the shared expert."""
    if "routed" not in w:
        return _mlp(y, w, quant)
    return routed_part(y, w["routed"], d, quant) + _mlp(
        y, w["routed"]["shared"], quant)


def _layer_one(h, w, d, kind, quant):
    """One block on one sequence h [S, D]; ``kind`` = (window, rope)."""
    h = h + _attention(_rmsnorm(h, w["attn_norm"], d["eps"]), w, d, *kind, quant)
    return h + ffn(_rmsnorm(h, w["mlp_norm"], d["eps"]), w, d, quant)


@functools.cache
def _programs(dkey: tuple, quant):
    d = dict(dkey)

    @jax.jit
    def embed(key, tokens):
        return W.outer_weights(key, d)["embed"].astype(jnp.float32)[tokens]

    @functools.partial(jax.jit, static_argnames=("kind", "sparse"))
    def layer(key, i, h, kind, sparse):
        return _layer_one(h, W.layer_weights(key, i, d, sparse), d, kind, quant)

    @jax.jit
    def mtp(key, h, nxt):
        """The block's outputs m [S, D] from the main model's h [S, D] and
        the tokens at t + 1."""
        o, mw = W.outer_weights(key, d), W.mtp_weights(key, d)
        e = o["embed"].astype(jnp.float32)[nxt]
        u = _linear(jnp.concatenate(
            [_rmsnorm(e, mw["embed_norm"], d["eps"]),
             _rmsnorm(h, mw["hidden_norm"], d["eps"])], -1), mw["w_eh"], quant)
        return _layer_one(u, mw["layer"], d, (None, False), quant)

    @functools.partial(jax.jit, static_argnames="which")
    def logps(key, h, at, tokens, temperature, which):
        """log-softmax(logits / temperature)[tokens] at positions ``at`` of
        h [S, D], through the final norm (``which``: the model's or the MTP
        block's) and the shared head.  Blocks of positions."""
        o = W.outer_weights(key, d)
        norm = (o if which == "main" else W.mtp_weights(key, d))["final_norm"]

        def block(args):
            a, t = args
            logits = _linear(_rmsnorm(h[a], norm, d["eps"]), o["lm_head"], quant)
            lp = jax.nn.log_softmax(logits / temperature, -1)
            return (jnp.take_along_axis(lp, t[:, None], -1)[:, 0],
                    jnp.isfinite(logits).all())

        blk = AT_BLOCK if at.shape[0] % AT_BLOCK == 0 else at.shape[0]
        lp, finite = lax.map(block, (at.reshape(-1, blk), tokens.reshape(-1, blk)))
        return lp.reshape(-1), finite.all()

    @functools.partial(jax.jit, static_argnames="which")
    def logits(key, h, which):
        o = W.outer_weights(key, d)
        norm = (o if which == "main" else W.mtp_weights(key, d))["final_norm"]
        return _linear(_rmsnorm(h, norm, d["eps"]), o["lm_head"], quant)

    return types.SimpleNamespace(embed=embed, layer=layer, mtp=mtp,
                                 logps=logps, logits=logits)


def _of(config, quant=None):
    d = W.dims(config)
    return d, _programs(tuple(sorted(d.items())), quant)


@_highest
def hidden_states(config, seed, tokens, quant=None):
    """(h [S, D] of ONE sequence after the last block, before the final
    norm; m [S - 1, D], the MTP block's outputs at positions 0 .. S - 2,
    each made of ``h_t`` and the sequence's own token at ``t + 1``)."""
    d, run = _of(config, quant)
    key = W.base_key(seed)
    tokens = jnp.asarray(tokens)
    h = run.embed(key, tokens)
    for i in range(d["L"]):
        h = run.layer(key, jnp.int32(i), h, kind=(d["windows"][i], d["rope"][i]),
                      sparse=i >= d["dense"])
    # The block at the last position would need the token behind the
    # sequence: it runs on S positions (one shape) and the last is dropped.
    m = run.mtp(key, h, jnp.roll(tokens, -1))
    return h, m[:-1]


@_highest
def full_logits(config, seed, tokens):
    """``(logits [n, S, V], draft logits [n, S - 1, V])`` at every
    position: what the CPU tests compare."""
    _d, run = _of(config)
    key = W.base_key(seed)
    out = [hidden_states(config, seed, row) for row in np.asarray(tokens)]
    return (jnp.stack([run.logits(key, h, which="main") for h, _m in out]),
            jnp.stack([run.logits(key, m, which="mtp") for _h, m in out]))


def _pad(a, n: int, fill):
    out = np.full((n,), fill, np.asarray(a).dtype if len(a) else np.int32)
    out[:len(a)] = a
    return jnp.asarray(out)


def _pack(sample: dict, pad_to: int, out_to: int):
    """One sample as the reference runs it: the sequence padded to
    ``pad_to`` where that is whole query blocks (the cell's ``max_len``:
    ONE shape for every sample), else left at its own length (the CPU
    tests); where served token j is predicted, position p + j - 1, and
    where the draft of served token j was made, MTP position p + j - 2,
    each padded to ``out_to`` rounded up to whole position blocks."""
    prompt, served = np.asarray(sample["prompt"]), np.asarray(sample["tokens"])
    p, m = len(prompt), len(served)
    if p + m > pad_to or m > out_to:
        raise ValueError(f"sample of {p}+{m} tokens exceeds {pad_to}/{out_to}")
    tokens = np.zeros((pad_to if pad_to % Q_BLOCK == 0 else p + m,), np.int32)
    tokens[:p], tokens[p:p + m] = prompt, served
    k = -(-out_to // AT_BLOCK) * AT_BLOCK if out_to >= AT_BLOCK else m
    drafts = sample["drafts"]            # [(served index j, token, log q)]
    return (tokens, _pad(p - 1 + np.arange(m), k, p - 1), _pad(served, k, 0),
            _pad([p + j - 2 for j, _d, _lq in drafts], k, 0),
            _pad([t for _j, t, _lq in drafts], k, 0), m, len(drafts))


@_highest
def _logps(config, seed, samples, pad_to, out_to, quant):
    """Per sample ``(main logp [m], draft logp [n drafts], finite)`` under
    the reference in ``quant`` (None: float32)."""
    run = _of(config, quant)[1]
    key = W.base_key(seed)
    temperature = jnp.float32(config["serve"]["temperature"] or 1.0)
    out = []
    for sample in samples:      # one sequence at a time: it fits
        tokens, at, served, d_at, d_tok, m, n = _pack(sample, pad_to, out_to)
        h, mt = hidden_states(config, seed, tokens, quant)
        lp, f1 = run.logps(key, h, at, served, temperature, which="main")
        dlp, f2 = run.logps(key, mt, d_at, d_tok, temperature, which="mtp")
        out.append((np.asarray(lp)[:m], np.asarray(dlp)[:n], bool(f1) and bool(f2)))
    return out


def _reduce(pairs, dpairs, finite) -> dict:
    gap = np.abs(np.concatenate([a - b for a, b in pairs]))
    dgap = np.abs(np.concatenate([a - b for a, b in dpairs]))
    return {"logp_gap_max": float(gap.max()), "logp_gap_mean": float(gap.mean()),
            "draft_logp_gap_mean": float(dgap.mean()) if dgap.size else float("inf"),
            "tokens": int(gap.size), "drafts": int(dgap.size),
            "sequences": len(pairs),
            "finite": bool(finite and np.isfinite(gap).all()
                           and np.isfinite(dgap).all())}


def served_logps(config: dict, seed: int, samples, pad_to: int, out_to: int) -> dict:
    """``samples``: [{"prompt": ids, "tokens": served ids, "logp": the
    served path's log-probability of each, "drafts": [(served index j the
    draft stood for, drafted token, the served path's log q)]}].  The gaps
    between the served path's log-probabilities and the float32
    reference's, over the served sequence.

    ``config["correct"]["decide_control"]`` (set by the calibration and by
    the test, never by a cell's file) puts the lower-precision control's
    readings here instead, so that the runner's own decision, with its own
    limits, is seen to come out ``correct: false`` for them."""
    if config.get("correct", {}).get("decide_control"):
        return control_logps(config, seed, samples, pad_to, out_to,
                             config["correct"]["control"])
    ref = _logps(config, seed, samples, pad_to, out_to, None)
    return _reduce(
        [(np.asarray(s["logp"], np.float32), r[0]) for s, r in zip(samples, ref)],
        [(np.asarray([lq for _j, _t, lq in s["drafts"]], np.float32), r[1])
         for s, r in zip(samples, ref)], all(r[2] for r in ref))


def control_logps(config: dict, seed: int, samples, pad_to: int, out_to: int,
                  quant: str) -> dict:
    """The same readings with the ``quant`` reference's log-probabilities
    (every linear layer, experts, router and MTP block rounded) in the
    served ones' place."""
    ref = _logps(config, seed, samples, pad_to, out_to, None)
    low = _logps(config, seed, samples, pad_to, out_to, quant)
    return _reduce([(l[0], r[0]) for l, r in zip(low, ref)],
                   [(l[1], r[1]) for l, r in zip(low, ref)],
                   all(r[2] and l[2] for l, r in zip(low, ref)))
