"""Plain reference of the ``qwen3-next`` configuration: the
Qwen3-Next-80B-A3B-Instruct block as its ``config.json`` and the model's
published code (``qwen3_next``) give it, in straightforward ``jax.numpy``
float32 under ``jax.default_matmul_precision("highest")``.  Pre-norm
blocks, ``h += Mixer(norm(h)); h += MoE(norm(h))``, untied head; every
RMSNorm but the DeltaNet's output norm is zero-centred, ``x / rms(x) * (1
+ w)``.  Layer ``i`` attends where ``(i + 1) % full_attention_interval ==
0``; the others are Gated DeltaNet, the gated delta rule TOKEN BY TOKEN
(``lax.scan`` over positions), 16 key heads under 32 value heads of 128:

    q~ | k~ | v~ | z = W_qkvz x           2048 | 2048 | 4096 | 4096
    b | a       = W_ba x                  32 | 32
    q, k, v     = SiLU(conv4(q~ | k~ | v~))   one depthwise causal convolution
    q, k        = q / |q|, k / |k| a key head;  q *= 128 ** -0.5;
                  value head j reads key head j // 2
    g_t         = -exp(A_log[h]) softplus(a + dt_bias)      ONE number a head
    beta_t      = sigmoid(b)                                [32]
    S'          = exp(g_t) S_{t-1}
    S_t         = S' + beta_t k_t (v_t - S'^T k_t)^T
    o_t         = S_t^T q_t
    y_t         = W_o [RMSNorm_128(o_t; w) * SiLU(z)]

An attention layer is grouped-query attention with whole [query block, S]
score matrices and an output gate:

    q | gate    = W_q x    (16 heads, each 256 query beside 256 gate values)
    k, v        = W_k x, W_v x                     (2 heads of 256)
    q, k        = zero-centred RMSNorm over each head's 256 values
    q, k        the first 64 values of a head rotated (split-half among
                themselves, theta 1e7), the other 192 as they are
    o           = causal softmax(q k^T 256 ** -0.5) v, 8 query heads a kv head
    y           = W_o [o * sigmoid(gate)]

The FFN of every layer: ``p = softmax(W_r x)`` over all 512 experts, the 10
largest, gates ``p / sum of the chosen``; an expert is ``W_d (SiLU(W_g x) *
W_u x)``; every held expert runs on every token and is weighted by its
gate, zero where it was not chosen; plus ``sigmoid(w_sg . x)`` times the
shared expert.  No cache, no kernel, no chunked form of the recurrence, no
sorting of tokens by expert, nothing imported from the program.  It makes
its own weights from the seed (``harness/weights_gdn_gqa_moe.py``), one
layer at a time, after the program's state is freed; one sequence at a
time, every sample padded to the cell's ``max_len`` where that is whole
query blocks, so that nothing compiles anew for a sample of another length.

Departures from the published code, each on purpose:

* This chip's SHARE of the deployment: of the router's ``E`` experts only
  ``held`` live here; the router, the top-k and the gates' normalisation
  run over all ``E``; chosen experts held elsewhere add nothing.  Embedding
  and head cover the held slice of the vocabulary.
* The columns inside ``W_qkvz``, ``W_ba`` and ``W_q`` lie as the
  configuration's file says (a loader's permutation of the checkpoint's).
* No multi-token-prediction block: the config has no key for one.
* What neither the config nor the code fixes for random weights (initial
  ``A_log`` and ``dt_bias``) is ASSUMED, in the weights' module and the
  configuration's file.

What it answers is what the other references answer (``served_gaps``,
``control_gaps``: the gap of a token's reference logit below the
reference's best, as a share of max |logit|).  The control precisions:
``int8`` / ``fp8`` round every linear layer's inputs and weights (router
and experts among them); ``bf16_state`` keeps the linear layers exact and
rounds the DeltaNet state to bfloat16 after every token (for information).
"""

from __future__ import annotations

import functools
import types

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax

from benchmark.harness import weights_gdn_gqa_moe as W

Q_BLOCK = 2048     # queries a score matrix holds: [Q_BLOCK, S] float32
AT_BLOCK = 512     # positions a block of logits holds: [AT_BLOCK, V] float32
L2_EPS = 1e-6


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
    """x [S, in] @ w [in, out] in float32; a control that rounds linear
    layers rounds both."""
    w = w.astype(jnp.float32)
    if quant in ("int8", "fp8"):
        x, w = _round_to(x, -1, quant), _round_to(w, 0, quant)
    return jnp.dot(x, w)


def _rmsnorm(x, w, eps):
    """The DeltaNet's output norm: the weight multiplies as it is."""
    return x * lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps) * w.astype(jnp.float32)


def _rmsnorm0(x, w, eps):
    """Every other norm of the model: zero-centred, ``* (1 + w)``."""
    return x * lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps) * (
        1.0 + w.astype(jnp.float32))


def _gdn(x, kw, wo, d, quant):
    """Gated DeltaNet of one sequence x [S, D], token by token."""
    S, Hk, Hv, dl, taps = x.shape[0], d["Hk"], d["Hv"], d["dl"], d["taps"]
    f32 = jnp.float32
    mixed = _linear(x, kw["w_qkvz"], quant)        # [q~ | k~ | v~ | z]
    wide = 2 * Hk * dl + Hv * dl
    padded = jnp.pad(mixed[:, :wide], ((taps - 1, 0), (0, 0)))
    conv = kw["conv"].astype(f32)
    y = jax.nn.silu(sum(padded[j:j + S] * conv[j] for j in range(taps)))
    q = y[:, :Hk * dl].reshape(S, Hk, dl)
    k = y[:, Hk * dl:2 * Hk * dl].reshape(S, Hk, dl)
    v = y[:, 2 * Hk * dl:].reshape(S, Hv, dl)
    z = mixed[:, wide:].reshape(S, Hv, dl)
    unit = lambda a: a * lax.rsqrt(jnp.sum(a * a, -1, keepdims=True) + L2_EPS)
    q, k = unit(q) * dl ** -0.5, unit(k)
    q, k = (jnp.repeat(a, Hv // Hk, axis=1) for a in (q, k))
    ba = _linear(x, kw["w_ba"], quant)                                # [S, 2 Hv]
    beta = jax.nn.sigmoid(ba[:, :Hv])
    g = -jnp.exp(kw["a_log"]) * jax.nn.softplus(ba[:, Hv:] + kw["dt_bias"])

    def token(s, xs):                                   # s [Hv, d_k, d_v]
        q, k, v, g, b = xs
        s = s * jnp.exp(g)[:, None, None]
        u = b[:, None] * (v - jnp.einsum("hkv,hk->hv", s, k))
        s = s + k[:, :, None] * u[:, None, :]
        if quant == "bf16_state":
            s = s.astype(jnp.bfloat16).astype(f32)
        return s, jnp.einsum("hkv,hk->hv", s, q)

    _s, o = lax.scan(token, jnp.zeros((Hv, dl, dl), f32), (q, k, v, g, beta))
    o = _rmsnorm(o, kw["o_norm"], d["eps"]) * jax.nn.silu(z)
    return _linear(o.reshape(S, -1), wo, quant)


def _rotate(a, d):
    """a [S, heads, hd]: the first ``rot`` values of each head rotated by
    position, split-half among themselves; the rest as they are."""
    rot = d["rot"]
    inv = d["theta"] ** (-jnp.arange(0, rot, 2, dtype=jnp.float32) / rot)
    ang = jnp.arange(a.shape[0], dtype=jnp.float32)[:, None] * inv
    c, s = jnp.cos(ang)[:, None, :], jnp.sin(ang)[:, None, :]
    a1, a2 = a[..., :rot // 2], a[..., rot // 2:rot]
    return jnp.concatenate([a1 * c - a2 * s, a1 * s + a2 * c, a[..., rot:]], -1)


def _attention(x, w, d, quant):
    """Gated grouped-query attention of one sequence x [S, D]."""
    S, Hq, Hkv, hd = x.shape[0], d["Hq"], d["Hkv"], d["hd"]
    qg = _linear(x, w["wq"], quant).reshape(S, Hq, 2 * hd)
    q, gate = qg[..., :hd], qg[..., hd:]
    k = _linear(x, w["wk"], quant).reshape(S, Hkv, hd)
    v = _linear(x, w["wv"], quant).reshape(S, Hkv, hd)
    q = _rotate(_rmsnorm0(q, w["q_head_norm"], d["eps"]), d)
    k = _rotate(_rmsnorm0(k, w["k_head_norm"], d["eps"]), d)
    blk = Q_BLOCK if S % Q_BLOCK == 0 else S
    kpos = jnp.arange(S)[None, :]

    def head(args):   # one query head, one block of queries at a time
        qh, h = args
        kh, vh = k[:, h // (Hq // Hkv)], v[:, h // (Hq // Hkv)]

        def block(args):
            qb, b = args
            keep = kpos <= b * blk + jnp.arange(blk)[:, None]
            s = jnp.dot(qb, kh.T) * hd ** -0.5
            return jnp.dot(jax.nn.softmax(jnp.where(keep, s, -jnp.inf), -1), vh)

        return lax.map(block, (qh.reshape(S // blk, blk, hd),
                               jnp.arange(S // blk))).reshape(S, hd)

    o = lax.map(head, (q.transpose(1, 0, 2), jnp.arange(Hq)))      # [Hq, S, hd]
    o = o.transpose(1, 0, 2) * jax.nn.sigmoid(gate)
    return _linear(o.reshape(S, -1), w["wo"], quant)


def _swiglu(x, w, quant):
    g = jax.nn.silu(_linear(x, w["w_gate"], quant)) * _linear(x, w["w_up"], quant)
    return _linear(g, w["w_down"], quant)


def route(x, rw, d, quant=None):
    """x [S, D] -> (probabilities [S, E], chosen [S, k], gates [S, k])."""
    p = jax.nn.softmax(_linear(x, rw["router"], quant), -1)
    g, idx = lax.top_k(p, d["top_k"])
    return p, idx, g / g.sum(-1, keepdims=True)


def routed_part(x, rw, d, quant=None):
    """The held experts' part of the routed result for x [S, D]: every held
    expert on every token, times the token's gate for it (0: not chosen)."""
    _p, idx, gates = route(x, rw, d, quant)

    def one(acc, ew_e):
        ew, e = ew_e
        gate = jnp.sum(jnp.where(idx == e, gates, 0.0), -1, keepdims=True)
        return acc + _swiglu(x, ew, quant) * gate, None

    held = d["first_held"] + jnp.arange(d["held"])
    return lax.scan(one, jnp.zeros_like(x), (
        {n: rw[n] for n in ("w_gate", "w_up", "w_down")}, held))[0]


def shared_part(x, rw, quant=None):
    """The shared expert under its own gate: what every chip adds alike."""
    return jax.nn.sigmoid(_linear(x, rw["shared_gate"], quant)) * _swiglu(
        x, rw["shared"], quant)


def _layer_one(h, w, d, quant):
    """One block on one sequence h [S, D]."""
    x = _rmsnorm0(h, w["attn_norm"], d["eps"])
    h = h + (_gdn(x, w["kda"], w["wo"], d, quant) if "kda" in w
             else _attention(x, w, d, quant))
    x = _rmsnorm0(h, w["mlp_norm"], d["eps"])
    return h + routed_part(x, w["routed"], d, quant) + shared_part(
        x, w["routed"], quant)


@functools.cache
def _programs(dkey: tuple, quant):
    d = dict(dkey)

    @jax.jit
    def embed(key, tokens):
        return W.outer_weights(key, d)["embed"].astype(jnp.float32)[tokens]

    @functools.partial(jax.jit, static_argnames=("linear",))
    def layer(key, i, h, linear):
        return _layer_one(h, W.layer_weights(key, i, d, linear), d, quant)

    @jax.jit
    def logits(key, h, at):
        """[len(at), V] at positions ``at`` of h [S, D]."""
        o = W.outer_weights(key, d)
        return _linear(_rmsnorm0(h[at], o["final_norm"], d["eps"]),
                       o["lm_head"], quant)

    @jax.jit
    def gaps(key, h, h_choice, at, chosen):
        """Per position of ``at``: (best - logit of the chosen token) / max
        |logit| under h; ``h_choice`` given: the token ITS logits (the
        control's, under its precision) put first.  Blocks of positions."""
        o = W.outer_weights(key, d)
        head = o["lm_head"].astype(jnp.float32)

        def block(args):
            a, c = args
            ref = jnp.dot(_rmsnorm0(h[a], o["final_norm"], d["eps"]), head)
            if h_choice is not None:
                c = jnp.argmax(_linear(_rmsnorm0(h_choice[a], o["final_norm"],
                                                 d["eps"]), o["lm_head"], quant), -1)
            got = jnp.take_along_axis(ref, c[:, None], -1)[:, 0]
            return ((ref.max(-1) - got) / jnp.abs(ref).max(-1),
                    jnp.isfinite(ref).all())

        blk = AT_BLOCK if at.shape[0] % AT_BLOCK == 0 else at.shape[0]
        gap, finite = lax.map(block, (at.reshape(-1, blk), chosen.reshape(-1, blk)))
        return gap.reshape(-1), finite.all()

    return types.SimpleNamespace(embed=embed, layer=layer, logits=logits, gaps=gaps)


def _of(config, quant=None):
    d = W.dims(config)
    return d, _programs(tuple(sorted(d.items())), quant)


@_highest
def hidden_states(config, seed, tokens, quant=None):
    """h [S, D] of ONE sequence after the last block."""
    d, run = _of(config, quant)
    key = W.base_key(seed)
    h = run.embed(key, jnp.asarray(tokens))
    for i in range(d["L"]):
        h = run.layer(key, jnp.int32(i), h, linear=d["linear"][i])
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
    ONE shape for every sample), else left at its own length (the CPU
    tests); and where served token j is predicted, position p + j - 1,
    padded to ``out_to`` rounded up to whole position blocks likewise.  A
    pad lies BEHIND every real position: causal attention and a causal
    recurrence never carry it back."""
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
