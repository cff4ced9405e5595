"""Plain reference of the ``phi4-mini-flash`` configuration:
Phi-4-mini-flash-reasoning (``model_type: phi4flash``; SambaY with
differential attention, arXiv 2507.06607; YOCO, arXiv 2405.05254;
Differential Transformer, arXiv 2410.05258; Mamba, arXiv 2312.00752) as its
``config.json`` and the papers give it, in straightforward ``jax.numpy``
float32 under ``jax.default_matmul_precision("highest")``.

D = 2560, H = 40 heads of d = 64, Hkv = 20, F = 10240, E = 2D = 5120, N = 16,
R = D / 16 = 160, 4 taps, window 512, 32 layers, vocabulary 200,064, tied,
NO positional encoding anywhere.  ``LN`` is LayerNorm with gain and bias,
eps 1e-5.  Layer ``l`` (0-based), input ``x``:

    h   = x + Mixer_l(LN1(x))
    out = h + W_2 (SiLU(g) * u),   [g | u] = LN2(h) W_1     (no bias)

and after the last layer a final ``LN``, then ``logits = h Emb^T`` (no
bias).  The mixer, by the layer's kind (the configuration's ``layout``):

``ssm`` (Mamba-1; l in {0, 2, .., 16}), on ``u = LN1(x)``, TOKEN BY TOKEN
(``lax.scan`` over positions):

    [s | z]   = u W_in                                  E wide each
    c_t       = SiLU(b_c + sum_j w_c[j] * s_{t-3+j})    depthwise, causal,
                                                        zeros before the start
    [r|B|C]   = c W_x                                   R | N | N
    dt        = softplus(r W_dt + b_dt)                 [E]
    S_t[e,n]  = exp(dt_t[e] A[e,n]) S_{t-1}[e,n] + dt_t[e] B_t[n] c_t[e]
    y_t[e]    = sum_n C_t[n] S_t[e,n] + D_skip[e] c_t[e]     A = -exp(A_log)
    mixer     = (y * SiLU(z)) W_out

and the LAST such layer also hands on ``m_t = y_t`` (before the gate): the
memory of the cross-decoder.

``gmu`` (l in {18, 20, .., 30}): ``(SiLU(u W_g) * m_t) W_o``, ``m_t`` the
memory of the SAME token.  It keeps nothing between tokens.

differential attention (``window``: odd l < 16, keys ``max(0, t - 511) ..
t``; ``full``: l = 17, keys ``0 .. t``; ``cross``: l in {19, .., 31}, keys
``0 .. t`` of LAYER 17's k and v; ``cross`` projects q only):

    [q|k|v]   = u W_qkv + b_qkv            (40 + 20 + 20) heads of 64
    pair j    : queries q_2j, q_2j+1; kv pair g = j // 2: keys k_2g, k_2g+1,
                value V_g = [v_2g | v_2g+1]                    (128 wide)
    P1, P2    = softmax(q_2j k_2g^T / 8), softmax(q_2j+1 k_2g+1^T / 8)
    o_j       = (P1 - lam_l P2) V_g
    o_j       = RMSNorm_128(o_j; w_sub, eps 1e-5) * (1 - lam0_l)
    mixer     = [o_0 | .. | o_19] W_o + b_o
    lam0_l    = 0.8 - 0.6 exp(-0.3 l)
    lam_l     = exp(lq1 . lk1) - exp(lq2 . lk2) + lam0_l

The two softmaxes of a pair are written out on the 64-wide heads, whole
[query block, S] score matrices; every layer runs over every position (no
early exit); no cache, no kernel, no chunked form of the recurrence, no
batching, nothing imported from the program.  It makes its own weights
from the seed (``harness/weights_ssm_yoco.py``), one layer at a time (15.4
GB of float32 do not fit whole), after the program's state is freed; one
sequence at a time, every sample padded to the cell's ``max_len`` where
that is whole query blocks, so that nothing compiles anew for a sample of
another length; logits in blocks at the served positions.

What neither the config nor the papers fix for random weights is ASSUMED,
in the weights' module and the configuration's file.  The weights' ``A_log``
lies ``[N, E]`` (the program's layout); here it is transposed back.

What it answers is what the other references answer (``served_gaps``,
``control_gaps``: the gap of a token's reference logit below the
reference's best, as a share of max |logit|).  The control precisions:
``int8`` / ``fp8`` round every linear layer's inputs and weights;
``bf16_state`` keeps the linear layers exact and rounds the Mamba state to
bfloat16 after every token (for information).
"""

from __future__ import annotations

import functools
import types

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax

from benchmark.harness import weights_ssm_yoco as W

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


def _linear(x, w, quant, b=None):
    """x [S, in] @ w [in, out] (+ b) in float32; a control that rounds
    linear layers rounds both operands."""
    w = w.astype(jnp.float32)
    if quant in ("int8", "fp8"):
        x, w = _round_to(x, -1, quant), _round_to(w, 0, quant)
    y = jnp.dot(x, w)
    return y if b is None else y + b.astype(jnp.float32)


def _layernorm(x, w, eps):
    """``w [2, D]``: the gain over the bias."""
    w = w.astype(jnp.float32)
    xc = x - jnp.mean(x, -1, keepdims=True)
    return xc * lax.rsqrt(jnp.mean(xc * xc, -1, keepdims=True) + eps) * w[0] + w[1]


def _mamba(u, w, wo, d, quant):
    """Mamba-1 of one sequence u [S, D], token by token: (mixer output,
    the scan's output before the gate)."""
    S, E, N, R, taps = u.shape[0], d["E"], d["N"], d["R"], d["taps"]
    f32 = jnp.float32
    sz = _linear(u, w["w_in"], quant)
    s, z = sz[:, :E], sz[:, E:]
    padded = jnp.pad(s, ((taps - 1, 0), (0, 0)))
    conv = w["conv_w"].astype(f32)
    c = jax.nn.silu(w["conv_b"].astype(f32)
                    + sum(padded[j:j + S] * conv[j] for j in range(taps)))
    rbc = _linear(c, w["w_x"], quant)
    r, bm, cm = rbc[:, :R], rbc[:, R:R + N], rbc[:, R + N:]
    dt = jax.nn.softplus(_linear(r, w["w_dt"], quant) + w["b_dt"])
    a = -jnp.exp(w["a_log"]).T                                      # [E, N]

    def token(state, xs):                                           # [E, N]
        dt, b, cc, x = xs
        state = jnp.exp(dt[:, None] * a) * state + (dt * x)[:, None] * b[None, :]
        if quant == "bf16_state":
            state = state.astype(jnp.bfloat16).astype(f32)
        return state, jnp.dot(state, cc) + w["d_skip"] * x

    _s, y = lax.scan(token, jnp.zeros((E, N), f32), (dt, bm, cm, c))
    return _linear(y * jax.nn.silu(z), wo, quant), y


def _project_kv(u, w, d, quant):
    """k, v [S, Hkv, hd] of one sequence."""
    S = u.shape[0]
    return (_linear(u, w["wk"], quant, w["bk"]).reshape(S, d["Hkv"], d["hd"]),
            _linear(u, w["wv"], quant, w["bv"]).reshape(S, d["Hkv"], d["hd"]))


def _diff_attention(u, w, i, k, v, d, quant, window=None):
    """Differential attention of one sequence u [S, D] over the keys and
    values k, v [S, Hkv, hd] (its own, or for a cross layer the full
    layer's), layer index ``i`` (for ``lam0``)."""
    S, H, Hkv, hd = u.shape[0], d["H"], d["Hkv"], d["hd"]
    f32 = jnp.float32
    q = _linear(u, w["wq"], quant, w["bq"]).reshape(S, H // 2, 2, hd)
    q = q.transpose(1, 2, 0, 3)                              # [H/2, 2, S, hd]
    kk = k.reshape(S, Hkv // 2, 2, hd).transpose(1, 2, 0, 3)  # [Hkv/2, 2, S, hd]
    vv = v.reshape(S, Hkv // 2, 2 * hd).transpose(1, 0, 2)    # [Hkv/2, S, 2hd]
    rep = (H // 2) // (Hkv // 2)
    lam0 = W.lambda_init(i)
    dot = lambda a, b: jnp.sum(w[a].astype(f32) * w[b].astype(f32))
    lam = jnp.exp(dot("lam_q1", "lam_k1")) - jnp.exp(dot("lam_q2", "lam_k2")) + lam0
    blk = Q_BLOCK if S % Q_BLOCK == 0 else S
    kpos = jnp.arange(S)[None, :]

    def pair(args):   # one differential head, one block of queries at a time
        qj, j = args
        kg, vg = kk[j // rep], vv[j // rep]

        def block(args):
            qb, b = args                                     # [2, blk, hd]
            qpos = b * blk + jnp.arange(blk)[:, None]
            keep = kpos <= qpos
            if window is not None:
                keep = keep & (kpos > qpos - window)
            p = [jax.nn.softmax(jnp.where(
                keep, jnp.dot(qb[t], kg[t].T) * hd ** -0.5, -jnp.inf), -1)
                for t in (0, 1)]
            return jnp.dot(p[0] - lam * p[1], vg)

        return lax.map(block, (qj.reshape(2, S // blk, blk, hd).transpose(1, 0, 2, 3),
                               jnp.arange(S // blk))).reshape(S, 2 * hd)

    o = lax.map(pair, (q, jnp.arange(H // 2)))                # [H/2, S, 2hd]
    o = o * lax.rsqrt(jnp.mean(o * o, -1, keepdims=True) + d["eps"])
    o = o * w["sub_norm"].astype(f32) * (1.0 - lam0)
    return _linear(o.transpose(1, 0, 2).reshape(S, -1), w["wo"], quant, w["bo"])


def _mlp(x, w, quant):
    g = jax.nn.silu(_linear(x, w["w_gate"], quant)) * _linear(x, w["w_up"], quant)
    return _linear(g, w["w_down"], quant)


def _layer_one(h, mem, k, v, w, i, kind, d, quant):
    """One block of ``kind`` on one sequence h [S, D]; ``mem`` the memory
    and ``k`` / ``v`` the full layer's keys and values as they stand."""
    u = _layernorm(h, w["attn_norm"], d["eps"])
    if kind == "ssm":
        mix, mem = _mamba(u, w["ssm"], w["wo"], d, quant)
    elif kind == "gmu":
        mix = _linear(jax.nn.silu(_linear(u, w["gmu_in"], quant)) * mem,
                      w["wo"], quant)
    elif kind == "cross":
        mix = _diff_attention(u, w, i, k, v, d, quant)
    else:
        mine = _project_kv(u, w, d, quant)
        mix = _diff_attention(u, w, i, *mine, d, quant,
                              window=d["window"] if kind == "window" else None)
        if kind == "full":
            k, v = mine
    h = h + mix
    return h + _mlp(_layernorm(h, w["mlp_norm"], d["eps"]), w, quant), mem, k, v


@functools.cache
def _programs(dkey: tuple, quant):
    d = dict(dkey)

    @jax.jit
    def embed(key, tokens):
        return W.outer_weights(key, d)["embed"].astype(jnp.float32)[tokens]

    @functools.partial(jax.jit, static_argnames=("kind",))
    def layer(key, i, h, mem, k, v, kind):
        return _layer_one(h, mem, k, v, W.layer_weights(key, i, d, kind), i,
                          kind, d, quant)

    def tail(o, h, q):
        """Final LayerNorm and the tied head: ``h Emb^T``."""
        return _linear(_layernorm(h, o["final_norm"], d["eps"]),
                       o["embed"].astype(jnp.float32).T, q)

    @jax.jit
    def logits(key, h, at):
        """[len(at), V] at positions ``at`` of h [S, D]."""
        return tail(W.outer_weights(key, d), h[at], quant)

    @jax.jit
    def gaps(key, h, h_choice, at, chosen):
        """Per position of ``at``: (best - logit of the chosen token) / max
        |logit| under h; ``h_choice`` given: the token ITS logits (the
        control's, under its precision) put first.  Blocks of positions."""
        o = W.outer_weights(key, d)

        def block(args):
            a, c = args
            ref = tail(o, h[a], None)
            if h_choice is not None:
                c = jnp.argmax(tail(o, h_choice[a], quant), -1)
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
    """h [S, D] of ONE sequence after the last block: every layer over
    every position."""
    d, run = _of(config, quant)
    key = W.base_key(seed)
    h = run.embed(key, jnp.asarray(tokens))
    S = h.shape[0]
    mem = jnp.zeros((S, d["E"]), jnp.float32)
    k = v = jnp.zeros((S, d["Hkv"], d["hd"]), jnp.float32)
    for i, kind in enumerate(d["kinds"]):
        h, mem, k, v = run.layer(key, jnp.int32(i), h, mem, k, v, kind=kind)
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
