"""The gated delta rule with a decay a channel (Kimi Delta Attention, KDA:
arXiv 2510.26692) or a decay a head (Gated DeltaNet, Qwen3-Next: every
channel of a head decays alike), in the two forms a served model runs.  A
head's state is a matrix ``S [d_k, d_v]`` in float32 and one token moves it
by

    S' = diag(exp(g_t)) S          g_t <= 0: a log-decay a channel of d_k
    S  = S' + beta_t k_t (v_t - S'^T k_t)^T
    o_t = S^T q_t

* :func:`kda_step` (decode): one token a row.  The kernel ``sw_kda_step``
  reads a (row, layer)'s state once out of the STACKED leaf ``[L, B, H,
  d_k, d_v]``, applies decay, delta update and read-out and writes it back
  where it came from (the output aliases the leaf: no second copy of a
  multi-GB state, ever; the layer is a prefetched scalar, the leaf is never
  sliced).  :func:`kda_step_lax` is its twin.
* :func:`kda_chunk` (prefill): a whole prompt in chunks of ``chunk``
  positions.  With ``G`` the running sum of ``g`` inside a chunk and ``S0``
  the state before it, the pseudo-values ``u_i = beta_i (v_i - S'_i^T
  k_i)`` solve ``(I + A) U = beta V - beta K+ S0`` with ``A[i, j] = beta_i
  sum_d k_i k_j exp(G_i - G_j)`` for ``j < i`` (a unit lower-triangular
  system, solved in blocks of rows: :func:`_solve_unit_lower`), the outputs are ``O = Q+
  S0 + P U`` with ``P[i, j] = sum_d q_i k_j exp(G_i - G_j)`` for ``j <=
  i``, and the chunk leaves ``S = diag(exp(G_C)) S0 + (K exp(G_C - G))^T
  U`` behind (``K+``, ``Q+``: rows times ``exp(G)``).  Every exponent is a
  decay BETWEEN two positions of the chunk, never above 0: nothing
  overflows however strong the decay (the factored form ``exp(G_i)
  exp(-G_j)`` does; :func:`_pairwise` says how the decays are taken; with
  ONE decay a head they are a ``[C, C]`` matrix a head and ``A``, ``P``
  plain matmuls times it: :func:`_pairwise_head`).  What is the same for
  every chunk (``A``, ``P``, the
  solve against ``beta V`` and ``beta K+``) is computed for all chunks in
  plain lax; the part that carries ``S`` from chunk to chunk, three
  matmuls and an update a chunk, is the kernel ``sw_kda_chunk``
  (:func:`kda_chunk_carry`; twin :func:`kda_chunk_carry_lax`).  A position
  with ``g = 0`` and ``beta = 0`` leaves the state as it was: that is how
  a padded bucket's pads are told to stand still.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax import lax
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from . import dispatch

HI = lax.Precision.HIGHEST


# ----------------------------------------------------------------- one step


def kda_step_lax(state, q, k, v, g, beta, *, layer):
    """:func:`kda_step` in plain lax: what runs where Pallas does not, and
    what the kernel is tested against."""
    s = lax.dynamic_index_in_dim(state, layer, 0, keepdims=False)
    s = s * jnp.exp(g)[..., None]
    pred = jnp.einsum("bhkv,bhk->bhv", s, k, precision=HI)
    u = beta[..., None] * (v - pred)
    s = s + k[..., None] * u[..., None, :]
    o = jnp.einsum("bhkv,bhk->bhv", s, q, precision=HI)
    return o, lax.dynamic_update_index_in_dim(state, s, layer, 0)


def _kda_step_kernel(layer_ref, qt_ref, kt_ref, at_ref, v_ref, b_ref, s_ref,
                     o_ref, s_out, *, heads: int):
    """One grid cell a (row, block of heads).  ``qt`` / ``kt`` / ``at``
    arrive transposed, ``[d_k, heads]``: a head's q, k and decay are
    COLUMNS, which broadcast along the lanes of its ``[d_k, d_v]`` state;
    v, beta and the output are rows."""
    del layer_ref
    for h in range(heads):
        a = at_ref[0, 0, :, h:h + 1]                      # [d_k, 1]
        kc = kt_ref[0, 0, :, h:h + 1]
        qc = qt_ref[0, 0, :, h:h + 1]
        s = s_ref[0, 0, h] * a                            # decay
        pred = jnp.sum(s * kc, axis=0, keepdims=True)     # [1, d_v]
        u = b_ref[0, h:h + 1, :] * (v_ref[0, h:h + 1, :] - pred)
        s = s + kc * u                                    # delta update
        o_ref[0, h:h + 1, :] = jnp.sum(s * qc, axis=0, keepdims=True)
        s_out[0, 0, h] = s


def _head_block(h: int) -> "int | None":
    """Heads a grid cell of :func:`kda_step_kernel` takes: 16 ``[128, 128]``
    float32 states are 1 MiB, 4 MiB with the pipeline's two buffers each
    way."""
    if h <= 16:
        return h
    return next((b for b in (16, 8) if h % b == 0), None)


def kda_step_kernel(state, q, k, v, g, beta, *, layer, interpret=None):
    """:func:`kda_step` as the Pallas kernel ``sw_kda_step``."""
    _layers, b, h, dk, dv = state.shape
    if interpret is None:
        interpret = jax.default_backend() != "tpu"
    hb = _head_block(h)
    f32 = jnp.float32

    def cols(x):   # [B, H, d_k] -> [B, H / hb, d_k, hb]
        return x.astype(f32).reshape(b, h // hb, hb, dk).transpose(0, 1, 3, 2)

    col_spec = pl.BlockSpec((1, 1, dk, hb), lambda i, j, *_: (i, j, 0, 0))
    row_spec = pl.BlockSpec((1, hb, dv), lambda i, j, *_: (i, j, 0))
    s_spec = pl.BlockSpec((1, 1, hb, dk, dv),
                          lambda i, j, layer: (layer[0], i, j, 0, 0))
    o, state = pl.pallas_call(
        functools.partial(_kda_step_kernel, heads=hb),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1,
            grid=(b, h // hb),
            in_specs=[col_spec, col_spec, col_spec, row_spec, row_spec, s_spec],
            out_specs=[row_spec, s_spec],
        ),
        out_shape=[jax.ShapeDtypeStruct((b, h, dv), f32),
                   jax.ShapeDtypeStruct(state.shape, state.dtype)],
        input_output_aliases={6: 1},   # the state: read and written in place
        interpret=interpret,
        name="sw_kda_step",
    )(jnp.asarray(layer, jnp.int32).reshape(1), cols(q), cols(k),
      cols(jnp.exp(g)), v.astype(f32),
      jnp.broadcast_to(beta.astype(f32)[..., None], (b, h, dv)), state)
    return o, state


def kda_step(state, q, k, v, g, beta, *, layer):
    """One token of every row through the recurrence, the operation.
    state: the stacked leaf ``[L, B, H, d_k, d_v]`` float32, ``layer`` a
    (traced) scalar; q, k ``[B, H, d_k]``, v ``[B, H, d_v]``, g ``[B, H,
    d_k]`` (log-decay), beta ``[B, H]``.  Returns ``(o [B, H, d_v] float32,
    state)``, the state of ``layer`` replaced.  On a TPU the kernel
    (in place), elsewhere and at sizes the chip cannot tile the lax
    twin."""
    _l, _b, h, dk, dv = state.shape
    if (dispatch.use_kernels() and dk % 128 == 0 and dv % 128 == 0
            and _head_block(h) is not None):
        return kda_step_kernel(state, q, k, v, g, beta, layer=layer)
    return kda_step_lax(state, q, k, v, g, beta, layer=layer)


# ------------------------------------------------------------ a whole prompt


def _chunked(x, chunk: int):
    """[B, H, S, ...] -> [B, H, S / chunk, chunk, ...]."""
    return x.reshape(x.shape[:2] + (x.shape[2] // chunk, chunk) + x.shape[3:])


SUB = 16   # rows of a sub-chunk: the blocks whose decays are taken pairwise


def _pairwise(q, k, gc, beta):
    """``A`` and ``P`` of the module docstring for a batch of chunks:
    q, k, gc ``[..., C, d_k]`` (gc the running sum of g), beta ``[..., C]``
    -> ``(A [..., C, C]`` strictly lower, ``P [..., C, C]`` lower), both
    from ONE sum over d_k (``beta k`` and ``q`` stacked as its two left
    sides).  Two levels.  Inside a sub-chunk of ``SUB`` rows the decay
    between two positions is taken as it is, ``exp(G_i - G_j)`` a channel:
    ``[SUB, SUB, d_k]`` exponentials a block.  Between sub-chunks I > J it
    is split at their borders, ``exp(G_i - s_I) exp(s_I - e_J) exp(e_J -
    G_j)`` (``s_I``: G before sub-chunk I's first row, ``e_J``: G at J's
    last), three factors that are each a decay, never above 0 in the
    exponent, so the block is a matmul over d_k of rows scaled by the
    first two with rows scaled by the third.  A chunk of at most ``SUB``
    rows is one block."""
    c_all, dk = q.shape[-2:]
    c = SUB if c_all % SUB == 0 else c_all
    m = c_all // c
    lead = q.shape[:-2]
    qs, ks, gs = (x.reshape(lead + (m, c, dk)) for x in (q, k, gc))
    left = jnp.stack([ks * beta.reshape(lead + (m, c, 1)), qs], -4)
    i, j = jnp.arange(c)[:, None], jnp.arange(c)[None, :]
    decay = jnp.exp(jnp.where((i >= j)[..., None],
                              gs[..., :, None, :] - gs[..., None, :, :],
                              -jnp.inf))                # [..., m, c, c, d_k]
    diag = jnp.sum(left[..., :, :, None, :]
                   * (ks[..., None, :, :] * decay)[..., None, :, :, :, :], -1)
    same = jnp.eye(m, dtype=bool)[:, None, :, None]      # [I, 1, J, 1]
    both = jnp.where(same, diag[..., :, :, None, :], 0.0)  # [.., 2, I, i, J, j]
    if m > 1:
        end = gs[..., -1, :]                             # e_J [..., m, d_k]
        start = jnp.concatenate(
            [jnp.zeros_like(end[..., :1, :]), end[..., :-1, :]], -2)   # s_I
        above = (jnp.arange(m)[:, None] > jnp.arange(m)[None, :])[..., None]
        mid = jnp.exp(jnp.where(above, start[..., :, None, :]
                                - end[..., None, :, :], -jnp.inf))
        lm = ((left * jnp.exp(gs - start[..., None, :])[..., None, :, :, :])
              [..., :, :, None, :] * mid[..., None, :, None, :, :])
        both = both + jnp.einsum(
            "...sIiJd,...Jjd->...sIiJj", lm,
            ks * jnp.exp(end[..., None, :] - gs), precision=HI)
    both = both.reshape(lead + (2, c_all, c_all))
    rows, cols = jnp.arange(c_all)[:, None], jnp.arange(c_all)[None, :]
    return jnp.where(rows > cols, both[..., 0, :, :], 0.0), both[..., 1, :, :]


def _pairwise_head(q, k, gc, beta):
    """:func:`_pairwise` where a head has ONE decay: gc ``[..., C]``.  The
    decay between two positions is then a number, ``exp(G_i - G_j)`` a
    ``[C, C]`` matrix a head (every exponent at most 0), and ``A`` and
    ``P`` are ``K K^T`` and ``Q K^T`` times it: two matmuls over d_k, no
    ``[C, C, d_k]`` array and no sub-chunks."""
    c = q.shape[-2]
    i, j = jnp.arange(c)[:, None], jnp.arange(c)[None, :]
    decay = jnp.exp(jnp.where(i >= j, gc[..., :, None] - gc[..., None, :],
                              -jnp.inf))
    kk = jnp.einsum("...id,...jd->...ij", k * beta[..., None], k, precision=HI)
    qk = jnp.einsum("...id,...jd->...ij", q, k, precision=HI)
    return jnp.where(i > j, kk * decay, 0.0), qk * decay


def _solve_unit_lower(a, rhs):
    """``(I + a)^{-1} rhs`` for strictly lower ``a [..., C, C]``, in blocks
    of ``SUB`` rows.  A diagonal block ``d`` is nilpotent (``d^SUB = 0``),
    so its inverse is the finite product ``(I - d)(I + d^2)(I + d^4) ...``:
    a few small matmuls for all blocks of all chunks at once, where
    substitution row by row is ``C`` dependent steps of a handful of
    operations each, most of an admission's launches.  Then the blocks in
    order: a block's right-hand side loses what the blocks above it give
    (one matmul) and meets its block's inverse (another)."""
    c = a.shape[-1]
    sub = SUB if c % SUB == 0 else c
    m = c // sub
    mm = functools.partial(jnp.matmul, precision=HI)
    eye = jnp.eye(sub, dtype=a.dtype)
    blocks = a.reshape(a.shape[:-2] + (m, sub, m, sub))
    d = jnp.stack([blocks[..., i, :, i, :] for i in range(m)], -3)
    inv, power, n = eye - d, d, 2
    while n < sub:                       # (I + d^2)(I + d^4) ... up to d^(sub/2)
        power = mm(power, power)
        inv = mm(inv, eye + power)
        n *= 2
    done = []
    for i in range(m):
        r = rhs[..., i * sub:(i + 1) * sub, :]
        if done:
            r = r - mm(a[..., i * sub:(i + 1) * sub, :i * sub],
                       jnp.concatenate(done, -2))
        done.append(mm(inv[..., i, :, :], r))
    return jnp.concatenate(done, -2)


def kda_chunk_carry_lax(qp, w, ut, p, ktail, decay):
    """:func:`kda_chunk_carry` in plain lax: a scan over the chunks."""
    b, h, _n, _c, dk = qp.shape
    dv = ut.shape[-1]

    def step(s, x):
        qp, w, ut, p, ktail, decay = x
        u = ut - jnp.einsum("bhck,bhkv->bhcv", w, s, precision=HI)
        o = (jnp.einsum("bhck,bhkv->bhcv", qp, s, precision=HI)
             + jnp.einsum("bhij,bhjv->bhiv", p, u, precision=HI))
        s = s * decay[..., 0, :, None] + jnp.einsum(
            "bhck,bhcv->bhkv", ktail, u, precision=HI)
        return s, o

    xs = tuple(jnp.moveaxis(x, 2, 0) for x in (qp, w, ut, p, ktail, decay))
    s, o = lax.scan(step, jnp.zeros((b, h, dk, dv), jnp.float32), xs)
    return jnp.moveaxis(o, 0, 2), s


def _kda_chunk_kernel(qp_ref, w_ref, ut_ref, p_ref, kt_ref, d_ref, o_ref,
                      st_ref, st_scr):
    """One grid cell a (row, head, chunk), the chunks in order: the state
    rides ``st_scr`` TRANSPOSED, ``[d_v, d_k]``, so that the decay a
    channel of d_k is a row."""
    n = pl.program_id(2)

    @pl.when(n == 0)
    def _start():
        st_scr[:] = jnp.zeros_like(st_scr)

    def dot(a, b, dims):
        return lax.dot_general(a, b, (dims, ((), ())), precision=HI,
                               preferred_element_type=jnp.float32)

    st = st_scr[:]
    u = ut_ref[0, 0, 0] - dot(w_ref[0, 0, 0], st, ((1,), (1,)))      # [C, d_v]
    o_ref[0, 0, 0] = (dot(qp_ref[0, 0, 0], st, ((1,), (1,)))
                      + dot(p_ref[0, 0, 0], u, ((1,), (0,))))
    st = st * d_ref[0, 0, 0] + dot(u, kt_ref[0, 0, 0], ((0,), (0,)))
    st_scr[:] = st

    @pl.when(n == pl.num_programs(2) - 1)
    def _end():
        st_ref[0, 0] = st


def kda_chunk_carry_kernel(qp, w, ut, p, ktail, decay, *, interpret=None):
    """:func:`kda_chunk_carry` as the Pallas kernel ``sw_kda_chunk``."""
    b, h, n, c, dk = qp.shape
    dv = ut.shape[-1]
    if interpret is None:
        interpret = jax.default_backend() != "tpu"

    def spec(rows, width):
        return pl.BlockSpec((1, 1, 1, rows, width),
                            lambda i, j, m: (i, j, m, 0, 0))

    o, st = pl.pallas_call(
        _kda_chunk_kernel,
        grid=(b, h, n),
        in_specs=[spec(c, dk), spec(c, dk), spec(c, dv), spec(c, c),
                  spec(c, dk), spec(1, dk)],
        out_specs=[spec(c, dv),
                   pl.BlockSpec((1, 1, dv, dk), lambda i, j, m: (i, j, 0, 0))],
        out_shape=[jax.ShapeDtypeStruct((b, h, n, c, dv), jnp.float32),
                   jax.ShapeDtypeStruct((b, h, dv, dk), jnp.float32)],
        scratch_shapes=[pltpu.VMEM((dv, dk), jnp.float32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary")),
        interpret=interpret,
        name="sw_kda_chunk",
    )(qp, w, ut, p, ktail, decay)
    return o, jnp.swapaxes(st, -1, -2)


def kda_chunk_carry(qp, w, ut, p, ktail, decay):
    """The part of :func:`kda_chunk` that goes from chunk to chunk, from a
    zero state.  Per (row, head, chunk): ``qp = Q exp(G)`` and ``w = (I +
    A)^{-1} beta K exp(G)`` ``[C, d_k]``, ``ut = (I + A)^{-1} beta V [C,
    d_v]``, ``p [C, C]``, ``ktail = K exp(G_C - G) [C, d_k]``, ``decay =
    exp(G_C) [1, d_k]``; all ``[B, H, N, ...]`` float32.  In chunk order,
    ``U = ut - w S``; ``O = qp S + p U``; ``S = diag(decay) S + ktail^T
    U``.  Returns ``(O [B, H, N, C, d_v], S [B, H, d_k, d_v])``."""
    c, dk = qp.shape[-2:]
    if (dispatch.use_kernels() and dk % 128 == 0 and ut.shape[-1] % 128 == 0
            and c % 8 == 0):
        return kda_chunk_carry_kernel(qp, w, ut, p, ktail, decay)
    return kda_chunk_carry_lax(qp, w, ut, p, ktail, decay)


def kda_chunk(q, k, v, g, beta, *, chunk: int = 64):
    """A whole prompt through the recurrence, chunk by chunk, from a zero
    state, the operation.  q, k ``[B, H, S, d_k]``, v ``[B, H, S, d_v]``,
    g ``[B, H, S, d_k]`` (a log-decay a channel) or ``[B, H, S]`` (one a
    head), beta ``[B, H, S]``; a position with ``g = 0`` and ``beta = 0``
    does not move the state (a bucket's pads).  Returns ``(o [B, H, S,
    d_v] float32, state [B, H, d_k, d_v] float32)``: every position's
    read-out and the state after the last.  S is padded to whole chunks
    with such standing positions."""
    f32 = jnp.float32
    s = q.shape[2]
    by_head = g.ndim == 3
    if by_head:
        g = g[..., None]          # [B, H, S, 1]: broadcasts against d_k
    pad = -s % chunk
    if pad:
        q, k, v, g = (jnp.pad(x, ((0, 0), (0, 0), (0, pad), (0, 0)))
                      for x in (q, k, v, g))
        beta = jnp.pad(beta, ((0, 0), (0, 0), (0, pad)))
    q, k, v, g, beta = (_chunked(x.astype(f32), chunk)
                        for x in (q, k, v, g, beta))
    gc = jnp.cumsum(g, axis=-2)                          # [B, H, N, C, d_k]
    if by_head:
        a, p = _pairwise_head(q, k, gc[..., 0], beta)
    else:
        # A chunk at a time: [B, H, C, C, d_k] of decays is 64 MiB at 32
        # heads.
        a, p = lax.map(lambda x: _pairwise(*x), tuple(
            jnp.moveaxis(x, 2, 0) for x in (q, k, gc, beta)))
        a, p = jnp.moveaxis(a, 0, 2), jnp.moveaxis(p, 0, 2)
    grow = jnp.exp(gc)
    dv = v.shape[-1]
    solved = _solve_unit_lower(a, jnp.concatenate(
        [v, k * grow], -1) * beta[..., None])
    total = gc[..., -1:, :]                              # G at the chunk's end
    decay = jnp.exp(total)
    if by_head:   # the carry takes a decay a channel: every channel alike
        decay = jnp.broadcast_to(decay, total.shape[:-1] + k.shape[-1:])
    o, state = kda_chunk_carry(q * grow, solved[..., dv:], solved[..., :dv],
                               p, k * jnp.exp(total - gc), decay)
    o = o.reshape(o.shape[:2] + (-1, dv))
    return o[:, :, :s], state
