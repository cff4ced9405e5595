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
  system), the outputs are ``O = Q+ S0 + P U`` with ``P[i, j] = sum_d q_i
  k_j exp(G_i - G_j)`` for ``j <= i``, and the chunk leaves ``S =
  diag(exp(G_C)) S0 + (K exp(G_C - G))^T U`` behind (``K+``, ``Q+``: rows
  times ``exp(G)``).  Every exponent is a decay BETWEEN two positions of
  the chunk, never above 0: nothing overflows however strong the decay
  (the factored form ``exp(G_i) exp(-G_j)`` does).

  ONE kernel, ``sw_kda_chunk`` (:func:`kda_chunk_kernel`), walks the
  chunks: a grid cell is a (row, head, chunk), the chunks in order, and
  reads the chunk's q, k, v, log-decays and beta where the projections
  left them (a head a 128-lane column block of ``[B, S, H * d]``; a key
  head is read by each value head over it) and writes the chunk's outputs
  the same way.  ``K+``, ``Q+``, ``K exp(G_C - G)``, the pseudo-values and
  the state (VMEM scratch, written out after the last chunk) never exist
  in HBM.  float32, every matmul at ``HIGHEST``.  What a cell is bound by
  is the NUMBER of its matmuls (a float32 product at ``HIGHEST`` is six
  passes that each latch a whole weight tile: 0.13-0.19 us however small
  its operands, where XLA batches the same product over every cell of a
  call for a fraction of that), so the work is split by what was measured
  (PERF.md section 6, PR 43):

  - a decay a CHANNEL builds everything in the cell.  ``G`` is a doubling
    scan of sublane rolls.  ``A`` and ``P`` come level by level: the chunk
    halves ``log2 C`` times; at a level every pair of sibling blocks
    splits the decay between its second block's row i and its first
    block's row j at the pair's middle, ``exp(G_i - G_mid) exp(G_mid -
    G_j)``, both factors decays (sums of log-decays inside a block of
    four, differences of ``G`` with the middle's above it), so the level
    is ONE matmul over d_k of rows scaled by their own factor (``K_l [K_l
    | Q_l]^T``: ``A^T`` and ``P^T`` side by side), masked to the sibling
    pairs.  The levels' masks tile ``j < i`` exactly once; the diagonal of
    ``P`` is ``q_i k_i``; no ``[SUB, SUB, d_k]`` block of exponentials is
    built and no factor is above 1.  The solve (:func:`_solve_in_cell`)
    goes in blocks of ``SUB`` rows: a block loses what the rows above it
    give in one matmul and solves itself by forward substitution on the
    VPU (fifteen multiply-subtracts of a row: exact, where the twin's
    nilpotent product cancels); as nine chained ``[C, C]`` matmuls the
    same solve was 1.4 of a cell's 3.0 us;
  - a decay a HEAD takes ``A``, ``P`` and ``(I + A)^{-1}`` from lax
    (:func:`_pairwise_head` and :func:`_solve_unit_lower` against the
    identity: two matmuls over the key heads and a solve batched over
    every chunk at once) as two ``[C, C]`` operands a cell: built in the
    cell they made it 2.6 times slower than the lax form they replaced;
  - both then run the carry: ``U = (I + A)^{-1} beta (V - K+ S0)``, ``O =
    Q+ S0 + P U``, ``S = diag(exp(G_C)) S0 + (K exp(G_C - G))^T U``
    (``[K+ ; Q+] S0`` one matmul).

  :func:`kda_chunk_lax` is the twin, what runs off the chip and at shapes
  the kernel does not tile: ``A`` and ``P`` for all chunks at once
  (:func:`_pairwise`: exact ``[SUB, SUB, d_k]`` decays inside a sub-chunk,
  a three-factor split at the borders between them; :func:`_pairwise_head`
  for a decay a head), the blocked solve (:func:`_solve_unit_lower`) and a
  scan that carries the state (:func:`kda_chunk_carry_lax`).  A position
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
        interpret = dispatch.interpret()
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


SUB = 16   # rows of a sub-chunk: the solve's blocks, and the twin's exact decays


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
    """:func:`_pairwise` where a head has ONE decay: gc ``[..., H, C]``.
    The decay between two positions is then a number, ``exp(G_i - G_j)`` a
    ``[C, C]`` matrix a head (every exponent at most 0), and ``A`` and
    ``P`` are ``K K^T`` and ``Q K^T`` times it: two matmuls over d_k, no
    ``[C, C, d_k]`` array and no sub-chunks.  q, k ``[..., Hk, C, d_k]``:
    where the axis in front of C is shorter than gc's (key heads under
    value heads) a key head's products serve each value head over it."""
    c = q.shape[-2]
    rep = gc.shape[-2] // q.shape[-3]
    i, j = jnp.arange(c)[:, None], jnp.arange(c)[None, :]
    decay = jnp.exp(jnp.where(i >= j, gc[..., :, None] - gc[..., None, :],
                              -jnp.inf))
    kk = jnp.einsum("...id,...jd->...ij", k, k, precision=HI)
    qk = jnp.einsum("...id,...jd->...ij", q, k, precision=HI)
    if rep > 1:
        kk, qk = jnp.repeat(kk, rep, axis=-3), jnp.repeat(qk, rep, axis=-3)
    return (jnp.where(i > j, kk * decay, 0.0) * beta[..., None], qk * decay)


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
    """The part of :func:`kda_chunk_lax` that goes from chunk to chunk, from
    a zero state: a scan over the chunks.  Per (row, head, chunk): ``qp = Q
    exp(G)`` and ``w = (I + A)^{-1} beta K exp(G)`` ``[C, d_k]``, ``ut = (I
    + A)^{-1} beta V [C, d_v]``, ``p [C, C]``, ``ktail = K exp(G_C - G) [C,
    d_k]``, ``decay = exp(G_C) [1, d_k]``; all ``[B, H, N, ...]`` float32.
    In chunk order, ``U = ut - w S``; ``O = qp S + p U``; ``S = diag(decay)
    S + ktail^T U``.  Returns ``(O [B, H, N, C, d_v], S [B, H, d_k,
    d_v])``."""
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


def _dot(a, b, dims=((1,), (0,))):
    """A float32 matmul of a cell at ``HIGHEST``; ``dims``: the contracting
    dimensions of the two operands."""
    return lax.dot_general(a, b, (dims, ((), ())), precision=HI,
                           preferred_element_type=jnp.float32)


def _solve_in_cell(a, r, sub):
    """``(I + a)^{-1} r`` for strictly lower ``a [C, C]`` in blocks of
    ``sub`` rows, left-looking: a block's rows first lose what the rows
    above the block give (ONE matmul of ``sub`` rows against the answer so
    far; ``a`` masked to the columns left of the block, so what the
    unfinished rows hold does not matter), then the block solves itself by
    forward substitution on the VPU, row by row (a row that stands is
    taken from the rows under it, ``a[i, j]`` times: ``a`` is zero on and
    above its diagonal, so whole 8-row tiles are updated without a
    mask)."""
    c, width = r.shape
    col = lax.broadcasted_iota(jnp.int32, (sub, c), 1)
    blocks = []
    for lo in range(0, c, sub):
        rows = a[lo:lo + sub]
        x = r[lo:lo + sub]
        if lo:
            sofar = jnp.concatenate(blocks + [r[lo:]], 0)
            x = x - _dot(jnp.where(col < lo, rows, 0.0), sofar)
        tiles = [x[t:t + 8] for t in range(0, sub, 8)]
        for row in range(sub - 1):
            t0 = row // 8
            done = jnp.broadcast_to(tiles[t0][row % 8:row % 8 + 1], (8, width))
            for t in range(t0, sub // 8):
                tiles[t] = tiles[t] - jnp.broadcast_to(
                    rows[8 * t:8 * t + 8, lo + row:lo + row + 1],
                    (8, width)) * done
        blocks.append(jnp.concatenate(tiles, 0))
    return jnp.concatenate(blocks, 0)


def _kda_chunk_kernel(*refs, by_head: bool, sub: int):
    """One grid cell a (row, head, chunk), the chunks in order: from the
    chunk's q, k, v, log-decays and beta to its outputs (see the module
    docstring).  With a decay a head two more operands arrive, the chunk's
    ``(I + A)^{-1}`` and ``P``.  The state rides ``st_scr`` TRANSPOSED,
    ``[d_v, d_k]``, so that the decay a channel of d_k is a row.  Nothing
    here loops over heads, rows of the batch or chunks; the loops are over
    the ``log2 C`` levels of the pairwise decays and the steps of the
    solve."""
    if by_head:
        (q_ref, k_ref, v_ref, g_ref, b_ref, inv_ref, p_ref, o_ref, st_ref,
         st_scr) = refs
    else:
        q_ref, k_ref, v_ref, g_ref, b_ref, o_ref, st_ref, st_scr = refs
    n = pl.program_id(2)

    @pl.when(n == 0)
    def _start():
        st_scr[:] = jnp.zeros_like(st_scr)

    dot, nt = _dot, ((1,), (1,))                        # nt: a @ b^T
    f32 = jnp.float32
    q, k, v = (x[0].astype(f32) for x in (q_ref, k_ref, v_ref))
    c, dk = q.shape
    i = lax.broadcasted_iota(jnp.int32, (c, c), 0)
    j = lax.broadcasted_iota(jnp.int32, (c, c), 1)
    row = lax.broadcasted_iota(jnp.int32, (c, dk), 0)

    def column(ref):     # a chunk's numbers arrive as a row: [1, C] -> [C, 1]
        wide = jnp.broadcast_to(ref[0, 0, 0].astype(f32), (c, c))
        return jnp.sum(jnp.where(i == j, wide, 0.0), 1, keepdims=True)

    def back(x, far):                        # row i takes row i - far
        return pltpu.roll(x, far % c, 0)

    beta = column(b_ref)
    g = (jnp.broadcast_to(column(g_ref), (c, dk)) if by_head
         else g_ref[0].astype(f32))
    gc, far = g, 1                           # G, the running sum, by doubling
    while far < c:
        gc = gc + jnp.where(row >= far, back(gc, far), 0.0)
        far *= 2
    grow, tail = jnp.exp(gc), jnp.exp(gc[c - 1:c] - gc)   # exp(G), exp(G_C - G)

    def level(s):
        """The side of level ``s``'s split (pairs of blocks of ``2^s``
        rows) a row carries: a second block's row its decay since the
        pair's middle, a first block's row the decay from itself to there.
        Inside a block of four the sums of log-decays themselves; above,
        differences of G with the middle's, one row a pair."""
        b = 1 << s
        if b == 1:
            e = jnp.where((row & 1) == 1, g, 0.0)
        elif b == 2:
            at = row & 3
            e = jnp.where(at == 0, back(g, -1), jnp.where(
                at == 1, 0.0, jnp.where(at == 2, g, g + back(g, 1))))
        else:
            mid = jnp.concatenate([
                jnp.broadcast_to(gc[m + b - 1:m + b], (2 * b, dk))
                for m in range(0, c, 2 * b)], 0)
            e = jnp.where(((row >> s) & 1) == 1, gc - mid, mid - gc)
        return jnp.exp(e)

    st = st_scr[:]
    read = dot(jnp.concatenate([k, q], 0) * jnp.concatenate([grow, grow], 0),
               st, nt)                       # [K+ ; Q+] S0: [2C, d_v]
    r = beta * (v - read[:c])
    if by_head:
        # Every channel decays alike: A and P are two matmuls a chunk and
        # the solve one more for ALL chunks at once, which XLA batches far
        # better than a cell can chain them (kda_chunk_kernel says more).
        p = p_ref[0, 0, 0]
        u = dot(inv_ref[0, 0, 0], r)
    else:
        # A decay a channel: A^T and P^T level by level, a level ONE
        # matmul over d_k of rows that each carry their side of the split
        # at their pair's middle, masked to the sibling pairs.
        j2 = lax.broadcasted_iota(jnp.int32, (c, 2 * c), 0)
        i2 = lax.broadcasted_iota(jnp.int32, (c, 2 * c), 1) & (c - 1)
        both = jnp.zeros((c, 2 * c), f32)
        for s in range(c.bit_length() - 2, -1, -1):
            ws = level(s)
            ks = k * ws
            z = dot(ks, jnp.concatenate([ks, q * ws], 0), nt)    # [C, 2C]
            both = jnp.where((i2 > j2) & ((i2 ^ j2) >> s == 1), z, both)
        both = both.T                                            # [2C, C]
        p = both[c:] + jnp.where(i == j, jnp.sum(q * k, 1, keepdims=True), 0.0)
        u = _solve_in_cell(both[:c] * beta, r, sub)
    o_ref[0] = read[c:] + dot(p, u)
    st = st * grow[c - 1:c] + dot(u, k * tail, ((0,), (0,)))
    st_scr[:] = st

    @pl.when(n == pl.num_programs(2) - 1)
    def _end():
        st_ref[0, 0] = st


def _kernel_takes(dk: int, dv: int, chunk: int) -> bool:
    """Shapes the fused kernel tiles: heads of whole lane blocks and a
    chunk that halves down to whole sublane tiles."""
    return (dk % 128 == 0 and dv % 128 == 0 and chunk % 8 == 0
            and chunk & (chunk - 1) == 0)


def kda_chunk_kernel(q, k, v, g, beta, *, chunk: int = 64, interpret=None):
    """:func:`kda_chunk` as the Pallas kernel ``sw_kda_chunk``, S a whole
    number of chunks.  q, k, v and a decay a channel are read where the
    projections left them, a head a column block of ``[B, S, H * d]``, and
    the output is written the same way; a key head is read by each of the
    value heads over it.

    With a decay a head the chunks' ``(I + A)^{-1}`` and ``P`` come from
    lax (:func:`_pairwise_head`, :func:`_solve_unit_lower` against the
    identity) as ``[C, C]`` operands: they are then two matmuls and a
    solve batched over every (head, chunk) at once, 0.4 us a cell, where
    a cell that chains them pays 0.13-0.19 us a float32 matmul at
    ``HIGHEST`` (fourteen of them: measured 2.6 times the lax form's
    whole operation).  With a decay a channel the pairwise part is what
    XLA runs badly, and the cell builds everything."""
    b, s, hk, dk = q.shape
    h, dv = v.shape[2:]
    by_head = g.ndim == 3
    if interpret is None:
        interpret = dispatch.interpret()
    f32 = jnp.float32
    rep, n = h // hk, s // chunk
    sub = SUB if chunk % SUB == 0 else chunk

    def cols(width, index):     # a head's [C, width] of [B, S, heads * width]
        return pl.BlockSpec((1, chunk, width), index)

    def chunks(x):              # [B, S, H, ..] -> [B, N, H, C, ..]
        x = x.astype(f32)
        return jnp.moveaxis(x.reshape((b, n, chunk) + x.shape[2:]), 2, 3)

    own = lambda i, j, m: (i, m, j)
    key = lambda i, j, m: (i, m, j // rep)
    by_chunk = lambda i, j, m: (i, m, j, 0, 0)
    row_spec = pl.BlockSpec((1, 1, 1, 1, chunk), by_chunk)
    beta = chunks(beta)                                  # [B, N, H, C]
    operands = [q.reshape(b, s, hk * dk), k.reshape(b, s, hk * dk),
                v.reshape(b, s, h * dv)]
    specs = [cols(dk, key), cols(dk, key), cols(dv, own)]
    if by_head:
        g = chunks(g)
        a, p = _pairwise_head(chunks(q), chunks(k), jnp.cumsum(g, -1), beta)
        eye = jnp.broadcast_to(jnp.eye(chunk, dtype=f32), a.shape)
        operands += [g[..., None, :], beta[..., None, :],
                     _solve_unit_lower(a, eye), p]
        specs += [row_spec, row_spec] + [pl.BlockSpec(
            (1, 1, 1, chunk, chunk), by_chunk)] * 2
    else:
        operands += [g.reshape(b, s, h * dk), beta[..., None, :]]
        specs += [cols(dk, own), row_spec]
    o, st = pl.pallas_call(
        functools.partial(_kda_chunk_kernel, by_head=by_head, sub=sub),
        grid=(b, h, n),
        in_specs=specs,
        out_specs=[cols(dv, own),
                   pl.BlockSpec((1, 1, dv, dk), lambda i, j, m: (i, j, 0, 0))],
        out_shape=[jax.ShapeDtypeStruct((b, s, h * dv), f32),
                   jax.ShapeDtypeStruct((b, h, dv, dk), f32)],
        scratch_shapes=[pltpu.VMEM((dv, dk), f32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary")),
        interpret=interpret,
        name="sw_kda_chunk",
    )(*operands)
    return o.reshape(b, s, h, dv), jnp.swapaxes(st, -1, -2)


def kda_chunk_lax(q, k, v, g, beta, *, chunk: int = 64):
    """:func:`kda_chunk` in plain lax, S a whole number of chunks: what
    runs where Pallas does not, and what the kernel is tested against.
    What is the same for every chunk (``A``, ``P``, the solve against
    ``beta V`` and ``beta K+``) is computed for all chunks at once, and a
    scan carries the state (:func:`kda_chunk_carry_lax`)."""
    f32 = jnp.float32
    by_head = g.ndim == 3
    rep = v.shape[2] // q.shape[2]
    if rep > 1:
        q, k = jnp.repeat(q, rep, axis=2), jnp.repeat(k, rep, axis=2)
    if by_head:
        g = g[..., None]          # [B, S, H, 1]: broadcasts against d_k
    q, k, v, g, beta = (_chunked(jnp.moveaxis(x.astype(f32), 2, 1), chunk)
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
    o, state = kda_chunk_carry_lax(q * grow, solved[..., dv:], solved[..., :dv],
                                   p, k * jnp.exp(total - gc), decay)
    return jnp.moveaxis(o.reshape(o.shape[:2] + (-1, dv)), 1, 2), state


def kda_chunk(q, k, v, g, beta, *, chunk: int = 64):
    """A whole prompt through the recurrence, chunk by chunk, from a zero
    state, the operation, in the projections' layout.  q, k ``[B, S, Hk,
    d_k]`` (``Hk`` key heads, each read by ``H / Hk`` value heads), v ``[B,
    S, H, d_v]``, g ``[B, S, H, d_k]`` (a log-decay a channel) or ``[B, S,
    H]`` (one a head), beta ``[B, S, H]``; a position with ``g = 0`` and
    ``beta = 0`` does not move the state (a bucket's pads).  Returns ``(o
    [B, S, H, d_v] float32, state [B, H, d_k, d_v] float32)``: every
    position's read-out and the state after the last.  S is padded to whole
    chunks with such standing positions.  On a TPU the one kernel, elsewhere
    and at shapes it does not tile the lax twin."""
    s = q.shape[1]
    pad = -s % chunk
    if pad:
        q, k, v, g, beta = (
            jnp.pad(x, ((0, 0), (0, pad)) + ((0, 0),) * (x.ndim - 2))
            for x in (q, k, v, g, beta))
    run = (kda_chunk_kernel if dispatch.use_kernels() and _kernel_takes(
        q.shape[-1], v.shape[-1], chunk) else kda_chunk_lax)
    o, state = run(q, k, v, g, beta, chunk=chunk)
    return o[:, :s], state
