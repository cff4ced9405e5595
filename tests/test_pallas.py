"""Pallas flash-attention kernel vs the lax oracle (interpret mode on CPU;
the same kernel lowers through Mosaic on TPU -- validated on hardware via
the bench path)."""

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from starway_tpu.ops.attention import attention_reference, repeat_kv
from starway_tpu.ops.pallas_attention import flash_attention


@pytest.mark.parametrize("causal", [False, True])
@pytest.mark.parametrize("seq", [128, 200])  # 200 exercises padding
def test_flash_matches_reference(causal, seq):
    k1, k2, k3 = jax.random.split(jax.random.PRNGKey(0), 3)
    B, Hq, Hkv, D = 2, 4, 2, 32
    q = jax.random.normal(k1, (B, Hq, seq, D), jnp.float32)
    k = jax.random.normal(k2, (B, Hkv, seq, D), jnp.float32)
    v = jax.random.normal(k3, (B, Hkv, seq, D), jnp.float32)
    ref = attention_reference(q, repeat_kv(k, 2), repeat_kv(v, 2), causal=causal)
    out = flash_attention(q, k, v, causal=causal, block_q=64, block_k=64, interpret=True)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref), atol=3e-5, rtol=3e-5)


def test_flash_no_gqa():
    k1, k2, k3 = jax.random.split(jax.random.PRNGKey(1), 3)
    q = jax.random.normal(k1, (1, 2, 64, 16), jnp.float32)
    k = jax.random.normal(k2, (1, 2, 64, 16), jnp.float32)
    v = jax.random.normal(k3, (1, 2, 64, 16), jnp.float32)
    ref = attention_reference(q, k, v, causal=True)
    out = flash_attention(q, k, v, causal=True, block_q=32, block_k=32, interpret=True)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref), atol=3e-5, rtol=3e-5)


@pytest.mark.parametrize("causal", [False, True])
@pytest.mark.parametrize("seq", [128, 150])  # 150 exercises padding in bwd
def test_flash_gradients_match_oracle(causal, seq):
    """custom_vjp backward (two-pass Pallas kernel) vs differentiating the
    lax oracle.  GQA: dk/dv must sum over the grouped query heads."""
    from starway_tpu.ops.attention import blockwise_attention

    k1, k2, k3, k4 = jax.random.split(jax.random.PRNGKey(7), 4)
    B, Hq, Hkv, D = 2, 4, 2, 32
    q = jax.random.normal(k1, (B, Hq, seq, D), jnp.float32)
    k = jax.random.normal(k2, (B, Hkv, seq, D), jnp.float32)
    v = jax.random.normal(k3, (B, Hkv, seq, D), jnp.float32)
    do = jax.random.normal(k4, (B, Hq, seq, D), jnp.float32)

    def loss_flash(q, k, v):
        o = flash_attention(q, k, v, causal=causal, block_q=64, block_k=64,
                            interpret=True)
        return jnp.sum(o * do)

    def loss_ref(q, k, v):
        return jnp.sum(blockwise_attention(q, k, v, causal=causal,
                                           block_k=64) * do)

    g_flash = jax.grad(loss_flash, argnums=(0, 1, 2))(q, k, v)
    g_ref = jax.grad(loss_ref, argnums=(0, 1, 2))(q, k, v)
    for a, b in zip(g_flash, g_ref):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   atol=5e-5, rtol=5e-4)


def test_flash_grad_uneven_blocks():
    """block_q != block_k and bwd blocks differing from fwd blocks."""
    from starway_tpu.ops.attention import blockwise_attention
    from starway_tpu.ops.pallas_attention import _Cfg, _flash

    k1, k2, k3 = jax.random.split(jax.random.PRNGKey(9), 3)
    B, H, S, D = 1, 2, 256, 32
    q = jax.random.normal(k1, (B, H, S, D), jnp.float32)
    k = jax.random.normal(k2, (B, H, S, D), jnp.float32)
    v = jax.random.normal(k3, (B, H, S, D), jnp.float32)
    cfg = _Cfg(causal=True, sm_scale=1.0 / D**0.5, block_q=64, block_k=128,
               bwd_block_q=128, bwd_block_k=64, interpret=True)
    g = jax.grad(lambda *a: _flash(*a, cfg)[0].sum(), argnums=(0, 1, 2))(q, k, v)
    g_ref = jax.grad(
        lambda *a: blockwise_attention(*a, causal=True).sum(),
        argnums=(0, 1, 2))(q, k, v)
    for a, b in zip(g, g_ref):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   atol=5e-5, rtol=5e-4)


@pytest.mark.parametrize("pos", [0, 5, 127, 128, 299])
@pytest.mark.parametrize("block_k", [128, None])
def test_decode_kernel_matches_lax(pos, block_k):
    """block_k=128 forces a MULTI-block sweep at T=300 (the cross-block
    online-softmax rescale never runs otherwise; the 512 default is
    single-block at test sizes); None covers the default config."""
    from starway_tpu.ops.pallas_decode import (decode_attention,
                                               decode_attention_lax)

    k1, k2, k3 = jax.random.split(jax.random.PRNGKey(2), 3)
    B, Hq, Hkv, T, D = 2, 8, 2, 300, 64
    q = jax.random.normal(k1, (B, Hq, 1, D), jnp.float32)
    k = jax.random.normal(k2, (B, Hkv, T, D), jnp.float32)
    v = jax.random.normal(k3, (B, Hkv, T, D), jnp.float32)
    ref = decode_attention_lax(q, k, v, pos)
    kw = {} if block_k is None else {"block_k": block_k}
    out = decode_attention(q, k, v, pos, interpret=True, **kw)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref), atol=2e-5, rtol=2e-5)


@pytest.mark.parametrize("window", [None, 96])
def test_decode_kernel_multi_query(window):
    """C>1 query positions (the speculative chunk verify): C x n_rep rows
    share one narrow cache stream, each row masked by its own cursor —
    pinned against the generalized lax oracle at ragged per-row bases,
    multi-block, fp and int8, crossing a block boundary mid-chunk."""
    from starway_tpu.ops.pallas_decode import (decode_attention,
                                               decode_attention_lax)
    from starway_tpu.ops.quantize import quantize_kv

    k1, k2, k3 = jax.random.split(jax.random.PRNGKey(5), 3)
    B, Hq, Hkv, T, D, C = 2, 8, 2, 300, 64, 5
    q = jax.random.normal(k1, (B, Hq, C, D), jnp.float32)
    k = jax.random.normal(k2, (B, Hkv, T, D), jnp.float32)
    v = jax.random.normal(k3, (B, Hkv, T, D), jnp.float32)
    pos = jnp.asarray([125, 290], jnp.int32)  # chunk straddles block 128
    ref = decode_attention_lax(q, k, v, pos,
                         window=window)
    out = decode_attention(q, k, v, pos, interpret=True,
                           block_k=128, window=window)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref), atol=2e-5,
                               rtol=2e-5)

    k8, ks = quantize_kv(k)
    v8, vs = quantize_kv(v)
    refq = decode_attention_lax(q, k8, v8, pos,
                          window=window, k_scale=ks, v_scale=vs)
    outq = decode_attention(q, k8, v8, pos, interpret=True,
                            block_k=128, window=window, k_scale=ks,
                            v_scale=vs)
    np.testing.assert_allclose(np.asarray(outq), np.asarray(refq),
                               atol=2e-5, rtol=2e-5)


def test_decode_kernel_traced_pos_under_jit():
    from starway_tpu.ops.pallas_decode import (decode_attention,
                                               decode_attention_lax)

    k1, k2, k3 = jax.random.split(jax.random.PRNGKey(3), 3)
    B, Hq, Hkv, T, D = 1, 4, 4, 130, 32  # no-GQA shape + padding tail
    q = jax.random.normal(k1, (B, Hq, 1, D), jnp.float32)
    k = jax.random.normal(k2, (B, Hkv, T, D), jnp.float32)
    v = jax.random.normal(k3, (B, Hkv, T, D), jnp.float32)
    step = jax.jit(lambda q, k, v, p: decode_attention(q, k, v, p, interpret=True))
    ref = decode_attention_lax(q, k, v, 77)
    out = step(q, k, v, jnp.int32(77))
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref), atol=2e-5, rtol=2e-5)


def test_decode_kernel_per_row_pos():
    """Ragged decode: a [B] position vector masks (and DMA-clamps) each
    batch row at its own cursor; every row must match a standalone
    scalar-pos call."""
    from starway_tpu.ops.pallas_decode import (decode_attention,
                                               decode_attention_lax)

    k1, k2, k3 = jax.random.split(jax.random.PRNGKey(4), 3)
    B, Hq, Hkv, T, D = 3, 8, 2, 300, 64
    q = jax.random.normal(k1, (B, Hq, 1, D), jnp.float32)
    k = jax.random.normal(k2, (B, Hkv, T, D), jnp.float32)
    v = jax.random.normal(k3, (B, Hkv, T, D), jnp.float32)
    pos = jnp.asarray([7, 255, 130], jnp.int32)

    # block_k=128: multi-block sweep, so each row's DMA really stops at a
    # different block index.
    out = decode_attention(q, k, v, pos, interpret=True, block_k=128)
    lax_out = decode_attention_lax(q, k, v, pos)
    np.testing.assert_allclose(np.asarray(out), np.asarray(lax_out),
                               atol=2e-5, rtol=2e-5)
    for b in range(B):
        solo = decode_attention(q[b:b + 1], k[b:b + 1], v[b:b + 1],
                                int(pos[b]), interpret=True)
        np.testing.assert_allclose(np.asarray(out[b]), np.asarray(solo[0]),
                                   atol=2e-5, rtol=2e-5, err_msg=f"row {b}")


def test_decode_kernel_sliding_window():
    """Windowed decode: kernel == lax windowed oracle, multi-block, with
    the window straddling block boundaries; scalar and per-row pos."""
    from starway_tpu.ops.pallas_decode import (decode_attention,
                                               decode_attention_lax)

    k1, k2, k3 = jax.random.split(jax.random.PRNGKey(6), 3)
    B, Hq, Hkv, T, D, W = 2, 8, 2, 520, 64, 200
    q = jax.random.normal(k1, (B, Hq, 1, D), jnp.float32)
    k = jax.random.normal(k2, (B, Hkv, T, D), jnp.float32)
    v = jax.random.normal(k3, (B, Hkv, T, D), jnp.float32)
    for pos in (0, 150, 380, 519):
        out = decode_attention(q, k, v, pos, interpret=True, block_k=128,
                               window=W)
        ref = decode_attention_lax(q, k, v, pos,
                             window=W)
        np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                                   atol=2e-5, rtol=2e-5, err_msg=f"pos={pos}")
    pos_v = jnp.asarray([519, 77], jnp.int32)
    out = decode_attention(q, k, v, pos_v, interpret=True, block_k=128,
                           window=W)
    ref = decode_attention_lax(q, k, v, pos_v, window=W)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               atol=2e-5, rtol=2e-5)


def test_blockwise_window_matches_reference():
    from starway_tpu.ops.attention import blockwise_attention

    k1, k2, k3 = jax.random.split(jax.random.PRNGKey(7), 3)
    B, H, S, D, W = 1, 4, 300, 32, 90
    q = jax.random.normal(k1, (B, H, S, D), jnp.float32)
    k = jax.random.normal(k2, (B, H, S, D), jnp.float32)
    v = jax.random.normal(k3, (B, H, S, D), jnp.float32)
    ref = attention_reference(q, k, v, causal=True, window=W)
    out = blockwise_attention(q, k, v, causal=True, block_k=64, window=W)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               atol=2e-5, rtol=2e-5)
    with pytest.raises(ValueError):
        attention_reference(q, k, v, causal=False, window=W)


@pytest.mark.parametrize("window", [1, 40, 90, 300])
def test_flash_sliding_window_matches_reference(window):
    """Windowed flash fwd: multi-block both dims, window crossing block
    boundaries, incl. window=1 (self only) and window >= S (= full causal)."""
    k1, k2, k3 = jax.random.split(jax.random.PRNGKey(11), 3)
    B, Hq, Hkv, S, D = 1, 4, 2, 200, 32
    q = jax.random.normal(k1, (B, Hq, S, D), jnp.float32)
    k = jax.random.normal(k2, (B, Hkv, S, D), jnp.float32)
    v = jax.random.normal(k3, (B, Hkv, S, D), jnp.float32)
    ref = attention_reference(q, repeat_kv(k, 2), repeat_kv(v, 2),
                              causal=True, window=window)
    out = flash_attention(q, k, v, causal=True, block_q=64, block_k=64,
                          interpret=True, window=window)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               atol=3e-5, rtol=3e-5)
    with pytest.raises(ValueError):
        flash_attention(q, k, v, causal=False, window=window)


@pytest.mark.parametrize("window", [40, 130])
def test_flash_sliding_window_gradients(window):
    """Windowed custom_vjp: dq/dk/dv vs differentiating the windowed lax
    path — exercises the window clamps in BOTH backward passes (block 64,
    S=200: multi-block with dead blocks on each side of the band)."""
    from starway_tpu.ops.attention import blockwise_attention

    k1, k2, k3, k4 = jax.random.split(jax.random.PRNGKey(13), 4)
    B, Hq, Hkv, S, D = 1, 4, 2, 200, 32
    q = jax.random.normal(k1, (B, Hq, S, D), jnp.float32)
    k = jax.random.normal(k2, (B, Hkv, S, D), jnp.float32)
    v = jax.random.normal(k3, (B, Hkv, S, D), jnp.float32)
    do = jax.random.normal(k4, (B, Hq, S, D), jnp.float32)

    def loss_flash(q, k, v):
        o = flash_attention(q, k, v, causal=True, block_q=64, block_k=64,
                            interpret=True, window=window)
        return jnp.sum(o * do)

    def loss_ref(q, k, v):
        return jnp.sum(blockwise_attention(q, k, v, causal=True, block_k=64,
                                           window=window) * do)

    g_flash = jax.grad(loss_flash, argnums=(0, 1, 2))(q, k, v)
    g_ref = jax.grad(loss_ref, argnums=(0, 1, 2))(q, k, v)
    for a, b in zip(g_flash, g_ref):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   atol=5e-5, rtol=5e-4)


def test_window_validation():
    """window < 1 and non-causal windows are rejected at every entry."""
    from starway_tpu.models.llama import LlamaConfig
    from starway_tpu.ops.attention import blockwise_attention
    from starway_tpu.ops.pallas_decode import decode_attention

    x = jnp.zeros((1, 2, 16, 8), jnp.float32)
    xq = jnp.zeros((1, 2, 1, 8), jnp.float32)
    for bad in (0, -3):
        with pytest.raises(ValueError, match=">= 1"):
            flash_attention(x, x, x, causal=True, window=bad, interpret=True)
        with pytest.raises(ValueError, match=">= 1"):
            blockwise_attention(x, x, x, causal=True, window=bad)
        with pytest.raises(ValueError, match=">= 1"):
            attention_reference(x, x, x, causal=True, window=bad)
        with pytest.raises(ValueError, match=">= 1"):
            decode_attention(xq, x, x, 0, window=bad, interpret=True)
        with pytest.raises(ValueError, match=">= 1"):
            LlamaConfig.preset("debug", sliding_window=bad)


@pytest.mark.parametrize("layer", [0, 2], ids=["first", "last"])
@pytest.mark.parametrize("n_q", [1, 4], ids=["c1", "c4"])
@pytest.mark.parametrize("window", [None, 96], ids=["full", "window"])
@pytest.mark.parametrize("per_row", [True, False], ids=["rows", "scalar"])
@pytest.mark.parametrize("int8", [False, True], ids=["bf16", "int8"])
def test_decode_attention_reads_stacked_cache_by_layer(int8, per_row, window,
                                                       n_q, layer):
    """``decode_attention(stacked, layer=i)`` is bit-equal to the per-layer
    call on ``stacked[i]``: the layer only moves the DMA's source address.
    The layer is traced (as inside the layer scan) and T = 256 makes two
    blocks of 128."""
    from starway_tpu.ops.pallas_decode import decode_attention
    from starway_tpu.ops.quantize import quantize_kv

    ks = jax.random.split(jax.random.PRNGKey(11), 3)
    L, B, Hq, Hkv, T, D = 3, 2, 8, 2, 256, 64
    q = jax.random.normal(ks[0], (B, Hq, n_q, D)).astype(jnp.bfloat16)
    k = jax.random.normal(ks[1], (L, B, Hkv, T, D)).astype(jnp.bfloat16)
    v = jax.random.normal(ks[2], (L, B, Hkv, T, D)).astype(jnp.bfloat16)
    scales = {}
    if int8:
        k, scales["k_scale"] = quantize_kv(k)
        v, scales["v_scale"] = quantize_kv(v)
    pos = jnp.asarray([125, 250], jnp.int32) if per_row else 130
    kw = dict(window=window, block_k=128, interpret=True)
    stacked = jax.jit(lambda li: decode_attention(
        q, k, v, pos, layer=li, **scales, **kw))(jnp.int32(layer))
    one = decode_attention(
        q, k[layer], v[layer], pos,
        **{n: s[layer] for n, s in scales.items()}, **kw)
    assert stacked.shape == (B, Hq, n_q, D)
    np.testing.assert_array_equal(np.asarray(stacked, np.float32),
                                  np.asarray(one, np.float32))


# The grid of the decode kernel (a batch row's kv heads share a cell, and
# a cell starts the NEXT cell's first block): name -> (Hq, Hkv, D, C, T,
# int8, kind), ``kind`` one of None (plain rows), ("window", w), ("whole",)
# (a ring of exactly one window: every cursor clamped to T - 1),
# ("masked", w) (a ring longer than its window, absolute cursors) or
# ("by_row", rows a head) (a prompt's piece on its request's slot).
_DECODE_CELLS = {
    "hkv1_c1": (4, 1, 128, 1, 384, False, None),
    "hkv2_d256_c1": (16, 2, 256, 1, 384, False, None),
    "hkv2_d256_c2_window": (16, 2, 256, 2, 384, False, ("window", 150)),
    "hkv4_rep7_c1": (28, 4, 128, 1, 384, False, None),
    "hkv4_rep7_whole_ring": (28, 4, 128, 1, 256, False, ("whole",)),
    "hkv8_c1": (32, 8, 128, 1, 384, False, None),
    "hkv8_c2": (64, 8, 128, 2, 384, False, None),
    "hkv8_c2_masked_ring": (64, 8, 128, 2, 256, False, ("masked", 128)),
    "hkv8_c4_window": (32, 8, 128, 4, 512, False, ("window", 200)),
    "hkv8_c1_one_block_of_200": (32, 8, 128, 1, 200, False, None),
    "hkv32_c1": (32, 32, 128, 1, 384, False, None),
    "hkv32_c4_window": (32, 32, 128, 4, 384, False, ("window", 130)),
    "int8_hkv1_c1": (4, 1, 128, 1, 384, True, None),
    "int8_hkv2_d256_c2": (16, 2, 256, 2, 384, True, None),
    "int8_hkv4_c4_window": (16, 4, 128, 4, 384, True, ("window", 150)),
    "int8_hkv8_c1": (32, 8, 128, 1, 384, True, None),
    "int8_hkv8_c2_masked_ring": (64, 8, 128, 2, 256, True, ("masked", 128)),
    "int8_hkv8_whole_ring": (32, 8, 128, 1, 256, True, ("whole",)),
    "int8_hkv32_c1": (32, 32, 128, 1, 384, True, None),
    # 128 rows a head: four of the eight heads a cell, two cells a tile.
    "by_row_128_rows_hkv8": (8, 8, 128, 128, 512, False, ("by_row", 128)),
    # 512 rows a head (Mistral's 4 x 128): one head a cell.
    "by_row_512_rows_hkv2": (8, 2, 128, 128, 512, False, ("by_row", 512)),
    "by_row_512_rows_hkv2_int8": (8, 2, 128, 128, 512, True,
                                  ("by_row", 512)),
    "by_row_128_rows_hkv8_int8": (8, 8, 128, 128, 512, True,
                                  ("by_row", 128)),
}


@pytest.mark.parametrize("name", list(_DECODE_CELLS))
def test_decode_cells_match_lax(name):
    """The kernel's grid against the lax twins, in interpret mode, over
    the head counts, head sizes, query counts, cache kinds and cache
    dtypes that reach it.  Every batch is ragged: its FIRST row is the
    longest and its LAST the shortest (a cell fetches the first block of
    the cell after it: it must never attend what was fetched for another
    cell's row, head or window), with a cursor of 0, one either side of a
    block edge and one that reaches T - 1.  Blocks of 128, so that rows
    span one to four of them; the caches are stacked and the layer
    traced."""
    from starway_tpu.ops.pallas_decode import (
        _cell_shape, decode_attention, decode_attention_lax, slot_attention,
        slot_attention_lax)
    from starway_tpu.ops.quantize import quantize_kv

    hq, hkv, d, c, t, int8, kind = _DECODE_CELLS[name]
    kind = kind or (None,)
    L, layer = 2, jnp.int32(1)
    by_row = kind[0] == "by_row"
    n_rows = 5 if by_row else 4          # cache rows
    b = 3 if by_row else n_rows          # batch rows
    q, k, v = _rand(len(name), (b, hq, c, d), (L, n_rows, hkv, t, d),
                    (L, n_rows, hkv, t, d), dtype=jnp.bfloat16)
    kw = {}
    if int8:
        (k, ks), (v, vs) = quantize_kv(k), quantize_kv(v)
        kw.update(k_scale=ks, v_scale=vs)
    if by_row:
        # Three pieces of 128 queries: the longest first (it reaches the
        # cache's end), the shortest last, on rows that are not theirs.
        assert hq // hkv * c == kind[1]
        pos = jnp.asarray([t - c, 128, 0], jnp.int32)
        rows = jnp.asarray([4, 0, 2], jnp.int32)
        assert _cell_shape(hkv, kind[1], t, d * k.dtype.itemsize, 512,
                           int8)[0] == 512 // kind[1]
        got = jax.jit(lambda li: slot_attention(
            q, k, v, pos, rows, layer=li, interpret=True, **kw))(layer)
        want = slot_attention_lax(q, k, v, pos, rows, layer=layer, **kw)
    else:
        window = kind[1] if kind[0] in ("window", "masked") else None
        masked = kind[0] == "masked"
        top = (5 * t if masked else t) - c
        pos = jnp.asarray([top, 127, 128 - c + 1, 0], jnp.int32)
        if kind[0] == "whole":   # what cached_attention hands the kernel
            pos = jnp.minimum(jnp.asarray([9 * t, t + 3, 127, 0]), t - 1)
        got = jax.jit(lambda li: decode_attention(
            q, k, v, pos, layer=li, window=window, ring=masked, block_k=128,
            interpret=True, **kw))(layer)
        want = decode_attention_lax(q, k, v, pos, layer=layer, window=window,
                                    ring=masked, **kw)
    assert got.shape == q.shape
    np.testing.assert_allclose(np.asarray(got, np.float32),
                               np.asarray(want, np.float32),
                               atol=2e-2, rtol=2e-2)


@pytest.mark.parametrize("hkv,rows_q,t,pos_bytes,quant,want", [
    (8, 8, 2048, 256, False, (8, 512)),      # mistral7b decode
    (8, 16, 256, 256, False, (8, 256)),      # k-exaone's ring, two rows
    (4, 8, 16384, 256, False, (4, 512)),     # smallthinker-21b's full rows
    (2, 8, 4096, 512, False, (2, 512)),      # qwen3-next, heads of 256
    (8, 512, 2048, 256, False, (1, 512)),    # mistral7b's piece: 512 rows
    (8, 128, 2048, 256, False, (4, 512)),
    (32, 8, 2048, 256, False, (32, 128)),    # MHA: the block gives way
    (32, 8, 2048, 128, True, (32, 256)),
    (8, 8, 4088, 256, False, (1, 4088)),     # one block of the whole row
    (8, 8, 1000, 256, False, (4, 1000)),
    (1, 1024, 512, 256, False, (1, 512)),
])
def test_cell_shape_table(hkv, rows_q, t, pos_bytes, quant, want):
    """Heads a cell and kv block from the shapes alone: every head of the
    row while their query rows stay within 512 and one of their blocks
    within 1 MiB, the block giving way before the heads do."""
    from starway_tpu.ops.pallas_decode import (_CELL_BLOCK_BYTES, _CELL_ROWS,
                                               _cell_shape)

    heads, block = _cell_shape(hkv, rows_q, t, pos_bytes, 512, quant)
    assert (heads, block) == want
    assert hkv % heads == 0 and t % block == 0
    assert heads == 1 or (heads * rows_q <= _CELL_ROWS
                          and heads * block * pos_bytes <= _CELL_BLOCK_BYTES)


@pytest.mark.parametrize("n_new", [1, 4], ids=["c1", "c4"])
@pytest.mark.parametrize("leaf", ["bf16", "int8", "scales"])
def test_kv_write_in_place_matches_dynamic_update_slice(leaf, n_new):
    """``kv_write`` (the aliased read-modify-write of one tile a row) puts
    exactly what ``lax.dynamic_update_slice`` puts, at a tile's first row,
    its last row, the row after it and the cache's last position (where a
    C = 4 start is clamped), into the asked layer and rows only: every
    other entry of the stacked array stays bit-equal.  ``kv_write_lax``,
    what the CPU path runs, is held to the same."""
    from starway_tpu.ops.pallas_decode import kv_write, kv_write_lax

    dtype, tile = {"bf16": (jnp.bfloat16, 16), "int8": (jnp.int8, 32),
                   "scales": (jnp.float32, 128)}[leaf]
    L, R, Hkv, T, D = 3, 6, 2, 2 * tile, 64
    tail = () if leaf == "scales" else (D,)
    ks = jax.random.split(jax.random.PRNGKey(13), 4)

    def draw(key, shape):
        return (jax.random.normal(key, shape) * 40).astype(dtype)

    caches = (draw(ks[0], (L, R, Hkv, T) + tail),
              draw(ks[1], (L, R, Hkv, T) + tail))
    pos = jnp.asarray([0, tile - 1, tile, T - 1], jnp.int32)
    rows = jnp.asarray([4, 0, 5, 2], jnp.int32)  # not the identity
    updates = (draw(ks[2], (4, Hkv, n_new) + tail),
               draw(ks[3], (4, Hkv, n_new) + tail))
    layer = 1

    def reference(c, u):
        for n in range(4):
            c = jax.lax.dynamic_update_slice(
                c, u[n][None, None],
                (layer, rows[n], 0, pos[n]) + (0,) * len(tail))
        return c

    want = [reference(c, u) for c, u in zip(caches, updates)]
    got = jax.jit(lambda c, u, li: kv_write(
        c, u, li, rows, pos, interpret=True))(caches, updates,
                                             jnp.int32(layer))
    lax_got = kv_write_lax(caches, updates, jnp.int32(layer), rows, pos)
    for w, g, lg, c in zip(want, got, lax_got, caches):
        np.testing.assert_array_equal(np.asarray(g), np.asarray(w))
        np.testing.assert_array_equal(np.asarray(lg), np.asarray(w))
        assert (np.asarray(w) != np.asarray(c)).any()


def test_kv_write_long_chunk_goes_in_pieces(monkeypatch):
    """A chunk whose window would not fit the kernel's VMEM budget (a
    prefix admit's long suffix) is written a piece at a time, to the same
    result."""
    from starway_tpu.ops import pallas_decode
    from starway_tpu.ops.pallas_decode import kv_write, kv_write_lax

    monkeypatch.setattr(pallas_decode, "_WRITE_VMEM_BYTES", 32 << 10)
    L, R, Hkv, T, D, C = 2, 1, 2, 512, 64, 300  # 256 B a position: 112 a piece
    ks = jax.random.split(jax.random.PRNGKey(19), 2)
    cache = jax.random.normal(ks[0], (L, R, Hkv, T, D)).astype(jnp.bfloat16)
    update = jax.random.normal(ks[1], (R, Hkv, C, D)).astype(jnp.bfloat16)
    rows, pos = jnp.zeros((1,), jnp.int32), jnp.asarray([37], jnp.int32)
    got, = kv_write((cache,), (update,), 1, rows, pos, interpret=True)
    want, = kv_write_lax((cache,), (update,), 1, rows, pos)
    np.testing.assert_array_equal(np.asarray(got, np.float32),
                                  np.asarray(want, np.float32))


def _ingest_widths():
    from starway_tpu.models.serving import INGEST_WIDTHS

    return INGEST_WIDTHS


@pytest.mark.parametrize("int8", [False, True], ids=["bf16", "int8"])
@pytest.mark.parametrize("first", [0, 384, 896], ids=lambda f: f"at{f}")
@pytest.mark.parametrize("width", _ingest_widths())
def test_slot_attention_matches_lax(width, first, int8):
    """``slot_attention`` (the kernel behind ``ops.ingest_attention``:
    ``W`` queries of one prompt piece against the ONE cache row its
    request owns, named by an index beside the layer's) against its lax
    twin, at every width the server ingests at.  T = 1024 is two kv blocks
    of 512: a piece at 384 straddles the block boundary, one at 896
    reaches past the cache's end with its pads (their rows mean nothing
    and are left out), and the row read is not the batch row."""
    from starway_tpu.ops.pallas_decode import (slot_attention,
                                               slot_attention_lax)
    from starway_tpu.ops.quantize import quantize_kv

    L, R, Hq, Hkv, T, D = 2, 3, 4, 2, 1024, 64
    q, k, v = _rand(31, (1, Hq, width, D), (L, R, Hkv, T, D),
                    (L, R, Hkv, T, D), dtype=jnp.bfloat16)
    kw = {}
    if int8:
        (k, ks), (v, vs) = quantize_kv(k), quantize_kv(v)
        kw.update(k_scale=ks, v_scale=vs)
    args = (q, k, v, jnp.asarray([first], jnp.int32),
            jnp.asarray([2], jnp.int32))
    got = jax.jit(lambda li: slot_attention(
        *args, layer=li, interpret=True, **kw))(jnp.int32(1))
    want = slot_attention_lax(*args, layer=jnp.int32(1), **kw)
    live = min(width, T - first)
    assert got.shape == (1, Hq, width, D) and live >= 128
    np.testing.assert_allclose(np.asarray(got, np.float32)[:, :, :live],
                               np.asarray(want, np.float32)[:, :, :live],
                               atol=2e-2, rtol=2e-2)


@pytest.mark.parametrize("leaf", ["bf16", "int8", "scales"])
def test_kv_write_counted_writes_only_what_is_valid(leaf):
    """``kv_write(count=)``: of a row's C positions only the first
    ``count`` are written and the start is NOT clamped -- a prompt's last
    piece, padded to the piece's width, ends inside the cache while its
    pads would lie past it.  Rows: a whole piece, a short one, an empty
    one, and one that ends at the cache's last position; kernel and lax
    twin against a plain loop."""
    from starway_tpu.ops.pallas_decode import kv_write, kv_write_lax

    dtype, tile = {"bf16": (jnp.bfloat16, 16), "int8": (jnp.int8, 32),
                   "scales": (jnp.float32, 128)}[leaf]
    L, R, Hkv, T, D, C = 2, 5, 2, 4 * tile, 64, tile + 8
    tail = () if leaf == "scales" else (D,)
    ks = jax.random.split(jax.random.PRNGKey(17), 2)
    draw = lambda key, shape: (jax.random.normal(key, shape) * 40).astype(dtype)
    cache = draw(ks[0], (L, R, Hkv, T) + tail)
    update = draw(ks[1], (4, Hkv, C) + tail)
    rows = jnp.asarray([3, 0, 4, 1], jnp.int32)
    pos = jnp.asarray([tile, 5, 0, T - 9], jnp.int32)
    count = jnp.asarray([C, 3, 0, 9], jnp.int32)
    want = np.array(cache)
    for n in range(4):
        c, at = int(count[n]), int(pos[n])
        want[1, int(rows[n]), :, at:at + c] = np.asarray(update)[n, :, :c]
    got, = jax.jit(lambda li: kv_write(
        (cache,), (update,), li, rows, pos, count=count,
        interpret=True))(jnp.int32(1))
    lax_got, = kv_write_lax((cache,), (update,), jnp.int32(1), rows, pos,
                            count)
    np.testing.assert_array_equal(np.asarray(got), want)
    np.testing.assert_array_equal(np.asarray(lax_got), want)


# ------------------------------------------------ the seam (ops/dispatch.py)


def _rand(seed, *shapes, dtype=jnp.float32):
    keys = jax.random.split(jax.random.PRNGKey(seed), len(shapes))
    return [jax.random.normal(k, s).astype(dtype)
            for k, s in zip(keys, shapes)]


def _case_decode(int8=False, window=None, c=1):
    from starway_tpu.ops import cached_attention
    from starway_tpu.ops.pallas_decode import (decode_attention,
                                               decode_attention_lax)
    from starway_tpu.ops.quantize import quantize_kv

    L, B, Hq, Hkv, T, D = 2, 2, 8, 2, 256, 64
    q, k, v = _rand(21, (B, Hq, c, D), (L, B, Hkv, T, D), (L, B, Hkv, T, D))
    kw = dict(layer=jnp.int32(1), window=window)
    if int8:
        (k, ks), (v, vs) = quantize_kv(k), quantize_kv(v)
        kw.update(k_scale=ks, v_scale=vs)
    args = (q, k, v, jnp.asarray([130, 250 - c], jnp.int32))
    return (lambda: cached_attention(*args, **kw),
            lambda: decode_attention(*args, interpret=True, **kw),
            lambda: decode_attention_lax(*args, **kw))


def _case_ingest(int8=False):
    from starway_tpu.ops import ingest_attention
    from starway_tpu.ops.pallas_decode import (slot_attention,
                                               slot_attention_lax)
    from starway_tpu.ops.quantize import quantize_kv

    L, R, Hq, Hkv, T, D = 2, 3, 8, 2, 256, 64
    q, k, v = _rand(25, (1, Hq, 16, D), (L, R, Hkv, T, D), (L, R, Hkv, T, D))
    kw = dict(layer=jnp.int32(1))
    if int8:
        (k, ks), (v, vs) = quantize_kv(k), quantize_kv(v)
        kw.update(k_scale=ks, v_scale=vs)
    args = (q, k, v, jnp.asarray([120], jnp.int32), jnp.asarray([2], jnp.int32))
    return (lambda: ingest_attention(*args, **kw),
            lambda: slot_attention(*args, interpret=True, **kw),
            lambda: slot_attention_lax(*args, **kw))


def _case_latent():
    from starway_tpu.ops import latent_attention
    from starway_tpu.ops.pallas_decode import (mla_decode_attention,
                                               mla_decode_attention_lax)

    q, c = _rand(22, (2, 4, 1, 128), (2, 2, 1, 256, 128))
    args = (q, c, jnp.asarray([100, 255], jnp.int32))
    kw = dict(rank=64, sm_scale=0.1, layer=jnp.int32(1))
    return (lambda: latent_attention(*args, **kw),
            lambda: mla_decode_attention(*args, interpret=True, **kw),
            lambda: mla_decode_attention_lax(*args, **kw))


def _case_write(kind):
    from starway_tpu.ops import cache_write
    from starway_tpu.ops.pallas_decode import kv_write, kv_write_lax

    L, R, T = 2, 3, 256
    tail, hkv, n, dtype = {"kv": ((64,), 2, 2, jnp.bfloat16),
                           "counted": ((64,), 2, 2, jnp.bfloat16),
                           "scales": ((), 2, 2, jnp.float32),
                           "latent": ((128,), 1, 1, jnp.bfloat16)}[kind]
    caches = tuple(_rand(23, *[(L, R, hkv, T) + tail] * n, dtype=dtype))
    updates = tuple(_rand(24, *[(R, hkv, 1) + tail] * n, dtype=dtype))
    args = (caches, updates, jnp.int32(1), jnp.arange(R),
            jnp.asarray([0, 131, 255], jnp.int32))
    if kind == "counted":  # a prompt piece: some of its positions are pads
        args += (jnp.asarray([1, 0, 1], jnp.int32),)
        return (lambda: cache_write(*args),
                lambda: kv_write(*args[:5], count=args[5], interpret=True),
                lambda: kv_write_lax(*args))
    return (lambda: cache_write(*args),
            lambda: kv_write(*args, interpret=True),
            lambda: kv_write_lax(*args))


def _case_gmm(gated):
    from starway_tpu.ops import grouped_matmul
    from starway_tpu.ops.pallas_gmm import gmm, gmm_lax

    x, w, w2 = _rand(25, (64, 32), (3, 32, 128), (3, 32, 128))
    args = (x, w, jnp.asarray([0, 0, 2, 2], jnp.int32), jnp.int32(3))
    kw = dict(tile_m=16, w2=w2 if gated else None)
    live = lambda out: out[:48]  # the fourth tile is dead: never written
    return (lambda: live(grouped_matmul(*args, **kw)),
            lambda: live(gmm(*args, interpret=True, **kw)),
            lambda: live(gmm_lax(*args, **kw)))


def _case_int8_matmul():
    from starway_tpu.ops import quantized_matmul
    from starway_tpu.ops.pallas_gemv import int8_matmul, int8_matmul_lax
    from starway_tpu.ops.quantize import quantize_weight

    x, w = _rand(26, (5, 64), (64, 256))
    qw = quantize_weight(w)
    args = (x, qw["q"], qw["s"])
    return (lambda: quantized_matmul(*args),
            lambda: int8_matmul(*args, interpret=True),
            lambda: int8_matmul_lax(*args))


def _case_self_attention(window=None):
    from starway_tpu.ops import self_attention
    from starway_tpu.ops.attention import blockwise_attention
    from starway_tpu.ops.pallas_attention import flash_attention

    q, k, v = _rand(27, (1, 4, 256, 32), (1, 2, 256, 32), (1, 2, 256, 32))
    kw = dict(causal=True, window=window)
    return (lambda: self_attention(q, k, v, **kw),
            lambda: flash_attention(q, k, v, interpret=True, **kw),
            lambda: blockwise_attention(q, k, v, **kw))


def _case_ring_step(causal, bwd=False):
    from starway_tpu.ops import ring_step, ring_step_bwd
    from starway_tpu.ops.pallas_attention import (
        flash_partial, flash_partial_bwd, flash_partial_bwd_lax,
        flash_partial_lax)

    q, k, v, do = _rand(28, (1, 4, 64, 32), (1, 2, 64, 32), (1, 2, 64, 32),
                        (1, 4, 64, 32))
    offs, kw = (jnp.int32(64), jnp.int32(32)), dict(causal=causal,
                                                    sm_scale=32 ** -0.5)
    if not bwd:
        args = (q, k, v, *offs)
        return (lambda: ring_step(*args, causal, kw["sm_scale"]),
                lambda: flash_partial(*args, interpret=True, **kw),
                lambda: flash_partial_lax(*args, **kw))
    o, m, l = flash_partial_lax(q, k, v, *offs, **kw)
    lse = m + jnp.log(jnp.maximum(l, 1e-30))
    out = o / jnp.maximum(l, 1e-30)[..., None]
    args = (q, do, k, v, lse, jnp.sum(do * out, -1), *offs)
    return (lambda: ring_step_bwd(*args, causal, kw["sm_scale"]),
            lambda: flash_partial_bwd(*args, interpret=True, **kw),
            lambda: flash_partial_bwd_lax(*args, **kw))


def _case_paged():
    """One implementation: the dispatcher is the kernel (interpreted off
    the chip) whatever the decision says; the oracle is the dense twin
    over the gathered logical cache."""
    from starway_tpu.ops import paged_attention
    from starway_tpu.ops.pallas_decode import decode_attention_lax
    from starway_tpu.ops.pallas_paged import (gather_logical,
                                              paged_decode_attention)

    q, kp, vp = _rand(29, (2, 4, 1, 64), (7, 2, 128, 64), (7, 2, 128, 64))
    table = jnp.asarray([[3, 1, 6], [2, 5, 4]], jnp.int32)
    pos = jnp.asarray([200, 383], jnp.int32)
    return (lambda: paged_attention(q, kp, vp, table, pos),
            lambda: paged_decode_attention(q, kp, vp, table, pos,
                                           interpret=True),
            lambda: decode_attention_lax(q, gather_logical(kp, table),
                                         gather_logical(vp, table), pos))


_DISPATCH = {
    "decode_bf16": _case_decode,
    "decode_int8": lambda: _case_decode(int8=True),
    "decode_windowed": lambda: _case_decode(window=96),
    "decode_c4": lambda: _case_decode(c=4),
    "ingest_bf16": _case_ingest,
    "ingest_int8": lambda: _case_ingest(int8=True),
    "latent_decode": _case_latent,
    "write_kv": lambda: _case_write("kv"),
    "write_counted": lambda: _case_write("counted"),
    "write_scales": lambda: _case_write("scales"),
    "write_latent": lambda: _case_write("latent"),
    "gmm_gated": lambda: _case_gmm(True),
    "gmm_down": lambda: _case_gmm(False),
    "int8_matmul": _case_int8_matmul,
    "attention_full": _case_self_attention,
    "attention_windowed": lambda: _case_self_attention(window=96),
    "ring_step_masked": lambda: _case_ring_step(True),
    "ring_step_unmasked": lambda: _case_ring_step(False),
    "ring_step_bwd_masked": lambda: _case_ring_step(True, bwd=True),
    "paged": _case_paged,
}


@pytest.mark.parametrize("name", list(_DISPATCH))
def test_op_dispatch_agrees_with_its_twin(name, force_kernels):
    """Every public operation of ``starway_tpu.ops`` decides ALONE and by
    the one function: with ``dispatch.use_kernels`` forced on it returns
    exactly what its kernel returns (interpreted here), forced off exactly
    what its ``*_lax`` twin returns, and the two agree.  (The paged
    attention has one implementation: both settings give the kernel.)"""
    op, kernel, twin = _DISPATCH[name]()
    leaves = lambda out: [np.asarray(x, np.float32)
                          for x in jax.tree_util.tree_leaves(out)]
    force_kernels(True)
    on = leaves(op())
    force_kernels(False)
    off = leaves(op())
    want_on, want_off = leaves(kernel()), leaves(twin())
    for got, want in zip(on, want_on):
        np.testing.assert_array_equal(got, want)
    for got, want in zip(off, want_on if name == "paged" else want_off):
        np.testing.assert_array_equal(got, want)
    for a, b in zip(want_on, want_off):
        np.testing.assert_allclose(a, b, atol=3e-5, rtol=3e-4)


def test_only_ops_chooses_an_implementation():
    """The decision has one home.  Outside ``starway_tpu/ops/`` no module
    asks ``jax.default_backend()`` (``device.py``'s platform check is no
    choice of implementation and is the one exception), none names the
    switches PR 28 removed (spelled in halves below so that a grep for
    them finds nothing), and none imports from a ``pallas_*``
    module anything but a ``*_lax`` twin: a kernel is reached through its
    operation in ``starway_tpu.ops``."""
    import ast
    from pathlib import Path

    root = Path(__file__).resolve().parents[1] / "starway_tpu"
    bad = []
    for path in sorted(root.rglob("*.py")):
        rel = path.relative_to(root).as_posix()
        if rel.startswith("ops/"):
            continue
        for node in ast.walk(ast.parse(path.read_text())):
            where = f"{rel}:{getattr(node, 'lineno', 0)}"
            if (isinstance(node, ast.Attribute)
                    and node.attr == "default_backend"
                    and rel != "device.py"):
                bad.append(f"{where} asks default_backend")
            names = {getattr(node, "id", None), getattr(node, "arg", None),
                     getattr(node, "attr", None)}
            if names & {"use_" + "pallas", "use_" + "kernel"}:
                bad.append(f"{where} names a removed switch")
            if (isinstance(node, ast.ImportFrom) and node.module
                    and "pallas_" in node.module.rsplit(".", 1)[-1]):
                bad += [f"{where} imports {a.name} from {node.module}"
                        for a in node.names if not a.name.endswith("_lax")]
    assert not bad, "\n".join(bad)


@pytest.mark.parametrize("quant", [False, True], ids=["bf16", "int8"])
@pytest.mark.parametrize("t,want", [
    (128, (128, 128)), (256, (256, 256)), (296, (296, None)),
    (300, (None, None)), (2048, (512, 512)), (4104, (None, None)),
    (5120, (512, 512))])
def test_pick_block_table(t, want, quant):
    """The kv block DIVIDES the cache length: the widest multiple of 128
    up to ``block_k`` (512); a bf16 cache of whole sublane tiles up to
    4096 goes as one block (the scales of an int8 one lie on the 128
    lanes); ``None`` sends the call down the slice-and-pad path."""
    from starway_tpu.ops.pallas_decode import _pick_block

    got = _pick_block(t, 512, quant)
    assert got == want[quant]
    assert got is None or t % got == 0


def test_kv_write_rows_sharing_a_tile_lose_an_update():
    """The documented limit of ``kv_write`` (ROADMAP D8): the rows of one
    call must not share a tile.  Two neighbouring positions of one cache
    row, written as two rows of a call, each read the tile, set their own
    position and write the tile back: the later write-back carries the
    earlier position as it was READ, so one update is lost.  The lax twin
    keeps both, which is why the paged prefix admit (one row a token of a
    page) calls ``kv_write_lax`` by name.  If the kernel learns to merge
    such rows, this test changes with its docstring."""
    from starway_tpu.ops.pallas_decode import kv_write, kv_write_lax

    (cache,) = _rand(31, (1, 2, 2, 128, 64), dtype=jnp.bfloat16)
    (upd,) = _rand(32, (2, 2, 1, 64), dtype=jnp.bfloat16)
    args = ((cache,), (upd,), jnp.int32(0), jnp.asarray([1, 1]),
            jnp.asarray([40, 41], jnp.int32))
    (lax_out,) = kv_write_lax(*args)
    np.testing.assert_array_equal(np.asarray(lax_out[0, 1, :, 40:42], np.float32),
                                  np.asarray(jnp.moveaxis(upd[:, :, 0], 0, 1),
                                             np.float32))
    (out,) = kv_write(*args, interpret=True)
    kept = [bool(jnp.array_equal(out[0, 1, :, 40 + i], upd[i, :, 0]))
            for i in range(2)]
    assert kept.count(True) == 1, kept
    # Everything outside the two positions is untouched either way.
    rest = np.ones(cache.shape, bool)
    rest[0, 1, :, 40:42] = False
    np.testing.assert_array_equal(np.asarray(out, np.float32)[rest],
                                  np.asarray(cache, np.float32)[rest])
