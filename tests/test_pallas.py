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


@pytest.mark.parametrize("stream", [True, False], ids=["stream", "grid"])
@pytest.mark.parametrize("pos", [0, 5, 127, 128, 299])
@pytest.mark.parametrize("block_k", [128, None])
def test_decode_kernel_matches_lax(pos, block_k, stream):
    """block_k=128 forces a MULTI-block sweep at T=300 (the cross-block
    online-softmax rescale — and, for the grid kernel, the repeated-block
    DMA clamp — never run otherwise; the 512 default is single-block at
    test sizes); None covers the default config.  Both kernel variants
    (double-buffered stream, grid pipeline) are pinned."""
    from starway_tpu.models.generate import _attend_cached
    from starway_tpu.ops.pallas_decode import decode_attention

    k1, k2, k3 = jax.random.split(jax.random.PRNGKey(2), 3)
    B, Hq, Hkv, T, D = 2, 8, 2, 300, 64
    q = jax.random.normal(k1, (B, Hq, 1, D), jnp.float32)
    k = jax.random.normal(k2, (B, Hkv, T, D), jnp.float32)
    v = jax.random.normal(k3, (B, Hkv, T, D), jnp.float32)
    ref = _attend_cached(q, k, v, pos, Hq // Hkv, use_pallas=False)
    kw = {} if block_k is None else {"block_k": block_k}
    out = decode_attention(q, k, v, pos, interpret=True, stream=stream, **kw)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref), atol=2e-5, rtol=2e-5)


@pytest.mark.parametrize("stream", [True, False], ids=["stream", "grid"])
@pytest.mark.parametrize("window", [None, 96])
def test_decode_kernel_multi_query(stream, window):
    """C>1 query positions (the speculative chunk verify): C x n_rep rows
    share one narrow cache stream, each row masked by its own cursor —
    pinned against the generalized lax oracle at ragged per-row bases,
    multi-block, fp and int8, crossing a block boundary mid-chunk."""
    from starway_tpu.models.generate import _attend_cached
    from starway_tpu.ops.pallas_decode import decode_attention
    from starway_tpu.ops.quantize import quantize_kv

    k1, k2, k3 = jax.random.split(jax.random.PRNGKey(5), 3)
    B, Hq, Hkv, T, D, C = 2, 8, 2, 300, 64, 5
    q = jax.random.normal(k1, (B, Hq, C, D), jnp.float32)
    k = jax.random.normal(k2, (B, Hkv, T, D), jnp.float32)
    v = jax.random.normal(k3, (B, Hkv, T, D), jnp.float32)
    pos = jnp.asarray([125, 290], jnp.int32)  # chunk straddles block 128
    ref = _attend_cached(q, k, v, pos, Hq // Hkv, use_pallas=False,
                         window=window)
    out = decode_attention(q, k, v, pos, interpret=True, stream=stream,
                           block_k=128, window=window)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref), atol=2e-5,
                               rtol=2e-5)

    k8, ks = quantize_kv(k)
    v8, vs = quantize_kv(v)
    refq = _attend_cached(q, k8, v8, pos, Hq // Hkv, use_pallas=False,
                          window=window, k_scale=ks, v_scale=vs)
    outq = decode_attention(q, k8, v8, pos, interpret=True, stream=stream,
                            block_k=128, window=window, k_scale=ks,
                            v_scale=vs)
    np.testing.assert_allclose(np.asarray(outq), np.asarray(refq),
                               atol=2e-5, rtol=2e-5)


def test_decode_kernel_traced_pos_under_jit():
    from starway_tpu.models.generate import _attend_cached
    from starway_tpu.ops.pallas_decode import decode_attention

    k1, k2, k3 = jax.random.split(jax.random.PRNGKey(3), 3)
    B, Hq, Hkv, T, D = 1, 4, 4, 130, 32  # no-GQA shape + padding tail
    q = jax.random.normal(k1, (B, Hq, 1, D), jnp.float32)
    k = jax.random.normal(k2, (B, Hkv, T, D), jnp.float32)
    v = jax.random.normal(k3, (B, Hkv, T, D), jnp.float32)
    step = jax.jit(lambda q, k, v, p: decode_attention(q, k, v, p, interpret=True))
    ref = _attend_cached(q, k, v, 77, 1, use_pallas=False)
    out = step(q, k, v, jnp.int32(77))
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref), atol=2e-5, rtol=2e-5)


@pytest.mark.parametrize("stream", [True, False], ids=["stream", "grid"])
def test_decode_kernel_per_row_pos(stream):
    """Ragged decode: a [B] position vector masks (and DMA-clamps) each
    batch row at its own cursor; every row must match a standalone
    scalar-pos call."""
    from starway_tpu.models.generate import _attend_cached
    from starway_tpu.ops.pallas_decode import decode_attention

    k1, k2, k3 = jax.random.split(jax.random.PRNGKey(4), 3)
    B, Hq, Hkv, T, D = 3, 8, 2, 300, 64
    q = jax.random.normal(k1, (B, Hq, 1, D), jnp.float32)
    k = jax.random.normal(k2, (B, Hkv, T, D), jnp.float32)
    v = jax.random.normal(k3, (B, Hkv, T, D), jnp.float32)
    pos = jnp.asarray([7, 255, 130], jnp.int32)

    # block_k=128: multi-block sweep, so each row's DMA really stops at a
    # different block index.
    out = decode_attention(q, k, v, pos, interpret=True, block_k=128,
                           stream=stream)
    lax_out = _attend_cached(q, k, v, pos, Hq // Hkv, use_pallas=False)
    np.testing.assert_allclose(np.asarray(out), np.asarray(lax_out),
                               atol=2e-5, rtol=2e-5)
    for b in range(B):
        solo = decode_attention(q[b:b + 1], k[b:b + 1], v[b:b + 1],
                                int(pos[b]), interpret=True, stream=stream)
        np.testing.assert_allclose(np.asarray(out[b]), np.asarray(solo[0]),
                                   atol=2e-5, rtol=2e-5, err_msg=f"row {b}")


@pytest.mark.parametrize("stream", [True, False], ids=["stream", "grid"])
def test_decode_kernel_sliding_window(stream):
    """Windowed decode: kernel == lax windowed oracle, multi-block, with
    the window straddling block boundaries; scalar and per-row pos."""
    from starway_tpu.models.generate import _attend_cached
    from starway_tpu.ops.pallas_decode import decode_attention

    k1, k2, k3 = jax.random.split(jax.random.PRNGKey(6), 3)
    B, Hq, Hkv, T, D, W = 2, 8, 2, 520, 64, 200
    q = jax.random.normal(k1, (B, Hq, 1, D), jnp.float32)
    k = jax.random.normal(k2, (B, Hkv, T, D), jnp.float32)
    v = jax.random.normal(k3, (B, Hkv, T, D), jnp.float32)
    for pos in (0, 150, 380, 519):
        out = decode_attention(q, k, v, pos, interpret=True, block_k=128,
                               window=W, stream=stream)
        ref = _attend_cached(q, k, v, pos, Hq // Hkv, use_pallas=False,
                             window=W)
        np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                                   atol=2e-5, rtol=2e-5, err_msg=f"pos={pos}")
    pos_v = jnp.asarray([519, 77], jnp.int32)
    out = decode_attention(q, k, v, pos_v, interpret=True, block_k=128,
                           window=W, stream=stream)
    ref = _attend_cached(q, k, v, pos_v, Hq // Hkv, use_pallas=False, window=W)
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
    The layer is traced (as inside the layer scan), T = 256 makes two
    blocks of 128, and both kernel variants read the same stack."""
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
    for stream in (True, False):
        kw = dict(window=window, block_k=128, interpret=True, stream=stream)
        stacked = jax.jit(lambda li: decode_attention(
            q, k, v, pos, layer=li, **scales, **kw))(jnp.int32(layer))
        one = decode_attention(
            q, k[layer], v[layer], pos,
            **{n: s[layer] for n, s in scales.items()}, **kw)
        assert stacked.shape == (B, Hq, n_q, D)
        np.testing.assert_array_equal(np.asarray(stacked, np.float32),
                                      np.asarray(one, np.float32))


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
