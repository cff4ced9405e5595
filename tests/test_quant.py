"""int8 KV-cache quantization (ops/quantize.py + the quant decode paths).

Contracts pinned here:
* quantize/dequantize round-trip error is bounded by the scheme's
  worst case (amax/254 per element);
* BOTH pallas decode variants on an int8 cache match the lax path run on
  the dequantized cache (the kernel's dequant-folding algebra is exact up
  to float rounding) — including ragged positions and sliding windows;
* generate() with ``kv_quant="int8"`` works end to end on the aligned,
  ragged, and rolling-cache paths and its greedy tokens track the
  full-precision run on the debug model;
* SlotServer serves int8-cache configs, request outputs matching the
  standalone int8 generate() oracle (admission writes the scale leaves).

No reference counterpart (/root/reference is a transport library) — this
is the TPU build's serving-stack extension.
"""

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from starway_tpu.models import LlamaConfig, SlotServer, init_params
from starway_tpu.models.cache import init_cache
from starway_tpu.models.generate import generate
from starway_tpu.ops.quantize import dequantize_kv, quantize_kv


def test_quantize_roundtrip_bound():
    x = jax.random.normal(jax.random.PRNGKey(0), (3, 5, 64), jnp.float32)
    q, s = quantize_kv(x)
    assert q.dtype == jnp.int8 and s.shape == (3, 5)
    err = jnp.abs(dequantize_kv(q, s, jnp.float32) - x)
    # Per-vector bound: half a quantization step = amax / 254.
    bound = (jnp.max(jnp.abs(x), axis=-1, keepdims=True) / 254.0) * 1.01
    assert bool(jnp.all(err <= bound))


def test_quantize_zero_vectors_stay_zero():
    x = jnp.zeros((2, 4, 8), jnp.float32)
    q, s = quantize_kv(x)
    assert bool(jnp.all(q == 0)) and bool(jnp.all(s == 0))
    assert bool(jnp.all(dequantize_kv(q, s) == 0))


@pytest.mark.parametrize("window,ragged", [(None, False), (None, True),
                                           (96, True)])
def test_decode_kernel_int8_matches_dequant_oracle(window, ragged):
    """Kernel on the int8 cache == lax path on the dequantized cache: the
    in-kernel scale folding is algebraically exact (f32 score chain)."""
    from starway_tpu.ops.pallas_decode import (decode_attention,
                                               decode_attention_lax)

    b, hq, hkv, t, d = 2, 8, 2, 384, 64
    kq, kk, kv = jax.random.split(jax.random.PRNGKey(1), 3)
    q = jax.random.normal(kq, (b, hq, 1, d), jnp.float32)
    k = jax.random.normal(kk, (b, hkv, t, d), jnp.float32)
    v = jax.random.normal(kv, (b, hkv, t, d), jnp.float32)
    kq8, ks = quantize_kv(k)
    vq8, vs = quantize_kv(v)
    pos = (jnp.asarray([133, 380], jnp.int32) if ragged
           else jnp.asarray(300, jnp.int32))

    out = decode_attention(q, kq8, vq8, pos, k_scale=ks, v_scale=vs,
                           interpret=True, block_k=128, window=window)
    ref = decode_attention_lax(q, dequantize_kv(kq8, ks, jnp.float32),
                               dequantize_kv(vq8, vs, jnp.float32), pos,
                               window=window)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref), atol=2e-5)


def test_decode_attention_rejects_inconsistent_scales():
    from starway_tpu.ops.pallas_decode import decode_attention

    q = jnp.zeros((1, 4, 1, 64), jnp.float32)
    k = jnp.zeros((1, 2, 128, 64), jnp.float32)
    k8 = k.astype(jnp.int8)
    s = jnp.zeros((1, 2, 128), jnp.float32)
    with pytest.raises(ValueError, match="BOTH"):
        decode_attention(q, k8, k8, 0, k_scale=s, interpret=True)
    with pytest.raises(ValueError, match="inconsistent"):
        decode_attention(q, k, k, 0, k_scale=s, v_scale=s, interpret=True)
    with pytest.raises(ValueError, match="inconsistent"):
        decode_attention(q, k8, k8, 0, interpret=True)


def test_init_cache_int8_layout():
    cfg = LlamaConfig.preset("debug", kv_quant="int8")
    cache = init_cache(cfg, 2, 32)
    assert cache["k"].dtype == jnp.int8
    assert cache["k_scale"].shape == cache["k"].shape[:-1]
    assert cache["k_scale"].dtype == jnp.float32


def test_config_rejects_unknown_kv_quant():
    with pytest.raises(ValueError, match="kv_quant"):
        LlamaConfig.preset("debug", kv_quant="fp8")


@pytest.fixture(scope="module")
def params():
    return init_params(jax.random.PRNGKey(0), LlamaConfig.preset("debug"))


def test_generate_int8_tracks_fp(params):
    """Aligned greedy generation: the int8 cache's tokens track the
    full-precision run (identical on the debug model at this seed; the
    assert allows a small divergence tail so the pin survives numerics
    drift in jax point releases)."""
    cfg_fp = LlamaConfig.preset("debug")
    cfg_q = LlamaConfig.preset("debug", kv_quant="int8")
    prompt = jnp.asarray(np.random.default_rng(0).integers(
        1, cfg_fp.vocab_size, (2, 16), dtype=np.int32))
    out_fp = generate(params, cfg_fp, prompt, 12)
    out_q = generate(params, cfg_q, prompt, 12)
    assert float((out_fp == out_q).mean()) >= 0.9


def test_generate_int8_ragged(params):
    """Ragged decode on an int8 cache: per-row cursors, per-row scale
    writes.  Row-equivalence contract: each row matches its own solo
    aligned run over the unpadded prompt."""
    cfg = LlamaConfig.preset("debug", kv_quant="int8")
    rng = np.random.default_rng(1)
    P = 12
    lengths = [5, 12]
    prompt = np.zeros((2, P), np.int32)
    for i, n in enumerate(lengths):
        prompt[i, :n] = rng.integers(1, cfg.vocab_size, n)
    out = generate(params, cfg, jnp.asarray(prompt), 6,
                   prompt_lengths=jnp.asarray(lengths, jnp.int32))
    for i, n in enumerate(lengths):
        solo = generate(params, cfg,
                        jnp.asarray(prompt[i:i + 1, :n]), 6)
        np.testing.assert_array_equal(np.asarray(out[i]),
                                      np.asarray(solo[0, n:]))


def test_generate_int8_rolling(params):
    """Sliding-window int8 decode: teacher-forcing through the rolling
    O(window) cache (circular writes of values AND scales) matches the
    full-size windowed int8 cache step by step — both paths quantize the
    same post-RoPE k/v, so only the softmax's key-summation order differs.
    Then the compiled generate path runs past the wrap point."""
    from starway_tpu.models.cache import init_rolling_cache
    from starway_tpu.models.generate import decode_step
    from starway_tpu.models.llama import rope_tables

    W = 5
    cfg = LlamaConfig.preset("debug", kv_quant="int8", sliding_window=W)
    B, S = 2, 14  # crosses the window: slots wrap twice
    tokens = jnp.asarray(np.random.default_rng(2).integers(
        1, cfg.vocab_size, (B, S), dtype=np.int32))
    rope = rope_tables(S, cfg.head_dim, cfg.rope_theta)
    rolling = init_rolling_cache(cfg, B)
    full = init_cache(cfg, B, S)
    for i in range(S):
        lr, rolling = decode_step(params, rolling, tokens[:, i], i, cfg,
                                  rope, rolling=True)
        lf, full = decode_step(params, full, tokens[:, i], i, cfg, rope)
        np.testing.assert_allclose(np.asarray(lr), np.asarray(lf),
                                   atol=2e-4, rtol=2e-4, err_msg=f"pos {i}")
    assert rolling["k"].shape[3] == W and rolling["k"].dtype == jnp.int8
    assert rolling["k_scale"].shape[3] == W

    out = generate(params, cfg, tokens[:, :8], 20)  # W < max_len -> rolling
    assert out.shape == (B, 28)


def test_prefill_rolling_int8_tracks_stepwise(params):
    """Quantized chunked prefill: the O(chunk + window) streaming path on
    an int8 rolling cache lands within one quantization bucket of the
    stepwise int8 decode (in-chunk attention is wide in the chunked path
    — the same choice the aligned prefill makes — so exact equality is
    not the contract; a <= 2-ulp int8 cache and close logits are)."""
    from starway_tpu.models.cache import init_rolling_cache
    from starway_tpu.models.generate import decode_step, prefill_rolling
    from starway_tpu.models.llama import rope_tables

    W, P = 6, 17
    cfg = LlamaConfig.preset("debug", kv_quant="int8", sliding_window=W)
    prompt = jnp.asarray(np.random.default_rng(4).integers(
        1, cfg.vocab_size, (2, P), dtype=np.int32))
    logits_c, cache_c = prefill_rolling(params, cfg, prompt, chunk=5)
    assert cache_c["k"].dtype == jnp.int8
    assert cache_c["k_scale"].shape == (cfg.n_layers, 2, cfg.n_kv_heads, W)

    cache_s = init_rolling_cache(cfg, 2)
    rope = rope_tables(P, cfg.head_dim, cfg.rope_theta)
    for i in range(P):
        logits_s, cache_s = decode_step(params, cache_s, prompt[:, i], i,
                                        cfg, rope, rolling=True)
    np.testing.assert_allclose(np.asarray(logits_c), np.asarray(logits_s),
                               atol=0.1, rtol=0.1)
    assert int(jnp.max(jnp.abs(
        cache_c["k"].astype(jnp.int32) - cache_s["k"].astype(jnp.int32)))) <= 2


def test_rolling_slotserver_int8_matches_primitive_oracle(params):
    """Sliding-window continuous batching on an int8 cache: every request
    matches a single-request loop over the SAME primitives
    (prefill_rolling + rolling decode_step + greedy sample) bit-exactly —
    the same oracle discipline as the fp rolling serving test."""
    from conftest import rolling_primitive_oracle

    cfg = LlamaConfig.preset("debug", kv_quant="int8", sliding_window=8)
    oracle = rolling_primitive_oracle(params, cfg)
    reqs = [([5, 1, 7, 2, 9, 4, 3, 8, 6, 2, 7], 6), ([3, 8], 9),
            ([1, 2, 3, 4, 5, 6, 7, 8, 9, 1, 2, 3], 4)]
    srv = SlotServer(params, cfg, n_slots=2, max_len=48, chunk=4)
    rids = [srv.submit(p, m) for p, m in reqs]
    done = srv.run()
    for rid, (prompt, max_new) in zip(rids, reqs):
        np.testing.assert_array_equal(
            done[rid], oracle(prompt, max_new, 48),
            err_msg=f"request {rid} (P={len(prompt)})")


def test_slotserver_int8_matches_generate(params):
    """Continuous batching over an int8 cache: every request's greedy
    continuation equals its standalone int8 generate() run (admission
    must write the scale leaves alongside k/v)."""
    cfg = LlamaConfig.preset("debug", kv_quant="int8")
    rng = np.random.default_rng(3)
    reqs = [(list(rng.integers(1, cfg.vocab_size, n)), m)
            for n, m in [(3, 6), (9, 4), (5, 8)]]
    srv = SlotServer(params, cfg, n_slots=2, max_len=64, chunk=4)
    rids = [srv.submit(p, m) for p, m in reqs]
    done = srv.run()
    for rid, (prompt, max_new) in zip(rids, reqs):
        want = generate(params, cfg, jnp.asarray([prompt], jnp.int32),
                        max_new)
        np.testing.assert_array_equal(
            done[rid], np.asarray(want[0, len(prompt):]),
            err_msg=f"request {rid}")
