"""Every Pallas entry point the serve and train paths reach, compiled by
the TPU's own compiler for a DESCRIBED v5e (no chip attached).

Interpret mode checks a kernel's numerics; ``lower(lowering_platforms=
("tpu",))`` stops before Mosaic.  Neither sees what the chip's compiler
refuses: a slice below the (8, 128) tile, a block shape it cannot lay
out, a program that does not fit 16 GB.  These cases ask it, at
published widths (llama3-8b: Hq 32, Hkv 8, D 128, d_ff 14336, vocab
128256; llama2-7b: MHA, n_rep 1), a second or less per kernel.  Nothing
runs, so they say nothing about results or speed -- chip_smoke.py does
that on the chip.

``jax.default_backend()`` still answers "cpu" here, so kernels are called
with ``interpret=False`` and whole programs are traced with that one
function patched to answer "tpu": the steering lives in this file, not in
an option of the program.
"""

import os

os.environ.setdefault("TPU_LOG_DIR", "disabled")  # else libtpu logs to /tmp

import pytest

import jax
import jax.numpy as jnp
from jax.sharding import SingleDeviceSharding

BF16, F32, I8, I32 = jnp.bfloat16, jnp.float32, jnp.int8, jnp.int32
# llama3-8b attention geometry; MHA is llama2-7b's.
HQ, HKV, D = 32, 8, 128
MHA = 32


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies

    try:
        return topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # no libtpu in this installation
        pytest.skip(f"cannot describe a v5e:2x2 topology: {e}")


@pytest.fixture(autouse=True)
def _no_persistent_cache():
    """A compile for a described chip is written to the persistent cache
    but cannot be read back without a chip (each later one warns and
    compiles again): keep it off around these tests."""
    from jax.experimental.compilation_cache import compilation_cache as cc

    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    cc.reset_cache()
    yield
    jax.config.update("jax_enable_compilation_cache", was)
    cc.reset_cache()


def _placed(args, sharding):
    return jax.tree_util.tree_map(
        lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype, sharding=sharding),
        args)


def _compile(fn, *args, sharding, **jit_kw):
    """Compile ``fn`` for ``args`` (pytrees of ShapeDtypeStructs), every
    leaf placed by ``sharding``."""
    return jax.jit(fn, **jit_kw).lower(*_placed(args, sharding)).compile()


def _s(shape, dtype):
    return jax.ShapeDtypeStruct(shape, dtype)


# ------------------------------------------------------------------ kernels


def _flash(hkv, *, window=None, grad=False, s=4096, hq=HQ, d=D):
    from starway_tpu.ops.pallas_attention import flash_attention

    def fwd(q, k, v):
        return flash_attention(q, k, v, causal=True, interpret=False,
                               window=window)

    def bwd(q, k, v):
        return jax.grad(lambda *a: fwd(*a).astype(F32).sum(),
                        argnums=(0, 1, 2))(q, k, v)

    kv = _s((1, hkv, s, d), BF16)
    return (bwd if grad else fwd), (_s((1, hq, s, d), BF16), kv, kv)


def _ring_step(*, bwd=False, causal=True, t=2048):
    """The per-step kernels ring and zigzag attention run on each shard
    (parallel/ring_attention.py: _step_fwd / _step_bwd); zigzag's
    provably-unmasked pair is the causal=False case."""
    from starway_tpu.ops.pallas_attention import (flash_partial,
                                                  flash_partial_bwd)

    q, kv = _s((1, HQ, t, D), BF16), _s((1, HKV, t, D), BF16)
    off = _s((), I32)
    if not bwd:
        return (lambda q, k, v, qo, ko: flash_partial(
            q, k, v, qo, ko, causal=causal, interpret=False)), (
                q, kv, kv, off, off)
    stat = _s((1, HQ, t), F32)
    return (lambda q, do, k, v, lse, dl, qo, ko: flash_partial_bwd(
        q, do, k, v, lse, dl, qo, ko, causal=causal, interpret=False)), (
            q, q, kv, kv, stat, stat, off, off)


def _decode(hkv, *, c=1, int8=False, window=None, b=8, t=2048, layers=None,
            hq=HQ, name="sw_decode_attn_stream", d=D, ring=False):
    """``layers``: the scan-stacked cache ``[layers, b, hkv, t, d]`` read
    through a traced layer index, as the serving chunk reads it.  ``ring``
    (with ``window``): a ring longer than its window, read under masks."""
    from starway_tpu.ops.pallas_decode import decode_attention

    lead = () if layers is None else (layers,)
    cache = _s(lead + (b, hkv, t, d), I8 if int8 else BF16)
    args = [_s((b, hq, c, d), BF16), cache, cache, _s((b,), I32)]
    if layers is not None:
        args.append(_s((), I32))
    if int8:
        args += [_s(lead + (b, hkv, t), F32)] * 2

    def fn(q, k, v, pos, *rest):
        layer, scales = ((None, rest) if layers is None
                         else (rest[0], rest[1:]))
        ks, vs = scales or (None, None)
        return decode_attention(q, k, v, pos, layer=layer, interpret=False,
                                window=window, k_scale=ks, v_scale=vs,
                                kernel_name=name, ring=ring)

    return fn, tuple(args)


def _ingest(width, *, pieces=1, slots=24, t=2048, layers=16):
    """``sw_ingest_attn``: a prompt's piece of ``width`` queries on its
    request's row of mistral7b.chat_closed's stacked cache (512 rows a kv
    head in tiles of 128 queries, the row named by an index)."""
    from starway_tpu.ops.pallas_decode import slot_attention

    cache = _s((layers, slots, HKV, t, D), BF16)
    at = _s((pieces,), I32)
    return (lambda q, k, v, pos, rows, layer: slot_attention(
        q, k, v, pos, rows, layer=layer, interpret=False)), (
            _s((pieces, HQ, width, D), BF16), cache, cache, at, at,
            _s((), I32))


def _kv_write(b, hkv, t, layers, d=D):
    """A decode step's new k and v of ``b`` slots into one layer of the
    stacked caches, in place."""
    from starway_tpu.ops.pallas_decode import kv_write

    cache, new = _s((layers, b, hkv, t, d), BF16), _s((b, hkv, 1, d), BF16)
    return (lambda k, v, nk, nv, layer, rows, pos: kv_write(
        (k, v), (nk, nv), layer, rows, pos, interpret=False)), (
            cache, cache, new, new, _s((), I32), _s((b,), I32), _s((b,), I32))


def _paged(page, *, b=8, max_len=2048):
    from starway_tpu.ops.pallas_paged import paged_decode_attention

    max_pages = max_len // page
    pool = _s((1 + b * max_pages, HKV, page, D), BF16)
    return (lambda q, k, v, t, p: paged_decode_attention(
        q, k, v, t, p, interpret=False)), (
            _s((b, HQ, 1, D), BF16), pool, pool, _s((b, max_pages), I32),
            _s((b,), I32))


def _gemv(d_in, d_out, m=8):
    from starway_tpu.ops.pallas_gemv import int8_matmul

    return (lambda x, w, s: int8_matmul(x, w, s, interpret=False)), (
        _s((m, d_in), BF16), _s((d_in, d_out), I8), _s((d_out,), F32))


# Kimi-K2's published widths: 64 heads of 128 + 64 over a 512 + 64 latent
# row, 7168 wide, experts 2048 wide, 12 held.
K2_H, K2_RANK, K2_ROPE, K2_D, K2_FE, K2_HELD = 64, 512, 64, 7168, 2048, 12


def _flash_latent(s=4096):
    """Expanded latent attention: 192-wide q/k over 128-wide values."""
    from starway_tpu.ops.pallas_attention import flash_attention

    qk = _s((1, K2_H, s, 128 + K2_ROPE), BF16)
    return (lambda q, k, v: flash_attention(
        q, k, v, causal=True, interpret=False, sm_scale=0.13)), (
            qk, qk, _s((1, K2_H, s, 128), BF16))


def _mla_decode(c=1, b=16, t=5120, layers=2, h=K2_H):
    from starway_tpu.ops.pallas_decode import mla_decode_attention

    w = 640  # 512 + 64 in whole lane tiles (LatentAttn.cache_width)
    return (lambda q, latent, pos, layer: mla_decode_attention(
        q, latent, pos, rank=K2_RANK, sm_scale=0.13, layer=layer,
        interpret=False)), (
            _s((b, h, c, w), BF16), _s((layers, b, 1, t, w), BF16),
            _s((b,), I32), _s((), I32))


def _latent_write(b=16, t=5120, layers=2):
    from starway_tpu.ops.pallas_decode import kv_write

    w = 640  # 512 + 64 in whole lane tiles (LatentAttn.cache_width)
    return (lambda cache, new, layer, rows, pos: kv_write(
        (cache,), (new,), layer, rows, pos, interpret=False)), (
            _s((layers, b, 1, t, w), BF16), _s((b, 1, 1, w), BF16),
            _s((), I32), _s((b,), I32), _s((b,), I32))


def _gmm(pairs, tile_m, k, n, gated, held=None, act="silu"):
    from starway_tpu.ops.pallas_gmm import gmm

    held = held or K2_HELD
    m = pairs + held * tile_m
    w = _s((held, k, n), BF16)
    args = (_s((m, k), BF16), w, _s((m // tile_m,), I32), _s((), I32))
    if gated:
        return (lambda x, w, te, nl, w2: gmm(
            x, w, te, nl, tile_m=tile_m, w2=w2, act=act,
            interpret=False)), args + (w,)
    return (lambda x, w, te, nl: gmm(
        x, w, te, nl, tile_m=tile_m, interpret=False)), args


def _kda_step(b=256, layers=6, h=32, d=128):
    from starway_tpu.ops.pallas_kda import kda_step_kernel

    vec = _s((b, h, d), F32)
    return (lambda st, q, k, v, g, beta, layer: kda_step_kernel(
        st, q, k, v, g, beta, layer=layer, interpret=False)), (
        _s((layers, b, h, d, d), F32), vec, vec, vec, vec, _s((b, h), F32),
        _s((), I32))


def _ssm_step(b=96, layers=9, e=5120, n=16):
    from starway_tpu.ops.pallas_ssm import ssm_step_kernel

    return (lambda st, dt, x, bm, cm, a, d, layer: ssm_step_kernel(
        st, dt, x, bm, cm, a, d, layer=layer, interpret=False)), (
        _s((layers, b, n, e), F32), _s((b, e), F32), _s((b, e), F32),
        _s((b, n), F32), _s((b, n), F32), _s((n, e), F32), _s((e,), F32),
        _s((), I32))


def _ssm_scan(positions=1024, e=5120, n=16):
    """One admission's bucket through the state-space recurrence."""
    from starway_tpu.ops.pallas_ssm import ssm_scan_kernel

    seq, col = _s((1, positions, e), F32), _s((1, positions, n), F32)
    return (lambda *a: ssm_scan_kernel(*a, interpret=False)), (
        seq, seq, col, col, _s((n, e), F32), _s((e,), F32))


def _kda_chunk(positions=1024, h=32, hk=None, d=128, by_head=False):
    """The fused prefill kernel on one admission's bucket: ``h`` value
    heads over ``hk`` key heads in the projections' layout, a decay a
    channel or (``by_head``) one a head."""
    from starway_tpu.ops.pallas_kda import kda_chunk_kernel

    keys = _s((1, positions, hk or h, d), F32)
    return (lambda *a: kda_chunk_kernel(*a, interpret=False)), (
        keys, keys, _s((1, positions, h, d), F32),
        _s((1, positions, h) if by_head else (1, positions, h, d), F32),
        _s((1, positions, h), F32))


KERNELS = {
    "flash_fwd": lambda: _flash(HKV),
    "flash_fwd_windowed": lambda: _flash(HKV, window=1024),
    "flash_bwd": lambda: _flash(HKV, grad=True),
    "flash_bwd_windowed": lambda: _flash(HKV, grad=True, window=1024),
    "flash_fwd_mha": lambda: _flash(MHA),
    "flash_bwd_mha": lambda: _flash(MHA, grad=True),
    "ring_step_fwd": lambda: _ring_step(),
    "ring_step_fwd_unmasked": lambda: _ring_step(causal=False),
    "ring_step_bwd": lambda: _ring_step(bwd=True),
    "ring_step_bwd_unmasked": lambda: _ring_step(bwd=True, causal=False),
    "decode_bf16": lambda: _decode(HKV),
    "decode_bf16_c4": lambda: _decode(HKV, c=4),
    "decode_bf16_windowed": lambda: _decode(HKV, window=1024),
    # mistral7b.chat_closed's own decode attention: 24 slots x 2048, one
    # layer of the 16 stacked, by a traced index.
    "decode_bf16_chat_closed": lambda: _decode(HKV, b=24, layers=16),
    # Lengths no block divides (_pick_block is None: the layer is sliced
    # out and padded to whole lane tiles; ROADMAP D11).
    "decode_bf16_padded": lambda: _decode(HKV, t=4104, layers=2),
    "decode_int8_padded": lambda: _decode(HKV, int8=True, t=2000, layers=2),
    "decode_bf16_mha": lambda: _decode(MHA),
    # k-exaone.think_closed: 96 slots verify two positions a step, 64 query
    # heads over 8 kv heads, on the window layers' masked rings of 256 =
    # window 128 + slack and on the full layers' rows of 4,096.  (With
    # decode_bf16_chat_closed, decode_longdoc_full / _ring and
    # decode_answer_full: the six shapes of scripts/kernel_bench.py's
    # ``decode_cells``.)
    "decode_think_ring": lambda: _decode(
        HKV, c=2, b=96, t=256, layers=6, hq=64, window=128, ring=True,
        name="sw_decode_attn_ring"),
    "decode_think_full": lambda: _decode(HKV, c=2, b=96, t=4096, layers=2,
                                         hq=64),
    # mistral7b's mixed chunk: a piece at each width the server ingests at.
    "ingest_attn_128": lambda: _ingest(128),
    "ingest_attn_256": lambda: _ingest(256),
    # Refused before PR 21: the [B*Hkv, T] scale operand was sliced one
    # row at a time, below the (8, 128) tile.
    "decode_int8": lambda: _decode(HKV, int8=True),
    "decode_int8_c4": lambda: _decode(HKV, int8=True, c=4),
    "decode_int8_windowed": lambda: _decode(HKV, int8=True, window=1024),
    "decode_int8_mha": lambda: _decode(MHA, int8=True),
    "paged_page16": lambda: _paged(16),
    "paged_page64": lambda: _paged(64),
    "paged_page128": lambda: _paged(128),
    # Every llama3-8b matmul shape the W8A16 tree routes through the gemv.
    "gemv_wq_wo": lambda: _gemv(4096, 4096),
    "gemv_wk_wv": lambda: _gemv(4096, 1024),
    "gemv_gate_up": lambda: _gemv(4096, 14336),
    "gemv_down": lambda: _gemv(14336, 4096),
    "gemv_lm_head": lambda: _gemv(4096, 128256),
    "flash_fwd_latent": lambda: _flash_latent(),
    "mla_decode": lambda: _mla_decode(),
    "mla_decode_c4": lambda: _mla_decode(c=4),
    "latent_write": lambda: _latent_write(),
    # 128 slots x 8 choices a decode step; a 4096-token admit.
    "gmm_gated_decode": lambda: _gmm(1024, 16, K2_D, K2_FE, True),
    "gmm_down_decode": lambda: _gmm(1024, 16, K2_FE, K2_D, False),
    "gmm_gated_admit": lambda: _gmm(32768, 128, K2_D, K2_FE, True),
    "gmm_down_admit": lambda: _gmm(32768, 128, K2_FE, K2_D, False),
    # smallthinker-21b.longdoc_closed: 48 slots, 28 query heads over 4 kv
    # heads; the full layers' rows of 16,384 and the window layers' rings
    # of 4,096 (the same kernel under the ring's name), their writes, and
    # 64 ReGLU experts of width 768: 48 x 6 pairs a decode step, a
    # 14,336-token admit's 86,016.
    "decode_longdoc_full": lambda: _decode(4, b=48, t=16384, layers=2, hq=28),
    "decode_longdoc_ring": lambda: _decode(4, b=48, t=4096, layers=6, hq=28,
                                           name="sw_decode_attn_ring"),
    "kv_write_longdoc_full": lambda: _kv_write(48, 4, 16384, 2),
    "kv_write_longdoc_ring": lambda: _kv_write(48, 4, 4096, 6),
    "gmm_relu_decode": lambda: _gmm(288, 16, 2560, 768, True, 64, "relu"),
    "gmm_relu_admit": lambda: _gmm(86016, 128, 2560, 768, True, 64, "relu"),
    "gmm_down_longdoc": lambda: _gmm(288, 16, 768, 2560, False, 64),
    # kimi-linear.reason_closed: 256 slots' state of 32 heads x 128 x 128
    # float32 in six stacked layers, by a traced index; the smallest and
    # the largest admission's whole chunked recurrence (a decay a channel)
    # in one kernel; 256 x 8 pairs a decode step on 16 held experts of
    # width 1024.
    "kda_step_reason": lambda: _kda_step(),
    "kda_chunk_reason_128": lambda: _kda_chunk(128),
    "kda_chunk_reason_4096": lambda: _kda_chunk(4096),
    "gmm_gated_reason": lambda: _gmm(2048, 16, 2304, 1024, True, 16),
    # qwen3-next.answer_closed: 16 query heads over 2 kv heads of 256 (8 a
    # kv head) in a 2,048-token admission's flash pass, the decode kernel
    # and the write over 192 slots' rows of 4,096; 192 slots' DeltaNet
    # state in six stacked layers; 192 x 10 pairs a decode step and a
    # 2,048-token admit's 20,480 on 128 held experts of width 512.
    "flash_fwd_answer": lambda: _flash(2, s=2048, hq=16, d=256),
    "decode_answer_full": lambda: _decode(2, b=192, t=4096, layers=2, hq=16,
                                          d=256),
    "kv_write_answer": lambda: _kv_write(192, 2, 4096, 2, d=256),
    "kda_step_answer": lambda: _kda_step(b=192),
    # ... and an admission's: 32 value heads read 16 key heads, one decay
    # a head.
    "kda_chunk_answer_256": lambda: _kda_chunk(256, hk=16, by_head=True),
    "kda_chunk_answer_4096": lambda: _kda_chunk(4096, hk=16, by_head=True),
    "gmm_gated_answer": lambda: _gmm(1920, 16, 2048, 512, True, 128),
    "gmm_down_answer": lambda: _gmm(1920, 16, 512, 2048, False, 128),
    "gmm_gated_answer_admit": lambda: _gmm(20480, 128, 2048, 512, True, 128),
    # k-exaone.think_closed: 96 slots x 2 verified rows x 8 choices a decode
    # step on 8 held experts of width 2,048 under 6,144: 12 pairs an expert
    # on average, so runs of two 16-row tiles, over 8 and 6 column blocks.
    "gmm_gated_think": lambda: _gmm(1536, 16, 6144, 2048, True, 8),
    "gmm_down_think": lambda: _gmm(1536, 16, 2048, 6144, False, 8),
    # phi4-mini-flash.cot_closed: 96 slots' Mamba state of 16 x 5,120
    # float32 in nine stacked layers, by a traced index; the smallest and
    # the largest admission's recurrence; 40 query rows over 10 PAIRS of
    # 64-wide kv heads (128 wide in the cache) in a 6,144-token admission's
    # flash pass, the decode kernel over the one full layer's rows of
    # 8,192 and the eight rings of 512, and their writes.
    "ssm_step_cot": lambda: _ssm_step(),
    "ssm_scan_cot_1024": lambda: _ssm_scan(1024),
    "ssm_scan_cot_6144": lambda: _ssm_scan(6144),
    "flash_fwd_cot": lambda: _flash(10, s=6144, hq=40),
    "decode_cot_full": lambda: _decode(10, b=96, t=8192, layers=1, hq=40),
    "decode_cot_ring": lambda: _decode(10, b=96, t=512, layers=8, hq=40,
                                       name="sw_decode_attn_ring"),
    "kv_write_cot_full": lambda: _kv_write(96, 10, 8192, 1),
    "kv_write_cot_ring": lambda: _kv_write(96, 10, 512, 8),
}


@pytest.mark.parametrize("name", list(KERNELS))
def test_kernel_compiles_for_v5e(topo, name):
    fn, args = KERNELS[name]()
    compiled = _compile(fn, *args,
                        sharding=SingleDeviceSharding(topo.devices[0]))
    assert "tpu_custom_call" in compiled.as_text()


def _kernel_module_text(fn, args):
    """The Mosaic module of the one Pallas kernel ``fn`` calls, printed
    without debug locations (lowered for the TPU; nothing is compiled)."""
    import base64
    import re

    from jax._src import tpu_custom_call  # noqa: F401  (registers dialects)
    from jax._src.lib import tpu
    from jax._src.lib.mlir import ir

    text = jax.jit(fn).trace(*args).lower(
        lowering_platforms=("tpu",)).as_text()
    (body,) = re.findall(r'body\\22: \\22([A-Za-z0-9+/=]+)', text)
    ctx = ir.Context()
    tpu.register_dialect(ctx)
    ctx.allow_unregistered_dialects = True
    with ctx:
        return ir.Module.parse(base64.b64decode(body)).operation.get_asm(
            enable_debug_info=False)


@pytest.mark.parametrize("case", ["decode", "decode_c2_masked_ring",
                                  "decode_int8"])
def test_decode_kernel_body_does_not_grow_with_the_heads(case):
    """What guards ``setup_s`` on the CPU: a warm start still traces and
    lowers the kernel once a call site of every program, so its body must
    not be unrolled over what a cell holds.  Eight kv heads a cell print a
    module no more than 1.3 times that of two (PR 41's unrolled body was
    refused for the seconds it cost every warm start: PERF.md section 6),
    and sixteen blocks a row the same module as four."""
    kw = {"decode": {}, "decode_int8": {"int8": True},
          "decode_c2_masked_ring": dict(c=2, window=128, ring=True, t=256)
          }[case]
    size = lambda hkv, **over: len(_kernel_module_text(
        *_decode(hkv, b=24, layers=4, hq=4 * hkv, **{**kw, **over})))
    two, eight = size(2), size(8)
    assert eight <= 1.3 * two, (two, eight)
    if "t" not in kw:
        assert abs(size(8, t=8192) - eight) <= 0.02 * eight


def test_latent_decode_kernel_body_does_not_grow_with_the_heads():
    """The same budget for ``sw_mla_decode_attn``, which is that kernel's
    body over one operand: Kimi-K2's 64 heads print a module no more than
    1.3 times that of 8 (the heads are rows of its two matmuls, nothing is
    unrolled over them), and sixteen blocks a row the same module as
    four."""
    size = lambda h, t=2048: len(_kernel_module_text(
        *_mla_decode(b=24, t=t, layers=4, h=h)))
    eight, k2 = size(8), size(K2_H)
    assert k2 <= 1.3 * eight, (eight, k2)
    assert abs(size(K2_H, t=8192) - k2) <= 0.02 * k2


@pytest.mark.parametrize("by_head", [False, True], ids=["a_channel", "a_head"])
def test_kda_chunk_kernel_body_is_one_size_at_every_bucket_and_head_count(
        by_head):
    """The start-up budget (PERF.md section 7): the fused kernel's body
    loops over the levels of its pairwise decays and the doublings of its
    solve, constants of the algorithm, and over nothing a shape sets.  Its
    printed module is the same at bucket 128 and at 4,096, for 2 heads and
    for 32 (but for the digits of the operands' shapes)."""
    size = lambda positions, h: len(_kernel_module_text(*_kda_chunk(
        positions, h, hk=h // 2 if by_head else h, by_head=by_head)))
    small = size(128, 2)
    assert abs(size(4096, 2) - small) <= 0.01 * small
    assert abs(size(128, 32) - small) <= 0.01 * small


# ----------------------------------------------------------- whole programs


def _llama3_8l(**kw):
    from starway_tpu.models import LlamaConfig

    return LlamaConfig.preset("llama3-8b", n_layers=8, **kw)


def _param_shapes(cfg):
    from starway_tpu.models import init_params

    return jax.eval_shape(lambda k: init_params(k, cfg), jax.random.PRNGKey(0))


def _as_tpu(monkeypatch):
    """Programs pick their TPU branches from jax.default_backend()."""
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")


N_SLOTS, MAX_LEN, CHUNK = 8, 2048, 8


def _slot_state(n_slots=N_SLOTS):
    return (_s((n_slots,), I32), _s((n_slots,), I32), _s((n_slots,), bool),
            _s((n_slots,), I32), jax.eval_shape(jax.random.PRNGKey, 0))


def _chunk_program(cfg, n_slots=N_SLOTS):
    from starway_tpu.models.cache import init_cache
    from starway_tpu.models.serving import _compiled_chunk

    run = _compiled_chunk(cfg, n_slots, MAX_LEN, CHUNK, 0.0, None, None, None)
    cache = jax.eval_shape(lambda: init_cache(cfg, n_slots, MAX_LEN))
    return run, (_param_shapes(cfg), cache, *_slot_state(n_slots))


def _ingest_chunk_program(cfg, width, n_slots=N_SLOTS):
    """The mixed chunk (models/serving.py): the decode chunk whose steps
    also carry a prompt piece of ``width`` tokens."""
    from starway_tpu.models.serving import (PIECE_FIELDS,
                                            _compiled_ingest_chunk)

    run = _compiled_ingest_chunk(cfg, n_slots, MAX_LEN, CHUNK, width, 0.0,
                                 None, None, None)
    _plain, args = _chunk_program(cfg, n_slots)
    return run, (*args, _s((CHUNK, len(PIECE_FIELDS)), I32),
                 _s((CHUNK, width), I32))


def _admit_program(cfg, bucket=2048):
    from starway_tpu.models.cache import init_cache
    from starway_tpu.models.serving import _compiled_admit

    run = _compiled_admit(cfg, bucket, 0.0, None, None)
    cache = jax.eval_shape(lambda: init_cache(cfg, N_SLOTS, MAX_LEN))
    return run, (_param_shapes(cfg), cache, _s((1, bucket), I32), _s((), I32),
                 _s((), I32), jax.eval_shape(jax.random.PRNGKey, 0))


def _paged_chunk_program(cfg, page=64):
    from starway_tpu.models.paged import _compiled_paged_chunk, init_paged_pool

    max_pages = MAX_LEN // page
    run = _compiled_paged_chunk(cfg, MAX_LEN, CHUNK, 0.0, None, None, None)
    pool = jax.eval_shape(
        lambda: init_paged_pool(cfg, 1 + N_SLOTS * max_pages, page))
    return run, (_param_shapes(cfg), pool, _s((N_SLOTS, max_pages), I32),
                 *_slot_state())


def _memory_gb(compiled):
    m = compiled.memory_analysis()
    return (m.argument_size_in_bytes + m.output_size_in_bytes
            + m.temp_size_in_bytes - m.alias_size_in_bytes) / 1e9


def _compile_program(topo, build, cfg, **kw):
    run, args = build(cfg, **kw)
    # The programs are jitted already (donation included): lower them as
    # the servers call them.
    return run.lower(
        *_placed(args, SingleDeviceSharding(topo.devices[0]))).compile()


def test_slot_server_decode_chunk_compiles_for_v5e(topo, monkeypatch):
    """The whole SlotServer decode-chunk program (models/serving.py) at
    llama3-8b widths, 8 layers, 8 slots x 2048 -- what chip_smoke.py
    phase c runs -- fits one 16 GB chip and holds the decode kernel."""
    _as_tpu(monkeypatch)
    compiled = _compile_program(topo, _chunk_program, _llama3_8l())
    assert "tpu_custom_call" in compiled.as_text()
    assert _memory_gb(compiled) < 15.75


# The benchmark's serving cells (benchmark/configs/mistral7b.json):
# Mistral-7B widths (the attention geometry above, d_ff 14336, vocab
# 32000), 16 layers, 24 slots x 2048, chunk 8.  Shapes only.
CELL_SLOTS = 24


def _mistral7b_16l(**kw):
    from starway_tpu.models import LlamaConfig

    return LlamaConfig(vocab_size=32000, d_model=4096, n_layers=16,
                       n_heads=HQ, n_kv_heads=HKV, d_ff=14336,
                       rope_theta=1e6, dtype="bfloat16", **kw)


def _cache_moves(text, n_layers, n_slots):
    """Instructions of the compiled program whose result has the stacked
    k/v cache's shape or one layer's, and that are not the carry handed
    on as it is (parameters, tuples, the loops, bitcasts) or a kernel
    that takes it in place: copies, scatters, dynamic slices and updates,
    and the fusions XLA makes of them, by whatever name."""
    import re

    layer = f"{n_slots},{HKV},{MAX_LEN},{D}]"
    shaped = re.compile(
        rf"^\s*(?:ROOT )?%?([\w.\-]+) = \w+\[(?:{n_layers},|1,)?"
        + re.escape(layer) + r"\S* ([\w\-]+)\(")
    passes = {"parameter", "get-tuple-element", "bitcast", "custom-call"}
    return [(m.group(1), m.group(2)) for m in map(shaped.match,
                                                  text.splitlines())
            if m and m.group(2) not in passes]


@pytest.mark.parametrize("kv", ["bf16", "int8"])
def test_decode_chunk_moves_no_cache_for_v5e(topo, monkeypatch, kv):
    """The decode chunk at the serving cells' geometry: the stacked cache
    rides the scans' carries and only kernels touch it.  (a) Temporaries
    stay under half the cache (the parent held a second whole cache: 4.23
    GB against 3.22); (b) no instruction produces a cache-shaped or
    layer-shaped array by copying, slicing or scattering (the parent:
    dynamic-slice and dynamic-update-slice fusions a layer, two whole
    copies a step; the XLA-only in-place forms re-lay the carry and copy
    it every layer, which no CPU test shows)."""
    _as_tpu(monkeypatch)
    cfg = _mistral7b_16l(**({"kv_quant": "int8"} if kv == "int8" else {}))
    compiled = _compile_program(topo, _chunk_program, cfg,
                                n_slots=CELL_SLOTS)
    text = compiled.as_text()
    assert "sw_kv_write" in text and "sw_decode_attn_stream" in text
    from starway_tpu.models.cache import init_cache

    cache_bytes = sum(  # 3.22 GB bf16; 1.66 GB int8 with its f32 scales
        a.size * a.dtype.itemsize for a in jax.tree_util.tree_leaves(
            jax.eval_shape(lambda: init_cache(cfg, CELL_SLOTS, MAX_LEN))))
    assert compiled.memory_analysis().temp_size_in_bytes < cache_bytes / 2
    assert _cache_moves(text, 16, CELL_SLOTS) == []


def _ingest_widths():
    from starway_tpu.models.serving import INGEST_WIDTHS

    return INGEST_WIDTHS


@pytest.mark.parametrize("width", _ingest_widths())
def test_ingest_chunk_moves_no_cache_for_v5e(topo, monkeypatch, width):
    """Every ``serve_decode_chunk_ingest_<W>`` at the serving cells'
    geometry (mistral7b, 24 x 2048): the piece's write and its attention
    are kernels on the stacked cache too (``sw_kv_write``,
    ``sw_ingest_attn`` through a row index), so the mixed chunk holds no
    copy of the cache either -- no cache-shaped or layer-shaped array is
    produced by anything but a kernel -- and its temporaries (printed;
    the plain chunk has 806,048,768 B) stay under half the cache."""
    _as_tpu(monkeypatch)
    cfg = _mistral7b_16l()
    compiled = _compile_program(topo, _ingest_chunk_program, cfg,
                                width=width, n_slots=CELL_SLOTS)
    text = compiled.as_text()
    assert all(name in text for name in (
        "sw_kv_write", "sw_decode_attn_stream", "sw_ingest_attn"))
    from starway_tpu.models.cache import init_cache

    cache_bytes = sum(
        a.size * a.dtype.itemsize for a in jax.tree_util.tree_leaves(
            jax.eval_shape(lambda: init_cache(cfg, CELL_SLOTS, MAX_LEN))))
    temp = compiled.memory_analysis().temp_size_in_bytes
    print(f"serve_decode_chunk_ingest_{width}: temporaries {temp} B")
    assert temp < cache_bytes / 2
    assert _cache_moves(text, 16, CELL_SLOTS) == []


@pytest.mark.parametrize("kv", ["bf16", "int8"])
def test_decode_chunk_fits_32_slots_on_v5e(topo, monkeypatch, kv):
    """32 slots x 2048 at the cells' widths: refused by the chip's
    compiler in PR 23 (16.49 of 15.75 GiB, the cache counted twice)."""
    _as_tpu(monkeypatch)
    cfg = _mistral7b_16l(**({"kv_quant": "int8"} if kv == "int8" else {}))
    compiled = _compile_program(topo, _chunk_program, cfg, n_slots=32)
    m = compiled.memory_analysis()
    assert (m.argument_size_in_bytes + m.output_size_in_bytes
            + m.temp_size_in_bytes - m.alias_size_in_bytes) < 15.75 * 2**30


def _cell_model(name):
    """(program config, abstract weights, n_slots, max_len) of a serving
    cell of the benchmark, from its own files (shapes only)."""
    from benchmark.harness import spec as S

    config = S.load_config(S.load_spec(), name)
    runner = S.load_runner(config["runner"])
    if config["runner"] == "serve":
        from benchmark.harness import weights as W

        cfg = runner.llama_config(config)
    else:
        import importlib

        W = importlib.import_module(
            "benchmark.harness.weights_" + config["runner"][len("serve_"):])
        cfg = runner.model_config(config)
    params = jax.eval_shape(
        lambda: runner.program_tree(W.make_model(0, W.dims(config))))
    return cfg, params, config["serve"]["n_slots"], config["serve"]["max_len"]


@pytest.mark.parametrize("cell,bucket", [("mistral7b", 512), ("kimi-k2", 1024)])
def test_admission_programs_at_the_cells_sizes_for_v5e(topo, monkeypatch, cell,
                                                       bucket):
    """An admission on the device, at the benchmark's sizes (mistral7b 24 x
    2048, kimi-k2 128 x 5120): the admit program takes the donated cache
    and hands the same buffer on (all of it aliased, no cache-sized
    temporary, no copy of a cache-shaped array), and ``serve_seat``, the
    program that follows it, returns the slot state -- four ``[n_slots]``
    vectors -- from a token that never left the device, and takes no
    cache."""
    import re

    from starway_tpu.models.cache import init_cache
    from starway_tpu.models.serving import _compiled_admit, _seat

    _as_tpu(monkeypatch)
    one = SingleDeviceSharding(topo.devices[0])
    cfg, params, n_slots, max_len = _cell_model(cell)
    cache = jax.eval_shape(lambda: init_cache(cfg, n_slots, max_len))
    cache_bytes = sum(a.size * a.dtype.itemsize
                      for a in jax.tree_util.tree_leaves(cache))
    args = (params, cache, _s((1, bucket), I32), _s((), I32), _s((), I32),
            jax.eval_shape(jax.random.PRNGKey, 0))
    admit = _compiled_admit(cfg, bucket, 0.0, None, None).lower(
        *_placed(args, one)).compile()
    m = admit.memory_analysis()
    assert m.alias_size_in_bytes == cache_bytes
    assert m.output_size_in_bytes - cache_bytes < 4096   # + the token
    assert m.temp_size_in_bytes < cache_bytes / 4
    shapes = {"[" + ",".join(map(str, leaf.shape)) + "]"
              for leaf in jax.tree_util.tree_leaves(cache)}
    copies = [line for line in admit.as_text().splitlines()
              if re.search(r"\bcopy(-start)?\(", line)
              and any(shape in line.split(" = ", 1)[-1].split("(", 1)[0]
                      for shape in shapes)]
    assert copies == []

    state = (_s((n_slots,), I32), _s((n_slots,), I32), _s((n_slots,), bool),
             _s((n_slots,), I32))
    seat = _seat.lower(*_placed((*state, _s((), I32), _s((4,), I32)),
                                one)).compile()
    assert [(o.shape, o.dtype) for o in seat.out_info] == [
        (a.shape, a.dtype) for a in state]
    assert seat.memory_analysis().argument_size_in_bytes < 4096


@pytest.mark.parametrize("cell", ["kimi-linear", "qwen3-next"])
def test_linear_layers_admit_in_one_kernel_a_layer_for_v5e(topo, monkeypatch,
                                                           cell):
    """A 1,024-token admission of the two linear-state cells: inside the
    layer's scope ``sw_kda_chunk`` nothing loops (the parent walked the
    chunks in a ``lax.map`` in front of its carry kernel) and no float32
    array a position as wide as a head's q (``[.., 1024, 128]`` by 32
    heads, head-major: the parent's ``moveaxis`` copies and its five
    ``[B, H, N, C, d]`` operands) is made there.  With a decay a channel
    the program holds no ``[.., C, C]`` float32 array either (``A``, ``P``
    and the decays between two positions live and die in VMEM); with a
    decay a head those two are what lax hands the kernel."""
    import re

    from starway_tpu.models.cache import init_cache
    from starway_tpu.models.serving import _compiled_admit

    _as_tpu(monkeypatch)
    cfg, params, n_slots, max_len = _cell_model(cell)
    cache = jax.eval_shape(lambda: init_cache(cfg, n_slots, max_len))
    args = (params, cache, _s((1, 1024), I32), _s((), I32), _s((), I32),
            jax.eval_shape(jax.random.PRNGKey, 0))
    text = _compiled_admit(cfg, 1024, 0.0, None, None).lower(*_placed(
        args, SingleDeviceSharding(topo.devices[0]))).compile().as_text()
    scope = [line for line in text.splitlines() if "/sw_kda_chunk/" in line]
    assert any('custom_call_target="tpu_custom_call"' in line
               for line in scope)
    assert [line for line in scope if " while(" in line] == []
    assert [line for line in scope
            if re.search(r"= f32\[1,32,(1024|16,64),128\]", line)] == []
    square = re.findall(r"f32\[(?:\d+,)*64,64\]", text)
    assert (square == []) == (cell == "kimi-linear"), square[:4]


def test_two_cache_kinds_ride_the_decode_chunk_for_v5e(topo, monkeypatch):
    """smallthinker-21b.longdoc_closed's decode chunk at the cell's shapes
    (48 slots; the 2 full layers' rows of 16,384 beside the 6 window
    layers' rings of 4,096): both kinds of leaves ride the scans' carries,
    written in place and read by one decode kernel under two names; no
    instruction copies, slices or scatters an array of either cache's
    shape or of one layer of it, and the temporaries are a small part of
    either kind (the XLA-only write forms would copy a whole cache a
    layer, which no CPU test shows)."""
    import re

    from starway_tpu.models.cache import init_cache
    from starway_tpu.models.serving import _compiled_chunk

    _as_tpu(monkeypatch)
    cfg, params, n_slots, max_len = _cell_model("smallthinker-21b")
    assert (n_slots, max_len) == (48, 16384)
    cache = jax.eval_shape(lambda: init_cache(cfg, n_slots, max_len))
    assert {k: v.shape for k, v in cache.items()} == {
        "k": (2, 48, 4, 16384, 128), "v": (2, 48, 4, 16384, 128),
        "k_ring": (6, 48, 4, 4096, 128), "v_ring": (6, 48, 4, 4096, 128)}
    run = _compiled_chunk(cfg, n_slots, max_len, CHUNK, 0.0, None, None, None)
    compiled = run.lower(*_placed(
        (params, cache, *_slot_state(n_slots)),
        SingleDeviceSharding(topo.devices[0]))).compile()
    text = compiled.as_text()
    for name in ("sw_kv_write", "sw_decode_attn_stream",
                 "sw_decode_attn_ring", "sw_moe_gmm"):
        assert name in text, name
    ring_bytes = cache["k_ring"].size * 2
    m = compiled.memory_analysis()
    assert m.temp_size_in_bytes < ring_bytes / 4      # 0.2 GB of 1.2
    assert m.alias_size_in_bytes == sum(
        a.size * 2 for a in jax.tree_util.tree_leaves(cache))
    moved = []
    for layers, t in ((2, 16384), (6, 4096)):
        shaped = re.compile(
            rf"^\s*(?:ROOT )?%?([\w.\-]+) = \w+\[(?:{layers},|1,)?"
            + re.escape(f"{n_slots},4,{t},128]") + r"\S* ([\w\-]+)\(")
        moved += [(mm.group(1), mm.group(2))
                  for mm in map(shaped.match, text.splitlines())
                  if mm and mm.group(2) not in {
                      "parameter", "get-tuple-element", "bitcast",
                      "custom-call"}]
    assert moved == []


def test_draft_and_verify_chunk_moves_no_rows_or_rings_for_v5e(topo,
                                                               monkeypatch):
    """k-exaone.think_closed's decode chunk at the cell's shapes (the 2
    full layers' rows of 4,096, the 6 window layers' masked rings of 256 =
    window 128 + slack, and the MTP block's own row), sampled as the cell
    samples and emitting log-probabilities: a step verifies two positions
    a slot, so a ring takes TWO writes a layer and is read under position
    masks, and the block's rows leave the cache dict for its own layer
    scan and come back.  All three kinds ride the scans' carries in place:
    every cache byte is aliased and no instruction copies, slices or
    scatters an array of any kind's shape or of one layer of it.  The
    step's three nucleus filters (top-p 0.95 over 96 x 19,200) search the
    logits' ordered bits: no ``sort`` over the vocabulary is compiled in."""
    import re

    from benchmark.harness import spec as S
    from benchmark.harness import weights_k_exaone as W
    from conftest import sorts_over
    from starway_tpu.models.cache import init_cache
    from starway_tpu.models.serving import _compiled_chunk

    _as_tpu(monkeypatch)
    config = S.load_config(S.load_spec(), "k-exaone")
    runner = S.load_runner(config["runner"])
    cfg, sv = runner.model_config(config), config["serve"]
    params = jax.eval_shape(
        lambda: runner.program_tree(W.make_model(0, W.dims(config))))
    n, max_len = sv["n_slots"], sv["max_len"]
    cache = jax.eval_shape(lambda: init_cache(cfg, n, max_len))
    assert {k: v.shape for k, v in cache.items()} == {
        "k": (2, n, 8, 4096, 128), "v": (2, n, 8, 4096, 128),
        "k_ring": (6, n, 8, 256, 128), "v_ring": (6, n, 8, 256, 128),
        "k_mtp": (1, n, 8, 4096, 128), "v_mtp": (1, n, 8, 4096, 128)}
    run = _compiled_chunk(cfg, n, max_len, sv["chunk"], sv["temperature"],
                          None, sv["top_p"], None, logprobs=True)
    draft = (_s((n,), I32), _s((n, cfg.vocab_size), F32), _s((n,), F32))
    compiled = run.lower(*_placed(
        (params, cache, *_slot_state(n), draft),
        SingleDeviceSharding(topo.devices[0]))).compile()
    text = compiled.as_text()
    for name in ("sw_kv_write", "sw_decode_attn_stream",
                 "sw_decode_attn_ring", "sw_moe_gmm"):
        assert name in text, name
    assert (sv["temperature"], sv["top_p"], cfg.vocab_size) == (
        1.0, 0.95, 19200)
    assert sorts_over(text, 19200) == []
    m = compiled.memory_analysis()
    assert m.alias_size_in_bytes >= sum(
        a.size * 2 for a in jax.tree_util.tree_leaves(cache))
    moved = []
    for t in (4096, 256):
        shaped = re.compile(
            r"^\s*(?:ROOT )?%?([\w.\-]+) = \w+\[(?:\d+,)?"
            + re.escape(f"{n},8,{t},128]") + r"\S* ([\w\-]+)\(")
        moved += [(mm.group(1), mm.group(2))
                  for mm in map(shaped.match, text.splitlines())
                  if mm and mm.group(2) not in {
                      "parameter", "get-tuple-element", "bitcast",
                      "custom-call"}]
    assert moved == []


def test_state_without_positions_rides_the_decode_chunk_for_v5e(topo,
                                                                monkeypatch):
    """kimi-linear.reason_closed's decode chunk at the cell's shapes (the
    6 linear layers' state of 256 slots beside the 2 latent layers' rows
    of 6,144): all three kinds of leaves ride the scans' carries; the
    state is moved in place by ``sw_kda_step`` and no instruction copies
    an array of the state's or of the latent rows' shape, whole or one
    layer of it (a second copy of the state would be 3.2 GB)."""
    import re

    from starway_tpu.models.cache import init_cache
    from starway_tpu.models.serving import _compiled_chunk

    _as_tpu(monkeypatch)
    cfg, params, n_slots, max_len = _cell_model("kimi-linear")
    assert max_len == 6144
    cache = jax.eval_shape(lambda: init_cache(cfg, n_slots, max_len))
    assert {k: v.shape for k, v in cache.items()} == {
        "ckv": (2, n_slots, 1, 6144, 640),
        "kda_state": (6, n_slots, 32, 128, 128),
        "kda_conv": (6, n_slots, 3, 3 * 4096)}
    run = _compiled_chunk(cfg, n_slots, max_len, CHUNK, 0.0, None, None, None)
    compiled = run.lower(*_placed(
        (params, cache, *_slot_state(n_slots)),
        SingleDeviceSharding(topo.devices[0]))).compile()
    text = compiled.as_text()
    for name in ("sw_kda_step", "sw_mla_decode_attn", "sw_kv_write",
                 "sw_moe_gmm"):
        assert name in text, name
    state_bytes = cache["kda_state"].size * 4
    m = compiled.memory_analysis()
    assert m.temp_size_in_bytes < state_bytes / 4        # 0.4 GB of 3.2
    assert m.alias_size_in_bytes == sum(
        a.size * a.dtype.itemsize for a in jax.tree_util.tree_leaves(cache))
    moved = []
    for shape in (f"{n_slots},32,128,128]", f"{n_slots},1,6144,640]"):
        shaped = re.compile(
            r"^\s*(?:ROOT )?%?([\w.\-]+) = \w+\[(?:\d+,)?"
            + re.escape(shape) + r"\S* ([\w\-]+)\(")
        moved += [(mm.group(1), mm.group(2))
                  for mm in map(shaped.match, text.splitlines())
                  if mm and mm.group(2) not in {
                      "parameter", "get-tuple-element", "bitcast",
                      "custom-call"}]
    assert moved == []


def test_state_beside_grouped_query_rows_rides_the_decode_chunk_for_v5e(
        topo, monkeypatch):
    """qwen3-next.answer_closed's decode chunk at the cell's shapes (the 6
    DeltaNet layers' state of 192 slots beside the 2 gated-attention
    layers' k / v rows of 4,096 at 256-wide heads): state and rows ride
    the scans' carries; the state is moved in place by ``sw_kda_step``
    (fed one decay a head as a decay a channel), the rows are written by
    ``sw_kv_write`` and read by ``sw_decode_attn_stream``, and no
    instruction copies an array of the state's or of the rows' shape,
    whole or one layer of it (a second copy of the state would be 2.4 GB,
    of the rows 3.2)."""
    import re

    from starway_tpu.models.cache import init_cache
    from starway_tpu.models.serving import _compiled_chunk

    _as_tpu(monkeypatch)
    cfg, params, n_slots, max_len = _cell_model("qwen3-next")
    assert max_len == 4096
    cache = jax.eval_shape(lambda: init_cache(cfg, n_slots, max_len))
    assert {k: v.shape for k, v in cache.items()} == {
        "k": (2, n_slots, 2, 4096, 256), "v": (2, n_slots, 2, 4096, 256),
        "kda_state": (6, n_slots, 32, 128, 128),
        "kda_conv": (6, n_slots, 3, 2048 + 2048 + 4096)}
    run = _compiled_chunk(cfg, n_slots, max_len, CHUNK, 0.0, None, None, None)
    compiled = run.lower(*_placed(
        (params, cache, *_slot_state(n_slots)),
        SingleDeviceSharding(topo.devices[0]))).compile()
    text = compiled.as_text()
    for name in ("sw_kda_step", "sw_decode_attn_stream", "sw_kv_write",
                 "sw_moe_gmm"):
        assert name in text, name
    state_bytes = cache["kda_state"].size * 4
    m = compiled.memory_analysis()
    assert m.temp_size_in_bytes < state_bytes / 4        # 0.16 GB of 2.4
    assert m.alias_size_in_bytes == sum(
        a.size * a.dtype.itemsize for a in jax.tree_util.tree_leaves(cache))
    assert (m.argument_size_in_bytes + m.temp_size_in_bytes) < 15.75 * 2**30
    moved = []
    for shape in (f"{n_slots},32,128,128]", f"{n_slots},2,4096,256]"):
        shaped = re.compile(
            r"^\s*(?:ROOT )?%?([\w.\-]+) = \w+\[(?:\d+,)?"
            + re.escape(shape) + r"\S* ([\w\-]+)\(")
        moved += [(mm.group(1), mm.group(2))
                  for mm in map(shaped.match, text.splitlines())
                  if mm and mm.group(2) not in {
                      "parameter", "get-tuple-element", "bitcast",
                      "custom-call"}]
    assert moved == []


def _moved(text, shapes):
    """Instructions of a compiled program's text that copy, slice or
    scatter an array of one of ``shapes`` (each the tail of a leaf's shape
    from the slot axis on), whole or one layer of it."""
    import re

    moved = []
    for shape in shapes:
        shaped = re.compile(r"^\s*(?:ROOT )?%?([\w.\-]+) = \w+\[(?:\d+,)?"
                            + re.escape(shape) + r"\S* ([\w\-]+)\(")
        moved += [(mm.group(1), mm.group(2))
                  for mm in map(shaped.match, text.splitlines())
                  if mm and mm.group(2) not in {
                      "parameter", "get-tuple-element", "bitcast",
                      "custom-call"}]
    return moved


def test_state_rings_and_rows_ride_the_decode_chunk_for_v5e(topo, monkeypatch):
    """phi4-mini-flash.cot_closed's decode chunk at the cell's shapes, the
    WHOLE model (32 layers, the 200,064-row tied table): the 9 Mamba
    layers' state, the 8 window layers' rings of 512 and the ONE full
    layer's rows of 8,192 ride the scans' carries; the state is moved in
    place by ``sw_ssm_step``, rings and rows are written by ``sw_kv_write``
    and read by one decode kernel under two names (the rows by eight
    layers: layer 17 and the seven cross layers, which keep nothing); no
    instruction copies an array of the state's, the rings' or the rows'
    shape, whole or one layer of it; the table is there ONCE (the head
    contracts over its ``D`` axis: a transposed copy would be 1.02 GB, and
    the arguments are the weights' and the cache's bytes and nothing
    else); and the 32 layers are at most FIVE loops with the chunk's own
    scan over its steps (a run of whole periods one body), not 32."""
    import re

    from starway_tpu.models.cache import init_cache
    from starway_tpu.models.serving import _compiled_chunk

    _as_tpu(monkeypatch)
    cfg, params, n_slots, max_len = _cell_model("phi4-mini-flash")
    assert max_len == 8192 and "lm_head" not in params
    cache = jax.eval_shape(lambda: init_cache(cfg, n_slots, max_len))
    assert {k: v.shape for k, v in cache.items()} == {
        "k": (1, n_slots, 10, 8192, 128), "v": (1, n_slots, 10, 8192, 128),
        "k_ring": (8, n_slots, 10, 512, 128),
        "v_ring": (8, n_slots, 10, 512, 128),
        "ssm_state": (9, n_slots, 16, 5120),
        "ssm_conv": (9, n_slots, 3, 5120)}
    run = _compiled_chunk(cfg, n_slots, max_len, CHUNK, 0.0, None, None, None)
    compiled = run.lower(*_placed(
        (params, cache, *_slot_state(n_slots)),
        SingleDeviceSharding(topo.devices[0]))).compile()
    text = compiled.as_text()
    for name in ("sw_ssm_step", "sw_decode_attn_stream",
                 "sw_decode_attn_ring", "sw_kv_write"):
        assert name in text, name
    size = lambda t: sum(a.size * a.dtype.itemsize
                         for a in jax.tree_util.tree_leaves(t))
    m = compiled.memory_analysis()
    assert m.alias_size_in_bytes == size(cache)
    assert m.temp_size_in_bytes < size(cache["k_ring"]) / 2    # 0.4 GB of 1.0
    assert (m.argument_size_in_bytes + m.temp_size_in_bytes) < 15.75 * 2**30
    # one table: the arguments are the tree, the cache and five vectors
    assert m.argument_size_in_bytes < size(params) + size(cache) + 2**20
    assert not re.search(r"= bf16\[(200064,2560|2560,200064)\]\S* "
                         r"(copy|transpose)\(", text)
    assert _moved(text, (f"{n_slots},16,5120]", f"{n_slots},10,512,128]",
                         f"{n_slots},10,8192,128]")) == []
    assert len(re.findall(r" while\(", text)) <= 5


@pytest.mark.parametrize("bucket", [1024, 6144])
def test_early_exit_admit_at_the_cells_size_for_v5e(topo, monkeypatch, bucket):
    """phi4-mini-flash.cot_closed's admissions, the smallest and the
    largest bucket, into the cell's cache: layers 0-16 over the bucket
    (``sw_ssm_scan`` a Mamba layer, the flash kernel a window layer), layer
    17's k / v over the bucket and its attention, like layers 18-31, for
    the prompt's last row through the DECODE kernel; the state is seated
    whole, no leaf of the cache is copied, the table is there once, and
    the 32 layers are at most five loops."""
    import re

    from starway_tpu.models.cache import init_cache
    from starway_tpu.models.serving import _compiled_admit

    _as_tpu(monkeypatch)
    cfg, params, n_slots, max_len = _cell_model("phi4-mini-flash")
    cache = jax.eval_shape(lambda: init_cache(cfg, n_slots, max_len))
    run = _compiled_admit(cfg, bucket, 0.0, None, None)
    compiled = run.lower(*_placed(
        (params, cache, _s((1, bucket), I32), _s((), I32), _s((), I32),
         jax.eval_shape(jax.random.PRNGKey, 0)),
        SingleDeviceSharding(topo.devices[0]))).compile()
    text = compiled.as_text()
    for name in ("sw_ssm_scan", "sw_flash_fwd", "sw_decode_attn_stream"):
        assert name in text, name
    size = lambda t: sum(a.size * a.dtype.itemsize
                         for a in jax.tree_util.tree_leaves(t))
    m = compiled.memory_analysis()
    assert m.alias_size_in_bytes == size(cache)
    assert (m.argument_size_in_bytes + m.temp_size_in_bytes) < 15.75 * 2**30
    assert not re.search(r"= bf16\[(200064,2560|2560,200064)\]\S* "
                         r"(copy|transpose)\(", text)
    # the slot's entries are seated by in-place dynamic-update-slices
    assert [op for _name, op in _moved(text, (
        f"{n_slots},16,5120]", f"{n_slots},10,512,128]",
        f"{n_slots},10,8192,128]")) if op == "copy"] == []
    assert len(re.findall(r" while\(", text)) <= 5


@pytest.mark.parametrize("which", ["step", "scan"])
def test_ssm_kernel_bodies_do_not_grow_with_what_they_walk(which):
    """What guards ``setup_s`` on the CPU (PERF.md section 7's start-up
    rule): rows and tokens are loops of the kernel's body, not unrolled
    into it, so 96 slots print the module of 8 and a 6,144-token bucket
    the module of 1,024."""
    if which == "step":
        size = lambda b: len(_kernel_module_text(*_ssm_step(b=b)))
        small, large = size(8), size(96)
    else:
        size = lambda s: len(_kernel_module_text(*_ssm_scan(s)))
        small, large = size(1024), size(6144)
    assert abs(large - small) <= 0.02 * small, (small, large)


@pytest.mark.slow
@pytest.mark.parametrize("name,build,kw", [
    ("chunk_int8", _chunk_program, dict(kv_quant="int8")),
    ("admit_2048", _admit_program, {}),
    ("admit_2048_int8", _admit_program, dict(kv_quant="int8")),
    ("paged_chunk", _paged_chunk_program, {}),
])
def test_serving_program_compiles_for_v5e(topo, monkeypatch, name, build, kw):
    _as_tpu(monkeypatch)
    compiled = _compile_program(topo, build, _llama3_8l(**kw))
    assert "tpu_custom_call" in compiled.as_text()
    assert _memory_gb(compiled) < 15.75


# chip_smoke.py phase d: llama2-7b widths, depth and batch cut to what one
# chip's 16 GB holds (chip_smoke.TRAIN).
@pytest.mark.slow
@pytest.mark.parametrize("attn", ["flash", "lax"])
def test_train_loss_and_grad_compile_for_v5e(topo, monkeypatch, attn):
    """The flash forward AND backward kernels under jax.grad, and the lax
    reference they are held to, whose temporaries are what bound the cut
    to depth 4 x batch 2."""
    import chip_smoke

    _as_tpu(monkeypatch)
    cfg = chip_smoke.train_config()
    batch = _s((chip_smoke.TRAIN["batch"], chip_smoke.TRAIN["seq"] + 1), I32)
    fn = chip_smoke._loss_and_grad_norm(
        cfg, None if attn == "flash" else chip_smoke._plain_attn())
    compiled = fn.lower(*_placed((_param_shapes(cfg), batch),
                                 SingleDeviceSharding(topo.devices[0]))
                        ).compile()
    assert ("tpu_custom_call" in compiled.as_text()) == (attn == "flash")
    assert _memory_gb(compiled) < 15.75


@pytest.mark.slow
def test_trainer_programs_fit_v5e(topo, monkeypatch):
    """Trainer.step_sync's two programs (grad, then adamw apply)."""
    import optax

    import chip_smoke
    from starway_tpu.models.llama import apply_updates, loss_fn

    _as_tpu(monkeypatch)
    cfg, tx = chip_smoke.train_config(), optax.adamw(1e-3)
    params = _param_shapes(cfg)
    opt = jax.eval_shape(tx.init, params)
    batch = _s((chip_smoke.TRAIN["batch"], chip_smoke.TRAIN["seq"] + 1), I32)
    one = SingleDeviceSharding(topo.devices[0])
    grad = _compile(lambda p, b: jax.value_and_grad(loss_fn)(p, b, cfg),
                    params, batch, sharding=one)
    apply = _compile(lambda p, o, g: apply_updates(tx, p, o, g), params, opt,
                     params, sharding=one, donate_argnums=(0, 1))
    # While the grad program runs the Trainer also holds the adamw state.
    opt_gb = sum(x.size * x.dtype.itemsize
                 for x in jax.tree_util.tree_leaves(opt)) / 1e9
    assert _memory_gb(grad) + opt_gb < 15.75
    assert _memory_gb(apply) < 15.75


# chip_smoke.py --chips 4: the dp x tp x sp train step with ring attention
# and the tp=2 SlotServer chunk, on the described 2x2 mesh.
@pytest.mark.slow
def test_mesh_train_step_compiles_for_v5e_2x2(topo, monkeypatch):
    import chip_smoke

    _as_tpu(monkeypatch)
    step, args = chip_smoke.mesh_train_program(topo.devices)
    compiled = step.lower(*args).compile()
    txt = compiled.as_text()
    assert "tpu_custom_call" in txt and "collective-permute" in txt
    assert _memory_gb(compiled) < 15.75


@pytest.mark.slow
@pytest.mark.parametrize("ingest", [None, 128], ids=["plain", "ingest_128"])
def test_tp_slot_server_chunk_compiles_for_v5e_2x2(topo, monkeypatch, ingest):
    import chip_smoke

    _as_tpu(monkeypatch)
    mesh, run, args = chip_smoke.tp_chunk_program(topo.devices[:2], ingest)
    with jax.set_mesh(mesh):
        compiled = run.lower(*args).compile()
    txt = compiled.as_text()
    # The decode kernel runs per head shard; only activations cross chips.
    assert "tpu_custom_call" in txt and "all-reduce" in txt
    assert _memory_gb(compiled) < 15.75
