"""SPMD layer tests on the virtual 8-device CPU mesh: attention algebra,
ring attention exactness, all-to-all shuffles, pytree DP exchange."""

import asyncio

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from starway_tpu.ops.attention import (
    attention_reference,
    blockwise_attention,
    repeat_kv,
)
from starway_tpu.ops.collectives import ring_reduce
from starway_tpu.parallel import make_mesh, make_ring_attention, make_shuffle
from starway_tpu.parallel.sharding import shard_array, shard_map_fn

pytestmark = pytest.mark.asyncio


def _qkv(key, b=2, h=4, t=256, d=32, dtype=jnp.float32):
    k1, k2, k3 = jax.random.split(key, 3)
    q = jax.random.normal(k1, (b, h, t, d), dtype)
    k = jax.random.normal(k2, (b, h, t, d), dtype)
    v = jax.random.normal(k3, (b, h, t, d), dtype)
    return q, k, v


@pytest.mark.parametrize("causal", [False, True])
@pytest.mark.parametrize("block_k", [64, 100])  # 100 exercises padding
def test_blockwise_matches_reference(causal, block_k):
    q, k, v = _qkv(jax.random.PRNGKey(0))
    ref = attention_reference(q, k, v, causal=causal)
    out = blockwise_attention(q, k, v, causal=causal, block_k=block_k)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref), atol=2e-5, rtol=2e-5)


def test_repeat_kv():
    x = jnp.arange(2 * 2 * 3 * 4, dtype=jnp.float32).reshape(2, 2, 3, 4)
    y = repeat_kv(x, 3)
    assert y.shape == (2, 6, 3, 4)
    np.testing.assert_array_equal(np.asarray(y[:, 0]), np.asarray(y[:, 2]))
    np.testing.assert_array_equal(np.asarray(y[:, 0]), np.asarray(x[:, 0]))


@pytest.mark.parametrize("causal", [False, True])
def test_ring_attention_matches_reference(causal):
    mesh = make_mesh({"sp": 8})
    q, k, v = _qkv(jax.random.PRNGKey(1), t=256)
    ref = attention_reference(q, k, v, causal=causal)

    ring = make_ring_attention(mesh, "sp", causal=causal)
    spec = ("sp",)
    qs = shard_array(mesh, q, None, None, "sp", None)
    ks = shard_array(mesh, k, None, None, "sp", None)
    vs = shard_array(mesh, v, None, None, "sp", None)
    out = ring(qs, ks, vs)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref), atol=2e-5, rtol=2e-5)


def test_ring_attention_bf16():
    mesh = make_mesh({"sp": 4})
    q, k, v = _qkv(jax.random.PRNGKey(2), t=128, dtype=jnp.bfloat16)
    ref = attention_reference(q.astype(jnp.float32), k.astype(jnp.float32),
                              v.astype(jnp.float32), causal=True)
    ring = make_ring_attention(mesh, "sp", causal=True)
    qs = shard_array(mesh, q, None, None, "sp", None)
    ks = shard_array(mesh, k, None, None, "sp", None)
    vs = shard_array(mesh, v, None, None, "sp", None)
    out = ring(qs, ks, vs).astype(jnp.float32)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref), atol=0.06, rtol=0.06)


def test_zigzag_indices_is_permutation():
    from starway_tpu.parallel import zigzag_indices

    idx = zigzag_indices(256, 8)
    assert sorted(idx) == list(range(256))
    # device 0's shard = first S/n entries = blocks 0 and 2n-1
    sb = 256 // 16
    np.testing.assert_array_equal(idx[:sb], np.arange(0, sb))
    np.testing.assert_array_equal(idx[sb : 2 * sb], np.arange(15 * sb, 16 * sb))
    with pytest.raises(ValueError):
        zigzag_indices(100, 8)  # not divisible by 2n


@pytest.mark.parametrize("gqa", [1, 2])
def test_zigzag_ring_attention_matches_reference(gqa):
    """Load-balanced causal layout must be exact, including grouped kv."""
    from starway_tpu.parallel import make_zigzag_ring_attention

    mesh = make_mesh({"sp": 8})
    q, _, _ = _qkv(jax.random.PRNGKey(3), t=256)
    _, k, v = _qkv(jax.random.PRNGKey(4), h=4 // gqa, t=256)
    ref = attention_reference(q, repeat_kv(k, gqa), repeat_kv(v, gqa), causal=True)

    zig = make_zigzag_ring_attention(mesh, "sp")
    qs = shard_array(mesh, q, None, None, "sp", None)
    ks = shard_array(mesh, k, None, None, "sp", None)
    vs = shard_array(mesh, v, None, None, "sp", None)
    out = zig(qs, ks, vs)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref), atol=2e-5, rtol=2e-5)


def test_zigzag_via_model_sharded_attn():
    """make_sharded_attn(layout='zigzag') slots in as the model's attn_fn."""
    from starway_tpu.models.llama import make_sharded_attn
    from starway_tpu.parallel import make_mesh as _mm

    mesh = _mm({"dp": 1, "tp": 1, "sp": 8})
    q, k, v = _qkv(jax.random.PRNGKey(5), t=128)
    ref = attention_reference(q, k, v, causal=True)
    attn = make_sharded_attn(mesh, layout="zigzag")
    out = jax.jit(attn)(q, k, v)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref), atol=2e-5, rtol=2e-5)


def test_shuffle_transposes_ownership():
    mesh = make_mesh({"x": 8})
    s, b, d = 16, 8, 4
    x = jnp.arange(s * b * d, dtype=jnp.float32).reshape(s, b, d)
    xs = shard_array(mesh, x, "x")
    shuffle = make_shuffle(mesh, "x")
    y = shuffle(xs)
    # Values must be preserved exactly; ownership moves from dim0 to dim1.
    np.testing.assert_array_equal(np.asarray(y), np.asarray(x))
    from jax.sharding import NamedSharding, PartitionSpec as P

    assert y.sharding.spec == P(None, "x")


def test_ring_reduce_matches_psum():
    mesh = make_mesh({"r": 8})
    x = jnp.arange(64, dtype=jnp.float32).reshape(8, 8)
    xs = shard_array(mesh, x, "r")

    def local(v):
        return ring_reduce(v, "r")

    from jax.sharding import PartitionSpec as P

    f = jax.jit(shard_map_fn(mesh, local, in_specs=(P("r"),), out_specs=P("r")))
    out = f(xs)
    expect = np.tile(np.asarray(x).sum(axis=0), (8, 1)).reshape(8, 8)
    np.testing.assert_allclose(np.asarray(out), expect)


async def test_dp_exchange_pytree_roundtrip():
    from starway_tpu import Client, Server
    from starway_tpu.parallel import ClientPort, ServerPort, recv_pytree, send_pytree

    from conftest import free_port

    port_num = free_port()
    server = Server()
    server.listen("127.0.0.1", port_num)
    client = Client()
    await client.aconnect("127.0.0.1", port_num)
    try:
        grads = {
            "w": jnp.arange(128, dtype=jnp.float32).reshape(8, 16),
            "b": jnp.ones((16,), dtype=jnp.bfloat16),
            "inner": [jnp.full((4, 4), 7, dtype=jnp.int32)],
        }
        send_task = asyncio.ensure_future(
            send_pytree(ClientPort(client), grads, base_tag=0x9000)
        )
        received = await recv_pytree(ServerPort(server), like=grads, base_tag=0x9000)
        n = await send_task
        assert n == 3
        for a, b in zip(jax.tree_util.tree_leaves(grads), jax.tree_util.tree_leaves(received)):
            np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
    finally:
        await client.aclose()
        await server.aclose()


@pytest.mark.parametrize("causal", [False, True])
def test_ring_attention_gradients_match_oracle(causal):
    """The backward ring (custom_vjp: dk/dv accumulators rotating home with
    their kv shards, global lse/delta per-step math) must reproduce the
    gradients of plain attention."""
    mesh = make_mesh({"sp": 4})
    q, k, v = _qkv(jax.random.PRNGKey(11), h=4, t=64)
    _, _, vd = _qkv(jax.random.PRNGKey(12), h=4, t=64)
    ring = make_ring_attention(mesh, "sp", causal=causal)

    def loss_ring(q, k, v):
        return jnp.sum(ring(q, k, v) * vd)

    def loss_ref(q, k, v):
        return jnp.sum(attention_reference(q, k, v, causal=causal) * vd)

    g1 = jax.grad(loss_ring, argnums=(0, 1, 2))(q, k, v)
    g2 = jax.grad(loss_ref, argnums=(0, 1, 2))(q, k, v)
    for a, b in zip(g1, g2):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   atol=5e-5, rtol=5e-4)


@pytest.mark.parametrize("gqa", [1, 2])
def test_zigzag_ring_gradients_match_oracle(gqa):
    """Zigzag backward: pair liveness mirrored from the forward; grouped
    dk/dv summed over the query-head group."""
    from starway_tpu.parallel import make_zigzag_ring_attention

    mesh = make_mesh({"sp": 4})
    q, _, _ = _qkv(jax.random.PRNGKey(13), h=4, t=64)
    _, k, v = _qkv(jax.random.PRNGKey(14), h=4 // gqa, t=64)
    _, _, vd = _qkv(jax.random.PRNGKey(15), h=4, t=64)
    zig = make_zigzag_ring_attention(mesh, "sp")

    def loss_zig(q, k, v):
        return jnp.sum(zig(q, k, v) * vd)

    def loss_ref(q, k, v):
        return jnp.sum(attention_reference(
            q, repeat_kv(k, gqa), repeat_kv(v, gqa), causal=True) * vd)

    g1 = jax.grad(loss_zig, argnums=(0, 1, 2))(q, k, v)
    g2 = jax.grad(loss_ref, argnums=(0, 1, 2))(q, k, v)
    for a, b in zip(g1, g2):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   atol=5e-5, rtol=5e-4)


def _on_both_sides(force_kernels, make, q, k, v):
    """``(out, grads)`` of ``make()``'s attention with the ops' decision
    forced to the kernels (interpret mode on the CPU), then to the lax
    twins.  Each side is built and traced under its own setting."""
    sides = []
    for on in (True, False):
        force_kernels(on)
        attn = make()
        sides.append((attn(q, k, v), jax.grad(
            lambda *a: jnp.sum(attn(*a) ** 2), argnums=(0, 1, 2))(q, k, v)))
    return sides


def test_ring_attention_kernel_path_interpret(force_kernels):
    """With the kernels chosen the ring steps run the Pallas partials
    (interpret mode on CPU): forward AND gradients must match the lax
    path exactly enough."""
    mesh = make_mesh({"sp": 2})
    q, k, v = _qkv(jax.random.PRNGKey(16), b=1, h=2, t=32, d=16)
    (out_k, g1), (out_l, g2) = _on_both_sides(
        force_kernels, lambda: make_ring_attention(mesh, "sp", causal=True),
        q, k, v)
    np.testing.assert_allclose(np.asarray(out_k), np.asarray(out_l),
                               atol=2e-5, rtol=2e-5)
    for a, b in zip(g1, g2):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   atol=5e-5, rtol=5e-4)


def test_zigzag_ring_kernel_path_interpret(force_kernels):
    """Zigzag with the kernels chosen: Pallas partials under lax.cond with
    offsets, incl. the causal=False hi-lo pair and GQA -- fwd and grads
    must match the lax path."""
    from starway_tpu.parallel import make_zigzag_ring_attention

    mesh = make_mesh({"sp": 2})
    q, _, _ = _qkv(jax.random.PRNGKey(17), b=1, h=2, t=32, d=16)
    _, k, v = _qkv(jax.random.PRNGKey(18), b=1, h=1, t=32, d=16)  # GQA 2
    (out_k, g1), (out_l, g2) = _on_both_sides(
        force_kernels, lambda: make_zigzag_ring_attention(mesh, "sp"),
        q, k, v)
    np.testing.assert_allclose(np.asarray(out_k), np.asarray(out_l),
                               atol=2e-5, rtol=2e-5)
    for a, b in zip(g1, g2):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   atol=5e-5, rtol=5e-4)


def test_flash_partial_identity_rows():
    """A partially-live block whose upper rows are fully masked must emit
    the identity partial for those rows (o=0, m=NEG_BIG, l=0), matching
    partial_attention -- not garbage from exp(NEG-NEG)=1."""
    from starway_tpu.ops.attention import NEG_BIG as NEG
    from starway_tpu.ops.pallas_attention import flash_partial

    B, H, T, D = 1, 1, 16, 16
    ks = jax.random.split(jax.random.PRNGKey(19), 3)
    q = jax.random.normal(ks[0], (B, H, T, D), jnp.float32)
    k = jax.random.normal(ks[1], (B, H, T, D), jnp.float32)
    v = jax.random.normal(ks[2], (B, H, T, D), jnp.float32)
    # kv shard starts mid-way through the q block: rows 0..7 see nothing.
    o, m, l = flash_partial(q, k, v, 0, 8, causal=True, block_q=16,
                            block_k=16, interpret=True)
    np.testing.assert_array_equal(np.asarray(l[0, 0, :8]), 0.0)
    assert np.all(np.asarray(m[0, 0, :8]) <= NEG / 2)
    np.testing.assert_array_equal(np.asarray(o[0, 0, :8]), 0.0)
    assert np.all(np.asarray(l[0, 0, 8:]) > 0)


@pytest.mark.parametrize("window", [24, 64])
def test_windowed_ring_attention_matches_reference(window):
    """Sliding-window ring attention (Mistral-style band over sp): forward
    matches the windowed oracle; out-of-band ring steps cond-skip, which
    must not perturb the merged partials."""
    mesh = make_mesh({"sp": 8})
    q, k, v = _qkv(jax.random.PRNGKey(21), t=256)
    ref = attention_reference(q, k, v, causal=True, window=window)
    ring = make_ring_attention(mesh, "sp", causal=True, window=window)
    out = ring(q, k, v)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               atol=2e-5, rtol=2e-5)


def test_windowed_ring_gradients_match_oracle():
    """Windowed backward ring: skipped pairs contribute zero grads; live
    band-edge pairs mask inside the step — all three gradients match the
    windowed oracle."""
    mesh = make_mesh({"sp": 4})
    q, k, v = _qkv(jax.random.PRNGKey(22), h=4, t=64)
    _, _, vd = _qkv(jax.random.PRNGKey(23), h=4, t=64)
    ring = make_ring_attention(mesh, "sp", causal=True, window=24)

    def loss_ring(q, k, v):
        return jnp.sum(ring(q, k, v) * vd)

    def loss_ref(q, k, v):
        return jnp.sum(attention_reference(q, k, v, causal=True,
                                           window=24) * vd)

    g1 = jax.grad(loss_ring, argnums=(0, 1, 2))(q, k, v)
    g2 = jax.grad(loss_ref, argnums=(0, 1, 2))(q, k, v)
    for a, b in zip(g1, g2):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   atol=5e-5, rtol=5e-4)


def test_windowed_model_sharded_attn():
    """make_sharded_attn(window=...) slots into forward() on a
    sliding-window config (resolve_attn_fn admits it via handles_window)
    and matches the single-device windowed forward."""
    from jax.sharding import NamedSharding
    from jax.sharding import PartitionSpec as P

    from starway_tpu.models import LlamaConfig, forward, init_params
    from starway_tpu.models.llama import make_sharded_attn, param_specs

    cfg = LlamaConfig.preset("debug", sliding_window=6)
    params = init_params(jax.random.PRNGKey(0), cfg)
    tokens = jnp.asarray(np.random.default_rng(0).integers(
        0, cfg.vocab_size, (2, 16), dtype=np.int32))
    ref = jax.jit(lambda p, t: forward(p, t, cfg))(params, tokens)

    mesh = make_mesh({"dp": 2, "tp": 2, "sp": 2})
    sharded = jax.tree_util.tree_map(
        lambda x, s: jax.device_put(x, NamedSharding(mesh, s)), params,
        param_specs(cfg))
    tok_sharded = jax.device_put(tokens, NamedSharding(mesh, P("dp", None)))
    attn = make_sharded_attn(mesh, window=cfg.sliding_window)
    assert attn.handles_window
    out = jax.jit(lambda p, t: forward(p, t, cfg, attn))(sharded, tok_sharded)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               atol=2e-4, rtol=2e-4)

    # window != ring layout refuses; windowed cfg without a window-aware
    # attn_fn still refuses at resolve time; a MISMATCHED band refuses
    # too (silently a different model otherwise).
    with pytest.raises(ValueError, match="ring"):
        make_sharded_attn(mesh, layout="zigzag", window=4)
    from starway_tpu.models.llama import resolve_attn_fn

    with pytest.raises(ValueError, match="handles_window"):
        resolve_attn_fn(cfg, make_sharded_attn(mesh))
    with pytest.raises(ValueError, match="window=4"):
        resolve_attn_fn(cfg, make_sharded_attn(mesh, window=4))


@pytest.mark.parametrize("case", ["decode_bf16", "decode_int8", "flash"])
def test_per_head_shard_matches_unsharded(case, force_kernels):
    """The Pallas attention calls run per tp shard of the head dimension
    under an ambient mesh (ops/dispatch.py per_head_shard: the TPU's
    compiler refuses a Mosaic kernel inside a partitioned program).  GQA
    pairing must survive the split: q head h reads kv head h // n_rep.
    The decode cases are the WHOLE op with the kernels chosen (a program
    on one side: the decision function is substituted), the flash case
    the wrapper around the kernel itself."""
    from jax.sharding import NamedSharding, PartitionSpec as P

    from starway_tpu.ops import cached_attention, per_head_shard
    from starway_tpu.ops.pallas_attention import flash_attention
    from starway_tpu.ops.quantize import quantize_kv

    force_kernels(True)

    b, hq, hkv, t, d = 2, 8, 4, 256, 64
    kq, kk, kv = jax.random.split(jax.random.PRNGKey(11), 3)
    k = jax.random.normal(kk, (b, hkv, t, d), jnp.float32)
    v = jax.random.normal(kv, (b, hkv, t, d), jnp.float32)
    if case == "flash":
        q = jax.random.normal(kq, (b, hq, t, d), jnp.float32)

        def run(q, k, v):
            return per_head_shard(
                lambda q, k, v: flash_attention(q, k, v, causal=True,
                                                interpret=True), (q, k, v))
        args = (q, k, v)
    else:
        q = jax.random.normal(kq, (b, hq, 1, d), jnp.float32)
        pos = jnp.asarray([100, 37], jnp.int32)
        scales = {}
        if case == "decode_int8":
            q, k, v = (x.astype(jnp.bfloat16) for x in (q, k, v))
            (k, ks), (v, vs) = quantize_kv(k), quantize_kv(v)
            scales = dict(k_scale=ks, v_scale=vs)

        def run(q, k, v, **sc):
            return cached_attention(q, k, v, pos, **sc)
        args = (q, k, v)

    want = jax.jit(run)(*args, **(scales if case != "flash" else {}))
    mesh = make_mesh({"tp": 2}, jax.devices()[:2])
    heads = NamedSharding(mesh, P(None, "tp"))
    sharded = [jax.device_put(x, heads) for x in args]
    kw = ({n: jax.device_put(s, heads) for n, s in scales.items()}
          if case != "flash" else {})
    with jax.set_mesh(mesh):
        jitted = jax.jit(run)
        got = jitted(*sharded, **kw)
        assert "shard_map" in str(jitted.trace(*sharded, **kw).jaxpr)
    assert got.sharding.spec[1] == "tp"
    np.testing.assert_allclose(np.asarray(got, np.float32),
                               np.asarray(want, np.float32),
                               atol=2e-6, rtol=2e-6)
@pytest.mark.parametrize("kv", ["bf16", "int8"])
def test_per_head_shard_stacked_cache(kv, force_kernels):
    """Write-then-attend on the scan-stacked cache ``[L, B, Hkv, T, D]``
    under a tp mesh: the caches' heads are dim 2 there (dim 1 of q and of
    the new entries), which ``per_head_shard`` is told, not left to guess.
    The in-place write returns the stacked cache still sharded by head,
    and both kernels agree with the unsharded run."""
    from jax.sharding import NamedSharding, PartitionSpec as P

    from starway_tpu.models.cache import _write_cached
    from starway_tpu.ops import cached_attention
    from starway_tpu.ops.quantize import quantize_kv

    force_kernels(True)
    n_layers, b, hq, hkv, t, d = 3, 2, 8, 4, 256, 64
    keys = jax.random.split(jax.random.PRNGKey(17), 5)
    q = jax.random.normal(keys[0], (b, hq, 1, d)).astype(jnp.bfloat16)
    cache = {"k": jax.random.normal(keys[1], (n_layers, b, hkv, t, d)),
             "v": jax.random.normal(keys[2], (n_layers, b, hkv, t, d))}
    new = {"k": jax.random.normal(keys[3], (b, hkv, 1, d)),
           "v": jax.random.normal(keys[4], (b, hkv, 1, d))}
    cache, new = ({n: a.astype(jnp.bfloat16) for n, a in tree.items()}
                  for tree in (cache, new))
    if kv == "int8":
        for tree in (cache, new):
            tree["k"], tree["k_scale"] = quantize_kv(tree["k"])
            tree["v"], tree["v_scale"] = quantize_kv(tree["v"])
    pos = jnp.asarray([100, 37], jnp.int32)

    def run(q, cache, new, layer):
        cache = _write_cached(cache, new, layer, pos)
        out = cached_attention(q, cache["k"], cache["v"], pos, layer=layer,
                               k_scale=cache.get("k_scale"),
                               v_scale=cache.get("v_scale"))
        return out, cache

    layer = jnp.int32(1)
    want, want_cache = jax.jit(run)(q, cache, new, layer)
    mesh = make_mesh({"tp": 2}, jax.devices()[:2])
    at = lambda dim: NamedSharding(mesh, P(*[None] * dim, "tp"))
    put = lambda tree, dim: {n: jax.device_put(a, at(dim))
                             for n, a in tree.items()}
    with jax.set_mesh(mesh):
        got, got_cache = jax.jit(run)(jax.device_put(q, at(1)),
                                      put(cache, 2), put(new, 1), layer)
    assert got.sharding.spec[1] == "tp"
    np.testing.assert_array_equal(np.asarray(got, np.float32),
                                  np.asarray(want, np.float32))
    for name, leaf in got_cache.items():
        assert leaf.sharding.spec[2] == "tp"
        np.testing.assert_array_equal(np.asarray(leaf, np.float32),
                                      np.asarray(want_cache[name],
                                                 np.float32))
        assert (np.asarray(leaf) != np.asarray(cache[name])).any()


