"""Paged KV-cache serving (ops/pallas_paged.py + models/paged.py):
the paged kernel matches the dense decode oracle on scrambled block
tables, PagedSlotServer's greedy outputs are bit-identical to standalone
generate() under slot reuse and page recycling, an UNDERSIZED pool (less
memory than the dense cache would reserve) still serves short requests,
and exhaustion fails loudly instead of corrupting."""

import re

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from starway_tpu.models import LlamaConfig, PagedSlotServer, init_params
from starway_tpu.models.generate import generate
from starway_tpu.ops.pallas_decode import decode_attention
from starway_tpu.ops.pallas_paged import (gather_logical,
                                          paged_decode_attention)


@pytest.fixture(scope="module")
def cfg():
    return LlamaConfig.preset("debug")


@pytest.fixture(scope="module")
def params(cfg):
    return init_params(jax.random.PRNGKey(0), cfg)


def _oracle(params, cfg, prompt, max_new, eos_id=None):
    out = generate(params, cfg, jnp.asarray([prompt], jnp.int32), max_new,
                   eos_id=eos_id)
    toks = np.asarray(out[0, len(prompt):])
    if eos_id is not None and eos_id in toks:
        toks = toks[: list(toks).index(eos_id) + 1]
    return toks


# ------------------------------------------------------------------ kernel
def test_paged_kernel_matches_dense_on_scrambled_tables():
    """Non-contiguous, permuted page tables: the paged stream kernel's
    output equals the dense kernel over the gathered logical cache."""
    rng = np.random.default_rng(0)
    B, Hq, Hkv, D, page, max_pages = 3, 8, 2, 128, 128, 4
    n_pages = B * max_pages + 2
    kp = jnp.asarray(rng.standard_normal((n_pages, Hkv, page, D)),
                     jnp.float32)
    vp = jnp.asarray(rng.standard_normal((n_pages, Hkv, page, D)),
                     jnp.float32)
    table = jnp.asarray(
        rng.permutation(n_pages)[:B * max_pages].reshape(B, max_pages),
        jnp.int32)
    pos = jnp.asarray([100, 300, 511], jnp.int32)  # straddle page edges
    q = jnp.asarray(rng.standard_normal((B, Hq, 1, D)), jnp.float32)

    out = paged_decode_attention(q, kp, vp, table, pos)
    ref = decode_attention(q, gather_logical(kp, table),
                           gather_logical(vp, table), pos)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               atol=2e-6, rtol=2e-6)


def test_paged_kernel_multi_query_chunk():
    """C > 1 (the chunk-verify shape) rides the same row packing."""
    rng = np.random.default_rng(1)
    B, Hq, Hkv, D, page, max_pages, C = 2, 4, 2, 64, 128, 3, 4
    n_pages = B * max_pages + 1
    kp = jnp.asarray(rng.standard_normal((n_pages, Hkv, page, D)),
                     jnp.float32)
    vp = jnp.asarray(rng.standard_normal((n_pages, Hkv, page, D)),
                     jnp.float32)
    table = jnp.asarray(
        rng.permutation(n_pages)[:B * max_pages].reshape(B, max_pages),
        jnp.int32)
    pos = jnp.asarray([60, 250], jnp.int32)
    q = jnp.asarray(rng.standard_normal((B, Hq, C, D)), jnp.float32)

    out = paged_decode_attention(q, kp, vp, table, pos)
    ref = decode_attention(q, gather_logical(kp, table),
                           gather_logical(vp, table), pos)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               atol=2e-6, rtol=2e-6)


def test_paged_kernel_mosaic_lowers_for_tpu():
    """The real (non-interpret) kernel cross-lowers through the mosaic
    pipeline at serving geometry — a tiling bug dies here, not on
    hardware."""
    B, Hq, Hkv, D, page, max_pages, n_pages = 2, 8, 2, 128, 512, 16, 40
    q = jax.ShapeDtypeStruct((B, Hq, 1, D), jnp.bfloat16)
    kp = jax.ShapeDtypeStruct((n_pages, Hkv, page, D), jnp.bfloat16)
    table = jax.ShapeDtypeStruct((B, max_pages), jnp.int32)
    pos = jax.ShapeDtypeStruct((B,), jnp.int32)
    txt = (jax.jit(lambda q, k, v, t, p: paged_decode_attention(
        q, k, v, t, p, interpret=False))
        .trace(q, kp, kp, table, pos)
        .lower(lowering_platforms=("tpu",)).as_text())
    assert re.findall(r'kernel_name = "(\w+)"', txt) == [
        "sw_paged_decode_attn"]


def test_paged_kernel_refuses_int8():
    q = jnp.zeros((1, 2, 1, 64), jnp.float32)
    kp = jnp.zeros((2, 1, 128, 64), jnp.int8)
    with pytest.raises(NotImplementedError, match="int8"):
        paged_decode_attention(q, kp, kp, jnp.zeros((1, 1), jnp.int32), 0)


# ------------------------------------------------------------------ server
def test_paged_server_matches_generate(cfg, params):
    """Mixed lengths, more requests than slots, pages recycling through
    the pool: every greedy continuation equals standalone generate()."""
    rng = np.random.default_rng(2)
    reqs = [(list(map(int, rng.integers(1, cfg.vocab_size, n))), m)
            for n, m in [(3, 6), (7, 4), (12, 9), (5, 1), (2, 11), (9, 3)]]
    srv = PagedSlotServer(params, cfg, n_slots=2, max_len=64, page=16,
                          n_pages=9, chunk=4)
    rids = [srv.submit(p, m) for p, m in reqs]
    done = srv.run()
    assert sorted(done) == sorted(rids)
    for rid, (prompt, max_new) in zip(rids, reqs):
        np.testing.assert_array_equal(done[rid],
                                      _oracle(params, cfg, prompt, max_new))
    assert srv.pages_in_use == 0  # everything returned to the pool


def test_paged_server_undersized_pool_serves_short_requests(cfg, params):
    """THE paging win: 4 slots x max_len=64 would reserve 16 pages
    densely; a 7-page pool (+trash) serves 8 short requests concurrently
    because nobody actually uses max_len."""
    rng = np.random.default_rng(3)
    reqs = [(list(map(int, rng.integers(1, cfg.vocab_size, 4))), 6)
            for _ in range(8)]
    srv = PagedSlotServer(params, cfg, n_slots=4, max_len=64, page=16,
                          n_pages=8, chunk=4)
    assert srv.n_pages - 1 < srv.n_slots * srv.max_pages
    rids = [srv.submit(p, m) for p, m in reqs]
    done = srv.run()
    for rid, (prompt, max_new) in zip(rids, reqs):
        np.testing.assert_array_equal(done[rid],
                                      _oracle(params, cfg, prompt, max_new))


def test_paged_server_eos_and_staggered_admission(cfg, params):
    prompt = [5, 1, 7, 2, 9]
    free = _oracle(params, cfg, prompt, 8)
    eos = int(free[1])
    srv = PagedSlotServer(params, cfg, n_slots=2, max_len=64, page=16,
                          n_pages=9, chunk=3, eos_id=eos)
    r0 = srv.submit(prompt, 8)
    done = dict(srv.step())  # r0 may already eos inside this chunk
    r1 = srv.submit([3, 8, 6], 5)  # joins/fills the freed slot
    done.update(srv.run())
    np.testing.assert_array_equal(done[r0],
                                  _oracle(params, cfg, prompt, 8,
                                          eos_id=eos))
    np.testing.assert_array_equal(done[r1],
                                  _oracle(params, cfg, [3, 8, 6], 5,
                                          eos_id=eos))


def test_paged_server_cancel_frees_pages(cfg, params):
    srv = PagedSlotServer(params, cfg, n_slots=2, max_len=64, page=16,
                          n_pages=9, chunk=4)
    rid = srv.submit(list(range(1, 10)), 20)
    srv.step()
    assert srv.pages_in_use > 0
    assert srv.cancel(rid) is True
    assert srv.pages_in_use == 0
    r1 = srv.submit([4, 2, 8], 5)  # pages recycle into the next request
    done = srv.run()
    np.testing.assert_array_equal(done[r1],
                                  _oracle(params, cfg, [4, 2, 8], 5))


def test_paged_server_pool_exhaustion_is_loud(cfg, params):
    """No silent corruption: admission past the pool's capacity raises,
    naming the fix."""
    srv = PagedSlotServer(params, cfg, n_slots=2, max_len=64, page=16,
                          n_pages=3, chunk=4)  # 2 usable pages
    srv.submit(list(range(1, 30)), 4)  # needs 2 pages at admission
    srv.submit(list(range(1, 30)), 4)  # pool is empty now
    with pytest.raises(RuntimeError, match="pool exhausted"):
        srv.run()


def test_only_no_room_yet_keeps_a_request_queued(cfg, params, monkeypatch):
    """step() keeps a request queued for the pool's ``NoRoomYet`` alone.
    Any other RuntimeError of an admission (a JaxRuntimeError is one) is a
    fault and surfaces from step(): kept queued, as every RuntimeError once
    was, it is admitted again at every step and run() never returns."""
    from starway_tpu.models.serving import NoRoomYet

    srv = PagedSlotServer(params, cfg, n_slots=2, max_len=64, page=16,
                          n_pages=3, chunk=4)  # 2 usable pages
    held = srv.submit(list(range(1, 30)), 1)
    waits = srv.submit(list(range(1, 30)), 1)  # no page left: NoRoomYet
    assert list(srv.step()) == [held]
    assert [rid for rid, *_ in srv._pending] == [waits]

    def fault(*args, **kwargs):
        raise RuntimeError("the device fell over")

    monkeypatch.setattr(srv, "_admit", fault)
    with pytest.raises(RuntimeError, match="fell over") as err:
        srv.step()
    assert not isinstance(err.value, NoRoomYet)
    assert not srv._pending and not srv._slot_rid  # nowhere twice


def test_paged_server_refusals(cfg, params):
    with pytest.raises(NotImplementedError, match="rolling"):
        PagedSlotServer(params, LlamaConfig.preset("debug",
                                                   sliding_window=16),
                        max_len=64)
    with pytest.raises(NotImplementedError, match="int8"):
        PagedSlotServer(params, LlamaConfig.preset("debug",
                                                   kv_quant="int8"),
                        max_len=64)


def test_paged_server_behind_transport_bridge(cfg, params):
    """The transport bridge is slot-server-agnostic: PagedSlotServer
    serves over the wire with streams equal to the oracle."""
    import asyncio

    from starway_tpu.models.remote_serving import (RemoteGenerateSession,
                                                   RemoteSlotServer)
    from tests.conftest import free_port

    async def drive():
        slot = PagedSlotServer(params, cfg, n_slots=2, max_len=64,
                               page=16, n_pages=9, chunk=4)
        bridge = RemoteSlotServer(slot)
        port = free_port()
        bridge.server.listen("127.0.0.1", port)
        task = asyncio.create_task(bridge.serve())
        session = await RemoteGenerateSession.aconnect("127.0.0.1", port)
        try:
            outs = await asyncio.gather(session.generate([4, 2, 8, 1], 7),
                                        session.generate([9, 1], 5))
        finally:
            bridge.stop()
            await task
            await session.aclose()
            await bridge.aclose()
        return outs

    outs = asyncio.run(drive())
    for prompt, got in zip(([4, 2, 8, 1], [9, 1]), outs):
        np.testing.assert_array_equal(
            got, _oracle(params, cfg, prompt, len(got)))

def test_paged_prefix_shared_pages(cfg, params):
    """Zero-copy prefix sharing: three suffix requests over one 20-token
    prefix (page=16 -> 1 whole shared page + a partial tail) generate
    exactly generate(prefix + suffix), and the shared page is counted
    ONCE however many slots reference it."""
    rng = np.random.default_rng(7)
    prefix_toks = list(map(int, rng.integers(1, cfg.vocab_size, 20)))
    srv = PagedSlotServer(params, cfg, n_slots=3, max_len=64, page=16,
                          n_pages=12, chunk=4)
    pid = srv.register_prefix(prefix_toks)
    base_pages = srv.pages_in_use
    assert base_pages == 1  # one whole shared page; the tail is host-held

    suffixes = [[3, 1, 4], [1, 5], [9, 2, 6, 5]]
    rids = [srv.submit(sfx, 6, prefix=pid) for sfx in suffixes]
    srv.step()  # all three admitted: shared page counted once
    assert srv.pages_in_use < 1 + 3 * 2 + 2  # far below per-slot copies
    done = srv.run()
    for rid, sfx in zip(rids, suffixes):
        want = _oracle(params, cfg, prefix_toks + sfx, 6)
        np.testing.assert_array_equal(done[rid], want,
                                      err_msg=f"suffix {sfx}")
    # All slot references released; the registry still holds its page.
    assert srv.pages_in_use == 1
    srv.drop_prefix(pid)
    assert srv.pages_in_use == 0


def test_paged_prefix_page_aligned(cfg, params):
    """plen % page == 0: no tail page at all — the suffix starts on its
    own fresh page."""
    rng = np.random.default_rng(8)
    prefix_toks = list(map(int, rng.integers(1, cfg.vocab_size, 16)))
    srv = PagedSlotServer(params, cfg, n_slots=2, max_len=64, page=16,
                          n_pages=10, chunk=4)
    pid = srv.register_prefix(prefix_toks)
    rid = srv.submit([7, 7, 2], 5, prefix=pid)
    done = srv.run()
    np.testing.assert_array_equal(
        done[rid], _oracle(params, cfg, prefix_toks + [7, 7, 2], 5))
    srv.drop_prefix(pid)
    assert srv.pages_in_use == 0


# ------------------------------------------------ a step queues, then fetches
def _paged(cfg, params):
    srv = PagedSlotServer(params, cfg, n_slots=4, max_len=64, page=16,
                          n_pages=17, chunk=3)
    return srv, [([5, 1, 7, 2, 9], 7, None), ([3, 8, 6], 5, None),
                 ([4, 2, 8, 1, 6, 6, 3], 9, None)]


def _paged_prefix(cfg, params):
    srv = PagedSlotServer(params, cfg, n_slots=4, max_len=64, page=16,
                          n_pages=17, chunk=3)
    pid = srv.register_prefix(list(range(1, 21)))   # a whole page and a tail
    return srv, [([5, 1, 7], 7, pid), ([3, 8], 5, pid), ([4, 2, 8, 1], 9, pid)]


@pytest.mark.parametrize("k", [1, 3, 4])
@pytest.mark.parametrize("kind", [_paged, _paged_prefix],
                         ids=lambda f: f.__name__.lstrip("_"))
def test_a_paged_step_queues_its_programs_and_fetches_twice(cfg, params, kind,
                                                            k, monkeypatch):
    """The paged server rides the dense server's tail: its chunk's page
    growth reads the host's copy of live/pos, never the device."""
    from tests.test_serving import check_step_queues_then_fetches

    srv, requests = kind(cfg, params)
    rids, done = check_step_queues_then_fetches(srv, requests, k, monkeypatch)
    assert sorted(done) == sorted(rids)
    for i, rid in enumerate(rids):
        assert len(done[rid]) == requests[i % len(requests)][1]


def test_paged_requests_ending_at_their_first_token_return_their_pages(
        cfg, params):
    from tests.test_serving import check_first_token_endings

    srv = PagedSlotServer(params, cfg, n_slots=4, max_len=64, page=16,
                          n_pages=17, chunk=3)
    check_first_token_endings(
        srv, lambda p, n, eos: _oracle(params, cfg, p, n, eos_id=eos))
    assert srv.pages_in_use == 0


def test_dead_slots_write_the_trash_page_and_nothing_reads_it(cfg, params):
    """ROADMAP D8's pin.  A dead slot's table row is all zeros, so the
    chunk program's frozen-cursor writes for it land on page 0, several
    dead slots on the same tile of it (the in-place write's rows may not
    share a tile EXCEPT there: whatever the race leaves is never read).
    Page 0 filled with NaN before the run and found rewritten after it
    shows both halves: the dead slots did write there, and no live slot's
    tokens, which equal generate()'s, ever read it (ids past a live
    slot's cursor point at page 0 too and are never fetched)."""
    srv = PagedSlotServer(params, cfg, n_slots=4, max_len=64, page=16,
                          n_pages=9, chunk=4)
    srv.cache = {n: a.at[:, 0].set(jnp.nan) for n, a in srv.cache.items()}
    prompt = [5, 9, 2, 7, 3]
    rid = srv.submit(prompt, 9)
    srv.step()
    assert (srv._tables[1:] == 0).all()  # three dead slots, all on page 0
    done = srv.run()
    np.testing.assert_array_equal(done[rid], _oracle(params, cfg, prompt, 9))
    trash = np.asarray(srv.cache["k"][:, 0], np.float32)
    assert np.isnan(trash).any() and not np.isnan(trash).all()
    live = np.asarray(srv.cache["k"][:, 1:], np.float32)
    assert not np.isnan(live).any()


def test_prefix_admit_writes_by_scatter_whatever_the_decision(cfg,
                                                              force_kernels):
    """The paged prefix admit writes one row a TOKEN, so neighbouring rows
    share a page tile: it is the one caller that must not take the
    in-place kernel (``kv_write_lax`` by name), even where the kernels are
    chosen and its attention does run the paged kernel."""
    from starway_tpu.models import init_params
    from starway_tpu.models.paged import (_compiled_paged_prefix_admit,
                                          init_paged_pool)

    force_kernels(True)
    page, max_pages, s_bucket = 16, 4, 24
    run = _compiled_paged_prefix_admit.__wrapped__(
        cfg, s_bucket, page, max_pages, False, 0.0, None, None)
    shapes = jax.eval_shape(
        lambda: (init_params(jax.random.PRNGKey(0), cfg),
                 init_paged_pool(cfg, 9, page)))
    i32 = lambda *s: jax.ShapeDtypeStruct(s, jnp.int32)
    text = str(run.trace(*shapes, i32(1, max_pages), i32(1, s_bucket), i32(),
                         i32(), jax.eval_shape(jax.random.PRNGKey, 0)).jaxpr)
    assert "sw_paged_decode_attn" in text
    assert "sw_kv_write" not in text and "scatter" in text
