"""Checkpoint round-trip, perf-model calibration."""

import numpy as np
import pytest

import jax.numpy as jnp

from starway_tpu import perf
from starway_tpu.utils.checkpoint import restore_pytree, save_pytree


def test_checkpoint_roundtrip(tmp_path):
    tree = {
        "w": jnp.arange(24, dtype=jnp.float32).reshape(4, 6),
        "b": jnp.full((6,), 2, dtype=jnp.bfloat16),
        "nested": {"step": jnp.asarray(7, dtype=jnp.int32)},
    }
    backend = save_pytree(str(tmp_path / "ckpt"), tree)
    assert backend in ("orbax", "npz")
    restored = restore_pytree(str(tmp_path / "ckpt"), like=tree)
    import jax

    for a, b in zip(jax.tree_util.tree_leaves(tree), jax.tree_util.tree_leaves(restored)):
        assert a.dtype == b.dtype
        np.testing.assert_array_equal(np.asarray(a, dtype=np.float32),
                                      np.asarray(b, dtype=np.float32))


def test_checkpoint_validates_structure(tmp_path):
    import json

    import pytest

    tree = {"w": jnp.ones((4, 6), jnp.float32), "b": jnp.zeros((6,), jnp.float32)}
    save_pytree(str(tmp_path / "ckpt"), tree)

    # Manifest records the backend + leaf specs (no file-existence guessing).
    manifest = json.loads((tmp_path / "ckpt" / "manifest.json").read_text())
    assert manifest["backend"] in ("orbax", "npz")
    assert manifest["n"] == 2

    # Shape mismatch fails loudly instead of restoring garbage.
    bad_shape = {"w": jnp.ones((4, 7), jnp.float32), "b": jnp.zeros((6,), jnp.float32)}
    with pytest.raises(ValueError, match="shape mismatch"):
        restore_pytree(str(tmp_path / "ckpt"), like=bad_shape)

    # Structure (leaf count) mismatch too.
    with pytest.raises(ValueError, match="structure mismatch"):
        restore_pytree(str(tmp_path / "ckpt"), like={"w": tree["w"]})


def test_token_batcher(tmp_path):
    """Deterministic epoch-shuffled windows: full coverage per epoch,
    reproducible order, cursor resume, raw/npy loading."""
    import numpy as np

    from starway_tpu.utils import TokenBatcher, load_tokens

    tokens = np.arange(1000, dtype=np.uint16)
    seq, bsz = 9, 4  # window 10 -> 100 windows, 25 batches/epoch
    it = iter(TokenBatcher(tokens, bsz, seq, seed=3, epochs=1))
    seen = []
    for batch in it:
        assert batch.shape == (bsz, seq + 1)
        assert batch.dtype == np.int32
        for row in batch:
            np.testing.assert_array_equal(row, np.arange(row[0], row[0] + seq + 1))
            seen.append(int(row[0]) // (seq + 1))
    assert sorted(seen) == list(range(100))  # every window exactly once

    # Same seed -> same order; different seed -> different order.
    first = next(iter(TokenBatcher(tokens, bsz, seq, seed=3)))
    again = next(iter(TokenBatcher(tokens, bsz, seq, seed=3)))
    other = next(iter(TokenBatcher(tokens, bsz, seq, seed=4)))
    np.testing.assert_array_equal(first, again)
    assert not np.array_equal(first, other)

    # Cursor resume: replaying from a saved state yields the same batches.
    b1 = TokenBatcher(tokens, bsz, seq, seed=3)
    i1 = iter(b1)
    next(i1); next(i1)
    state = b1.state()
    want = next(i1)
    b2 = TokenBatcher(tokens, bsz, seq, seed=3)
    b2.restore(state)
    np.testing.assert_array_equal(next(iter(b2)), want)

    # Guards: exhausted bounded batcher fails loudly until reset; a second
    # live iterator is rejected (the resume cursor is shared); a stale
    # cursor from different geometry is rejected.
    b3 = TokenBatcher(tokens, bsz, seq, seed=3, epochs=1)
    assert len(list(b3)) == 25
    with pytest.raises(RuntimeError, match="exhausted"):
        iter(b3)
    b3.reset()
    assert next(iter(b3)) is not None
    b4 = TokenBatcher(tokens, bsz, seq, seed=3)
    i4 = iter(b4)  # not yet advanced: the mark is taken at iter() time
    with pytest.raises(RuntimeError, match="one active iterator"):
        iter(b4)
    next(i4)
    with pytest.raises(RuntimeError, match="one active iterator"):
        iter(b4)
    with pytest.raises(RuntimeError, match="live iterator"):
        b4.reset()  # resetting under a running loop would rewind it
    i4.close()
    assert next(iter(b4)) is not None  # close released the mark
    i5 = iter(b4)  # abandoned before first next(): GC must release the mark
    del i5
    assert next(iter(b4)) is not None
    with pytest.raises(ValueError, match="state mismatch"):
        TokenBatcher(tokens, bsz + 1, seq, seed=3).restore(b4.state())

    # Loaders: npy header dtype vs raw + explicit dtype.
    np.save(tmp_path / "t.npy", tokens)
    (tmp_path / "t.bin").write_bytes(tokens.tobytes())
    np.testing.assert_array_equal(load_tokens(str(tmp_path / "t.npy")), tokens)
    np.testing.assert_array_equal(
        load_tokens(str(tmp_path / "t.bin"), dtype=np.uint16), tokens)
    with pytest.raises(ValueError):
        load_tokens(str(tmp_path / "t.bin"))


def test_perf_estimate_positive_and_monotone():
    for t in ("inproc", "tcp", "ici", "dcn", "unknown"):
        small = perf.estimate(t, 1)
        big = perf.estimate(t, 1 << 30)
        assert 0 < small < big


def test_perf_calibrate(perf_table_guard):
    # Synthetic samples from a known alpha/beta model round-trip the fit.
    alpha, beta = 5e-6, 2e9
    samples = [(n, alpha + n / beta) for n in (1024, 1 << 16, 1 << 20, 1 << 24)]
    a, b = perf.calibrate("tcp", samples)
    assert abs(a - alpha) / alpha < 0.05
    assert abs(b - beta) / beta < 0.05
    assert abs(perf.estimate("tcp", 1 << 20) - (alpha + (1 << 20) / beta)) < 1e-6


@pytest.fixture
def perf_table_guard():
    """calibrate() mutates the process-global class table; restore it."""
    models = dict(perf.LINK_MODELS)
    prov = dict(perf.PROVENANCE)
    calibrated = set(perf.CALIBRATED)
    yield
    perf.LINK_MODELS.clear()
    perf.LINK_MODELS.update(models)
    perf.PROVENANCE.clear()
    perf.PROVENANCE.update(prov)
    perf.CALIBRATED.clear()
    perf.CALIBRATED.update(calibrated)


def test_perf_detail_prior_vs_calibrated(perf_table_guard):
    """VERDICT r4 #5: an estimate from an uncalibrated spec-sheet prior
    must say so; a live fit must say that instead."""
    d = perf.estimate_detail("ici", 1 << 20)
    assert d["calibrated"] is False
    assert "prior" in d["source"] and "v5e" in d["source"]
    assert d["seconds"] == pytest.approx(perf.estimate("ici", 1 << 20))

    d = perf.estimate_detail("dcn", 1 << 20)
    assert d["calibrated"] is False and "prior" in d["source"]

    alpha, beta = 5e-6, 2e9
    samples = [(n, alpha + n / beta) for n in (1024, 1 << 16, 1 << 20)]
    perf.calibrate("dcn", samples)
    d = perf.estimate_detail("dcn", 1 << 20)
    assert d["calibrated"] is True
    assert "live class fit" in d["source"]
    assert d["beta"] == pytest.approx(beta, rel=0.05)

    # Unknown transports fall back to the tcp class and say so honestly.
    d = perf.estimate_detail("warp-drive", 1 << 20)
    assert d["transport"] == "tcp"


def test_perf_detail_per_endpoint_fit(perf_table_guard):
    """A conn carrying a live per-endpoint model reports calibrated=True
    with the endpoint-fit source; a bare conn reports the class entry."""

    class FakeConn:
        pass

    conn = FakeConn()
    d = perf.conn_estimate_detail(conn, "ici", 1 << 20)
    assert d["calibrated"] is False and "prior" in d["source"]

    conn.perf_model = (3e-6, 10e9)
    d = perf.conn_estimate_detail(conn, "ici", 1 << 20)
    assert d["calibrated"] is True and "per-endpoint" in d["source"]
    assert d["seconds"] == pytest.approx(3e-6 + (1 << 20) / 10e9)


def _dcn_standin_server(port, stop):
    import asyncio
    import os

    os.environ["STARWAY_TLS"] = "tcp"
    from starway_tpu import Server

    async def main():
        s = Server()
        s.listen("127.0.0.1", port)
        while not stop.is_set():
            await asyncio.sleep(0.05)
        await s.aclose()

    asyncio.run(main())


def test_autocalibrate_dcn_standin_two_processes(monkeypatch,
                                                 perf_table_guard, port):
    """The DCN class entry calibrated LIVE over a real 2-process TCP pair
    (the in-sandbox stand-in for a cross-host DCN link): after
    autocalibrate(transport="dcn"), both the class detail and the
    client's per-endpoint detail report calibrated=True."""
    import asyncio
    import multiprocessing as mp

    monkeypatch.setenv("STARWAY_TLS", "tcp")
    monkeypatch.setenv("STARWAY_NATIVE", "0")
    ctx = mp.get_context("spawn")
    stop = ctx.Event()
    srv = ctx.Process(target=_dcn_standin_server, args=(port, stop))
    srv.start()

    async def drive():
        from starway_tpu import Client

        client = None
        for _ in range(60):  # connect-once: fresh Client per attempt
            c = Client()
            try:
                await c.aconnect("127.0.0.1", port)
                client = c
                break
            except Exception:
                await asyncio.sleep(0.25)
        assert client is not None, "stand-in server never came up"
        assert perf.estimate_detail("dcn", 1 << 20)["calibrated"] is False
        await perf.autocalibrate(client, "dcn", sizes=(1 << 10, 1 << 14))
        class_d = perf.estimate_detail("dcn", 1 << 20)
        ep_d = client.evaluate_perf_detail(1 << 20)
        await client.aclose()
        return class_d, ep_d

    try:
        class_d, ep_d = asyncio.run(drive())
    finally:
        stop.set()
        srv.join(timeout=30)
        if srv.is_alive():
            srv.terminate()
    assert class_d["calibrated"] is True
    assert "live class fit" in class_d["source"]
    assert ep_d["calibrated"] is True
    assert "per-endpoint" in ep_d["source"]
    assert ep_d["seconds"] > 0


def _conn_is_sm(conn) -> bool:
    if getattr(conn, "sm_negotiated", False):
        return True  # Python engine
    t = getattr(conn, "transports", None)
    return bool(t) and conn.transports() == [("shm", "sm")]  # native


@pytest.mark.parametrize("native_flag", ["0", "1"])
def test_per_endpoint_evaluate_perf(monkeypatch, native_flag):
    """Reference fidelity for ucp_ep_evaluate_perf (VERDICT r3 #7): ONE
    server, one sm peer and one tcp peer; after server-side live probes
    (perf.autocalibrate_ep) each endpoint reports ITS OWN fitted model --
    estimates are distinct per endpoint, exactly alpha + n/beta of the
    endpoint's fit, and an uncalibrated endpoint still gets the class
    table.  Both engines."""
    import asyncio
    import json

    from starway_tpu import Client, Server
    from starway_tpu.core import native

    if native_flag == "1" and not native.available():
        pytest.skip("native engine unavailable")
    monkeypatch.setenv("STARWAY_NATIVE", native_flag)

    async def drive():
        monkeypatch.setenv("STARWAY_TLS", "tcp,sm")
        s = Server()
        s.listen("127.0.0.1", 0)
        port = json.loads(s.get_worker_address())["port"]
        c_sm = Client()
        await c_sm.aconnect("127.0.0.1", port)
        monkeypatch.setenv("STARWAY_TLS", "tcp")
        c_tcp = Client()
        await c_tcp.aconnect("127.0.0.1", port)

        eps = {_conn_is_sm(ep._conn): ep for ep in s.list_clients()}
        assert set(eps) == {True, False}, "need one sm and one tcp peer"
        ep_sm, ep_tcp = eps[True], eps[False]

        n = 1 << 20
        class_sm = s.evaluate_perf(ep_sm, n)
        class_tcp = s.evaluate_perf(ep_tcp, n)
        assert class_sm > 0 and class_tcp > 0

        m_sm = await perf.autocalibrate_ep(s, ep_sm,
                                           sizes=(1 << 10, 1 << 15, 1 << 19))
        live_sm = s.evaluate_perf(ep_sm, n)
        live_tcp = s.evaluate_perf(ep_tcp, n)
        # Calibrated endpoint reports exactly its own fit...
        assert live_sm == pytest.approx(m_sm[0] + n / m_sm[1])
        # ...while the uncalibrated peer still reports the class model.
        assert live_tcp == class_tcp

        m_tcp = await perf.autocalibrate_ep(s, ep_tcp,
                                            sizes=(1 << 10, 1 << 15, 1 << 19))
        live_tcp = s.evaluate_perf(ep_tcp, n)
        assert live_tcp == pytest.approx(m_tcp[0] + n / m_tcp[1])
        # Two live endpoints, two independent fits: distinct estimates.
        assert live_sm != live_tcp

        # Client side: autocalibrate attaches to the primary conn too.
        before = c_tcp.evaluate_perf(n)
        a, b = await perf.autocalibrate(c_tcp, "tcp",
                                        sizes=(1 << 10, 1 << 15))
        assert c_tcp.evaluate_perf(n) == pytest.approx(a + n / b)
        del before
        await c_sm.aclose()
        await c_tcp.aclose()
        await s.aclose()

    asyncio.run(drive())


def test_probe_tag_dropped_on_wire_both_engines(monkeypatch):
    """The reserved probe tag is consumed by BOTH engines' matchers over a
    real socket: autocalibrate against each engine, then a wildcard recv
    sees only real traffic."""
    import asyncio

    import numpy as np

    from starway_tpu import Client, Server
    from starway_tpu.core import native

    engines = ["0"] + (["1"] if native.available() else [])
    monkeypatch.setenv("STARWAY_TLS", "tcp")

    async def drive():
        for native_flag in engines:
            monkeypatch.setenv("STARWAY_NATIVE", native_flag)
            s = Server()
            s.listen("127.0.0.1", 0)
            import json

            port = json.loads(s.get_worker_address())["port"]
            c = Client()
            await c.aconnect("127.0.0.1", port)
            await perf.autocalibrate(c, "tcp", sizes=(1 << 10, 1 << 14))
            buf = np.zeros(8, dtype=np.uint8)
            fut = s.arecv(buf, 0, 0)  # wildcard
            await asyncio.sleep(0.05)
            await c.asend(np.arange(8, dtype=np.uint8), 99)
            tag, n = await asyncio.wait_for(fut, 10)
            assert (tag, n) == (99, 8), f"engine={native_flag}: probe leaked"
            np.testing.assert_array_equal(buf, np.arange(8, dtype=np.uint8))
            await c.aclose()
            await s.aclose()

    asyncio.run(drive())


# ------------------------------------------------- utils/chip.py (PR 21)


@pytest.mark.parametrize("from_env", [True, False])
def test_compile_cache_is_placed_from_outside(monkeypatch, tmp_path, from_env):
    """JAX_COMPILATION_CACHE_DIR set: no directory is set in code.  Unset:
    one fixed path inside the checkout, never a temporary name."""
    import jax

    from starway_tpu.utils import chip

    keys = ("jax_compilation_cache_dir",
            "jax_persistent_cache_min_compile_time_secs",
            "jax_persistent_cache_min_entry_size_bytes")
    before = {k: getattr(jax.config, k) for k in keys}
    try:
        if from_env:
            monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path))
            assert chip.enable_compile_cache() == str(tmp_path)
            assert jax.config.jax_compilation_cache_dir == before[keys[0]]
        else:
            monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
            first = chip.enable_compile_cache()
            assert first == chip.enable_compile_cache()  # fixed, not per call
            assert first == jax.config.jax_compilation_cache_dir
            repo = str(chip.Path(chip.__file__).resolve().parents[2])
            assert first == repo + "/.jax_cache"
    finally:
        for k, v in before.items():
            jax.config.update(k, v)


def test_measuring_entry_points_refuse_the_cpu():
    from starway_tpu.utils import chip

    assert chip.device_info()["platform"] == "cpu"  # the suite's platform
    with pytest.raises(SystemExit, match="no accelerator"):
        chip.require_accelerator()


def test_peaks_are_keyed_by_device_kind():
    from starway_tpu.utils import chip

    assert chip.peaks("TPU v5 lite")["bf16_flops"] == 197e12
    with pytest.raises(SystemExit, match="no published peaks"):
        chip.peaks("cpu")
