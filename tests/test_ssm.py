"""The state-space recurrence (ops/pallas_ssm.py) and the Mamba layer
around it (models/ssm.py): the kernels in interpret mode against their lax
twins against the recurrence written token by token in numpy, ragged
``lengths`` included; what a padded bucket leaves behind; prefill then
decode against one longer prefill."""

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from starway_tpu.ops import pallas_ssm as P

TOL = dict(rtol=2e-5, atol=2e-5)


def _operands(seed, b, s, e, n):
    rng = np.random.default_rng(seed)
    f = lambda *shape: rng.standard_normal(shape).astype(np.float32)
    dt = np.log1p(np.exp(f(b, s, e) - 2.0))             # a softplus: > 0
    a = -np.exp(f(n, e) * 0.5)
    return dt, f(b, s, e), f(b, s, n), f(b, s, n), a, f(e)


def _recurrence(dt, x, bm, cm, a, d, lengths=None, state=None):
    """Token by token, a row at a time, in float64."""
    b, s, e = dt.shape
    n = a.shape[0]
    state = np.zeros((b, n, e)) if state is None else state.astype(np.float64)
    y = np.zeros((b, s, e))
    for i in range(b):
        for t in range(s if lengths is None else int(lengths[i])):
            state[i] = (np.exp(dt[i, t][None, :] * a) * state[i]
                        + (dt[i, t] * x[i, t])[None, :] * bm[i, t][:, None])
            y[i, t] = cm[i, t] @ state[i] + d * x[i, t]
    return y, state


@pytest.mark.parametrize("b,e,n", [(2, 256, 8), (8, 384, 16), (16, 128, 8)])
def test_step_kernel_matches_its_twin_and_the_recurrence(b, e, n):
    dt, x, bm, cm, a, d = _operands(b + e, b, 1, e, n)
    rng = np.random.default_rng(7)
    stack = rng.standard_normal((3, b, n, e)).astype(np.float32)
    want_y, want_s = _recurrence(dt, x, bm, cm, a, d, state=stack[1])
    args = tuple(jnp.asarray(t[:, 0]) for t in (dt, x, bm, cm)) + (
        jnp.asarray(a), jnp.asarray(d))
    for run in (P.ssm_step_lax, lambda *ar, layer: P.ssm_step_kernel(
            *ar, layer=layer, interpret=True)):
        y, out = run(jnp.asarray(stack), *args, layer=jnp.int32(1))
        np.testing.assert_allclose(y, want_y[:, 0], **TOL)
        np.testing.assert_allclose(out[1], want_s, **TOL)
        # the other layers' states stand as they were
        np.testing.assert_array_equal(out[0], stack[0])
        np.testing.assert_array_equal(out[2], stack[2])


@pytest.mark.parametrize("b,s,e,n", [(1, 128, 256, 8), (2, 256, 128, 16)])
def test_scan_kernel_matches_its_twin_and_the_recurrence(b, s, e, n):
    dt, x, bm, cm, a, d = _operands(s + e, b, s, e, n)
    want_y, want_s = _recurrence(dt, x, bm, cm, a, d)
    args = tuple(jnp.asarray(t) for t in (dt, x, bm, cm, a, d))
    for run in (P.ssm_scan_lax,
                lambda *ar: P.ssm_scan_kernel(*ar, interpret=True)):
        y, state = run(*args)
        np.testing.assert_allclose(y, want_y, rtol=1e-4, atol=1e-4)
        np.testing.assert_allclose(state, want_s, rtol=1e-4, atol=1e-4)


@pytest.mark.parametrize("kernels", [False, True])
@pytest.mark.parametrize("lengths", [(5, 40), (1, 33), (40, 40)])
def test_scan_hands_back_the_state_after_the_last_real_token(
        lengths, kernels, force_kernels):
    """``ops.ssm_scan`` itself, ragged: a padded row leaves what the
    unpadded row leaves, on either side of the dispatch (S = 40 is padded
    to a whole chunk on the kernels' side)."""
    force_kernels(kernels)
    b, s, e, n = 2, 40, 128, 8
    dt, x, bm, cm, a, d = _operands(11, b, s, e, n)
    want_y, want_s = _recurrence(dt, x, bm, cm, a, d, lengths=lengths)
    y, state = P.ssm_scan(*(jnp.asarray(t) for t in (dt, x, bm, cm, a, d)),
                          lengths=jnp.asarray(lengths))
    np.testing.assert_allclose(state, want_s, rtol=1e-4, atol=1e-4)
    for i, n_real in enumerate(lengths):
        np.testing.assert_allclose(y[i, :n_real], want_y[i, :n_real],
                                   rtol=1e-4, atol=1e-4)


def test_step_takes_the_kernel_only_where_the_chip_tiles_it():
    assert P._step_tiles(96, 5120) == (8, 2560)
    assert P._step_tiles(4, 128) == (4, 128)
    assert P._step_tiles(12, 5120) is None      # rows in whole sublane tiles
    assert P._step_tiles(8, 100) is None        # channels in whole lane tiles
    assert P._channel_block(5120, 1280) == 1280


def _layer(seed=0):
    from starway_tpu.models.llama import LayerKinds, LlamaConfig, StateSpace
    from starway_tpu.models.ssm import init_ssm_params

    cfg = LlamaConfig(
        vocab_size=64, d_model=32, n_layers=2, n_heads=4, n_kv_heads=2,
        d_ff=64, dtype="float32", diff_attn=True,
        kinds=LayerKinds.in_runs(((("ssm", "full"), 1),)),
        ssm=StateSpace(d_inner=128, d_state=8, dt_rank=4))
    params = jax.tree_util.tree_map(
        lambda a: a[0], init_ssm_params(jax.random.PRNGKey(seed), 1, cfg))
    return cfg, params


@pytest.mark.parametrize("kernels", [False, True])
def test_prefill_then_decode_is_one_longer_prefill(kernels, force_kernels):
    from starway_tpu.models.cache import init_cache
    from starway_tpu.models.ssm import ssm_decode, ssm_prefill

    force_kernels(kernels)
    cfg, p = _layer()
    x = jax.random.normal(jax.random.PRNGKey(3), (2, 12, 32), jnp.float32)
    out, kv = ssm_prefill(x, p, cfg)
    lengths = jnp.asarray([7, 4])
    _o, part = ssm_prefill(x, p, cfg, lengths)
    # a padded row's state and tail are its own first lengths[b] tokens'
    for i, n_real in enumerate((7, 4)):
        _o1, alone = ssm_prefill(x[i:i + 1, :n_real], p, cfg)
        for name in ("ssm_state", "ssm_conv"):
            np.testing.assert_allclose(part[name][i], alone[name][0], **TOL)
    # decoding the rest of row 0 from its state at 7 gives the prefill's
    cache = init_cache(cfg, 1, 16)
    cache = {**cache, "ssm_state": part["ssm_state"][None, :1],
             "ssm_conv": part["ssm_conv"][None, :1]}
    for t in range(7, 12):
        o, cache, mem = ssm_decode(x[:1, t:t + 1], p, cfg, cache, jnp.int32(0))
        np.testing.assert_allclose(o[0, 0], out[0, t], **TOL)
        np.testing.assert_allclose(mem[0, 0], kv["mem"][0, t], **TOL)
    np.testing.assert_allclose(cache["ssm_state"][0, 0], kv["ssm_state"][0], **TOL)
    np.testing.assert_allclose(cache["ssm_conv"][0, 0], kv["ssm_conv"][0], **TOL)
