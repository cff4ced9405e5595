"""Chip-independent proof of the "dots" remat lever (VERDICT r4 #1).

The MFU-bench remat policy claims the backward replays only the
elementwise chain — no matmul recompute and, critically, no re-run of the
flash forward kernel.  Nothing on-chip is needed to verify that claim: the
train step is cross-lowered for the TPU platform from the CPU host and the
pallas custom calls are counted by kernel name in the lowered StableHLO
(post-jax-DCE, pre-XLA, one occurrence per call site — scan bodies appear
once regardless of depth).

Reference intent: the reference has no remat machinery at all (its compute
layer is torch); this pins the TPU-native lever that the
train_step_mfu >= 0.40 target (ROADMAP.md S7) rides on.

Background (jax 0.9): a whole-layer jax.checkpoint whose policy saves the
q/k/v projection dots makes partial-eval replay the flash custom_vjp's
forward kernel in the backward even when the kernel's outputs (o, lse) are
policy-saved.  llama.py therefore implements "dots" structurally — two
checkpointed chunks around an un-checkpointed attention call
(decoder_layer) — and these tests pin that structure's no-recompute
property so a refactor back to a policy cannot silently reintroduce the
extra forward.
"""

import re

import jax
import jax.numpy as jnp
import numpy as np
import optax
import pytest

from starway_tpu.models import LlamaConfig, init_params, make_train_step
from starway_tpu.ops.pallas_attention import flash_attention


def _tiny_cfg(**kw):
    kw.setdefault("dtype", "bfloat16")
    return LlamaConfig.preset(
        "debug", d_model=256, n_layers=2, n_heads=2, n_kv_heads=1,
        d_ff=512, vocab_size=512, **kw)


def _flash_attn(q, k, v):
    # interpret=False: the real mosaic lowering, cross-compiled for TPU.
    return flash_attention(q, k, v, causal=True, interpret=False)


def _kernel_calls(cfg):
    """Pallas kernel names at each call site of the lowered train step."""
    params = jax.eval_shape(lambda: init_params(jax.random.PRNGKey(0), cfg))
    tx = optax.adamw(1e-3)
    opt = jax.eval_shape(
        lambda: tx.init(init_params(jax.random.PRNGKey(0), cfg)))
    step = make_train_step(cfg, tx, _flash_attn)
    batch = jax.ShapeDtypeStruct((1, 257), jnp.int32)
    txt = (jax.jit(step).trace(params, opt, batch)
           .lower(lowering_platforms=("tpu",)).as_text())
    return re.findall(r'kernel_name = "(\w+)"', txt)


def test_dots_remat_never_reruns_flash_forward():
    """THE pin: scanned layers + "dots" remat lower to exactly one forward
    kernel call site — identical to the no-remat lowering."""
    calls = _kernel_calls(_tiny_cfg(remat=True, remat_policy="dots"))
    assert calls == ["sw_flash_fwd", "sw_flash_bwd_dkv", "sw_flash_bwd_dq"]


def test_no_remat_baseline_call_sites():
    calls = _kernel_calls(_tiny_cfg())
    assert calls == ["sw_flash_fwd", "sw_flash_bwd_dkv", "sw_flash_bwd_dq"]


def test_full_remat_replays_flash_forward():
    """Full-layer remat pays one extra forward kernel per layer body —
    the documented memory-for-flops trade (llama.py remat_policy=None)."""
    calls = _kernel_calls(_tiny_cfg(remat=True, remat_policy=None))
    assert calls.count("sw_flash_fwd") == 2


def test_dots_remat_unrolled_never_reruns_flash_forward():
    """scan_layers=False: one forward call site per layer, no recompute."""
    cfg = _tiny_cfg(remat=True, remat_policy="dots", scan_layers=False)
    calls = _kernel_calls(cfg)
    assert calls.count("sw_flash_fwd") == cfg.n_layers
    assert calls.count("sw_flash_bwd_dq") == cfg.n_layers


def test_dots_remat_backward_has_no_matmul_recompute():
    """Flops audit: the "dots" step's total dot_general count equals the
    no-remat step's (backward replays only elementwise ops), while full
    remat adds the replayed projection/MLP dots."""

    def n_dots(cfg):
        params = jax.eval_shape(
            lambda: init_params(jax.random.PRNGKey(0), cfg))
        tx = optax.adamw(1e-3)
        opt = jax.eval_shape(
            lambda: tx.init(init_params(jax.random.PRNGKey(0), cfg)))
        step = make_train_step(cfg, tx, _flash_attn)
        batch = jax.ShapeDtypeStruct((1, 257), jnp.int32)
        txt = (jax.jit(step).trace(params, opt, batch)
               .lower(lowering_platforms=("tpu",)).as_text())
        return txt.count("stablehlo.dot_general")

    base = n_dots(_tiny_cfg())
    dots = n_dots(_tiny_cfg(remat=True, remat_policy="dots"))
    full = n_dots(_tiny_cfg(remat=True, remat_policy=None))
    assert dots == base, (dots, base)
    assert full > base, (full, base)


def test_dots_remat_grads_match_no_remat():
    """Chunked checkpointing is numerically neutral: same loss, same
    grads as the un-rematted step (CPU blockwise attention path)."""
    from starway_tpu.models.llama import loss_fn

    rng = np.random.default_rng(0)
    batch = jnp.asarray(rng.integers(0, 512, (2, 33), dtype=np.int32))
    base_cfg = _tiny_cfg(dtype="float32")
    params = init_params(jax.random.PRNGKey(1), base_cfg)

    def loss_and_grads(cfg):
        val, g = jax.value_and_grad(
            lambda p: loss_fn(p, batch, cfg))(params)
        return val, g

    v0, g0 = loss_and_grads(base_cfg)
    v1, g1 = loss_and_grads(
        _tiny_cfg(dtype="float32", remat=True, remat_policy="dots"))
    np.testing.assert_allclose(np.asarray(v0), np.asarray(v1), rtol=1e-6)
    for a, b in zip(jax.tree_util.tree_leaves(g0),
                    jax.tree_util.tree_leaves(g1)):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   atol=1e-5, rtol=1e-4)


def test_unrolled_forward_matches_scanned():
    """scan_layers=False is the same model: logits bit-compare against
    the scanned forward."""
    from starway_tpu.models.llama import forward

    rng = np.random.default_rng(2)
    tokens = jnp.asarray(rng.integers(0, 512, (2, 16), dtype=np.int32))
    cfg_s = _tiny_cfg(dtype="float32")
    cfg_u = _tiny_cfg(dtype="float32", scan_layers=False)
    params = init_params(jax.random.PRNGKey(3), cfg_s)
    a = forward(params, tokens, cfg_s)
    b = forward(params, tokens, cfg_u)
    np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                               atol=1e-5, rtol=1e-5)


def test_unrolled_return_kv_matches_scanned():
    from starway_tpu.models.llama import forward

    rng = np.random.default_rng(4)
    tokens = jnp.asarray(rng.integers(0, 512, (1, 16), dtype=np.int32))
    cfg_s = _tiny_cfg(dtype="float32")
    cfg_u = _tiny_cfg(dtype="float32", scan_layers=False)
    params = init_params(jax.random.PRNGKey(5), cfg_s)
    _, kv_s = forward(params, tokens, cfg_s, return_kv=True)
    _, kv_u = forward(params, tokens, cfg_u, return_kv=True)
    (k_s, v_s), (k_u, v_u) = (kv_s["k"], kv_s["v"]), (kv_u["k"], kv_u["v"])
    np.testing.assert_allclose(np.asarray(k_s), np.asarray(k_u),
                               atol=1e-5, rtol=1e-5)
    np.testing.assert_allclose(np.asarray(v_s), np.asarray(v_u),
                               atol=1e-5, rtol=1e-5)


def test_dots_remat_grads_match_no_remat_moe():
    """The MoE branch rides the post chunk: chunked "dots" remat is
    numerically neutral there too."""
    from starway_tpu.models.llama import loss_fn

    rng = np.random.default_rng(6)
    batch = jnp.asarray(rng.integers(0, 512, (2, 17), dtype=np.int32))
    kw = dict(dtype="float32", n_experts=4, moe_top_k=2, moe_swiglu=True)
    base_cfg = _tiny_cfg(**kw)
    params = init_params(jax.random.PRNGKey(7), base_cfg)

    v0, g0 = jax.value_and_grad(
        lambda p: loss_fn(p, batch, base_cfg))(params)
    cfg_r = _tiny_cfg(remat=True, remat_policy="dots", **kw)
    v1, g1 = jax.value_and_grad(
        lambda p: loss_fn(p, batch, cfg_r))(params)
    np.testing.assert_allclose(np.asarray(v0), np.asarray(v1), rtol=1e-6)
    for a, b in zip(jax.tree_util.tree_leaves(g0),
                    jax.tree_util.tree_leaves(g1)):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   atol=1e-5, rtol=1e-4)


def test_flash_lse_is_aux_output():
    """flash_attention still returns just o; the lse primal output is an
    internal detail of the remat contract (discarded by the wrapper)."""
    q = jnp.zeros((1, 2, 64, 32), jnp.float32)
    out = flash_attention(q, q, q, causal=True, interpret=True)
    assert out.shape == (1, 2, 64, 32)
