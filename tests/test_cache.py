"""What a cache holds is said once (models/cache.py): for the tiny
configuration of every kind the suite builds, the description's leaves
against what ``init_cache`` / ``init_rolling_cache`` returned before the
description existed (written out: a golden, not a second call of the same
code), the description recovered from a cache in hand, which kind takes
its prompts piece by piece, and the rows a serving step logs."""

import numpy as np
import pytest

import jax.numpy as jnp

from benchmark.harness import spec as S
from starway_tpu.models import SlotServer
from starway_tpu.models.cache import (cache_spec, init_cache,
                                      init_rolling_cache, served_spec,
                                      spec_of)
from starway_tpu.models.llama import LlamaConfig
from tests.test_gdn import TINY as GDN
from tests.test_kda import TINY as KDA
from tests.test_mla_moe import TINY as MLA
from tests.test_mtp_serving import tiny_cfg
from tests.test_sambay import TINY as SAMBAY
from tests.test_window_moe import TINY as WINDOW

B, T = 3, 40
F32, I8 = "float32", "int8"


def _served(runner, config):
    return lambda: S.load_runner(runner).model_config(config)


def _kv(layers, heads, t, d, dtype=F32, kind=""):
    return [(name + kind, (layers, B, heads, t, d), dtype)
            for name in ("k", "v")]


def _scales(layers, heads, t):
    return [(name, (layers, B, heads, t), F32)
            for name in ("k_scale", "v_scale")]


# kind: (the configuration, rolling, [(leaf, shape, dtype)] as the parent's
# init_cache(cfg, 3, 40) / init_rolling_cache(cfg, 3) returned them, the
# facts, and the fields a step logs for the cursors of CURSORS)
CURSORS = [3, 10, 0, 25]
KINDS = {
    "dense": (lambda: LlamaConfig.preset("debug"), False,
              _kv(2, 4, T, 16), {}, {}),
    "dense_int8": (lambda: LlamaConfig.preset("debug", kv_quant="int8"),
                   False, _kv(2, 4, T, 16, I8) + _scales(2, 4, T),
                   dict(int8=True), {}),
    "rolling": (lambda: LlamaConfig.preset("debug", sliding_window=8), True,
                _kv(2, 4, 8, 16), dict(ring=8, rolling=True, length=8), {}),
    "rolling_int8": (
        lambda: LlamaConfig.preset("debug", sliding_window=8,
                                   kv_quant="int8"), True,
        _kv(2, 4, 8, 16, I8) + _scales(2, 4, 8),
        dict(ring=8, rolling=True, length=8, int8=True), {}),
    "latent": (_served("serve_mla_moe", MLA), False,
               [("ckv", (3, B, 1, T, 128), F32)], dict(latent=True), {}),
    "rings": (_served("serve_window_moe", WINDOW), False,
              _kv(2, 2, T, 16) + _kv(6, 2, 8, 16, kind="_ring"),
              dict(ring=8), dict(kv_rows_full=42, kv_rows_window=21)),
    "rings_slack": (lambda: tiny_cfg(mtp=0), False,
                    _kv(2, 2, T, 8) + _kv(6, 2, 8, 8, kind="_ring"),
                    dict(ring=8), dict(kv_rows_full=42, kv_rows_window=21)),
    "state_latent": (
        _served("serve_kda_mla_moe", KDA), False,
        [("ckv", (2, B, 1, T, 128), F32),
         ("kda_state", (6, B, 4, 16, 16), F32),
         ("kda_conv", (6, B, 3, 192), F32)],
        dict(latent=True, state=True),
        dict(state_slots=4, kv_rows_latent=42)),
    "state_grouped": (
        _served("serve_gdn_gqa_moe", GDN), False,
        _kv(2, 2, T, 32) + [("kda_state", (6, B, 4, 16, 16), F32),
                            ("kda_conv", (6, B, 3, 128), F32)],
        dict(state=True), dict(state_slots=4, kv_rows_full=42)),
    "mtp": (lambda: tiny_cfg(kinds=False), False,
            _kv(2, 2, T, 8) + _kv(1, 2, T, 8, kind="_mtp"), dict(mtp=1), {}),
    "mtp_rings_slack": (
        lambda: tiny_cfg(), False,
        _kv(2, 2, T, 8) + _kv(6, 2, 8, 8, kind="_ring")
        + _kv(1, 2, T, 8, kind="_mtp"), dict(ring=8, mtp=1),
        dict(kv_rows_full=46, kv_rows_window=23)),
    # state, rings AND rows in one cache (PR 46: no parent to hold it to;
    # the leaves as DESIGN.md 9b's table has them): a pair of 8-wide kv
    # heads is one 16-wide head, the states lie [N, E], and the two gated
    # memory units and two cross layers own no leaf.
    "state_rings_rows": (
        _served("serve_ssm_yoco", SAMBAY), False,
        _kv(1, 2, T, 16) + _kv(2, 2, 8, 16, kind="_ring")
        + [("ssm_state", (3, B, 8, 128), F32),
           ("ssm_conv", (3, B, 3, 128), F32)],
        dict(ring=8, state=True),
        dict(state_slots=4, kv_rows_full=42, kv_rows_window=21,
             kv_full_readers=3)),
}
FACTS = dict(latent=False, int8=False, length=T, ring=0, rolling=False,
             state=False, mtp=0)


@pytest.fixture(params=list(KINDS))
def kind(request):
    cfg, rolling, leaves, facts, logged = KINDS[request.param]
    return cfg(), rolling, leaves, {**FACTS, **facts}, logged


def _spec(cfg, rolling):
    return cache_spec(cfg, T, rolling=rolling)


def test_the_leaves_are_what_init_cache_returned_at_the_parent(kind):
    cfg, rolling, leaves, facts, _ = kind
    spec = _spec(cfg, rolling)
    assert [(leaf.name, (leaf.layers, B) + leaf.shape,
             jnp.dtype(leaf.dtype).name) for leaf in spec.leaves] == leaves
    cache = (init_rolling_cache(cfg, B) if rolling
             else init_cache(cfg, B, T))
    assert [(name, a.shape, a.dtype.name)
            for name, a in cache.items()] == leaves
    assert not any(np.asarray(a).any() for a in cache.values())
    assert {name: getattr(spec, name) for name in FACTS} == facts


def test_the_spec_of_a_cache_in_hand_is_the_one_made_for_it(kind):
    cfg, rolling, _, _, _ = kind
    spec = _spec(cfg, rolling)
    assert spec_of(cfg, spec.zeros(1)) == spec
    assert served_spec(cfg, T) == _spec(cfg, cfg.sliding_window is not None)


def test_dense_rows_as_computed_alone_take_prompts_piece_by_piece(kind):
    cfg, rolling, leaves, _, _ = kind
    dense = [name for name, _, dtype in leaves] == ["k", "v"] and not rolling
    assert _spec(cfg, rolling).piecewise == dense
    srv = SlotServer({}, cfg, n_slots=2, max_len=T, chunk=4)
    assert srv.rolling == rolling and bool(srv._widths) == dense
    assert list(srv.cache) == [name for name, _, _ in leaves]


def test_a_step_logs_the_rows_its_kind_reads(kind):
    cfg, rolling, _, _, logged = kind
    assert _spec(cfg, rolling).step_rows(
        np.asarray(CURSORS, np.int32)) == logged


def test_a_rolling_spec_needs_the_window():
    with pytest.raises(ValueError, match="sliding_window"):
        cache_spec(LlamaConfig.preset("debug"), T, rolling=True)
