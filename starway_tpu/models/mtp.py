"""The multi-token-prediction block (``cfg.mtp``): one decoder layer behind
the model's last that drafts the token AFTER the next one.

For position ``t`` with the main model's last hidden state ``h_t`` (before
the final norm) and the NEXT token ``x_{t+1}`` (DeepSeek-V3's form, whose
key ``num_nextn_predict_layers`` configurations use)::

    u_t = [RMSNorm(Emb(x_{t+1}); embed_norm) ; RMSNorm(h_t; hidden_norm)] W_eh
    m_t = Layer(u_0 .. u_t)            one FULL-attention layer, no rotation,
                                        the FFN kind of the model's later layers
    draft_logits_t = RMSNorm(m_t; final_norm) W_head        over x_{t+2}

with the model's own embedding and head.  The block's weights are the
subtree ``params["mtp"]``: ``embed_norm`` / ``hidden_norm`` / ``final_norm
[D]``, ``w_eh [2D, D]`` and ``layers``, a stacked segment of ONE layer with
the leaves of the model's attention layers (``llama._init_block_params``).
Its attention keeps a full row of its own a request, ``k_mtp`` / ``v_mtp``
beside the model's cache leaves (``cache.init_cache``): row ``t`` is
``u_t``'s entry.

Nothing here is a second model: the layer is
:func:`~starway_tpu.models.llama.decoder_layer` over a prompt
(:func:`mtp_prefill`) and :func:`~starway_tpu.models.generate.
cached_layer_scan` over the cache (:func:`mtp_chunk`), under the
sub-configuration :func:`mtp_config` names.  Who drafts with it, and
verifies, is ``models/serving.py``.
"""

from __future__ import annotations

import dataclasses

import jax
import jax.numpy as jnp

from .llama import (LlamaConfig, _init_block_params, cfg_rmsnorm,
                    decoder_layer, embed_tokens, matmul_w, resolve_attn_fn)


def mtp_config(cfg: LlamaConfig) -> LlamaConfig:
    """The block's one layer as a model of its own: full attention (no
    kinds, no window), the model's widths and head norms, a routed FFN
    where the model has one (no leading dense layer)."""
    routed = cfg.routed and dataclasses.replace(cfg.routed, first_dense=0)
    return dataclasses.replace(cfg, n_layers=1, kinds=None, mtp=0,
                               routed=routed)


def init_mtp_params(key, cfg: LlamaConfig) -> dict:
    dt, D = cfg.compute_dtype, cfg.d_model
    k_eh, k_layer = jax.random.split(key)
    (layers,) = _init_block_params(
        k_layer, mtp_config(cfg), plan=[(0, 1, cfg.routed is not None)])
    return {"embed_norm": jnp.ones((D,), dt), "hidden_norm": jnp.ones((D,), dt),
            "final_norm": jnp.ones((D,), dt),
            "w_eh": (jax.random.normal(k_eh, (2 * D, D), jnp.float32)
                     * (2 * D) ** -0.5).astype(dt),
            "layers": layers}


def mtp_inputs(params: dict, cfg: LlamaConfig, hidden, next_tokens):
    """``u [B, C, D]`` from ``hidden [B, C, D]`` (the main model's last
    layer's output at positions ``t``) and ``next_tokens [B, C]`` (the
    tokens at ``t + 1``)."""
    mp = params["mtp"]
    e = cfg_rmsnorm(embed_tokens(params, next_tokens, cfg), mp["embed_norm"],
                    cfg)
    h = cfg_rmsnorm(hidden, mp["hidden_norm"], cfg)
    return matmul_w(jnp.concatenate([e, h], axis=-1), mp["w_eh"])


def mtp_logits(params: dict, cfg: LlamaConfig, m):
    """Draft logits (float32) of the block's outputs ``m [..., D]``,
    through the model's own head."""
    m = cfg_rmsnorm(m, params["mtp"]["final_norm"], cfg)
    return matmul_w(m, params["lm_head"]).astype(jnp.float32)


def mtp_prefill(params: dict, cfg: LlamaConfig, hidden, next_tokens,
                max_len: int):
    """The block over a whole prompt: ``hidden [B, P, D]`` and
    ``next_tokens [B, P]`` (the prompt shifted by one, the request's first
    generated token behind its last).  Returns ``(m [B, P, D]``, the rows
    ``{"k_mtp", "v_mtp"} [1, B, Hkv, max_len, hd]`` zero-padded``)``.
    Positions behind a right-padded prompt's end compute junk that no real
    position attends (causal) and the cursor hides."""
    mcfg = mtp_config(cfg)
    u = mtp_inputs(params, cfg, hidden, next_tokens)
    lp = jax.tree_util.tree_map(lambda a: a[0], params["mtp"]["layers"])
    m, _aux, kv, _stats = decoder_layer(lp, u, mcfg, None, None,
                                        resolve_attn_fn(mcfg, None))
    pad = ((0, 0), (0, 0), (0, 0), (0, max_len - u.shape[1]), (0, 0))
    return m, {name + "_mtp": jnp.pad(x[None], pad) for name, x in kv.items()}


def mtp_chunk(params: dict, cfg: LlamaConfig, cache: dict, hidden,
              next_tokens, pos):
    """The block at ``C`` positions ``pos[b] ..`` of every row, through
    its own rows of ``cache`` (write-then-attend, the verify's semantics:
    :func:`~starway_tpu.models.speculative.chunk_decode_step`).  ``hidden``
    / ``next_tokens`` as :func:`mtp_inputs` takes them.  Returns ``(m [B,
    C, D], cache)``."""
    from .cache import _write_cached, attend_cache, mtp_rows
    from .generate import cached_layer_scan

    mcfg = mtp_config(cfg)
    u = mtp_inputs(params, cfg, hidden, next_tokens)
    m, rows, _counts = cached_layer_scan(
        {"layers": params["mtp"]["layers"]}, mtp_rows(cache), u, None, None,
        mcfg,
        lambda rows, new, layer: _write_cached(rows, new, layer, pos),
        lambda q, rows, layer: attend_cache(q, rows, pos, layer, mcfg))
    return m, {**cache, "k_mtp": rows["k"], "v_mtp": rows["v"]}
