"""Beam-search decoding: single-dispatch, static shapes, XLA-first.

Completes the decoding-strategy surface next to greedy/sampled
``generate()`` and the speculative decoders: K beams per row advance
through ONE compiled ``lax.scan`` (no per-token host round trip), with
the cache laid out as ``[L, B*K, ...]`` batch rows so every existing
decode machinery piece (``decode_step``'s per-row cursors, the pallas
grouped-stream kernel, int8 caches, W8 weights via ``matmul_w``) applies
unchanged.

Beam reordering is the one beam-specific cost: after each step's
top-K-of-(K·V) selection, surviving beams gather their parents' cache
rows — a cache-sized HBM shuffle per step.  That is the standard price of
exact beam search; latency-sensitive serving wants ``generate`` or the
speculative paths instead (DESIGN.md §9), and the docstring says so.

No reference counterpart (/root/reference is a transport library); this is
the TPU build's serving-stack extension implementing standard beam search.
"""

from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax import lax

from .cache import require_rows
from .generate import NEG_BIG, decode_step, prefill
from .llama import LlamaConfig, cfg_rope_tables


@functools.cache
def _compiled_beam(cfg: LlamaConfig, B: int, K: int, P: int, max_new: int,
                   max_len: int, eos_id: Optional[int]):
    rope = cfg_rope_tables(cfg, max_len)

    def run(params, prompt):
        logits, cache = prefill(params, cfg, prompt, max_len)  # rows = B
        logp0 = jax.nn.log_softmax(logits, -1)  # [B, V]
        V = logp0.shape[-1]

        # Seed K beams per row from the top-K first tokens (distinct by
        # construction), and tile the prompt cache K ways: beam k of row
        # b lives at batch row b*K + k from here on.
        top0, tok0 = lax.top_k(logp0, K)  # [B, K]
        scores = top0
        cache = jax.tree_util.tree_map(
            lambda a: jnp.repeat(a, K, axis=1), cache)
        toks0 = tok0.reshape(B * K)
        fin0 = (jnp.zeros((B, K), bool) if eos_id is None
                else tok0 == eos_id)

        out0 = jnp.zeros((B, K, max_new), jnp.int32)
        out0 = out0.at[:, :, 0].set(tok0)

        def step(carry, i):
            cache, scores, toks, fin, out = carry
            logits, cache = decode_step(params, cache, toks, P + i, cfg,
                                        rope)
            logp = jax.nn.log_softmax(logits, -1).reshape(B, K, V)
            if eos_id is not None:
                # A finished beam continues ONLY as itself: force its
                # candidate set to {eos} at zero added logprob, so it
                # competes with live expansions at its frozen score.
                frozen = jnp.full((B, K, V), NEG_BIG).at[:, :, eos_id].set(0.0)
                logp = jnp.where(fin[:, :, None], frozen, logp)
            cand = scores[:, :, None] + logp  # [B, K, V]
            scores, flat = lax.top_k(cand.reshape(B, K * V), K)
            parent = flat // V  # [B, K]
            tok = (flat % V).astype(jnp.int32)

            # Reorder per-beam state to the surviving parents.
            take = functools.partial(jnp.take_along_axis, axis=1)
            out = take(out, parent[:, :, None])
            fin = take(fin, parent)
            if eos_id is not None:
                fin = fin | (tok == eos_id)
            out = out.at[:, :, i + 1].set(tok)
            # Cache rows follow their parents: [L, B, K, ...] gather on
            # the beam axis — the per-step HBM shuffle beam search pays.
            idx = (jnp.arange(B)[:, None] * K + parent).reshape(B * K)
            cache = jax.tree_util.tree_map(
                lambda a: jnp.take(a, idx, axis=1), cache)
            return (cache, scores, tok.reshape(B * K), fin, out), None

        init = (cache, scores, toks0, fin0, out0)
        (cache, scores, _, fin, out), _ = lax.scan(
            step, init, jnp.arange(max_new - 1))
        # Beams come out of top_k score-sorted already.
        return out, scores, fin

    return jax.jit(run)


def generate_beam(params: dict, cfg: LlamaConfig, prompt,
                  max_new_tokens: int, *, beams: int = 4,
                  eos_id: Optional[int] = None, max_len: Optional[int] = None,
                  return_all: bool = False):
    """Beam-search generation.  prompt: [B, P] int32; K = ``beams``.

    Returns ``[B, P + max_new_tokens]`` — each row's highest-scoring beam
    (sum of token logprobs; beams that emit ``eos_id`` freeze their score
    and eos-fill, competing at that frozen score thereafter).  With
    ``return_all=True`` returns ``(sequences [B, K, max_new], scores
    [B, K], finished [B, K])`` score-sorted per row.  Audit property
    (pinned by tests/test_beam.py): every score is exactly the
    teacher-forced sum of the beam's emitted tokens' logprobs UP TO AND
    INCLUDING its first ``eos_id`` — the sampled eos counts, the forced
    eos-fill tail after it contributes nothing (a finished beam's score
    is frozen, which is what lets it compete fairly with live beams).

    ``beams=1`` reduces to greedy ``generate()`` bit-exactly.  Aligned
    batches, full caches (no sliding-window rolling), dense or MoE —
    but note each scan step re-gathers the K-way cache, so MoE capacity
    interactions and the per-step HBM shuffle make this a
    quality-search tool, not the latency path.
    """
    B, P = prompt.shape
    if max_new_tokens < 1:
        raise ValueError(
            f"max_new_tokens must be >= 1, got {max_new_tokens}")
    if beams < 1:
        raise ValueError(f"beams must be >= 1, got {beams}")
    if beams > cfg.vocab_size:
        raise ValueError(f"beams={beams} exceeds the vocab ({cfg.vocab_size})")
    require_rows(cfg, "reorder")  # each step re-gathers the K-way cache
    total = P + max_new_tokens
    if max_len is None:
        max_len = total
    elif max_len < total:
        raise ValueError(
            f"max_len={max_len} is smaller than prompt + max_new_tokens="
            f"{total}")
    from .llama import resolve_longrope

    cfg = resolve_longrope(cfg, max_len)  # one factor regime per run
    run = _compiled_beam(cfg, B, int(beams), P, max_new_tokens, max_len,
                         None if eos_id is None else int(eos_id))
    out, scores, fin = run(params, prompt)
    # No post-hoc eos-fill needed: a finished beam's only candidate
    # continuation inside the scan IS eos, so every surviving tail after
    # a first eos is already eos (pinned by tests/test_beam.py).
    if return_all:
        return out, scores, fin
    best = out[:, 0]  # top_k sorts scores descending
    return jnp.concatenate([prompt, best], axis=1)
