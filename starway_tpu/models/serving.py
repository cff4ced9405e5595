"""Continuous-batching serving: admit requests into a RUNNING batch.

``generate()`` (models/generate.py) serves one static batch per dispatch —
every row starts together and the dispatch lasts the full generation.  A
real serving workload is a stream: requests arrive at any time, finish at
different lengths, and a finished row's slot should start the next request
immediately instead of idling until the batch drains (the continuous-
batching idea of Orca/vLLM, built TPU-first here).

Design for XLA's compilation model — everything the device runs is one of
a FIXED, small set of compiled programs:

* **Slots, not batches.**  The KV cache is ``[L, n_slots, Hkv, max_len,
  Dh]``; every per-slot cursor (position, liveness, token budget) is a
  ``[n_slots]`` vector.  Shapes never depend on which requests are in
  flight.
* **Decode runs in chunks.**  One compiled ``lax.scan`` advances ALL live
  slots ``chunk`` tokens (dead slots are masked: frozen cursor, writes
  land on a position that admission or the advancing cursor overwrites
  before any read).  Host round-trips happen once per chunk, not once
  per token.
* **Prompts ride the decode chunk** (dense k/v caches that hold k and v
  as computed, no window; the one decision is the kind's,
  ``cache.CacheSpec.piecewise``).  A request that takes a
  free slot launches nothing: the host lays its prompt over the coming
  chunks' steps in pieces of ``W`` tokens (``INGEST_WIDTHS``, the smallest
  width that brings what waits in within a chunk: all of it, or the
  longest prompt while requests queue for slots), and
  each step of the mixed chunk
  (``serve_decode_chunk_ingest_<W>``) puts the ``n_slots`` decode rows and
  one piece's ``W`` rows through the weights as ONE batch: the decode step
  is bound by its weight reads, so the prompt tokens ride reads it pays
  for anyway, and no lane stands still for a prefill.  Only the cache
  write and the attention tell the rows apart (write-then-attend on the
  request's own cache row).  The step that holds a prompt's last piece
  samples the first token and seats the slot inside the scan.  One
  blocking read a step.
* **Admission = bucketed prefill** (every other kind: an int8 cache, a
  latent cache, a rolling window, rings beside full rows, a linear
  layer's state, the page pool, a ``prefix=`` request).  A new
  request's prompt is right-padded to a power-of-two bucket and prefilled
  in its own dispatch (one compile per bucket), then its kv rows are
  written into the slot with a dynamic slice.  Pad/garbage columns of a
  cache ROW are never read: attention masks by the slot's cursor, and
  decode overwrites each position before the cursor reaches it
  (write-then-attend).  A linear-attention layer's STATE has no cursor to
  hide a pad behind: its prefill is told the prompt's own length, the
  pads stand still (``generate.prefill``), and the admission REPLACES the
  slot's state whole, so nothing of the request before lives on in it.
* **A step queues, then fetches.**  On that path ``step()`` launches every
  admission it makes and then the chunk, back to back, with no device
  value read in between: an admit program's sampled token stays on the
  device, where ``serve_seat`` files it and the slot's cursor, budget and
  liveness into the slot state.  Two blocking reads follow, however many
  requests were admitted: the step's first tokens (ready when the last
  admit program has run, the chunk already queued behind it) and the
  chunk's result.
* **Greedy continuous batching is BIT-IDENTICAL to standalone
  ``generate()``** for every request, whatever the interleaving: the
  admit programs run the same prefill, and an ingested prompt's positions
  see what a prefill's see (which is why an int8 cache keeps its admit
  programs: a piece would attend over the cache's quantized entries,
  where a prefill reads the prompt's k/v exact) — pinned by
  tests/test_serving.py against the one-request oracle.

* **Prefix caching.**  ``register_prefix`` prefills a shared prefix once
  into a standalone [L, 1, Hkv, bucket, Dh] cache; prefixed admission
  copies those rows into the slot masked by position (< plen — bucket
  junk above the prefix must not land where suffix positions would
  attend it) and ingests the suffix through ONE
  ``chunk_decode_step`` forward against the slot's own rows
  (write-then-attend, the decode-path semantics) — so a prefixed request
  generates exactly what ``generate(prefix + suffix)`` would, while
  admission compute scales with the suffix.  One compile per
  (prefix bucket, suffix bucket).

Sliding-window (Mistral-family) models serve through per-slot ROLLING
caches: O(window) memory per slot however long each generation runs,
admission via the chunked ``prefill_rolling`` (no prompt bucketing — its
compiled chunk body is length-independent), and ``max_len`` bounding only
the rope horizon.  A model whose layers differ in kind (``cfg.kinds``:
window layers beside full ones) serves through the SAME dense programs
with two kinds of cache leaves side by side: the full layers' rows of
``max_len`` and the window layers' rings of one window a slot
(``cache.init_cache``); its bucketed admit programs fold each window
layer's last ``window`` prompt positions into the slot's ring
(``cache.ring_fold``).  MoE models serve when capacity is provably dropless
(``moe_capacity_factor >= n_experts``): expert capacity is shared
batch-wide, so slot cohabitation could otherwise perturb routing — the
same rule as ragged ``generate()``.  A model with linear-attention
layers (``cfg.linear``: a matrix a head a request, not a row a token)
serves through the same dense programs with a third kind of leaf beside
its attention layers' rows; its admit programs seat the state after the
prompt's own last token.
"""

from __future__ import annotations

import functools
import itertools
import time
from collections import deque
from typing import Optional

import numpy as np

import jax
import jax.numpy as jnp
from jax import lax

from .. import perf
from ..core import swtrace
from .cache import cache_len, served_spec, state_names
from .generate import (_filter_logits, _sample, decode_step_counted,
                       early_exit_at, ingest_decode_step, prefill)
from .llama import LlamaConfig, cfg_rope_tables, head_logits
from .moe import row_tile

# ----------------------------------------------------------- the serve logs
#
# The serve scope (DESIGN.md §13): two bounded, always-on logs of what the
# scheduler did, kept at module level so they outlive the server that wrote
# them (a caller that frees the server to make room on the chip still reads
# them).  One clock, ``time.perf_counter`` -- the call swtrace's ring stamps
# with, CLOCK_MONOTONIC on Linux, so the stamps compare with
# ``time.monotonic`` windows and with the engines' trace events.  One dict
# per request (stamped where its state changes, never per token) and one per
# ``step()``; the fields are listed at :func:`request_log` / :func:`step_log`.

LOG_ROWS = 4096
_request_log: deque = deque(maxlen=LOG_ROWS)
_step_log: deque = deque(maxlen=LOG_ROWS)
_server_ids = itertools.count(1)
_now = time.perf_counter


def request_log() -> list:
    """The last ``LOG_ROWS`` requests of this process, oldest first, as
    copies.  A SERVER row (``side: "server"``, written by
    :class:`SlotServer`) is identified by ``(server, rid)`` and carries
    ``n_prompt``, ``bucket`` (the admit program's prompt bucket; 0 where
    there is none: the rolling path, a prompt ingested inside the decode
    chunk), ``n_out``, ``step0`` (this server's ``step()`` count at
    its admission), ``steps`` (``step()`` calls it lived through) and the
    stamps ``t_submit``, ``t_admit0`` (its admission begins: it takes its
    slot), ``t_first`` (its first token is on the host: one instant for
    all a step admits or seats), ``t_done``; ``status`` is
    ``queued`` / ``running`` until it ends as ``done`` / ``cancelled`` /
    ``rejected`` (a rejected row has no rid).  Behind the transport bridge
    (models/remote_serving.py) the same row also carries ``route``
    (``"<client id>:<nonce>"``), ``t_recv`` (the REQUEST receive
    completed), ``t_first_post`` and ``t_done_post`` (the TOKENS sends
    were posted).  On a server that speculates (``cfg.mtp``) a row also
    carries ``spec_accepted``: the drafts of this request that the main
    model accepted.  A CLIENT row (``side: "client"``, written by
    ``RemoteGenerateSession.generate``) carries ``route``, ``t_send``,
    ``t_first_rx``, ``t_done_rx``, ``n_out``, ``status`` and ``server_us``
    (the done frame's timing trailer, None from a server without one)."""
    return [dict(row) for row in list(_request_log)]


def step_log() -> list:
    """The last ``LOG_ROWS`` ``SlotServer.step()`` calls of this process,
    oldest first: ``server``, ``n_slots``, ``t0``, ``t1``, ``queued`` and
    ``live`` (queue depth and occupied slots when the decode chunk was
    dispatched), ``admits`` (admissions of this step) and ``admit_s`` (the
    seconds in which every lane stalls for them: from ``t0``, when the
    device is empty and the first admission begins, until the step's first
    tokens are on the host; 0 in a step that admits nothing),
    ``dispatch_s`` (launching the chunk program; in a step that admits it
    lies inside ``admit_s``, the chunk being queued behind the admissions
    before their tokens are fetched), ``wait_s`` (the chunk's result
    copied to the host: the wait the loop always had), ``harvest_s`` (from
    there until the step returns, those ``on_tokens`` calls included) and
    ``fetches`` (blocking device-to-host reads the step made: at most two,
    the first tokens and the chunk's result, however many it admitted).
    On the ingest path (:meth:`SlotServer._ingest_widths`) no lane ever
    stands still for an admission: ``admit_s`` is 0 and ``fetches`` 1 unless a ``prefix=``
    request ran its admit program, ``admits`` counts the requests that
    took a slot, and three fields say what the chunk ingested:
    ``ingest_width`` (the width of the mixed chunk the step launched, 0
    for the plain chunk), ``ingest_rows`` (that width times the steps that
    carried a piece) and ``ingest_tokens`` (the real prompt tokens in
    them).  The durations leave out the Python between them.  A model with a
    routed FFN (``cfg.routed``) adds, for the chunk's decode steps and
    every slot's row in them: ``moe_assign`` ((token, choice) pairs that
    landed on held experts, all routed layers), ``moe_touched`` (held
    experts with at least one pair, mean over layers and steps),
    ``moe_tiles`` (the live row tiles a routed layer's grouped matmul
    walks, ``ceil(pairs / tile)`` summed over the held experts, the tile
    :func:`~starway_tpu.models.moe.row_tile`'s for the step's rows; mean
    over layers and steps: over ``moe_touched``, the row tiles that share
    one read of an expert's weights) and
    ``moe_max`` (the most pairs one expert got in one layer of one step);
    other models' rows carry none of the four.  A server whose cache
    holds rings beside full rows (``cfg.kinds``) adds ``kv_rows_full`` and
    ``kv_rows_window``: the cache positions the chunk's FIRST decode step
    attends in one full layer (``pos + 1``) and in one window layer
    (``min(pos + 1, window)``), summed over the slots that decode, from
    the cursors the host holds (each grows by one a step inside the chunk
    while its slot lives).  A server whose cache holds linear-attention
    state (``cfg.linear``) adds ``state_slots`` (the slots that decode:
    each one's state, every linear layer, is read and written once a step)
    and ``pos + 1`` summed over them, the cache rows one attention layer's
    step attends, from the same cursors: ``kv_rows_latent`` where those
    rows are latent, ``kv_rows_full`` where they are grouped-query ``k`` /
    ``v`` (state BESIDE full rows: no ``kv_rows_window``).  A server
    that speculates (``cfg.mtp``) adds ``spec_drafted`` (drafts verified:
    one a live slot a step of the chunk), ``spec_accepted`` (of them, those
    the main model accepted) and ``spec_emitted`` (tokens the chunk's steps
    yielded: one or two a live slot a step), from the chunk's own result;
    its cursors are the ones the chunk returned (a slot moves by one or
    two a step), and its ``kv_rows_full`` / ``kv_rows_window`` count what
    a step's TWO query positions read: ``pos + 2`` in a full row,
    ``min(pos + 2, ring)`` in a ring of ``window + slack`` positions,
    which is read whole once warm.  A server whose cache holds state,
    rings AND full rows (``LayerKinds.runs``) adds all three:
    ``state_slots``, ``kv_rows_full`` and ``kv_rows_window``; where cross
    layers read a full layer's rows it adds ``kv_full_readers`` (the
    layers that read ONE full layer's rows a step, its own among them)
    and, in a step that admits, ``admit_rows_self`` / ``admit_rows_cross``:
    the rows of the prompts' buckets that the layers which keep something
    and the layers behind them which keep nothing processed in its admit
    programs (the latter one a prompt where the admission exits early:
    ``generate.early_exit_at``)."""
    return [dict(row) for row in list(_step_log)]


def _moe_fields(pairs: np.ndarray, tile: int) -> dict:
    """A routed model's :func:`step_log` fields from the chunk's pair
    counts ``[steps, routed layers, held experts]`` and the rows of the
    row tiles its calls lay them in."""
    return {"moe_assign": int(pairs.sum()),
            "moe_touched": float((pairs > 0).sum(-1).mean()),
            "moe_tiles": float((-(-pairs // tile)).sum(-1).mean()),
            "moe_max": int(pairs.max())}


def log_request(row: dict) -> None:
    """Append one row to :func:`request_log` (the client side's entry)."""
    _request_log.append(row)


class NoRoomYet(RuntimeError):
    """An admission found no room for its request YET (the page pool's
    pages are out): raised before anything of the request was launched,
    and the one error :meth:`SlotServer.step` keeps a request queued
    for."""


def _named_jit(fn, name: str, **jit_kwargs):
    """``jax.jit`` under a name of the program's own: the profiler's ``XLA
    Modules`` line then reads ``jit_<name>(...)`` instead of ``jit_run``, so
    a trace reduction finds each serving program after a refactor."""
    fn.__name__ = fn.__qualname__ = name
    return jax.jit(fn, **jit_kwargs)


def _bucket(n: int, buckets) -> int:
    for b in buckets:
        if n <= b:
            return b
    raise ValueError(f"prompt length {n} exceeds the largest bucket "
                     f"{buckets[-1]}")


def _write_slot(cache, small, slot):
    """File one request's [L, 1, Hkv, T', D] cache rows into the slot.
    Writes every cache leaf — the int8 format's [L, 1, Hkv, T'] scale
    arrays and an MTP block's own rows ride along (the slot axis sits at
    index 1 in all of them).  A linear layer's state leaves (no position
    axis) are REPLACED whole for the slot, under a name of their own in
    the trace (``sw_kda_seat``)."""
    def put(name):
        return lax.dynamic_update_slice(
            cache[name], small[name].astype(cache[name].dtype),
            (0, slot) + (0,) * (cache[name].ndim - 2))

    state = state_names(cache)
    rows = {name: put(name) for name in cache if name not in state}
    with jax.named_scope("sw_kda_seat"):
        return {**rows, **{name: put(name) for name in state}}


def _write_slot_and_sample(cache, small, logits, slot, key, temperature,
                           top_k, top_p):
    """Shared tail of BOTH admission paths: :func:`_write_slot`, and the
    request's first token sampled."""
    cache = _write_slot(cache, small, slot)
    tok = _sample(logits, key, temperature, top_k, top_p)[0]
    return cache, tok


def _logp_of(logits, tokens, temperature: float):
    """Each token's log-probability under ``logits [..., V]`` at the served
    temperature, BEFORE top-k / nucleus (greedy: at temperature 1): what
    ``on_logprobs`` hands out."""
    lp = jax.nn.log_softmax(logits / (temperature or 1.0), axis=-1)
    return jnp.take_along_axis(lp, tokens[..., None], axis=-1)[..., 0]


def _draft_from(logits, key, temperature: float, top_k, top_p):
    """An MTP block's draft from its logits ``[B, V]``: ``(d [B]`` drawn as
    the server samples, ``q`` the distribution it was drawn from (``[B,
    V]``; greedy: ``[B, 1]`` zeros, the rule compares ids), ``log q(d)`` as
    :func:`_logp_of` reads it``)``: the draft state a slot carries from
    the step (or the admission) that drafted to the step that verifies."""
    if temperature == 0.0:
        d = jnp.argmax(logits, axis=-1).astype(jnp.int32)
        q = jnp.zeros(logits.shape[:-1] + (1,), jnp.float32)
    else:
        fl = _filter_logits(logits, temperature, top_k, top_p)
        d = jax.random.categorical(key, fl, axis=-1).astype(jnp.int32)
        q = jax.nn.softmax(fl, axis=-1)
    return d, q, _logp_of(logits, d, temperature)


def _seat_state(token, pos, live, remaining, tok, seat):
    """An admission's slot state, set on the device from its own sampled
    first token ``tok``: ``seat`` is ``[slot, cursor, max_new, eos id or
    -1]``.  The host never reads a first token in order to seat its
    request: behind every admit program (prefix, rolling, paged, latent)
    this is the program ``serve_seat``; a prompt ingested inside the
    decode chunk is seated by the same lines in the chunk's scan."""
    slot, cursor, max_new, eos = seat[0], seat[1], seat[2], seat[3]
    done = (max_new == 1) | (tok == eos)
    return (token.at[slot].set(tok), pos.at[slot].set(cursor),
            live.at[slot].set(~done), remaining.at[slot].set(max_new - 1))


# Not ``serve_admit*``: a trace reduction takes those for the prefills.
_seat = _named_jit(lambda *state_tok_seat: _seat_state(*state_tok_seat),
                   "serve_seat")
# A speculating server's: the slot's first draft (d, q, log q(d)) and its
# first token's log-probability, from the admit program that made them.
_seat_draft = _named_jit(
    lambda draft, first_lp, slot, d, q, dlp, lp: (
        tuple(a.at[slot].set(x) for a, x in zip(draft, (d, q, dlp))),
        first_lp.at[slot].set(lp)), "serve_seat_draft")


@functools.cache
def _compiled_admit(cfg: LlamaConfig, p_bucket: int, temperature: float,
                    top_k: Optional[int], top_p: Optional[float]):
    """Prefill one request into one slot: returns the updated cache and the
    request's FIRST generated token.  One compile per prompt bucket.  What
    the bucket's pads leave behind is harmless by KIND, not in general:
    rows of a cache are hidden by the cursor, a state is computed without
    them (``run``'s comment)."""

    def run(params, cache, prompt, length, slot, key):
        # prompt [1, p_bucket] right-padded; ragged single-row prefill.
        # In a cache ROW, columns >= length hold pad-garbage that is
        # overwritten (position by position) before the cursor lets
        # attention read it.  A linear layer's STATE has no such cover: it
        # comes back as it stood after position length - 1 (the pads do
        # not move it) and replaces the slot's.
        logits, small = prefill(params, cfg, prompt, p_bucket,
                                logit_positions=length[None] - 1)
        return _write_slot_and_sample(cache, small, logits, slot, key,
                                      temperature, top_k, top_p)

    def run_mtp(params, cache, prompt, length, slot, key):
        # The same prefill, and the MTP block over the prompt behind it:
        # the block's row t is made of the main model's hidden state at t
        # and the token at t + 1, the request's FIRST token behind the
        # prompt's last, so the token is sampled first.  The block's rows
        # are seated beside the model's and its output at the prompt's
        # last position drafts the token after the first.
        from .mtp import mtp_logits, mtp_prefill

        logits, small, hidden = prefill(
            params, cfg, prompt, p_bucket, logit_positions=length[None] - 1,
            return_hidden=True)
        key, sub = jax.random.split(key)
        tok = _sample(logits, sub, temperature, top_k, top_p)
        nxt = jnp.roll(prompt, -1, axis=1).at[0, length - 1].set(tok[0])
        m, rows = mtp_prefill(params, cfg, hidden, nxt, p_bucket)
        last = lax.dynamic_index_in_dim(m[0], length - 1, keepdims=True)
        with jax.named_scope("sw_mtp_draft"):
            d, q, dlp = _draft_from(mtp_logits(params, cfg, last), key,
                                    temperature, top_k, top_p)
        return _write_slot(cache, {**small, **rows}, slot), (
            tok[0], d[0], q[0], dlp[0], _logp_of(logits, tok, temperature)[0])

    return _named_jit(run_mtp if cfg.mtp else run, f"serve_admit_{p_bucket}",
                      donate_argnums=(1,))


@functools.cache
def _compiled_prefix_register(cfg: LlamaConfig, p_bucket: int):
    """Prefill one PREFIX into a standalone [L, 1, Hkv, p_bucket, D] cache
    (plus its next-token logits, so a zero-length suffix could continue).
    One compile per prefix bucket."""

    def run(params, prompt, length):
        return prefill(params, cfg, prompt, p_bucket,
                       logit_positions=length[None] - 1)

    return _named_jit(run, f"serve_prefix_register_{p_bucket}")


@functools.cache
def _compiled_prefix_admit(cfg: LlamaConfig, p_bucket: int, s_bucket: int,
                           max_len: int, temperature: float,
                           top_k: Optional[int], top_p: Optional[float]):
    """Admit one request as (cached prefix, fresh suffix) into one slot:

    1. file the prefix's cache rows into positions ``< plen`` of the
       slot (masked by position — bucket junk above ``plen`` must NOT
       land, suffix positions would attend it);
    2. ingest the suffix through :func:`chunk_decode_step` at positions
       ``plen ..`` — write-then-attend against the slot's own rows, the
       decode-path semantics, so the result is exactly what a full
       prefill of prefix+suffix would have produced;
    3. sample the first token from the suffix's last real position.

    One compile per (prefix bucket, suffix bucket).
    """
    from .speculative import chunk_decode_step

    rope = cfg_rope_tables(cfg, max_len)

    def run(params, cache, prefix_small, plen, suffix, s_len, slot, key):
        # Slot rows out: [L, 1, Hkv, max_len, ...] per leaf.
        rows = {
            name: lax.dynamic_slice(
                cache[name], (0, slot) + (0,) * (cache[name].ndim - 2),
                (cache[name].shape[0], 1) + cache[name].shape[2:])
            for name in cache
        }

        def merge(row, pre):
            # Prefix rows land where position < plen; everything else
            # keeps the slot's existing contents.  The T axis sits at
            # index 3 in EVERY cache leaf (k/v and the int8 scales).
            padded = lax.dynamic_update_slice(
                jnp.zeros_like(row), pre, (0,) * row.ndim)
            keep = (jnp.arange(row.shape[3]) < plen).reshape(
                (1, 1, 1, -1) + (1,) * (row.ndim - 4))
            return jnp.where(keep, padded, row)

        rows = {name: merge(rows[name], prefix_small[name])
                for name in rows}
        # Suffix ingestion: columns >= s_len are junk at positions above
        # the cursor — masked out of every real token's attention and
        # overwritten by decode before the cursor reaches them (the
        # standard covering argument).
        logits, rows = chunk_decode_step(params, rows, suffix, plen[None],
                                         cfg, rope)
        last = jnp.take_along_axis(
            logits, (s_len - 1)[None, None, None], axis=1)[:, 0]
        # rows are full-T slot rows — _write_slot_and_sample's T' = T.
        return _write_slot_and_sample(cache, rows, last, slot, key,
                                      temperature, top_k, top_p)

    return _named_jit(run, f"serve_prefix_admit_{p_bucket}_{s_bucket}",
                      donate_argnums=(1,))


@functools.cache
def _compiled_rolling_admit(cfg: LlamaConfig, temperature: float,
                            top_k: Optional[int], top_p: Optional[float]):
    """Rolling-cache admission, final part: write the request's [L, 1,
    Hkv, W, D] rolling cache into the slot and sample the first token."""

    def run(cache, small, logits, slot, key):
        return _write_slot_and_sample(cache, small, logits, slot, key,
                                      temperature, top_k, top_p)

    return _named_jit(run, "serve_rolling_admit", donate_argnums=(0,))


# Chunk-width denominations for rolling admission: covering the prompt
# greedily with these bounds admission to <= 3 compiled chunk programs per
# config and <= P/64 + 7 + 7 dispatches — arbitrary prompt lengths never
# trigger fresh XLA compiles mid-serve (the compile explosion prompt
# bucketing prevents on the dense path).
ROLLING_ADMIT_WIDTHS = (64, 8, 1)


def _rolling_prefill_state(params, cfg: LlamaConfig, prompt: np.ndarray):
    """(next_logits [1, V], rolling cache [L, 1, Hkv, W, D]) for one
    prompt via denomination-scheduled ``prefill_rolling``.  Shared by
    admission and the serving tests' single-request oracle."""
    from .generate import prefill_rolling

    return prefill_rolling(params, cfg, jnp.asarray(prompt[None], jnp.int32),
                           widths=ROLLING_ADMIT_WIDTHS)


@functools.cache
def _compiled_chunk(cfg: LlamaConfig, n_slots: int, max_len: int, chunk: int,
                    temperature: float, top_k: Optional[int],
                    top_p: Optional[float], eos_id: Optional[int],
                    rolling: bool = False, logprobs: bool = False):
    """Advance every live slot ``chunk`` steps in ONE dispatch.

    Per step: the pending token (at its slot's cursor) runs
    ``decode_step`` with per-row positions, the next token is sampled,
    budgets/eos update liveness.  Emits ``(tokens [chunk, B], mask
    [chunk, B])`` — mask marks which emissions are real (slot was live
    when its PENDING token was consumed, i.e. the sampled token continues
    a real request).  ``rolling``: the cache is circular per slot
    (``max_len`` is the rope horizon, not the cache size).  A model with
    an MTP block (``cfg.mtp``) speculates in every step (``run_mtp``
    below); ``logprobs``: its steps also emit what ``on_logprobs`` hands
    out.
    """
    rope = cfg_rope_tables(cfg, max_len)

    def run(params, cache, token, pos, live, remaining, key):
        step = make_chunk_scan_step(
            lambda cache, token, pos: decode_step_counted(
                params, cache, token, pos, cfg, rope, rolling=rolling),
            max_len, temperature, top_k, top_p, eos_id)
        # ``pairs``: what each held expert of each routed layer got, a
        # step ([chunk, routed layers, held]; None for a model with no
        # routed layer): a third output, fetched with the tokens.
        (cache, token, pos, live, remaining, key), (toks, mask, pairs) = (
            lax.scan(step, (cache, token, pos, live, remaining, key), None,
                     length=chunk))
        return cache, token, pos, live, remaining, key, toks, mask, pairs

    def run_mtp(params, cache, token, pos, live, remaining, key, draft):
        """The chunk of a server that speculates (``cfg.mtp``): a step
        verifies ``[pending, draft]`` at ``[pos, pos + 1]`` in one pass,
        accepts or resamples, and drafts again.  ``draft``: the slots'
        draft state (:func:`_draft_from`), carried beside the cursors.
        Emits tokens and masks ``[chunk, 2, B]``, the pair counts (of the
        model's routed layers over both verified rows a slot; the block's
        own layer is not among them) and ``spec``
        (:func:`make_chunk_scan_step`)."""
        from .mtp import mtp_chunk, mtp_logits
        from .speculative import chunk_decode_hidden

        def verify(cache, tokens, pos):
            # A dead slot may stand at the cache's last position: it
            # verifies one lower, where its junk is as harmless.
            h, cache, counts = chunk_decode_hidden(
                params, cache, tokens, jnp.minimum(pos, max_len - 2), cfg,
                rope)
            return (head_logits(h, params["final_norm"], params["lm_head"],
                                cfg.norm_eps, cfg.norm_zero_centred),
                    cache, h, counts)

        def draft_one(cache, hidden, tokens, pos, at):
            m, cache = mtp_chunk(params, cfg, cache, hidden, tokens,
                                 jnp.minimum(pos, max_len - 2))
            m = jnp.take_along_axis(m, at[:, None, None], axis=1)[:, 0]
            return mtp_logits(params, cfg, m), cache

        step = make_chunk_scan_step(verify, max_len, temperature, top_k,
                                    top_p, eos_id, draft_one=draft_one,
                                    logprobs=logprobs)
        (cache, token, pos, live, remaining, key, draft), (
            toks, mask, pairs, spec) = lax.scan(
                step, (cache, token, pos, live, remaining, key, draft), None,
                length=chunk)
        return (cache, token, pos, live, remaining, key, draft, toks, mask,
                pairs, spec)

    return _named_jit(run_mtp if cfg.mtp else run, "serve_decode_chunk",
                      donate_argnums=(1,))


# Widths of a prompt piece, the rows a step of the mixed chunk carries
# beside its decode rows: one compiled program a width.  A decode step is
# bound by the weights it reads and has rows to spare, so a narrow piece
# rides those reads and a wide one is paid in every lane's step: a chunk
# takes the smallest width at which what waits is in within the chunk (all
# of it, or the longest prompt while requests queue for slots:
# SlotServer._plan_ingest; what each width costs on the chip is PERF.md
# section 5's).
INGEST_WIDTHS = (128, 256)

# A step's piece as the host describes it to the mixed chunk, int32 each.
PIECE_FIELDS = ("slot", "first", "valid", "last", "max_new", "eos")


@functools.cache
def _compiled_ingest_chunk(cfg: LlamaConfig, n_slots: int, max_len: int,
                           chunk: int, width: int, temperature: float,
                           top_k: Optional[int], top_p: Optional[float],
                           eos_id: Optional[int]):
    """:func:`_compiled_chunk` whose every step also carries one piece of
    one request's prompt, up to ``width`` tokens (dense k/v caches).

    ``desc [chunk, 6]`` (``PIECE_FIELDS``) and ``ids [chunk, width]`` are
    the scan's inputs: step s ingests ``ids[s, :valid]`` at positions
    ``first ..`` of cache row ``slot`` in the same batch as the decode rows
    (:func:`~starway_tpu.models.generate.ingest_decode_step`) and leaves
    the slot's cursor at the piece's end.  A step that holds a prompt's
    LAST piece samples the request's first token from the piece's last
    valid position and seats the slot in the carry (:func:`_seat_state`,
    ``max_new == 1`` and eos-as-first-token included): the slot decodes
    from the next step of the same chunk.  ``valid == 0``: nothing to
    ingest in that step.  Emits, beside :func:`_compiled_chunk`'s outputs,
    ``firsts [chunk]``: each step's sampled first token (meaningful where
    ``last`` is set)."""
    rope = cfg_rope_tables(cfg, max_len)

    def run(params, cache, token, pos, live, remaining, key, desc, ids):
        def decode_one(cache, token, pos, piece):
            d, piece_ids = piece
            return ingest_decode_step(params, cache, token, pos,
                                      (piece_ids, d[0], d[1], d[2]), cfg, rope)

        decode = make_chunk_scan_step(decode_one, max_len, temperature,
                                      top_k, top_p, eos_id)

        def step(carry, piece):
            carry, (nxt, emit, piece_logits, pairs) = decode(carry, piece)
            cache, token, pos, live, remaining, key = carry
            slot, first, valid, last, max_new, eos = (
                piece[0][i] for i in range(len(PIECE_FIELDS)))
            key, sub = jax.random.split(key)
            tok = _sample(piece_logits[None], sub, temperature, top_k,
                          top_p)[0]
            # The slot is dead while its prompt comes in: the chunk's own
            # update left its state alone.  Its cursor follows the pieces
            # (ingest_decode_step says why); the last piece seats it.
            pos = jnp.where(valid > 0, pos.at[slot].set(first + valid), pos)
            seated = _seat_state(token, pos, live, remaining, tok, jnp.stack(
                [slot, first + valid, max_new, eos]))
            token, pos, live, remaining = (
                jnp.where(last > 0, new, old) for new, old
                in zip(seated, (token, pos, live, remaining)))
            return ((cache, token, pos, live, remaining, key),
                    (nxt, emit, pairs, tok))

        (cache, token, pos, live, remaining, key), (toks, mask, pairs,
                                                    firsts) = lax.scan(
            step, (cache, token, pos, live, remaining, key), (desc, ids))
        return (cache, token, pos, live, remaining, key, toks, mask, pairs,
                firsts)

    return _named_jit(run, f"serve_decode_chunk_ingest_{width}",
                      donate_argnums=(1,))


def make_chunk_scan_step(decode_one, max_len: int, temperature: float,
                         top_k, top_p, eos_id, draft_one=None,
                         logprobs: bool = False):
    """THE per-step body of every chunked serving loop — dense and paged
    (models/paged.py) scan exactly this, so the liveness/eos/budget/
    emission semantics cannot drift between cache layouts.
    ``decode_one(cache, token, pos) -> (logits, cache)``; what it returns
    beyond the two is emitted per step after ``(tokens, mask)``.  A scan
    over inputs hands each step's to ``decode_one`` as a fourth argument
    (the mixed chunk's prompt pieces).

    ``draft_one`` (a model with an MTP block): the step SPECULATES and
    yields one or two tokens a slot.  The carry then ends in the slots'
    draft state ``(d [B], q, log q(d) [B])`` (:func:`_draft_from`):

    1. verify (``sw_mtp_verify``): ``decode_one(cache, [pending, d], pos)
       -> (logits [B, 2, V], cache, hidden [B, 2, D], counts)``, both
       positions in one pass through the cache;
    2. accept (``sw_mtp_accept``): :func:`~starway_tpu.models.speculative.
       accept_rule`, the rule of every speculative driver: the step's
       tokens are ``[d, bonus]`` or ``[correction]``.  Budget, eos and
       ``max_len`` are applied token by token, in the lines every server
       runs, so a request never gets more than it asked for;
    3. draft (``sw_mtp_draft``): ``draft_one(cache, hidden, tokens [B, 2],
       pos, at) -> (draft logits [B, V], cache)`` runs the block
       at both positions (its row ``pos`` is made of the first token
       whether that was the draft or its correction) and drafts from the
       last emitted one's, ``at``.

    It emits ``(tokens [2, B], masks [2, B], counts, spec)``; ``spec``:
    ``accepted [B]`` (the slot's draft was accepted, and emitted) and,
    with ``logprobs``, ``logp [2, B]`` (each token's log-probability under
    the main model, :func:`_logp_of`), ``draft [B]`` and ``draft_logp
    [B]`` (the draft the step verified and its ``log q``).  A rejected
    draft leaves nothing behind: its entries lie beyond the cursor, where
    the next step's writes land before any query reaches them."""
    greedy = temperature == 0.0

    def probs_of(logits):
        return jax.nn.softmax(
            _filter_logits(logits, temperature, top_k, top_p), axis=-1)

    def step(carry, xs):
        cache, token, pos, live, remaining, key, *draft = carry
        if draft_one is None:
            logits, cache, *extra = decode_one(
                cache, token, pos, *(() if xs is None else (xs,)))
            key, sub = jax.random.split(key)
            toks = [_sample(logits, sub, temperature, top_k, top_p)]
        else:
            from .speculative import accept_rule

            (d, q, dlp), pos0 = draft[0], pos
            with jax.named_scope("sw_mtp_verify"):
                logits, cache, hidden, counts = decode_one(
                    cache, jnp.stack([token, d], axis=1), pos)
            with jax.named_scope("sw_mtp_accept"):
                a, c, key = accept_rule(d[:, None], q[:, None], logits, key,
                                        greedy=greedy, probs_of=probs_of)
                toks = [jnp.where(a > 0, d, c), c]
        # Token by token: the i-th is emitted where the slot still lives
        # behind the one before it (and the drafts before it were taken).
        masks = []
        for i, nxt in enumerate(toks):
            emit_live = live & (remaining > 0)
            if i:
                emit_live = emit_live & (a >= i)
            if eos_id is not None:
                newly_done = emit_live & (nxt == eos_id)
            else:
                newly_done = jnp.zeros_like(emit_live)
            remaining = remaining - emit_live.astype(jnp.int32)
            after = emit_live & ~newly_done & (remaining > 0) & (
                pos + (i + 2) < max_len)
            live = jnp.where(emit_live, after, live) if i else after
            masks.append(emit_live)
        # Dead slots freeze: cursor stays, pending token irrelevant
        # (their cache writes land on a position admission or the
        # cursor overwrites before any read — or, paged, in the trash
        # page).
        for emit_live, nxt in zip(masks, toks):
            pos = pos + emit_live.astype(jnp.int32)
            token = jnp.where(emit_live, nxt, token)
        if draft_one is None:
            return (cache, token, pos, live, remaining, key), (
                toks[0], masks[0], *extra)
        spec = {"accepted": masks[0] & (a > 0)}
        if logprobs:
            with jax.named_scope("sw_mtp_accept"):
                spec.update(logp=_logp_of(logits, jnp.stack(toks, axis=1),
                                          temperature).T,
                            draft=d, draft_logp=dlp)
        with jax.named_scope("sw_mtp_draft"):
            dl, cache = draft_one(
                cache, hidden, jnp.stack(toks, axis=1), pos0,
                masks[1].astype(jnp.int32))
            key, sub = jax.random.split(key)
            draft = _draft_from(dl, sub, temperature, top_k, top_p)
        return (cache, token, pos, live, remaining, key, draft), (
            jnp.stack(toks), jnp.stack(masks), counts, spec)

    return step


def _weights_mesh(params):
    """The mesh the weights are sharded over, or None on one device."""
    for leaf in jax.tree_util.tree_leaves(params):
        sharding = getattr(leaf, "sharding", None)
        if (isinstance(sharding, jax.sharding.NamedSharding)
                and len(sharding.device_set) > 1):
            return sharding.mesh
    return None


def _on_weights_mesh(method):
    """Run a server method under the mesh its weights are sharded over:
    the compiled programs trace their Pallas attention calls per head
    shard of that mesh (ops/dispatch.py per_head_shard) -- the TPU's
    compiler refuses a Mosaic kernel inside a GSPMD-partitioned program."""

    @functools.wraps(method)
    def on_mesh(self, *args, **kwargs):
        if self.mesh is None:
            return method(self, *args, **kwargs)
        with jax.set_mesh(self.mesh):
            return method(self, *args, **kwargs)

    return on_mesh


class SlotServer:
    """Continuous-batching front end over the compiled admit/decode programs.

    >>> srv = SlotServer(params, cfg, n_slots=4, max_len=512)
    >>> rid = srv.submit([1, 2, 3], max_new_tokens=32)
    >>> done = srv.run()          # {rid: np.ndarray of generated tokens}

    ``submit`` queues; ``step()`` admits pending requests into free slots
    and advances one decode chunk, returning newly finished requests;
    ``run()`` loops until everything queued has finished.  Generated
    tokens INCLUDE the terminating eos (when ``eos_id`` fires).

    WHICH PATH a request takes is decided from the cache this server
    holds (``self.spec.piecewise``), never from a model's name.  Dense k/v
    leaves as computed (no int8 scales, no window): the request takes its
    slot at once and its prompt is ingested inside the decode chunks that
    follow, a piece a step, beside the slots that decode; no admit program
    runs.  An int8 cache, a latent cache, a rolling window, the page pool
    (:class:`~starway_tpu.models.paged.PagedSlotServer`) and, on any
    server, a ``prefix=`` request: an admit program a request, queued in
    front of the chunk.

    STREAMING: ``on_tokens(rid, tokens, done)`` fires inside ``step()``,
    after the step's one read of the chunk's result (ingest path): for each
    request whose last piece the chunk held, its first token, in the order
    they were seated; then each request's chunk tokens (a request seated
    in step ``s`` of the chunk decodes from step ``s + 1``), and ``([],
    True)`` exactly once when the request finishes.  A prompt longer than
    a chunk ingests (``chunk x W`` tokens) yields nothing until a later
    step.  Behind admit programs a step launches its admissions and its
    chunk first and reads the device afterwards, so the first tokens of
    everything it admitted arrive together, in admission order, once the
    last admit program has run, before the chunk's result is read.  A
    request ``cancel()``led from a first-token callback has by then a
    chunk in flight: that chunk decodes its slot once more and the tokens
    are thrown away, never delivered; one cancelled half ingested frees
    its slot at the next step and delivers nothing.

    PREFIX CACHING: ``register_prefix(tokens)`` prefills a shared prefix
    (system prompt, few-shot preamble) once; ``submit(suffix,
    prefix=pid)`` requests then admit by copying the prefix's cache rows
    into the slot (masked by position) and ingesting only the suffix
    through one chunk forward — admission cost scales with the suffix,
    not the full prompt, and the generated text is exactly
    ``generate(prefix + suffix)``'s (tests/test_serving.py).  MoE models
    serve when their capacity is provably dropless
    (``moe_capacity_factor >= n_experts``, the Mixtral conversion
    default).
    """

    def __init__(self, params, cfg: LlamaConfig, *, n_slots: int = 4,
                 max_len: int = 512, chunk: int = 8,
                 temperature: float = 0.0, top_k: Optional[int] = None,
                 top_p: Optional[float] = None, eos_id: Optional[int] = None,
                 prompt_buckets=None, seed: int = 0, on_tokens=None,
                 on_logprobs=None):
        from .moe import require_dropless

        # Cohabiting slots share the batch-wide expert capacity; only
        # provable droplessness keeps them independent (moe.py, the
        # single source of the rule).
        require_dropless(cfg, "continuous batching")
        # LongRoPE: admit (bucket-length tables) and decode (max_len
        # tables) must share one factor regime — pin it to the serving
        # horizon (llama.resolve_longrope).
        from .llama import resolve_longrope

        cfg = resolve_longrope(cfg, max_len)
        if on_logprobs is not None and not cfg.mtp:
            raise ValueError(
                "on_logprobs hands out what a speculating server's accept "
                "rule computed: it needs a configuration with an MTP block "
                "(generate(return_logprobs=True) serves the others)")
        # What this server's cache holds (models/cache.py): the one
        # description every decision below reads.  ``rolling``: per-slot
        # rings of one window, a whole-model window's.
        self.spec = served_spec(cfg, max_len)
        self.rolling = self.spec.rolling
        if n_slots < 1 or chunk < 1:
            # Zero slots/chunk would make run() spin forever, not error.
            raise ValueError(f"need n_slots >= 1 and chunk >= 1, got "
                             f"{n_slots}/{chunk}")
        self.params = params
        self.mesh = _weights_mesh(params)
        self.cfg = cfg
        self.n_slots = n_slots
        self.max_len = max_len
        self.chunk = chunk
        self.sampling = (float(temperature), top_k, top_p)
        self.eos_id = None if eos_id is None else int(eos_id)
        if self.rolling:
            self.buckets = ()  # rolling admission never buckets prompts
        else:
            if prompt_buckets is None:
                b, buckets = 32, []
                while b < max_len:
                    buckets.append(b)
                    b *= 2
                # Always cover the full cache: a prompt up to max_len - 1
                # must have a bucket, or submit-accepted requests would
                # die at admission time.
                buckets.append(max_len)
                prompt_buckets = tuple(buckets)
            self.buckets = tuple(sorted(set(prompt_buckets)))
            if self.buckets[-1] > max_len:
                raise ValueError(f"bucket {self.buckets[-1]} exceeds "
                                 f"max_len={max_len}")
        self.key = jax.random.PRNGKey(seed)

        # Rolling (sliding-window) models keep an O(window) circular cache
        # per slot; max_len then bounds the ROPE horizon (prompt + budget),
        # not cache memory.  (_make_cache is a subclass hook: the paged
        # server allocates a shared page pool instead — models/paged.py.)
        self.cache = self._make_cache()
        # How a prompt enters that cache: () = by an admit program.
        self._widths = self._ingest_widths()
        self.token = jnp.zeros((n_slots,), jnp.int32)
        self.pos = jnp.zeros((n_slots,), jnp.int32)
        self.live = jnp.zeros((n_slots,), bool)
        self.remaining = jnp.zeros((n_slots,), jnp.int32)
        self._pairs = None  # the last chunk's third output (_launch_chunk)
        # A speculating server's (cfg.mtp): the slots' draft state, each
        # admission's first token's log-probability until it is handed
        # out, and the last chunk's ``spec`` output.
        self._spec = None
        if cfg.mtp:
            wide = cfg.vocab_size if self.sampling[0] else 1
            self._draft = (jnp.zeros((n_slots,), jnp.int32),
                           jnp.zeros((n_slots, wide), jnp.float32),
                           jnp.zeros((n_slots,), jnp.float32))
            self._first_lp = jnp.zeros((n_slots,), jnp.float32)
        # What the host knows of ``live`` and ``pos`` without asking the
        # device: the last chunk's final values (fetched with its tokens),
        # then each admission's cursor and whether it can outlive its first
        # token.  Only the entries of occupied slots mean anything.
        self._live_host = np.zeros((n_slots,), bool)
        self._pos_host = np.zeros((n_slots,), np.int32)
        # Admitted, first token still on the device: (slot, rid).
        self._firsts: list = []
        # The ingest path's: prompts on their way into a slot, in admission
        # order (slot -> [rid, prompt, max_new, tokens ingested]); the steps
        # of the chunk in flight that seat one, (step, rid), and their
        # first tokens, still on the device; whether the programs are built.
        self._ingest: dict[int, list] = {}
        self._seats: list = []
        self._seat_toks = None
        self._built = not self._widths
        self._step: dict = {}  # the step_log() row of the step under way

        self._next_rid = 0
        self._pending: deque = deque()
        self._slot_rid: dict[int, int] = {}
        self._collected: dict[int, list] = {}
        self._prefixes: dict[int, tuple] = {}  # pid -> (small, plen)
        # Streaming hook (the class docstring says when it fires).  The
        # transport bridge (models/remote_serving.py) rides this to stream
        # tokens over the wire without waiting for full completion.
        self.on_tokens = on_tokens
        # ``on_logprobs(rid, logp, drafts)`` (a speculating server's):
        # fires just before the ``on_tokens`` call that hands out the same
        # tokens, with each one's log-probability under the main model at
        # the served temperature, before top-k / nucleus (greedy: at
        # temperature 1), and ``drafts``: ``(at, token, log q, accepted)``
        # of each draft verified in the steps those tokens came from (none
        # beside a first token); ``at``: the index among them of the token
        # the draft stood for, which is the draft itself where accepted.
        self.on_logprobs = on_logprobs
        # The serve scope (DESIGN.md §13): this server's rows of the module
        # logs while their requests are open, its phase accumulator, and --
        # only when swtrace is armed -- a ring of its own in the registry,
        # so the trace CLI draws serve.* spans beside the engines' ops.
        self.server_id = next(_server_ids)
        self._rows: dict[int, dict] = {}
        self._n_steps = 0
        self.stage_scope = perf.StageScope(ring=swtrace.worker_ring())
        swtrace.register_worker(self)
        self._post_init()
        self._next_pid = 0

    # ------------------------------------------------------------ intake
    def _make_cache(self):
        return self.spec.zeros(self.n_slots)

    def _ingest_widths(self) -> tuple:
        """The widths at which a prompt enters the cache piece by piece
        inside the decode chunk; ``()``: by an admit program.  WHETHER a
        cache takes pieces is its kind's to say
        (:attr:`~starway_tpu.models.cache.CacheSpec.piecewise`, which
        lists the kinds that keep their admit programs and why; a subclass
        with a layout of its own, the page pool, overrides this); the
        widths are the scheduler's: those of ``INGEST_WIDTHS`` the plan
        can choose (:meth:`_plan_ingest`), the last being the first at
        which the longest prompt this cache holds comes in within one
        chunk.  A ``prefix=`` request takes its admit program on every
        kind (:meth:`_ingests`)."""
        if not self.spec.piecewise:
            return ()
        widths = []
        for w in INGEST_WIDTHS:
            widths.append(w)
            if self.chunk * w >= self.max_len - 1:
                break
        return tuple(widths)

    def _ingests(self, prefix: Optional[int]) -> bool:
        """Whether a request's prompt comes in inside the decode chunk."""
        return bool(self._widths) and prefix is None

    def _post_init(self) -> None:
        """Subclass hook, called at the end of __init__."""

    def _on_slot_freed(self, slot: int) -> None:
        """Subclass hook: a slot's request finished or was cancelled (the
        paged server returns its pages to the pool here)."""

    # ------------------------------------------------------ observability
    @property
    def trace_label(self) -> str:
        return f"serve-{self.server_id}"

    def trace_events(self) -> list:
        """Snapshot of this server's swtrace ring ([] when tracing off)."""
        ring = self.stage_scope.ring
        return ring.snapshot() if ring is not None else []

    def open_row(self, rid: int) -> Optional[dict]:
        """The live :func:`request_log` row of a request that has not
        ended yet, or None: a front end (the transport bridge) adds its
        own stamps to it."""
        return self._rows.get(rid)

    def close(self) -> None:
        """Hand the trace ring to swtrace's retired list, so its serve.*
        spans outlive this object (a no-op with tracing off).  The logs
        and the device state need no closing."""
        swtrace.retire(self)

    @_on_weights_mesh
    def register_prefix(self, tokens) -> int:
        """Prefill a shared PREFIX (system prompt, few-shot preamble) once
        and return its id; requests submitted with ``prefix=pid`` reuse
        its cache rows instead of re-prefilling them — admission then
        costs one suffix-bucket chunk ingest, not a full-prompt prefill.
        The prefix cache lives in host-visible HBM ([L, 1, Hkv, bucket,
        D] per prefix) until :meth:`drop_prefix`."""
        if self.cfg.mtp:
            raise ValueError("prefix caching copies the model's rows; an "
                             "MTP block's own row over the prefix would "
                             "have to be kept and copied too (ROADMAP M5)")
        if self.spec.ring:
            raise ValueError("prefix caching needs the dense slot cache; "
                             "rolling (sliding-window) slots rebuild their "
                             "window per request anyway")
        if self.spec.state:
            raise ValueError("prefix caching copies cache rows by position; "
                             "a linear-attention layer's state has none: a "
                             "prefix would need a snapshot of the state at "
                             "its end (ROADMAP M4)")
        tokens = np.asarray(tokens, np.int32).reshape(-1)
        if len(tokens) < 1:
            raise ValueError("empty prefix")
        if len(tokens) + self.buckets[0] + 1 > self.max_len:
            # The suffix ingest writes bucket-wide, so a prefix must leave
            # at least the SMALLEST bucket plus one generated token —
            # checked here, before a full prefill is burned on a prefix no
            # submit() could ever use.
            raise ValueError(
                f"prefix ({len(tokens)}) + smallest suffix bucket "
                f"({self.buckets[0]}) + 1 exceeds max_len={self.max_len}")
        pb = _bucket(len(tokens), self.buckets)
        padded = np.zeros((1, pb), np.int32)
        padded[0, :len(tokens)] = tokens
        reg = _compiled_prefix_register(self.cfg, pb)
        _logits, small = reg(self.params, jnp.asarray(padded),
                             jnp.asarray(len(tokens), jnp.int32))
        pid = self._next_pid
        self._next_pid += 1
        self._prefixes[pid] = (small, len(tokens))
        return pid

    def drop_prefix(self, pid: int) -> None:
        """Free a registered prefix's cache rows.  Refuses while a QUEUED
        request still references it — dropping under it would otherwise
        blow up mid-step after the request left the queue, destroying
        that step's already-harvested results (admitted requests no
        longer need the prefix; only the queue is checked)."""
        if any(p == pid for _rid, _pr, _mn, p in self._pending):
            raise ValueError(
                f"prefix {pid} is still referenced by queued requests; "
                f"run()/step() them first")
        del self._prefixes[pid]

    def submit(self, prompt, max_new_tokens: int,
               prefix: Optional[int] = None) -> int:
        """Queue one request; returns its id (resolved by step()/run()).

        ``prefix``: a :meth:`register_prefix` id — ``prompt`` is then the
        SUFFIX continuing it (the generated text continues
        ``prefix_tokens + prompt``)."""
        prompt = np.asarray(prompt, np.int32).reshape(-1)
        row = {"side": "server", "server": self.server_id, "rid": None,
               "n_prompt": len(prompt), "bucket": None, "n_out": 0,
               "step0": None, "steps": 0, "t_submit": _now(), "t_admit0": None,
               "t_first": None, "t_done": None, "status": "queued"}
        if self.cfg.mtp:
            row["spec_accepted"] = 0
        try:
            self._check_request(prompt, max_new_tokens, prefix)
        except (ValueError, KeyError):
            row.update(status="rejected", t_done=row["t_submit"])
            _request_log.append(row)
            raise
        rid = self._next_rid
        self._next_rid += 1
        row["rid"] = rid
        self._rows[rid] = row
        _request_log.append(row)
        self._pending.append((rid, prompt, int(max_new_tokens), prefix))
        return rid

    def _check_request(self, prompt: np.ndarray, max_new_tokens: int,
                       prefix: Optional[int]) -> None:
        """Everything submit() refuses, raised before the request gets an
        id: un-bucketable and un-placeable requests fail NOW, not at
        admission time after they have left the queue."""
        if max_new_tokens < 1:
            raise ValueError("max_new_tokens must be >= 1")
        if len(prompt) < 1:
            raise ValueError("empty prompt")
        plen = 0
        if prefix is not None:
            if prefix not in self._prefixes:
                raise KeyError(f"unknown prefix id {prefix}")
            plen = self._prefixes[prefix][1]
        if plen + len(prompt) + max_new_tokens > self.max_len:
            raise ValueError(
                f"prefix ({plen}) + prompt ({len(prompt)}) + max_new "
                f"({max_new_tokens}) exceeds max_len={self.max_len}")
        if prefix is not None:
            sb = _bucket(len(prompt), self.buckets)
            if plen + sb > self.max_len:
                raise ValueError(
                    f"prefix ({plen}) + suffix bucket ({sb}, rounded up "
                    f"from {len(prompt)}) exceeds max_len={self.max_len}: "
                    f"the suffix ingest writes bucket-wide")
        elif not self.rolling:
            _bucket(len(prompt), self.buckets)

    # ------------------------------------------------------------- engine
    def _admit(self, slot: int, rid: int, prompt: np.ndarray,
               max_new: int, prefix: Optional[int] = None) -> None:
        if self._ingests(prefix):
            # No program: the coming chunks carry the prompt, piece by
            # piece (_plan_ingest), and seat the request themselves.
            self._ingest[slot] = [rid, prompt, max_new, 0]
            self._occupy(slot, rid)
            return
        self.key, sub = jax.random.split(self.key)
        plen = 0
        if prefix is not None:
            if prefix not in self._prefixes:
                raise KeyError(
                    f"prefix {prefix} was dropped while request {rid} "
                    f"waited in the queue")
            small, plen = self._prefixes[prefix]
            sb = _bucket(len(prompt), self.buckets)
            padded = np.zeros((1, sb), np.int32)
            padded[0, :len(prompt)] = prompt
            admit = _compiled_prefix_admit(
                self.cfg, cache_len(small), sb, self.max_len,
                *self.sampling)
            self.cache, tok = admit(
                self.params, self.cache, small,
                jnp.asarray(plen, jnp.int32), jnp.asarray(padded),
                jnp.asarray(len(prompt), jnp.int32),
                jnp.asarray(slot, jnp.int32), sub)
        elif self.rolling:
            # Chunked O(window) prefill with denomination widths: at most
            # len(ROLLING_ADMIT_WIDTHS) compiled programs, any prompt
            # length.
            logits, small = _rolling_prefill_state(
                self.params, self.cfg, prompt)
            admit = _compiled_rolling_admit(self.cfg, *self.sampling)
            self.cache, tok = admit(self.cache, small, logits,
                                    jnp.asarray(slot, jnp.int32), sub)
        else:
            pb = _bucket(len(prompt), self.buckets)
            padded = np.zeros((1, pb), np.int32)
            padded[0, :len(prompt)] = prompt
            if self.spec.readers > 1:
                # Rows the layers that keep something, and those behind
                # them that keep nothing, saw of this prompt's bucket.
                exits = early_exit_at(self.cfg) is not None
                step = self._step
                step["admit_rows_self"] = step.get("admit_rows_self", 0) + pb
                step["admit_rows_cross"] = (step.get("admit_rows_cross", 0)
                                            + (1 if exits else pb))
            admit = _compiled_admit(self.cfg, pb, *self.sampling)
            self.cache, tok = admit(
                self.params, self.cache, jnp.asarray(padded),
                jnp.asarray(len(prompt), jnp.int32),
                jnp.asarray(slot, jnp.int32), sub)
        self._finish_admit(slot, rid, tok, plen + len(prompt), max_new)

    def _finish_admit(self, slot: int, rid: int, tok, cursor: int,
                      max_new: int) -> None:
        """Shared tail of every admission path (dense, prefix, rolling,
        paged): seat the request in its slot, on the device, from the admit
        program's own token ``tok``, and note that its first token is owed.
        Never reads the device: ``step()`` fetches the first tokens of all
        it admitted at once (:meth:`_hand_out_firsts`), and ``on_tokens``
        fires there."""
        eos = -1 if self.eos_id is None else self.eos_id
        if self.cfg.mtp:  # the admit program's: token, then the draft's
            tok, *spec = tok
            self._draft, self._first_lp = _seat_draft(
                self._draft, self._first_lp, jnp.asarray(slot, jnp.int32),
                *spec)
        self.token, self.pos, self.live, self.remaining = _seat(
            self.token, self.pos, self.live, self.remaining, tok,
            jnp.asarray([slot, cursor, max_new, eos], jnp.int32))
        self._occupy(slot, rid)
        self._pos_host[slot] = cursor
        self._live_host[slot] = max_new > 1  # eos: once the token is here
        self._firsts.append((slot, rid))

    def _occupy(self, slot: int, rid: int) -> None:
        """The slot is the request's from now on (either path)."""
        row = self._rows.get(rid)
        if row is not None:
            row.update(status="running", step0=self._n_steps)
        self._slot_rid[slot] = rid
        self._collected[rid] = []

    def _fetch(self, tree):
        """THE blocking device-to-host read of the serve loop: everything
        ``step()`` learns from the device comes through here, counted in
        the step's ``fetches``."""
        self._step["fetches"] += 1
        return jax.device_get(tree)

    def _seated(self):
        """What :meth:`_hand_out_firsts` reads, as the admissions left it:
        the slots' pending tokens and, where log-probabilities are handed
        out, the first tokens' beside them."""
        return (self.token if self.on_logprobs is None
                else (self.token, self._first_lp))

    def _hand_out_firsts(self, seated) -> None:
        """The first tokens of everything admitted since the last call, in
        one read of ``seated``: ``self.token`` as the last admission left
        it, where every seated token sits at its slot until a chunk moves
        it (that chunk may be queued already).  One stamp for all, then
        ``on_tokens`` in admission order."""
        if not self._firsts:
            return
        firsts, self._firsts = self._firsts, []
        with perf.stage_span(self.stage_scope, "serve.first_wait"):
            tokens = self._fetch(seated)
        if self.on_logprobs is not None:
            tokens, first_lp = tokens
        step = self._step
        step["admit_s"] = _now() - step["t0"]
        t_first = step["t0"] + step["admit_s"]  # as step_log()'s readers add
        for slot, rid in firsts:
            if rid not in self._collected:
                continue  # cancelled by an earlier first-token callback
            tok = int(tokens[slot])
            row = self._rows.get(rid)
            if row is not None:
                row["t_first"] = t_first
            self._collected[rid].append(tok)
            if tok == self.eos_id:  # max_new == 1: known at admission
                self._live_host[slot] = False
            if self.on_logprobs is not None:
                self.on_logprobs(rid, [float(first_lp[slot])], [])
            if self.on_tokens is not None:
                self.on_tokens(rid, [tok], False)

    def cancel(self, rid: int) -> bool:
        """Abort a request: de-queue it if pending, else kill its slot so
        the next step() frees it for waiting work (the transport bridge
        calls this when a client disconnects or sends CANCEL — decoding
        for a peer that will never read the tokens is wasted chip time).

        Returns True if the request was found (pending or in a slot);
        a finished/unknown rid returns False.  A cancelled request is
        NOT reported by step()/run() and emits no on_tokens done event —
        cancellation is the caller declaring the stream dead."""
        for i, (qrid, *_rest) in enumerate(self._pending):
            if qrid == rid:
                del self._pending[i]
                self._close_row(rid, "cancelled", 0)
                return True
        for slot, srid in self._slot_rid.items():
            if srid == rid:
                self.live = self.live.at[slot].set(False)
                self.remaining = self.remaining.at[slot].set(0)
                self._live_host[slot] = False
                del self._slot_rid[slot]
                self._ingest.pop(slot, None)  # a prompt half ingested
                self._close_row(rid, "cancelled",
                                len(self._collected.pop(rid, ())))
                self._on_slot_freed(slot)
                return True
        return False

    def _close_row(self, rid: int, status: str, n_out: int) -> None:
        """A request's last stamp: its row leaves this server (the log
        keeps it)."""
        row = self._rows.pop(rid, None)
        if row is not None:
            step0 = row["step0"]
            row.update(t_done=_now(), status=status, n_out=n_out,
                       steps=0 if step0 is None else self._n_steps - step0 + 1)

    def _harvest_dead(self, finished: dict) -> None:
        """Close every occupied slot the host knows to be dead
        (``_live_host``: the chunk's final ``live``, or a first token that
        ended its request).  Reads nothing from the device."""
        live = self._live_host
        # Snapshot + tolerant pops: a done-event on_tokens callback may
        # cancel() another request that finished in this same step,
        # removing its entries before the loop reaches them.
        for slot, rid in list(self._slot_rid.items()):
            if not live[slot] and slot not in self._ingest:
                if rid not in self._collected:
                    self._slot_rid.pop(slot, None)  # cancelled mid-loop
                    continue
                finished[rid] = np.asarray(self._collected.pop(rid),
                                           np.int32)
                self._close_row(rid, "done", len(finished[rid]))
                self._slot_rid.pop(slot, None)
                self._on_slot_freed(slot)
                if self.on_tokens is not None:
                    self.on_tokens(rid, [], True)

    @_on_weights_mesh
    def step(self) -> dict:
        """Admit what fits, decode one chunk; returns {rid: tokens} for
        requests that finished during this step.

        The device programs of one step are queued with no host round trip
        between them: every admission that has a program (its admit
        program, then ``serve_seat``), then the chunk, which on the ingest
        path carries the waiting prompts' pieces.  Only then does the host
        read the device: the first tokens of what the admit programs
        admitted, if any, and the chunk's tokens with the first tokens it
        seated and the final ``live`` and ``pos``."""
        finished: dict = {}
        scope = self.stage_scope
        self._n_steps += 1
        with perf.stage_span(scope, "serve.step") as whole:
            step = self._step = {
                "server": self.server_id, "n_slots": self.n_slots,
                "t0": whole.t0, "t1": whole.t0, "queued": 0, "live": 0,
                "admits": 0, "admit_s": 0.0, "dispatch_s": 0.0,
                "wait_s": 0.0, "harvest_s": 0.0, "fetches": 0,
                "ingest_tokens": 0, "ingest_rows": 0, "ingest_width": 0}
            if not self._built:
                self._build_programs()
            free = [s for s in range(self.n_slots)
                    if s not in self._slot_rid]
            # WHICH requests a step admits is the queue's order (its first
            # ``len(free)``); among them the one with most tokens to
            # produce goes first.  Each admission stalls every request
            # admitted before it, and a stall costs a request's time per
            # token as its share of the tokens it is spread over: after a
            # cold fill of many slots the first-admitted would otherwise
            # carry the whole fill on however few tokens they asked for
            # (PERF.md, PR 26).  Chosen one at a time: a refused admission
            # (below) ends the step's admissions where they stand.
            while free and self._pending:
                at = max(range(min(len(free), len(self._pending))),
                         key=lambda i: (self._pending[i][2], -i))
                rid, prompt, max_new, prefix = self._pending[at]
                del self._pending[at]
                row = self._rows.get(rid)
                span = perf.stage_span(scope, "serve.admit", rid)
                try:
                    with span:
                        if row is not None:
                            row["t_admit0"] = span.t0
                            row["bucket"] = (
                                0 if not self.buckets or self._ingests(prefix)
                                else _bucket(len(prompt), self.buckets))
                        self._admit(free.pop(0), rid, prompt, max_new, prefix)
                except NoRoomYet:
                    # Transient resource exhaustion (the paged server's
                    # pool), raised before the request's admit program
                    # was launched: it STAYS QUEUED, where it was —
                    # in-flight work frees capacity and a later step
                    # admits it (the class docstring's "callers keep it
                    # queued / retry" contract).  Any other error of an
                    # admission is a fault and surfaces: kept queued, a
                    # request that can never be admitted spins run().
                    self._pending.insert(at, (rid, prompt, max_new, prefix))
                    break
                step["admits"] += 1
            step["queued"], step["live"] = (len(self._pending),
                                            len(self._slot_rid))
            step.update(self.spec.step_rows(self._pos_host[
                [s for s in self._slot_rid if self._live_host[s]]]))
            # A slot the host knows dead already (a one-token request just
            # admitted) needs no chunk; one whose first token may be its
            # eos is found out after the chunk was queued, and rides it
            # masked.
            if self._ingest or any(self._live_host[s]
                                   for s in self._slot_rid):
                self.key, sub = jax.random.split(self.key)
                toks, mask = self._run_chunk(sub)
                with perf.stage_span(scope, "serve.chunk_wait") as span:
                    toks, mask, pairs, firsts, live, pos, spec = self._fetch(
                        (toks, mask, self._pairs, self._seat_toks, self.live,
                         self.pos, self._spec))
                step["wait_s"] = span.seconds
                self._live_host, self._pos_host = np.array(live), np.array(pos)
                if pairs is not None:  # a routed model's
                    # The rows of one call: each slot's (a verified draft
                    # beside it), and a mixed chunk's piece.
                    rows = (self.n_slots * (1 + self.cfg.mtp)
                            + step["ingest_width"])
                    step.update(_moe_fields(pairs, row_tile(
                        rows * self.cfg.routed.top_k)))
                if spec is not None:  # a speculating server's
                    step.update(spec_drafted=int(mask[:, 0].sum()),
                                spec_accepted=int(spec["accepted"].sum()),
                                spec_emitted=int(mask.sum()))
                with perf.stage_span(scope, "serve.harvest") as span:
                    self._hand_out_seated(firsts)
                    # Snapshot: an on_tokens callback may legally cancel()
                    # a request (its own or another), which mutates
                    # _slot_rid/_collected.
                    for slot, rid in list(self._slot_rid.items()):
                        if rid not in self._collected:
                            continue  # cancelled by an earlier callback
                        # ([chunk, B], or a speculating server's [chunk,
                        # 2, B]: a step's tokens in their order)
                        kept = mask[..., slot].ravel()
                        new = [int(t) for t in toks[..., slot].ravel()[kept]]
                        self._collected[rid].extend(new)
                        if spec is not None and new:
                            self._note_spec(rid, slot, kept, mask, spec)
                        if self.on_tokens is not None and new:
                            self.on_tokens(rid, new, False)
                    self._harvest_dead(finished)
                step["harvest_s"] = span.seconds
            else:
                self._hand_out_firsts(self._seated())
                self._harvest_dead(finished)
        step["t1"] = whole.t0 + whole.seconds
        _step_log.append(step)
        return finished

    def _note_spec(self, rid: int, slot: int, kept, mask, spec) -> None:
        """A chunk's speculation for one request: its accepted drafts into
        its :func:`request_log` row and, where asked for, the tokens'
        log-probabilities and the drafts to ``on_logprobs``."""
        verified = mask[:, 0, slot]
        row = self._rows.get(rid)
        if row is not None:
            row["spec_accepted"] += int(spec["accepted"][verified, slot].sum())
        if self.on_logprobs is not None:
            # where each verifying step's first token lies among ``kept``
            at = (np.cumsum(kept) - 1)[0::2][verified]
            drafts = [(int(i), int(d), float(lq), bool(ok))
                      for i, d, lq, ok in zip(
                          at, spec["draft"][verified, slot],
                          spec["draft_logp"][verified, slot],
                          spec["accepted"][verified, slot])]
            self.on_logprobs(
                rid, [float(x) for x in spec["logp"][..., slot].ravel()[kept]],
                drafts)

    @property
    def busy(self) -> bool:
        """True while any request is queued or occupying a slot."""
        return bool(self._pending or self._slot_rid)

    def _run_chunk(self, sub):
        """Queue one decode chunk behind the step's admissions and, while
        the device works through them, hand out the step's first tokens;
        returns the chunk's (tokens, mask), still on the device.  The
        first tokens go out from here and not from ``step()``: a caller
        that times this call and then waits on what it returned (the
        benchmark's traced runs do) would otherwise hold them back for a
        whole chunk."""
        seated = self._seated()
        with perf.stage_span(self.stage_scope, "serve.chunk_dispatch") as span:
            out = self._launch_chunk(sub)
        self._step["dispatch_s"] = span.seconds
        self._hand_out_firsts(seated)
        return out

    def _launch_chunk(self, sub):
        """Launch the chunk program (subclass hook: the paged server runs
        its page-table program here); returns (tokens, mask).  With
        prompts waiting to be ingested it is the mixed chunk of the width
        the plan chose (:meth:`_plan_ingest`), else the plain one."""
        pieces = self._plan_ingest() if self._ingest else ()
        run = self._chunk_program(pieces[1].shape[1] if pieces else None)
        if self.cfg.mtp:  # the draft state in, and out with ``spec``
            (self.cache, self.token, self.pos, self.live, self.remaining,
             _key, self._draft, toks, mask, self._pairs, self._spec) = run(
                 self.params, self.cache, self.token, self.pos, self.live,
                 self.remaining, sub, self._draft)
            return toks, mask
        (self.cache, self.token, self.pos, self.live, self.remaining,
         _key, toks, mask, self._pairs, *firsts) = run(
             self.params, self.cache, self.token, self.pos, self.live,
             self.remaining, sub, *pieces)
        self._seat_toks = firsts[0] if firsts else None
        return toks, mask

    def _chunk_program(self, width: Optional[int]):
        """The plain chunk (``width`` None) or the mixed one of a width."""
        if width is None:
            return _compiled_chunk(
                self.cfg, self.n_slots, self.max_len, self.chunk,
                *self.sampling, self.eos_id, rolling=self.rolling,
                **({"logprobs": True} if self.on_logprobs is not None
                   else {}))
        return _compiled_ingest_chunk(
            self.cfg, self.n_slots, self.max_len, self.chunk, width,
            *self.sampling, self.eos_id)

    def _build_programs(self) -> None:
        """Before the first request is served: every program the ingest
        path can launch is compiled (or loaded from the compile cache) by
        one run on the idle server -- the plain chunk and the mixed chunk
        of each width, nothing to ingest, no slot live -- so that no later
        step compiles, whatever backlog it meets."""
        for width in (None, *self._widths):
            pieces = () if width is None else (
                np.zeros((self.chunk, len(PIECE_FIELDS)), np.int32),
                np.zeros((self.chunk, width), np.int32))
            (self.cache, self.token, self.pos, self.live,
             self.remaining, *_rest) = self._chunk_program(width)(
                 self.params, self.cache, self.token, self.pos, self.live,
                 self.remaining, self.key, *pieces)
        self._built = True

    def _plan_ingest(self):
        """Lay the waiting prompts' pieces over the coming chunk's steps:
        in admission order, one request's piece a step, a prompt's pieces
        in consecutive steps, its last piece padded to the width; what does
        not fit continues in the next chunk.  The width is the smallest of
        ``self._widths`` at which what waits comes in within this chunk,
        else the largest: ALL the waiting prompts while no request waits
        for a slot, the LONGEST of them while some do.  A wider piece's
        rows are paid in every lane's step and a prompt left to the next
        chunk idles one lane: with requests queued for slots the lanes are
        what is scarce, without them a chunk's wait for a first token is
        what is felt (and a width that leaves the second of two arrivals
        to the next chunk puts a second mode at the 95th percentile of the
        time to first token: PERF.md section 6, PR 29, has the readings).
        The backlog decides, nothing else.  Returns ``(desc [chunk, 6],
        ids [chunk, width])`` for :func:`_compiled_ingest_chunk` and notes
        in ``self._seats`` which steps seat which request."""
        left = [len(p) - at for _rid, p, _max_new, at in self._ingest.values()]
        pieces = max if self._pending else sum
        width = next((w for w in self._widths
                      if pieces(-(-n // w) for n in left) <= self.chunk),
                     self._widths[-1])
        desc = np.zeros((self.chunk, len(PIECE_FIELDS)), np.int32)
        ids = np.zeros((self.chunk, width), np.int32)
        eos = -1 if self.eos_id is None else self.eos_id
        s = 0
        for slot, req in list(self._ingest.items()):
            rid, prompt, max_new, at = req
            while s < self.chunk and at < len(prompt):
                n = min(width, len(prompt) - at)
                last = at + n == len(prompt)
                desc[s] = (slot, at, n, last, max_new, eos)
                ids[s, :n] = prompt[at:at + n]
                at += n
                if last:
                    self._seats.append((s, rid))
                    del self._ingest[slot]
                s += 1
            req[3] = at
            if s == self.chunk:
                break
        self._step.update(ingest_tokens=int(desc[:, 2].sum()),
                          ingest_rows=s * width, ingest_width=width)
        return desc, ids

    def _hand_out_seated(self, firsts) -> None:
        """The first tokens of the requests the chunk seated (``firsts``:
        its fourth output, on the host), in the order it seated them; each
        before the request's chunk tokens, which the caller hands out
        next."""
        seats, self._seats = self._seats, []
        t_first = _now()
        for at, rid in seats:
            if rid not in self._collected:
                continue  # cancelled while its chunk was in flight
            tok = int(firsts[at])
            row = self._rows.get(rid)
            if row is not None:
                row["t_first"] = t_first
            self._collected[rid].append(tok)
            if self.on_tokens is not None:
                self.on_tokens(rid, [tok], False)

    def run(self) -> dict:
        """Drive step() until every submitted request has finished."""
        finished: dict = {}
        while self.busy:
            finished.update(self.step())
        return finished
