"""Paged continuous batching: the serving cache as a shared page pool.

The dense :class:`~starway_tpu.models.serving.SlotServer` cache reserves
``n_slots x max_len`` positions whatever the requests actually use;
paging (the vLLM block-table idea, built TPU-first here) shares one pool
of fixed-size pages across slots, so HBM scales with LIVE tokens:

* pool ``k/v [L, n_pages, Hkv, page, D]`` — sized by expected total
  tokens in flight, independent of ``n_slots x max_len``;
* host-managed page tables ``[n_slots, max_pages]`` + free list; pages
  allocate lazily as each cursor grows and return to the pool the
  moment a request finishes or is cancelled;
* decode attention walks the table INSIDE the pallas kernel's DMA
  stream (ops/pallas_paged.py) — no dense view is ever materialised,
  and bandwidth per token equals the dense stream kernel's.

Page id 0 is a reserved TRASH page: freed slots' table rows point at it,
so the chunk program's frozen-cursor writes for dead slots (the dense
design's "overwritten before read" invariant does not survive page
REUSE) land in scratch that no live slot ever attends.

PREFIX SHARING, zero copy: a registered prefix's whole pages are
REFERENCED by every suffix request (refcounted; only the partial tail
page the suffix continues inside is copied per slot) — strictly less
admission work and less memory than the dense server's per-slot row
copy, and the natural payoff of the paged layout.

Greedy outputs are bit-identical to the dense SlotServer and the
standalone ``generate()`` oracle (tests/test_paged.py) — paging changes
WHERE bytes live, never what attention computes.  v1 scope: full-causal
bf16/f32 models (sliding-window/rolling and int8 pools are refused
loudly).
"""

from __future__ import annotations

import functools
from typing import Optional

import numpy as np

import jax
import jax.numpy as jnp
from jax import lax

from ..ops import paged_attention
from ..ops.pallas_decode import kv_write_lax
from .cache import _write_cached, require_rows
from .generate import _sample, cached_layer_scan, prefill
from .llama import (LlamaConfig, cfg_rmsnorm, cfg_rope_tables, embed_tokens,
                    matmul_w)
from .serving import (NoRoomYet, SlotServer, _bucket, _named_jit,
                      _on_weights_mesh, make_chunk_scan_step)


def init_paged_pool(cfg: LlamaConfig, n_pages: int, page: int) -> dict:
    """k/v pools ``[L, n_pages, Hkv, page, D]`` (page 0 is the trash
    page)."""
    hd = cfg.head_dim
    shape = (cfg.n_layers, n_pages, cfg.n_kv_heads, page, hd)
    return {"k": jnp.zeros(shape, cfg.compute_dtype),
            "v": jnp.zeros(shape, cfg.compute_dtype)}


def paged_decode_step(params, pool, table, token, pos, cfg: LlamaConfig,
                      rope):
    """One token in, next-token logits out, over the paged pool.

    Mirrors :func:`~starway_tpu.models.generate.decode_step` exactly —
    same :func:`cached_layer_scan` body — with page-table write/attend
    closures: the write scatters each slot's k/v into
    ``pool[table[b, pos_b // page], head, pos_b % page]``, and attention
    streams the slot's pages through the paged kernel.  token/pos: [B]
    (per-slot cursors, the serving shape)."""
    page = pool["k"].shape[3]
    cos, sin = rope
    pos = jnp.asarray(pos, jnp.int32)
    pids = jnp.take_along_axis(table, (pos // page)[:, None], axis=1)[:, 0]
    offs = pos % page
    cos_p = cos[pos][:, None, None, :]
    sin_p = sin[pos][:, None, None, :]

    def write(pool, new, layer):
        # The pool's "row" is a page and the cursor an offset inside it.
        # Distinct LIVE slots own distinct pages (allocator invariant), so
        # their tiles never collide; dead slots' zeroed table rows may
        # collide on trash page 0, whose contents are never read.
        return _write_cached(pool, new, layer, offs, rows=pids)

    def attend(q, pool, layer):
        return paged_attention(q, pool["k"], pool["v"], table, pos,
                               layer=layer)

    h = embed_tokens(params, token, cfg)[:, None, :]
    h, out, _ = cached_layer_scan(params, pool, h, cos_p, sin_p, cfg, write,
                                  attend)
    h = cfg_rmsnorm(h, params["final_norm"], cfg)
    logits = matmul_w(h[:, 0, :], params["lm_head"]).astype(jnp.float32)
    return logits, out


@functools.cache
def _compiled_paged_admit(cfg: LlamaConfig, p_bucket: int, page: int,
                          temperature: float, top_k: Optional[int],
                          top_p: Optional[float]):
    """Prefill one request and scatter its cache into ``p_bucket // page``
    pool pages; returns (pool, first token).  One compile per bucket."""
    npb = p_bucket // page

    def run(params, pool, prompt, length, pids, key):
        logits, small = prefill(params, cfg, prompt, p_bucket,
                                logit_positions=length[None] - 1)
        pool = dict(pool)
        for name in ("k", "v"):
            # small[name] [L, 1, Hkv, p_bucket, D] -> [L, npb, Hkv, page, D]
            L, _, hkv, _, d = small[name].shape
            paged = small[name].reshape(L, hkv, npb, page, d).transpose(
                0, 2, 1, 3, 4)
            pool[name] = pool[name].at[:, pids].set(paged)
        tok = _sample(logits, key, temperature, top_k, top_p)[0]
        return pool, tok

    return _named_jit(run, f"serve_paged_admit_{p_bucket}",
                      donate_argnums=(1,))


@functools.cache
def _compiled_paged_chunk(cfg: LlamaConfig, max_len: int, chunk: int,
                          temperature: float, top_k: Optional[int],
                          top_p: Optional[float], eos_id: Optional[int]):
    """The chunk program over the pool: identical control flow to the
    dense ``_compiled_chunk`` (liveness, budgets, eos, emission mask) —
    only the decode step is paged."""
    rope = cfg_rope_tables(cfg, max_len)

    def run(params, pool, table, token, pos, live, remaining, key):
        step = make_chunk_scan_step(
            lambda pool, token, pos: paged_decode_step(
                params, pool, table, token, pos, cfg, rope),
            max_len, temperature, top_k, top_p, eos_id)
        (pool, token, pos, live, remaining, key), (toks, mask) = lax.scan(
            step, (pool, token, pos, live, remaining, key), None,
            length=chunk)
        return pool, token, pos, live, remaining, key, toks, mask

    return _named_jit(run, "serve_paged_decode_chunk", donate_argnums=(1,))


@functools.cache
def _compiled_paged_prefix_write(cfg: LlamaConfig, p_bucket: int, page: int,
                                 n_full: int):
    """Prefill a PREFIX once; scatter its ``n_full`` whole pages into the
    pool and return the remainder as one padded tail page (junk above
    ``plen % page`` — overwritten by the suffix ingest before any read).
    One compile per (prefix bucket, n_full)."""

    def run(params, pool, prompt, length, pids):
        _logits, small = prefill(params, cfg, prompt, p_bucket,
                                 logit_positions=length[None] - 1)
        tails = {}
        pool = dict(pool)
        for name in ("k", "v"):
            L, _, hkv, _, d = small[name].shape
            paged = small[name].reshape(L, hkv, p_bucket // page, page,
                                        d).transpose(0, 2, 1, 3, 4)
            if n_full:
                pool[name] = pool[name].at[:, pids].set(paged[:, :n_full])
            # The page holding positions [n_full*page, plen): the suffix
            # continues inside it, so it is copied per slot, not shared.
            tails[name] = (paged[:, n_full] if n_full < p_bucket // page
                           else jnp.zeros((L, hkv, page, d),
                                          small[name].dtype))
        return pool, tails["k"], tails["v"]

    return _named_jit(run, f"serve_paged_prefix_register_{p_bucket}",
                      donate_argnums=(1,))


@functools.cache
def _compiled_paged_prefix_admit(cfg: LlamaConfig, s_bucket: int, page: int,
                                 max_pages: int, has_tail: bool,
                                 temperature: float, top_k: Optional[int],
                                 top_p: Optional[float]):
    """Admit (shared prefix, fresh suffix): copy the prefix's partial
    tail page into the slot's first OWN page, then ingest the suffix as
    ONE C=s_bucket chunk forward over the page table (write-then-attend,
    the decode-path semantics) and sample from the suffix's last real
    position.  One compile per (suffix bucket, has_tail) — plen is a
    traced argument, so prefixes of any length share the program."""
    rope = cfg_rope_tables(cfg, max_pages * page)
    cos, sin = rope
    name = f"serve_paged_prefix_admit_{s_bucket}"

    def run(params, pool, tail_k, tail_v, row, suffix, s_len, plen, key):
        pool = dict(pool)
        if has_tail:
            own0 = row[0, plen // page]
            pool["k"] = pool["k"].at[:, own0].set(tail_k)
            pool["v"] = pool["v"].at[:, own0].set(tail_v)

        spos = plen + jnp.arange(s_bucket)      # suffix positions
        pids_c = row[0, spos // page]
        offs = spos % page
        cos_p = cos[spos][None, None, :, :]
        sin_p = sin[spos][None, None, :, :]

        def write(pool, new, layer):
            # [1, Hkv, s_bucket, D] -> one row a TOKEN at (pid, :, off).
            # Neighbouring tokens share a page tile, which the in-place
            # kernel's rows must not (they would race): this write is
            # kv_write_lax BY NAME, the XLA scatter on every backend, not
            # ops.cache_write (PERF.md section 7).
            k, v = kv_write_lax(
                (pool["k"], pool["v"]),
                tuple(jnp.moveaxis(new[name][0], 1, 0)[:, :, None]
                      for name in ("k", "v")), layer, pids_c, offs)
            return {**pool, "k": k, "v": v}

        def attend(q, pool, layer):
            return paged_attention(q, pool["k"], pool["v"], row, plen[None],
                                   layer=layer)

        from .llama import embed_tokens, head_logits

        h = embed_tokens(params, suffix[0], cfg)[None]  # [1, s_bucket, D]
        h, pool, _ = cached_layer_scan(params, pool, h, cos_p, sin_p, cfg,
                                       write, attend)
        logits = head_logits(h[:, s_len - 1][:, None], params["final_norm"],
                             params["lm_head"],
                             cfg.norm_eps, cfg.norm_zero_centred)[:, 0]
        tok = _sample(logits, key, temperature, top_k, top_p)[0]
        return pool, tok

    if not has_tail:
        # No tail operands at all: page-aligned prefixes must not pay two
        # dead [L, Hkv, page, D] transfers per admission.
        def run_no_tail(params, pool, row, suffix, s_len, plen, key):
            return run(params, pool, None, None, row, suffix, s_len, plen,
                       key)

        return _named_jit(run_no_tail, name, donate_argnums=(1,))
    return _named_jit(run, name, donate_argnums=(1,))


class PagedSlotServer(SlotServer):
    """Continuous batching over a shared page pool.

    >>> srv = PagedSlotServer(params, cfg, n_slots=8, max_len=512,
    ...                       page=64, n_pages=33)
    >>> rid = srv.submit(prompt, max_new_tokens=32)
    >>> done = srv.run()

    Same queue/streaming/cancel surface and the same greedy-equals-
    ``generate()`` guarantee as the dense server; the difference is
    memory: ``n_pages`` bounds TOTAL live tokens (``(n_pages - 1) *
    page``), not per-slot reservations, so short requests don't pay for
    ``max_len``, and pages recycle the moment a request finishes.
    A request whose prompt the pool cannot cover yet simply STAYS
    QUEUED (step() catches the allocator's ``NoRoomYet``, a RuntimeError,
    and retries once in-flight work frees pages); lazy per-chunk growth
    exhausting the pool mid-generation raises it out of step() —
    preemption is not wired, so size ``n_pages`` for the expected
    concurrency.
    """

    def __init__(self, params, cfg: LlamaConfig, *, n_slots: int = 4,
                 max_len: int = 512, page: int = 64,
                 n_pages: Optional[int] = None, chunk: int = 8,
                 temperature: float = 0.0, top_k: Optional[int] = None,
                 top_p: Optional[float] = None,
                 eos_id: Optional[int] = None, seed: int = 0,
                 on_tokens=None):
        # The pool pages full rows of dense k / v, every layer alike.
        require_rows(cfg, "page", NotImplementedError)
        if max_len % page:
            raise ValueError(f"page ({page}) must divide max_len "
                             f"({max_len})")
        self.page = int(page)
        self.max_pages = max_len // page
        if n_pages is None:
            n_pages = 1 + n_slots * self.max_pages  # dense-equivalent
        if n_pages < 2:
            raise ValueError("need n_pages >= 2 (page 0 is the trash page)")
        self.n_pages = int(n_pages)
        # Buckets must be page multiples so admission scatters whole pages.
        b, buckets = page, []
        while b < max_len:
            buckets.append(b)
            b *= 2
        buckets.append(max_len)
        super().__init__(params, cfg, n_slots=n_slots, max_len=max_len,
                         chunk=chunk, temperature=temperature, top_k=top_k,
                         top_p=top_p, eos_id=eos_id,
                         prompt_buckets=tuple(sorted(set(buckets))),
                         seed=seed, on_tokens=on_tokens)

    # ------------------------------------------------------------- hooks
    def _make_cache(self):
        return init_paged_pool(self.cfg, self.n_pages, self.page)

    def _ingest_widths(self) -> tuple:
        # Prompts enter the page pool by its own admit programs (_admit).
        return ()

    def _post_init(self) -> None:
        # Host-side allocator: every slot starts on the trash page.
        self._tables = np.zeros((self.n_slots, self.max_pages), np.int32)
        self._free = list(range(1, self.n_pages))
        # Shared prefix pages: refcount = registry (1) + referencing
        # slots; a page returns to the pool at refcount 0.
        self._page_refs: dict[int, int] = {}

    def _on_slot_freed(self, slot: int) -> None:
        for pid in self._tables[slot]:
            pid = int(pid)
            if pid == 0:
                continue
            if pid in self._page_refs:  # shared prefix page
                self._page_refs[pid] -= 1
                if self._page_refs[pid] == 0:  # prefix already dropped
                    del self._page_refs[pid]
                    self._free.append(pid)
            else:
                self._free.append(pid)
        self._tables[slot] = 0

    @property
    def pages_in_use(self) -> int:
        """Live pool pages (the memory the paging saves elsewhere)."""
        return self.n_pages - 1 - len(self._free)

    def _alloc_to(self, slot: int, n_needed: int) -> None:
        """Extend the slot's table to ``n_needed`` pages (the prefix of
        the row is whatever admission set — shared or owned)."""
        row = self._tables[slot]
        have = int((row != 0).sum())
        if n_needed > self.max_pages:
            n_needed = self.max_pages
        if n_needed > have and len(self._free) < n_needed - have:
            raise NoRoomYet(
                f"page pool exhausted: slot {slot} needs "
                f"{n_needed - have} more page(s), {len(self._free)} free "
                f"(n_pages={self.n_pages}); finish/cancel requests or "
                f"size the pool for the workload")
        for i in range(have, n_needed):
            row[i] = self._free.pop()

    # --------------------------------------------------------- admission
    @_on_weights_mesh
    def register_prefix(self, tokens) -> int:
        """Prefill a shared prefix ONCE into pool pages; requests with
        ``prefix=pid`` then REFERENCE its whole pages (zero copy, pages
        refcounted across slots) and copy only the partial tail page the
        suffix continues inside — strictly less admission work AND less
        memory than the dense server's per-slot row copy."""
        tokens = np.asarray(tokens, np.int32).reshape(-1)
        if len(tokens) < 1:
            raise ValueError("empty prefix")
        if len(tokens) + self.buckets[0] + 1 > self.max_len:
            raise ValueError(
                f"prefix ({len(tokens)}) + smallest suffix bucket "
                f"({self.buckets[0]}) + 1 exceeds max_len={self.max_len}")
        plen = len(tokens)
        n_full = plen // self.page
        if len(self._free) < n_full:
            raise NoRoomYet(
                f"page pool exhausted: prefix needs {n_full} page(s), "
                f"{len(self._free)} free")
        pids = [self._free.pop() for _ in range(n_full)]
        pb = _bucket(max(plen, self.page), self.buckets)
        padded = np.zeros((1, pb), np.int32)
        padded[0, :plen] = tokens
        reg = _compiled_paged_prefix_write(self.cfg, pb, self.page, n_full)
        self.cache, tail_k, tail_v = reg(
            self.params, self.cache, jnp.asarray(padded),
            jnp.asarray(plen, jnp.int32), jnp.asarray(pids, jnp.int32))
        for pid_page in pids:
            self._page_refs[pid_page] = 1  # the registry's own reference
        pid = self._next_pid
        self._next_pid += 1
        has_tail = plen % self.page != 0
        self._prefixes[pid] = (
            (tuple(pids), tail_k if has_tail else None,
             tail_v if has_tail else None), plen)
        return pid

    def drop_prefix(self, pid: int) -> None:
        """Release the registry's reference; whole pages return to the
        pool once no admitted slot still reads them."""
        if any(p == pid for _rid, _pr, _mn, p in self._pending):
            raise ValueError(
                f"prefix {pid} is still referenced by queued requests; "
                f"run()/step() them first")
        (pids, _tk, _tv), _plen = self._prefixes.pop(pid)
        for pid_page in pids:
            self._page_refs[pid_page] -= 1
            if self._page_refs[pid_page] == 0:
                del self._page_refs[pid_page]
                self._free.append(pid_page)

    def _admit(self, slot: int, rid: int, prompt: np.ndarray,
               max_new: int, prefix=None) -> None:
        if prefix is not None:
            return self._admit_prefixed(slot, rid, prompt, max_new, prefix)
        self.key, sub = jax.random.split(self.key)
        pb = _bucket(len(prompt), self.buckets)
        self._alloc_to(slot, pb // self.page)
        padded = np.zeros((1, pb), np.int32)
        padded[0, :len(prompt)] = prompt
        pids = jnp.asarray(self._tables[slot, :pb // self.page])
        admit = _compiled_paged_admit(self.cfg, pb, self.page,
                                      *self.sampling)
        self.cache, tok = admit(self.params, self.cache,
                                jnp.asarray(padded),
                                jnp.asarray(len(prompt), jnp.int32),
                                pids, sub)
        self._finish_admit(slot, rid, tok, len(prompt), max_new)

    def _admit_prefixed(self, slot: int, rid: int, suffix: np.ndarray,
                        max_new: int, prefix: int) -> None:
        if prefix not in self._prefixes:
            raise KeyError(f"prefix {prefix} was dropped while request "
                           f"{rid} waited in the queue")
        (shared, tail_k, tail_v), plen = self._prefixes[prefix]
        n_full = plen // self.page
        sb = _bucket(len(suffix), self.buckets)
        need = -(-(plen + sb) // self.page)
        n_own = need - n_full
        if len(self._free) < n_own:
            raise NoRoomYet(
                f"page pool exhausted: prefixed admission needs {n_own} "
                f"own page(s), {len(self._free)} free")
        row = self._tables[slot]
        row[:n_full] = shared
        for i in range(n_full, need):
            row[i] = self._free.pop()
        for pid_page in shared:
            self._page_refs[pid_page] += 1
        self.key, sub = jax.random.split(self.key)
        padded = np.zeros((1, sb), np.int32)
        padded[0, :len(suffix)] = suffix
        has_tail = tail_k is not None
        admit = _compiled_paged_prefix_admit(
            self.cfg, sb, self.page, self.max_pages, has_tail,
            *self.sampling)
        args = (jnp.asarray(self._tables[slot:slot + 1]),
                jnp.asarray(padded), jnp.asarray(len(suffix), jnp.int32),
                jnp.asarray(plen, jnp.int32), sub)
        if has_tail:
            self.cache, tok = admit(self.params, self.cache, tail_k,
                                    tail_v, *args)
        else:
            self.cache, tok = admit(self.params, self.cache, *args)
        self._finish_admit(slot, rid, tok, plen + len(suffix), max_new)

    # ------------------------------------------------------------ decode
    def _launch_chunk(self, sub):
        # Lazy growth: every live slot needs pages covering its cursor's
        # reach this chunk (writes go through table[pos // page]).  From
        # the host's own copy of live/pos (the last chunk's, and this
        # step's admissions): a request whose first token will turn out to
        # be its eos gets its pages too, and returns them when it is
        # harvested.
        for slot in sorted(self._slot_rid):
            if self._live_host[slot]:
                # The chunk writes positions pos .. pos+chunk-1 (reads
                # only written positions), so the last page touched is
                # (pos+chunk-1) // page.
                reach = min(int(self._pos_host[slot]) + self.chunk,
                            self.max_len)
                self._alloc_to(slot, -(-reach // self.page))
        run = _compiled_paged_chunk(self.cfg, self.max_len, self.chunk,
                                    *self.sampling, self.eos_id)
        (self.cache, self.token, self.pos, self.live, self.remaining,
         _key, toks, mask) = run(self.params, self.cache,
                                 jnp.asarray(self._tables), self.token,
                                 self.pos, self.live, self.remaining, sub)
        return toks, mask
