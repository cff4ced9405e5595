"""Serving over the transport: tagged requests in, token streams out.

The repo's two halves meet here (VERDICT r4 #2): the async tag-matched
P2P transport — the reference's actual product surface
(/root/reference/src/bindings/main.cpp:370,1172 — tag send/recv over
endpoint connections) — carries the serving stack's actual workload.
Requests arrive as tagged messages on a :class:`~starway_tpu.Server`,
:class:`~starway_tpu.models.serving.SlotServer` admits them into its
continuous batch, and each request's tokens stream back per decode chunk
over the same connection.  Works over every data plane behind the one
worker contract (in-process, TCP, shared-memory rings, the C++ engine) —
pinned by tests/test_serve_remote.py's transport matrix.

Wire protocol — all payloads are little-endian int32 arrays; the 64-bit
tag's top byte is the message type (tag routing, reference-style):

====== ========= ================ =======================================
type   direction tag              payload
====== ========= ================ =======================================
0xA1   S -> C    ASSIGN           [client_id, max_prompt_tokens] — sent
                                  on accept; identity for request tags +
                                  the server's request-size limit
0xA2   C -> S    REQUEST | cid    [nonce, max_new, n, prompt x n]
0xA3   S -> C    TOKENS | nonce   [nonce, status, count, tokens x count]
                                  status: 0 = streaming, 1 = done,
                                  2 = aborted (rejected or cancelled).
                                  A done frame carries a TRAILER after
                                  its tokens: the server's timing of the
                                  request, ``TIMING_WORDS`` int32 values
                                  (microsecond durations and a count,
                                  below).  A reader that stops at
                                  ``3 + count`` words never sees it
0xA4   C -> S    CANCEL | cid     [nonce] — abort that request; its slot
                                  frees on the next decode step
====== ========= ================ =======================================

Routing: the matcher reports a completed wildcard recv's SENDER TAG, not
its endpoint, so the request tag carries the server-assigned client_id
(low 32 bits) and the bridge maps it back to the accepted endpoint.  The
token stream needs no client id in its tag — it rides the requesting
client's own connection — so the low bits carry the client-chosen nonce,
letting one client run many concurrent generates.

The timing trailer (the stream's ``Server-Timing``; DESIGN.md §13) --
each duration in microseconds on the server's clock, clipped to int32:

================ =====================================================
word             from -> to
================ =====================================================
recv_submit      REQUEST receive completed -> ``SlotServer.submit`` (the
                 bridge's queue: a ``step()`` was running in the executor)
submit_admit0    submit -> the request's admission begins (the
                 scheduler's queue: no slot was free, or a chunk ran)
admit0_first     admission begins -> its first token is on the host
                 (with those of every request its step admitted)
first_post       first token -> its TOKENS send is posted (the bridge
                 sends after ``step()`` returns: the rest of the chunk)
recv_done_post   REQUEST receive completed -> this frame is posted
steps            ``step()`` calls the request lived through (a count)
================ =====================================================

``RemoteGenerateSession.generate`` reads the trailer only when the
received length says it is there (an older server sends none), stamps
its own ``t_send`` / ``t_first_rx`` / ``t_done_rx``, fills
``handle.timing`` and appends one client row to
``serving.request_log()`` of its process.

The per-chunk TOKENS messages for one request are FIFO on one
connection (the engine preserves per-connection send order), so the
client just accumulates until ``done``.  Send completion is local
(CLAUDE.md contract): mid-stream no flush is needed (a dead client just
fails its pending sends, logged and dropped), but serve() flushes once
before returning so a close right after cannot cancel the final chunks
out from under still-reading clients.
"""

from __future__ import annotations

import asyncio
import logging
import time
from collections import deque
from typing import Optional

import numpy as np

from .. import perf
from ..api import Client, Server
from .serving import SlotServer, log_request

logger = logging.getLogger("starway.serve_remote")

TAG_TYPE_SHIFT = 56
TAG_ASSIGN = 0xA1 << TAG_TYPE_SHIFT
TAG_REQUEST = 0xA2 << TAG_TYPE_SHIFT
TAG_TOKENS = 0xA3 << TAG_TYPE_SHIFT
TAG_CANCEL = 0xA4 << TAG_TYPE_SHIFT
TYPE_MASK = 0xFF << TAG_TYPE_SHIFT

STATUS_STREAMING, STATUS_DONE, STATUS_ABORTED = 0, 1, 2
FULL_MASK = (1 << 64) - 1
_ID_MASK = (1 << 32) - 1

#: The done frame's trailer, in wire order (the table in the module
#: docstring).  All but ``steps`` are microseconds.
TIMING_WORDS = ("recv_submit", "submit_admit0", "admit0_first",
                "first_post", "recv_done_post", "steps")
_now = time.perf_counter   # the clock of serving.request_log()
_I32_MAX = (1 << 31) - 1


def _timing_trailer(row: dict) -> list:
    """The trailer words of a finished request's server row; a stamp the
    row lacks makes its word 0."""
    def us(a: str, b: str) -> int:
        t0, t1 = row.get(a), row.get(b)
        if t0 is None or t1 is None:
            return 0
        return max(0, min(_I32_MAX, round((t1 - t0) * 1e6)))

    return [us("t_recv", "t_submit"), us("t_submit", "t_admit0"),
            us("t_admit0", "t_first"), us("t_first", "t_first_post"),
            us("t_recv", "t_done_post"), min(_I32_MAX, row.get("steps", 0))]


def _wire(words) -> np.ndarray:
    """int32 payload -> the uint8 byte view the transport sends."""
    return np.ascontiguousarray(np.asarray(words, np.int32)).view(np.uint8)


def _recv_buf(n_words: int) -> np.ndarray:
    """Receive target (the transport requires uint8); read back with
    ``buf.view(np.int32)``."""
    return np.empty(4 * n_words, np.uint8)


class RemoteSlotServer:
    """Serve a :class:`SlotServer` behind a transport :class:`Server`.

    >>> bridge = RemoteSlotServer(slot_server)
    >>> bridge.server.listen("127.0.0.1", port)
    >>> await bridge.serve()            # until bridge.stop() from a task

    Request ingestion is callback-chained on the engine thread (each
    completed wildcard recv immediately re-posts); the asyncio drive loop
    drains them into ``SlotServer.submit`` and advances decode chunks in
    an executor so the event loop keeps absorbing arrivals while the
    device computes.  Token emission rides ``SlotServer.on_tokens``.
    """

    def __init__(self, slot_server: SlotServer, server: Optional[Server] = None,
                 *, max_prompt_tokens: int = 8192):
        if slot_server.on_tokens is not None:
            raise ValueError("slot_server.on_tokens is already claimed")
        slot_server.on_tokens = self._on_tokens
        self.slot = slot_server
        self.server = server if server is not None else Server()
        self.max_prompt_tokens = int(max_prompt_tokens)
        self._eps: dict[int, object] = {}      # client_id -> endpoint
        self._next_cid = 1
        self._rid_route: dict[int, tuple] = {}  # rid -> (cid, nonce)
        self._rid_row: dict[int, dict] = {}     # rid -> its request_log() row
        self._emissions: list = []              # (rid, tokens, done)
        self._requests: deque = deque()   # (sender_tag, payload copy, t_recv)
        self._unassigned: deque = deque()       # cids awaiting their ASSIGN
        self._dead_cids: deque = deque()        # send-failed clients to drop
        self._stopping = False
        self._closed = False
        self._recv_posted = False
        self._cancels: deque = deque()          # (cid, nonce) to abort
        # Cancels that arrived BEFORE their request was submitted (both
        # can land in the queues during one multi-second decode step and
        # cancels drain first): consulted at submit time so the request
        # is rejected instead of the cancel being silently lost.
        # Insertion-ordered and bounded: a cancel for a nonce that never
        # shows up must not leak.
        self._pre_cancels: dict[tuple, bool] = {}
        self.server.set_accept_cb(self._on_accept)

    # ------------------------------------------------- engine-thread side
    def _on_accept(self, ep) -> None:
        cid = self._next_cid
        self._next_cid += 1
        self._eps[cid] = ep
        # The ASSIGN cannot be sent from here: on the in-process path the
        # accept callback fires inline DURING the client's connect, before
        # the client worker reaches RUNNING, and the send would die with
        # "peer closed".  The serve loop flushes it (the client's
        # register() recv waits however late it lands).
        self._unassigned.append(cid)

    def _post_typed_recv(self, tag: int, n_words: int, on_msg) -> None:
        """One self-re-posting wildcard recv chain per message type.
        ``on_msg(sender_tag, words)`` runs on the engine thread and must
        only enqueue.  Failures re-post too: a failed recv is consumed by
        the matcher, so without the re-post one bad message (e.g. a
        truncated oversized request) would permanently halt that type's
        intake."""
        buf = _recv_buf(n_words)

        def done(stag, length, buf=buf):
            try:
                on_msg(int(stag), buf.view(np.int32)[:length // 4].copy())
            except Exception:
                # A sink crash must not break the re-post chain.
                logger.exception("recv sink failed (tag type %x)",
                                 tag >> TAG_TYPE_SHIFT)
            if not self._closed:
                self._post_typed_recv(tag, n_words, on_msg)

        def fail(reason):
            # Expected at close ("cancel...") — not the CANCEL message
            # type, but the engine's op-cancellation reason string.
            if self._closed or "cancel" in reason:
                return
            logger.warning("recv (tag type %x) failed: %s",
                           tag >> TAG_TYPE_SHIFT, reason)
            try:
                self._post_typed_recv(tag, n_words, on_msg)
            except Exception:
                pass  # worker shutting down

        self.server.recv(buf, tag, TYPE_MASK, done, fail)

    def _post_request_recv(self) -> None:
        self._post_typed_recv(
            TAG_REQUEST, 3 + self.max_prompt_tokens,
            lambda stag, words: self._requests.append((stag, words, _now())))

    def _post_cancel_recv(self) -> None:
        def on_msg(stag, words):
            if len(words) >= 1:  # an empty CANCEL payload is just noise
                self._cancels.append((stag & _ID_MASK, int(words[0])))

        self._post_typed_recv(TAG_CANCEL, 1, on_msg)

    def _on_tokens(self, rid: int, tokens: list, done: bool) -> None:
        # Fires inside SlotServer.step() (executor thread); the drive
        # loop flushes after the step returns, preserving order.
        self._emissions.append((rid, tokens, done))

    # --------------------------------------------------- loop-thread side
    def _drop_dead_clients(self) -> None:
        while self._dead_cids:
            cid = self._dead_cids.popleft()
            if self._eps.pop(cid, None) is not None:
                logger.warning("dropping client %d (send failed)", cid)
            for rid, (rcid, _nonce) in list(self._rid_route.items()):
                if rcid == cid:
                    # Decoding for a peer that will never read the
                    # stream is wasted chip time: free the slot too.
                    self.slot.cancel(rid)
                    self._unroute(rid)
            for k in [k for k in self._pre_cancels if k[0] == cid]:
                self._pre_cancels.pop(k, None)  # free the stash budget

    def _drain_cancels(self) -> None:
        while self._cancels:
            cid, nonce = self._cancels.popleft()
            for rid, (rcid, rnonce) in list(self._rid_route.items()):
                if rcid == cid and rnonce == nonce:
                    self.slot.cancel(rid)
                    self._unroute(rid)
                    # Closure marker so a still-listening generate()
                    # terminates instead of awaiting forever.
                    self._send_chunk(cid, nonce, [], STATUS_ABORTED)
                    break
            else:
                if cid not in self._eps:
                    continue  # junk/stale cid: nothing to stash for
                # Not routed yet: the REQUEST may still be in flight
                # behind this cancel.  Stash so submit rejects it.
                # Budget is PER CLIENT so one cancel-spraying peer
                # cannot evict another client's genuine pre-cancel.
                self._pre_cancels[(cid, nonce)] = True
                mine = [k for k in self._pre_cancels if k[0] == cid]
                for k in mine[:max(0, len(mine) - 64)]:
                    self._pre_cancels.pop(k, None)

    def _flush_assigns(self) -> None:
        while self._unassigned:
            cid = self._unassigned.popleft()
            ep = self._eps.get(cid)
            if ep is None:
                continue
            # max_prompt_tokens rides along so the client can reject an
            # oversized prompt LOCALLY — sent to the server it would
            # truncate the wildcard recv before the nonce is parsed,
            # leaving nothing to reply to.
            self.server.send(
                ep, _wire([cid, self.max_prompt_tokens]), TAG_ASSIGN,
                lambda: None,
                lambda reason, cid=cid: logger.warning(
                    "assign to client %d failed: %s", cid, reason))

    def _drain_requests(self) -> int:
        n = 0
        while self._requests:
            stag, arr, t_recv = self._requests.popleft()
            cid = stag & _ID_MASK
            if cid not in self._eps:
                # No endpoint to reply over; the sender is gone or buggy.
                logger.warning("request from unknown client id %d", cid)
                continue
            if len(arr) < 3 or len(arr) != 3 + int(arr[2]):
                logger.warning("malformed request from client %d "
                               "(%d words)", cid, len(arr))
                if len(arr) >= 1:
                    # The nonce survived: reject fatally instead of
                    # leaving the client's generate() awaiting forever.
                    self._send_chunk(cid, int(arr[0]), [], STATUS_ABORTED)
                continue
            nonce, max_new, n_tok = int(arr[0]), int(arr[1]), int(arr[2])
            if self._pre_cancels.pop((cid, nonce), False):
                # Cancelled before it was ever submitted (the CANCEL
                # overtook the REQUEST in the drain order).
                self._send_chunk(cid, nonce, [], STATUS_ABORTED)
                continue
            try:
                rid = self.slot.submit(arr[3:3 + n_tok], max_new)
            except (ValueError, KeyError) as e:
                # Reject without killing the serve loop: an empty, fatal
                # "done" stream tells the client this request is over.
                logger.warning("rejected request from client %d: %s",
                               cid, e)
                self._send_chunk(cid, nonce, [], STATUS_ABORTED)
                continue
            self._rid_route[rid] = (cid, nonce)
            row = self.slot.open_row(rid)
            if row is not None:
                row.update(route=f"{cid}:{nonce}", t_recv=t_recv,
                           t_first_post=None, t_done_post=None)
                self._rid_row[rid] = row
            n += 1
        return n

    def _unroute(self, rid: int) -> None:
        del self._rid_route[rid]
        self._rid_row.pop(rid, None)

    def _send_chunk(self, cid: int, nonce: int, tokens: list,
                    status, trailer=()) -> None:
        ep = self._eps.get(cid)
        if ep is None:
            return
        def failed(reason, cid=cid):
            # Engine-thread callback: only enqueue; the serve loop drops
            # the endpoint and its routes (no cross-thread dict mutation).
            logger.warning("token chunk to client %d failed: %s",
                           cid, reason)
            self._dead_cids.append(cid)

        self.server.send(
            ep, _wire([nonce, int(status), len(tokens), *tokens, *trailer]),
            TAG_TOKENS | nonce, lambda: None, failed)

    def _flush_emissions(self) -> None:
        emissions, self._emissions = self._emissions, []
        if not emissions:
            return
        with perf.stage_span(self.slot.stage_scope, "bridge.emit"):
            for rid, tokens, done in emissions:
                route = self._rid_route.get(rid)
                if route is None:
                    continue  # cancelled mid-step; stream already closed
                cid, nonce = route
                row = self._rid_row.get(rid, {})
                if tokens and row.get("t_first_post") is None:
                    row["t_first_post"] = _now()
                if not done:
                    self._send_chunk(cid, nonce, tokens, STATUS_STREAMING)
                    continue
                row["t_done_post"] = _now()
                self._send_chunk(cid, nonce, tokens, STATUS_DONE,
                                 _timing_trailer(row))
                self._unroute(rid)

    async def serve(self, *, idle_sleep: float = 0.002) -> None:
        """Drive until :meth:`stop` AND all in-flight work has drained.
        The server must be listening (posting a recv needs a RUNNING
        worker), so call ``bridge.server.listen(...)`` first."""
        if not self._recv_posted:
            self._post_request_recv()
            self._post_cancel_recv()
            self._recv_posted = True
        loop = asyncio.get_running_loop()
        while not (self._stopping and not self.slot.busy
                   and not self._requests):
            if (self._requests or self._cancels or self._unassigned
                    or self._dead_cids):
                with perf.stage_span(self.slot.stage_scope, "bridge.drain"):
                    self._drop_dead_clients()
                    self._drain_cancels()
                    self._flush_assigns()
                    self._drain_requests()
            if self.slot.busy:
                await loop.run_in_executor(None, self.slot.step)
                self._flush_emissions()
            else:
                await asyncio.sleep(idle_sleep)
        self._flush_emissions()
        # Send completion is LOCAL (CLAUDE.md); a close right after serve()
        # could cancel the final TOKENS chunks still in flight and hang
        # mid-stream clients — the flush is the delivery barrier.
        try:
            await self.server.aflush()
        except Exception as e:  # worker already closing
            logger.warning("final flush failed: %s", e)

    def stop(self) -> None:
        """Finish in-flight requests, then let serve() return."""
        self._stopping = True

    async def aclose(self) -> None:
        self._closed = True
        self.slot.close()   # its serve.* / bridge.* spans outlive it
        await self.server.aclose()


class RemoteGenerateSession:
    """Client-side counterpart: submit prompts, await token streams.

    >>> session = await RemoteGenerateSession.aconnect(addr, port)
    >>> tokens = await session.generate(prompt, max_new_tokens=32)

    ``generate`` calls may run concurrently on one session (distinct
    nonces route the streams); tokens accumulate per decode chunk, so
    wrapping the recv loop yields true streaming if a caller wants it.
    """

    class Handle:
        """Out-param for generate(): carries the request nonce so the
        caller can cancel() a stream it no longer wants, and, once the
        stream has ended, ``timing``: the request's client row of
        ``serving.request_log()`` (``t_send`` / ``t_first_rx`` /
        ``t_done_rx`` on this process's ``time.perf_counter``, and
        ``server_us``, the done frame's trailer by ``TIMING_WORDS`` name,
        None from a server that sent none)."""

        nonce: Optional[int] = None
        timing: Optional[dict] = None

    def __init__(self, client: Client):
        self.client = client
        self.client_id: Optional[int] = None
        self.server_max_prompt: Optional[int] = None
        self._nonce = 0

    @classmethod
    async def aconnect(cls, addr: str, port: int) -> "RemoteGenerateSession":
        client = Client()
        await client.aconnect(addr, port)
        session = cls(client)
        await session.register()
        return session

    async def register(self) -> int:
        """Receive the server-assigned client id (sent on accept)."""
        buf = _recv_buf(2)
        await self.client.arecv(buf, TAG_ASSIGN, FULL_MASK)
        words = buf.view(np.int32)
        self.client_id = int(words[0])
        self.server_max_prompt = int(words[1])
        return self.client_id

    async def generate(self, prompt, max_new_tokens: int,
                       *, max_chunk_tokens: int = 4096,
                       on_tokens=None, handle: "Optional[Handle]" = None) -> np.ndarray:
        """Round-trip one request; returns the generated tokens.

        ``on_tokens(list)``: optional per-chunk streaming callback.
        ``handle``: a :class:`Handle` that receives the request nonce
        before the request is sent — pass it to :meth:`cancel` from
        another task to abort the stream server-side."""
        if self.client_id is None:
            raise RuntimeError("call register() (or aconnect()) first")
        prompt = np.asarray(prompt, np.int32).reshape(-1)
        if (self.server_max_prompt is not None
                and len(prompt) > self.server_max_prompt):
            # Server-side this would truncate the request recv before the
            # nonce is parsed — unanswerable; reject here instead.
            raise ValueError(
                f"prompt ({len(prompt)} tokens) exceeds the server's "
                f"request limit ({self.server_max_prompt})")
        nonce = self._nonce
        self._nonce += 1
        if handle is not None:
            handle.nonce = nonce
        req = _wire(np.concatenate([
            np.asarray([nonce, int(max_new_tokens), len(prompt)], np.int32),
            prompt]))
        row = {"side": "client", "route": f"{self.client_id}:{nonce}",
               "n_prompt": len(prompt), "n_out": 0, "t_send": _now(),
               "t_first_rx": None, "t_done_rx": None, "status": "running",
               "server_us": None}
        await self.client.asend(req, TAG_REQUEST | self.client_id)
        out: list = []
        try:
            while True:
                buf = _recv_buf(3 + max_chunk_tokens + len(TIMING_WORDS))
                _stag, length = await self.client.arecv(
                    buf, TAG_TOKENS | nonce, FULL_MASK)
                words = buf.view(np.int32)
                count, status = int(words[2]), int(words[1])
                chunk = [int(t) for t in words[3:3 + count]]
                if chunk and row["t_first_rx"] is None:
                    row["t_first_rx"] = _now()
                out.extend(chunk)
                if chunk and on_tokens is not None:
                    on_tokens(chunk)
                if status == STATUS_ABORTED:
                    row["status"] = "aborted"
                    raise ValueError(
                        "request rejected or cancelled by the server "
                        f"(after {len(out)} tokens); rejections mean "
                        "prompt/max_new exceeded the server's max_len")
                if status == STATUS_DONE:
                    row["status"] = "done"
                    end = 3 + count + len(TIMING_WORDS)
                    if length // 4 >= end:   # an older server sends none
                        row["server_us"] = dict(zip(
                            TIMING_WORDS, map(int, words[3 + count:end])))
                    return np.asarray(out, np.int32)
        finally:
            if row["status"] == "running":   # the transport failed, or
                row["status"] = "failed"     # the caller gave up
            row.update(t_done_rx=_now(), n_out=len(out))
            log_request(row)
            if handle is not None:
                handle.timing = row

    async def cancel(self, handle: "Handle") -> None:
        """Abort the stream identified by ``handle`` server-side: its
        slot frees on the next decode step and the stream terminates
        with an aborted marker (the awaiting generate() raises)."""
        if handle.nonce is None:
            raise ValueError("handle was never passed to generate()")
        await self.client.asend(_wire([handle.nonce]),
                                TAG_CANCEL | self.client_id)

    async def aclose(self) -> None:
        await self.client.aclose()
