"""PagedBatcher: admission on pages-at-current-lengths, not max_seq slots.

The slot batcher admits whenever a slot is free, because a slot IS the
worst case: ``max_seq`` rows, reserved up front. With paged KV the
resource is the page pool, and the question changes from "is a slot
free" to "are there enough pages for THIS prompt at ITS length, plus
headroom for the sequences already running". This subclass keeps the
whole tick loop (the compiled-step dispatch, token delivery, finish and
deadline logic are inherited unchanged) and replaces the memory policy:

- **admit** maps exactly the pages the prompt needs now. If the pool
  (or slot table) can't take it, the request parks in a pending deque
  — admission is no longer slot-gated, so ``free_slots`` reports 0
  while anything is pending, and ``active`` counts pending so the
  worker keeps ticking (each tick frees pages, which is what pending
  requests are waiting for). A request that can't fit even with the
  pool EMPTY of other users fails outright instead of deadlocking.

- **per-tick capacity**: before each tick, one page-table pass maps the
  write position of the step about to be dispatched (``+k+1`` under
  speculation) for every active slot. The plain tick runs one step ahead
  (``ContinuousBatcher.tick``), so with a step unfetched that position is
  ``req.seq_len``, not ``req.seq_len - 1``. When the pool runs dry
  mid-stream, unpinned prefix entries are dropped first, then the step in
  flight is settled (a row run ahead takes no page from anyone: its tokens
  may end a request and free what is needed), then the YOUNGEST request is
  evicted (least progress lost) — pages reclaimed mid-stream, the
  slot-path analogue being deadline eviction. A request whose last row is
  in flight (it ends by length) gets no page for the row after it: that row
  is dropped, and writes the trash page or a page the slot holds.

- **prefix sharing is zero-copy**: a :class:`PagedPrefixStore` hit
  adopts full shared pages by table splice (``bytes_shared``), and when
  the entry extends past the last full page boundary the one partial
  page is COW-split (``adopt_copied_page``: the only bytes a hit ever
  copies, counted in ``bytes_copied`` — page-aligned hits copy ZERO).
  On a miss, the freshly prefilled sequence's own page-aligned head is
  claimed by the store by refcount, again copying nothing.

- **page groups** (``paged/pool.py``): where a cache has a group of window
  layers beside the group that keeps every page, the admission math above is
  the FIRST group's (the whole prompt, so that no prompt stalls half way)
  and every further group is asked for its own need (a window group's is
  bounded: ``groups_short``). A chunked admission maps a window group's
  pages as the chunks advance (``ensure_pages`` before a chunk) and gives
  back what a chunk left behind the window after it (``release_behind``);
  the decode capacity pass does the same a tick. With one group both do
  what ``ensure_pages`` always did.

- **chunked prefill** (``LLMEngineConfig.prefill_chunk``, a decoder that
  offers ``chunk_prefill``): an admission maps the whole prompt's pages (one
  dispatch) and parks the request in ``_prefilling``; every tick then runs
  at most ONE chunk of the oldest such request before the decode step, so
  the slots that decode wait a chunk and not a prompt. The slot joins the
  decode batch after its last chunk, which samples its first token. While
  it is prefilled the slot's ``finished`` flag is set, which keeps the
  decode step's writes off it. One compiled chunk program serves every
  offset. Span ``serving.llm/prefill_chunk``, counters ``prefill_chunks``
  and ``worker.prefill_chunk_s``, histogram ``prefill_chunk_ms`` (from a
  chunk's dispatch to the end of the fetch that follows it: the tick's,
  the first token's, or the chunk's own where nothing decodes). An engine
  with chunked prefill keeps the tick SERIAL (``_room_ahead``): the chunk
  between two ticks is the contract, its histogram is defined against a
  loop that fetches what it dispatched, and the one cell that measures it
  cannot tell a faster engine from a slower one (``PERF.md`` section 7).

Gauges: ``<stat_prefix>.pages_free`` and ``.pages_cow_splits`` publish
the pool state at every admission and tick (the /metricsz view of the
admission math in docs/serving.md).
"""
from __future__ import annotations

import collections
from typing import Dict, Optional

import numpy as np

import jax.numpy as jnp

from ...request import RequestTooLarge
from ..decode import pack_sampling
from ..scheduler import ContinuousBatcher, GenerationRequest
from .decode import GPTPagedDecoder, plain_walk_recurrence
from .pool import PagedKVCache, PagesExhausted, pages_for_tokens
from .prefix import PagedPrefixStore


class PagedBatcher(ContinuousBatcher):
    """Page-pool admission + COW prefix sharing over the inherited tick
    loop. Single-threaded like the base: only the engine worker calls
    in."""

    def __init__(self, decoder: GPTPagedDecoder, config, registry,
                 clock=None, prefix_store=None, spec_decoder=None):
        if getattr(decoder, "kv_layout", None) != "paged":
            raise TypeError("PagedBatcher needs a paged decoder "
                            "(kv_layout='paged')")
        if prefix_store is not None:
            raise NotImplementedError(
                "paged engines share prefix pages inside their own arena "
                "— an external (host) PrefixStore cannot be attached; "
                "set prefix_cache=True and let the batcher build its "
                "PagedPrefixStore")
        kw = {} if clock is None else {"clock": clock}
        super().__init__(decoder, config, registry, prefix_store=None,
                         spec_decoder=spec_decoder, **kw)
        self.kv: PagedKVCache
        self._pending = collections.deque()
        #: slot -> [request, tokens prefilled, admission time]: requests
        #: whose prompts are still entering a chunk at a time, oldest first
        self._prefilling: Dict[int, list] = {}
        self._chunk_dispatched: Optional[float] = None
        if config.prefix_cache:
            self.prefix_store = PagedPrefixStore(
                self.kv, registry=registry,
                stat_prefix=f"{config.stat_prefix}.prefix")
        #: a group of the cache gives pages back behind a window
        self._windowed = self.kv.has_window
        self._stat_set("pages_free", self.kv.pool.free_pages)
        self._stat_set("pages_cow_splits", 0)
        #: the recurrence of paged_attn's plain walk, static an engine
        self.attn_recurrence = plain_walk_recurrence(decoder, self.kv)
        if self.attn_recurrence is not None:
            self._stat_set("paged_attn.recurrence_mxu",
                           int(self.attn_recurrence == "mxu"))
        decoder.publish_gauges(self.kv, self._stat_set)  # a family's state

    # -- introspection -------------------------------------------------------
    @property
    def active(self) -> int:
        # pending requests count: the worker must keep ticking (ticks
        # free pages) and drain must not exit while any wait for pages
        return len(self._reqs) + len(self._pending) + len(self._prefilling)

    @property
    def free_slots(self) -> int:
        # stop pulling from the queue while requests already wait for
        # pages — queue order is admission order
        if self._pending:
            return 0
        return self.kv.free_slots

    def _publish_pages(self):
        self._stat_set("pages_free", self.kv.pool.free_pages)
        self._stat_set("pages_cow_splits", self.kv.cow_splits)

    # -- admission -----------------------------------------------------------
    def admit(self, req: GenerationRequest):
        self._drain_pending()
        if self._pending or not self._try_admit(req):
            self._park_or_fail(req)
        self._publish_pages()

    def _drain_pending(self):
        while self._pending:
            head = self._pending[0]
            if head.expired:
                self._pending.popleft()
                head.fail_expired()
                continue
            if not self._try_admit(head):
                if not self._reqs and not self._prefilling:
                    # nothing running -> no pages will ever free up;
                    # _try_admit already drained the prefix store, so
                    # this request simply does not fit the pool
                    self._pending.popleft()
                    self._fail_oversize(head)
                    continue
                break
            self._pending.popleft()

    def _park_or_fail(self, req: GenerationRequest):
        if not self._reqs and not self._prefilling:
            self._fail_oversize(req)
            return
        self._pending.append(req)
        self._stat_set("pages_pending_requests", len(self._pending))

    def _fail_oversize(self, req: GenerationRequest):
        need = pages_for_tokens(req.prompt_len, self.kv.page_size)
        req.fail(RequestTooLarge(
            f"prompt of {req.prompt_len} tokens needs {need} pages but "
            f"the pool holds {self.kv.pool.num_pages} "
            f"({self.kv.pool.free_pages} free, none reclaimable)"))
        self._stat_add("rejected_pool_exhausted", 1)

    def _try_admit(self, req: GenerationRequest) -> bool:
        """Admit ``req`` if a slot AND enough pages are available at its
        actual length; True on success. No partial state on False: the
        page math runs before any allocation."""
        if self.kv.free_slots < 1:
            return False
        page = self.kv.page_size
        sig = self.decoder.prefix_sig(self.kv)
        entry, reuse_n = None, 0
        if self.prefix_store is not None:
            entry, reuse_n = self.prefix_store.lookup(
                req.prompt, req.prompt_len - 1, sig)
            # the PADDED tail bucket must fit behind the reused head
            # (same shrink rule as the slot path)
            while reuse_n > 0 and reuse_n + self.config.bucket_for(
                    req.prompt_len - reuse_n) > self.config.max_seq:
                reuse_n -= page
            if entry is not None and reuse_n <= 0:
                self.prefix_store.unpin(entry)
                entry, reuse_n = None, 0
        # COW extension: when the entry's pages run past the last FULL
        # page boundary we may reuse (store hits are page-aligned, the
        # reusable-token cap prompt_len-1 usually is not), the one
        # partial page is copied and the divergent tail overwrites the
        # private copy — rows [reuse_n, ext_n) come along for free.
        cow_src = None
        ext_n = min(entry.n_tokens, req.prompt_len - 1) if entry else 0
        if (entry is not None and reuse_n < ext_n
                and ext_n - reuse_n < page
                and ext_n + self.config.bucket_for(
                    req.prompt_len - ext_n) <= self.config.max_seq
                and np.array_equal(entry.tokens[reuse_n:ext_n],
                                   req.prompt[reuse_n:ext_n])):
            cow_src = entry.page_ids[reuse_n // page]
        else:
            ext_n = reuse_n
        shared_pages = reuse_n // page
        total_pages = pages_for_tokens(req.prompt_len, page)
        need_alloc = total_pages - shared_pages     # COW page included
        # headroom: one lookahead page per running sequence, so an
        # admission cannot immediately force a mid-stream eviction at
        # the next tick's capacity pass
        reserve = len(self._reqs) + len(self._prefilling)
        shortfall = need_alloc + reserve - self.kv.pool.free_pages
        if shortfall > 0 and self.prefix_store is not None:
            shortfall -= self.prefix_store.evict_unpinned(shortfall)
        if shortfall > 0 or self.kv.groups_short(
                req.prompt_len, self._span(req.prompt_len), reserve):
            if entry is not None:
                self.prefix_store.unpin(entry)
            return False
        self._admit_paged(req, entry, reuse_n, ext_n, cow_src)
        return True

    def _span(self, prompt_len: int) -> int:
        """Rows one prefill program writes at most: a chunk, or the
        prompt."""
        return min(self.config.prefill_chunk or prompt_len, prompt_len)

    def _admit_paged(self, req: GenerationRequest, entry, reuse_n: int,
                     ext_n: int, cow_src: Optional[int]):
        """The committed admission: slot + page mapping + prefill +
        first-token delivery (the paged lane's ``ContinuousBatcher.admit``)."""
        t0 = self._clock()
        # queue and parking in _pending since it was enqueued
        self._stat_add("queue_wait_s", t0 - req.t_enqueue)
        page = self.kv.page_size
        with self.phase("admit_pages"):
            slot = self.kv.alloc()
            req.weights_version = self.weights_version
            self._reqs[slot] = req
            self._slot_samp[slot] = req.sampling
            self._samp_vecs = pack_sampling(self._slot_samp)
            samp1 = pack_sampling([req.sampling])
            slot_arr = jnp.asarray([slot], jnp.int32)
            if reuse_n > 0:
                for pid in entry.page_ids[:reuse_n // page]:
                    self.kv.adopt_shared_page(slot, pid)
                self.prefix_store.note_shared(
                    (reuse_n // page) * self.kv.page_nbytes())
            if cow_src is not None:
                self.kv.adopt_copied_page(slot, cow_src)
                self.prefix_store.note_copied(self.kv.page_nbytes())
                self._stat_add("prefix.cow_splits", 1)
            # a chunked admission reserves the prompt where every page is
            # kept; a window group's pages come a chunk at a time
            self.kv.ensure_pages(
                slot, req.prompt_len,
                windowed=self.config.prefill_chunk is None)
            if self.config.prefill_chunk is not None:
                # the prompt enters a chunk a tick; until its last chunk the
                # decode step must leave the slot alone
                del self._reqs[slot]
                self._finished = self._finished.at[slot].set(True)
                self._prefilling[slot] = [req, 0, t0]
                return
        with self.phase("prefill", {"req": req.req_id}):
            if entry is not None:
                req._prefix_entry = entry   # stays pinned until release
                tail = req.prompt[ext_n:]
                lt = self.config.bucket_for(int(tail.size))
                padded = np.zeros((1, lt), np.int32)
                padded[0, :tail.size] = tail
                nxt, self._finished = self.decoder.tail_prefill(
                    self.kv, self._params, jnp.asarray(padded),
                    jnp.asarray([int(tail.size)], jnp.int32),
                    jnp.asarray([ext_n], jnp.int32), slot_arr,
                    self._finished, samp1, self._next_key())
                self._stat_add("prefix.reused_tokens", ext_n)
            else:
                lp = self.config.bucket_for(req.prompt_len)
                padded = np.zeros((1, lp), np.int32)
                padded[0, :req.prompt_len] = req.prompt
                nxt, self._finished = self.decoder.prefill(
                    self.kv, self._params, jnp.asarray(padded),
                    jnp.asarray([req.prompt_len], jnp.int32), slot_arr,
                    self._finished, samp1, self._next_key())
                if self.prefix_store is not None:
                    # miss: claim the page-aligned head BY REFERENCE — the
                    # store retains the sequence's own pages, nothing moves
                    n = (req.prompt_len // page) * page
                    if n >= page:
                        ins = self.prefix_store.insert(
                            req.prompt[:n],
                            self.kv.slot_page_ids(slot)[:n // page],
                            self.decoder.prefix_sig(self.kv))
                        if ins is not None:
                            req._prefix_entry = ins
            if self.spec is not None:
                self._draft_prefill(req, slot_arr, samp1)
            self._last = self._last.at[jnp.asarray([slot])].set(nxt)
        self._deliver_first_token(req, slot, nxt, t0)

    # -- per-tick capacity ---------------------------------------------------
    def tick(self) -> int:
        # a chunk first: a prompt whose last chunk this is decodes in this
        # very tick, so the capacity pass below must see it
        if self._prefilling:
            self._advance_prefill()
        with self.phase("tick_capacity"):
            self._drain_pending()
            self._stat_set("pages_pending_requests", len(self._pending))
            if self._reqs:
                self._ensure_decode_capacity()
        n = super().tick()     # 0 when the capacity pass evicted the last
        self._publish_pages()
        return n

    def _advance_prefill(self):
        """One chunk of the oldest request still being prefilled; after its
        last chunk the request gets its first token and joins the decode
        batch."""
        slot = next(iter(self._prefilling))
        req, start, t0 = self._prefilling[slot]
        if req.expired:
            del self._prefilling[slot]
            self.kv.free(slot)
            req.fail_expired()
            self._stat_add("evicted_midstream", 1)
            return
        chunk = self.config.prefill_chunk
        n = min(chunk, req.prompt_len - start)
        last = start + n == req.prompt_len
        try:    # a window group's pages of this chunk (elsewhere: mapped)
            self.kv.ensure_pages(slot, start + n)
        except PagesExhausted:
            # the requests that decode hold the pages and end; the others
            # that wait here hold none of a window group's
            if self._reqs:
                self._stat_add("prefill_chunk_stalls", 1)
                return
            del self._prefilling[slot]
            self.kv.free(slot)
            self._fail_oversize(req)
            return
        with self.phase("prefill_chunk", {"req": req.req_id, "start": start,
                                          "n": n}):
            padded = np.zeros((1, chunk), np.int32)
            padded[0, :n] = req.prompt[start:start + n]
            self._chunk_dispatched = self._clock()
            nxt, self._finished = self.decoder.chunk_prefill(
                self.kv, self._params, jnp.asarray(padded), start, n, last,
                slot, self._finished, pack_sampling([req.sampling]),
                self._next_key())
            self._stat_add("prefill_chunks", 1)
            self.decoder.note_chunk(start, n, self.kv.pages_per_seq,
                                    self._stat_add)
            # the next query is at row start + n: what lies behind its window
            self.kv.release_behind(slot, start + n)
            if not last:
                self._prefilling[slot][1] = start + n
                if not self._reqs:      # no tick's fetch follows: its own
                    nxt.block_until_ready()  # noqa: PTA002 -- nothing decodes, so the worker has only this chunk to wait for; the wait bounds the chunk's histogram sample
                    self._chunk_fetched()
                return
            del self._prefilling[slot]
            self._reqs[slot] = req
            self._last = self._last.at[jnp.asarray([slot])].set(nxt)
        self._deliver_first_token(req, slot, nxt, t0)

    def _chunk_fetched(self):
        """A fetch has ended: the chunk dispatched before it, if any, is
        done."""
        if self._chunk_dispatched is not None:
            self._stat_observe(
                "prefill_chunk_ms",
                (self._clock() - self._chunk_dispatched) * 1000.0)
            self._chunk_dispatched = None

    def _deliver_first_token(self, req, slot, nxt, t0):
        super()._deliver_first_token(req, slot, nxt, t0)
        self._chunk_fetched()

    def _note_step(self, ahead):
        # what paged_attn's walk covers in this step, from the lengths held
        # here: the pages up to each request's write position, of the
        # table rows its slots have
        page = self.kv.page_size
        # each request's length as this step sees it, its new row included
        lens = [req.seq_len + self._behind(ahead, slot, req)
                for slot, req in self._reqs.items()]
        self.decoder.note_lengths(lens, self._stat_add)  # a family's walks
        self._stat_add("paged_attn.pages_live",
                       sum((n - 1) // page + 1 for n in lens))
        self._stat_add("paged_attn.pages_table",
                       len(lens) * self.kv.pages_per_seq)
        if self._windowed:      # what the window groups hold, sampled
            held, unbounded = self.kv.window_pages()
            self._stat_add("kv_pages.window_held", held)
            self._stat_add("kv_pages.window_unbounded", unbounded)
            self._stat_set("kv_pages.window_released",
                           self.kv.window_released)

    def _finish_step(self, step) -> int:
        n = super()._finish_step(step)
        self._chunk_fetched()
        return n

    def _room_ahead(self) -> bool:
        """Nothing was in flight and the first step is dispatched: map the
        row after it for every request, if the pool has those pages free
        or held by unpinned prefix entries alone. Nobody is evicted for a
        row run ahead: short of pages the tick stays serial (and evicts as
        it always did). So does the tick of an engine that prefills in
        chunks (the module's docstring says why)."""
        if self.config.prefill_chunk is not None:
            return False
        need = {}
        for slot, req in self._reqs.items():
            if self._ends_by_length(req):
                continue
            tok = min(req.seq_len + 1, self.config.max_seq)
            if pages_for_tokens(tok, self.kv.page_size) \
                    > self.kv.mapped_pages(slot):
                need[slot] = tok
        short = len(need) - self.kv.pool.free_pages     # a page a request
        if short > 0 and self.prefix_store is not None:
            self.prefix_store.evict_unpinned(short)
        if len(need) > self.kv.pool.free_pages:
            return False
        try:
            for slot, tok in need.items():
                self.kv.ensure_pages(slot, tok)
        except PagesExhausted:      # a further group's pool: stay serial
            return False
        return True

    def _ensure_decode_capacity(self):
        """Map, for every active slot, the write position of the step about
        to be dispatched — ``+1`` token plain, ``+k+1`` speculative (the
        verify step lands k+1 candidate rows); a request with a row in the
        step in flight is one token further than the host has counted.
        Pool dry: drop unpinned prefix entries, then settle the step in
        flight and start over (nobody loses a page to a row run ahead),
        then evict the youngest request; a lone un-mappable sequence
        finishes with reason 'length' (nothing left to reclaim)."""
        horizon = (self.spec.k + 1) if self.spec is not None else 1
        ahead = self._inflight
        for slot in sorted(self._reqs):
            req = self._reqs.get(slot)
            if req is None:
                continue
            behind = self._behind(ahead, slot, req)
            if behind and self._ends_by_length(req):
                continue    # the row after its last is dropped: no page
            pos = req.seq_len - 1 + behind
            need_tok = min(pos + horizon, self.config.max_seq)
            # a window group gives back what the step's query, at row pos,
            # no longer reads, before it is asked for the step's page
            if self._windowed:
                self.kv.release_behind(slot, pos)
            while True:
                try:
                    self.kv.ensure_pages(slot, need_tok)
                    break
                except PagesExhausted:
                    short = (pages_for_tokens(need_tok, self.kv.page_size)
                             - self.kv.mapped_pages(slot)
                             - self.kv.pool.free_pages)
                    if self.prefix_store is not None and \
                            self.prefix_store.evict_unpinned(
                                max(1, short)) > 0:
                        continue
                    if self._inflight is not None:
                        # what is in flight may end a request and free the
                        # pages; whoever is evicted has had its tokens
                        self.settle()
                        return self._ensure_decode_capacity()
                    victim = self._youngest_other(slot)
                    if victim is None:
                        # this is the only sequence and the pool cannot
                        # grow it — finish at current length rather
                        # than deadlock
                        self._stat_add("pages_truncations", 1)
                        self._release(slot, req, "length")
                        break
                    self._evict_for_pages(victim)

    def _youngest_other(self, slot: int) -> Optional[int]:
        others = [(s, r) for s, r in self._reqs.items() if s != slot]
        others += [(s, st[0]) for s, st in self._prefilling.items()]
        if not others:
            return None
        return max(others, key=lambda sr: sr[1].t_enqueue)[0]

    def _evict_for_pages(self, slot: int):
        req = (self._prefilling.pop(slot)[0] if slot in self._prefilling
               else self._reqs.pop(slot))
        self.kv.free(slot)
        self._unpin_prefix(req)
        req.fail(PagesExhausted(
            f"request {req.req_id} evicted after {len(req.tokens)} "
            f"tokens: page pool exhausted and it was the youngest "
            f"sequence"))
        self._stat_add("pages_evicted_midstream", 1)
        self._stat_add("evicted_midstream", 1)

    # -- live sequence migration (docs/fault_tolerance.md) -------------------
    @property
    def supports_export(self) -> bool:
        """The paged substrate can ship sequences as page payloads, for a
        decoder family whose whole per-sequence state is pages."""
        return self.decoder.supports_export

    def export_all(self):
        """Snapshot-and-detach every live sequence into host-side
        manifests (worker thread, the tick in flight settled first). A
        request still
        mid-replay from an earlier resume ships payload-free — its
        cache is not yet a faithful transcript, so the target replays
        it instead of splicing. Pending (page-starved) requests ship
        cold. On return the batcher holds none of them."""
        from ...fleet.migrate import SequenceManifest
        self.settle()       # the cache must hold what the clients have
        sig = self.decoder.prefix_sig(self.kv)
        out = []
        for slot in sorted(self._reqs):
            req = self._reqs[slot]
            if req._replay_pos is None:
                n_cached = req.seq_len - 1   # last token not yet in cache
                pids, k_pages, v_pages = self.decoder.export_sequence(
                    self.kv, slot, n_cached)
                man = SequenceManifest(
                    req, req.prompt, req.tokens, req.sampling,
                    weights_version=req.weights_version,
                    n_cached_tokens=n_cached,
                    page_size=self.kv.page_size, sig=sig,
                    k_pages=k_pages, v_pages=v_pages)
            else:
                man = SequenceManifest.for_queued(req)
            out.append(man)
            del self._reqs[slot]
            self.kv.free(slot)
            self._unpin_prefix(req)
        while self._pending:
            out.append(SequenceManifest.for_queued(
                self._pending.popleft()))
        self._stat_set("pages_pending_requests", 0)
        self._publish_pages()
        return out

    def import_manifest(self, man) -> bool:
        """Splice a migrated sequence into a free slot and arm it for
        the next tick (worker thread, a closure of the control plane: the
        tick in flight is settled before it runs). Page-aligned
        prompt-prefix pages this engine already holds are adopted
        zero-copy through the prefix store's chain hash; the rest are
        allocated and filled from the shipped payload. Returns False
        WITHOUT side effects when geometry differs or the slot table /
        page pool cannot take it — the migrator falls back to replay."""
        if man.sig != self.decoder.prefix_sig(self.kv) \
                or man.page_size != self.kv.page_size:
            return False
        n_cached = man.n_cached_tokens
        if not (0 < n_cached < self.config.max_seq) or not man.tokens:
            return False
        if self.kv.free_slots < 1:
            return False
        req = man.req
        page = self.kv.page_size
        total = pages_for_tokens(n_cached, page)
        entry, reuse_n = None, 0
        if self.prefix_store is not None:
            entry, reuse_n = self.prefix_store.lookup(
                req.prompt, min(req.prompt_len, n_cached), man.sig)
            reuse_n = (reuse_n // page) * page   # whole pages only
            if entry is not None and reuse_n <= 0:
                self.prefix_store.unpin(entry)
                entry, reuse_n = None, 0
        shared = reuse_n // page
        # same admission math as _try_admit: tail pages + one lookahead
        # page per running sequence
        shortfall = (total - shared) + len(self._reqs) \
            - self.kv.pool.free_pages
        if shortfall > 0 and self.prefix_store is not None:
            shortfall -= self.prefix_store.evict_unpinned(shortfall)
        if shortfall > 0:
            if entry is not None:
                self.prefix_store.unpin(entry)
            return False
        slot = self.kv.alloc()
        try:
            if shared:
                for pid in entry.page_ids[:shared]:
                    self.kv.adopt_shared_page(slot, pid)
                self.prefix_store.note_shared(
                    shared * self.kv.page_nbytes())
            self.decoder.import_sequence(
                self.kv, slot, n_cached, man.k_pages, man.v_pages,
                shared_pages=shared)
        except Exception:
            self.kv.free(slot)
            if entry is not None:
                self.prefix_store.unpin(entry)
            raise
        req._prefix_entry = entry
        req._t_last = None
        self._reqs[slot] = req
        self._slot_samp[slot] = req.sampling
        self._samp_vecs = pack_sampling(self._slot_samp)
        # arm the compiled step's per-slot state: the next tick feeds
        # the last emitted token and writes its KV row at n_cached
        self._finished = self._finished.at[slot].set(False)
        self._last = self._last.at[slot].set(int(req.tokens[-1]))
        self._stat_add("migrated_pages_shared", shared)
        self._stat_add("migrated_pages_copied", total - shared)
        self._publish_pages()
        return True

    # -- exits ---------------------------------------------------------------
    def _drop_prefilling(self):
        """Detach the requests still being prefilled: slots, pages and
        states go back, the requests are the caller's."""
        out = [st[0] for st in self._prefilling.values()]
        for slot in list(self._prefilling):
            del self._prefilling[slot]
            self.kv.free(slot)
        return out

    def evacuate(self):
        out = super().evacuate()
        out.extend(self._drop_prefilling())   # nothing streamed yet
        while self._pending:
            out.append(self._pending.popleft())
        self._stat_set("pages_pending_requests", 0)
        self._publish_pages()
        return out

    def abort_all(self, exc_factory):
        super().abort_all(exc_factory)
        for req in self._drop_prefilling():
            req.fail(exc_factory(req))
        while self._pending:
            req = self._pending.popleft()
            req.fail(exc_factory(req))
        self._publish_pages()

    # -- mfu -----------------------------------------------------------------
    def _measure_decode_flops(self):
        # the XLA cost probe compiles the SLOT decode program, which the
        # paged engine never runs; skip rather than mis-measure
        self._decode_flops = 0.0
