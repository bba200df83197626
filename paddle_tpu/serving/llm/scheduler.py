"""ContinuousBatcher + LLMEngine: the token-level serving loop.

Classifier serving dispatches whole requests; LLM serving schedules at
token granularity. One worker thread runs ticks of the SINGLE compiled
decode step over all KV slots; between ticks, sequences join (bucketed
prefill into a free slot, straight off the shared :class:`BatchQueue`) or
leave (eos / length budget / mid-stream deadline eviction) — continuous
batching in the Orca sense: admission never waits for the current batch
to finish, and a finished sequence's slot is reusable on the very next
tick.

Host<->device traffic per tick is exactly one fetch: the ``[num_slots]``
next-token vector, which streaming delivery needs on host anyway. Slot
bookkeeping, finish detection, and deadline eviction are all host-side
reads of that vector plus counters the scheduler already tracks, so the
device never round-trips for control flow. That is also why the tick can
run one step ahead (:meth:`ContinuousBatcher.tick`): step t+1 is
dispatched before step t's tokens are fetched, and delivery, finishing and
eviction follow one tick behind, beside the device's work instead of
between two pieces of it.

Drain semantics match the classifier engine: ``begin_drain`` (or SIGTERM
through the chained handler) stops admission, and the worker keeps
ticking until every in-flight sequence finishes and the queue is flushed
— preemption never strands a future mid-generation.
"""
from __future__ import annotations

import collections
import itertools
import os
import queue as _pyqueue
import threading
import time
from typing import Dict, List, Optional, Sequence, Union

import numpy as np

import jax
import jax.numpy as jnp

from ...core import monitor as _mon
from ...observability import flight as _flight
from ...observability import tracer as _otrace
from ..buckets import pow2_buckets
from ..cache import ExecutableCache, default_cache
from ..engine import DrainableEngineBase
from ..queue import BatchQueue
from ...utils.resilience import fault_injector
from ..request import (Deadline, DeadlineExceeded, EngineDraining,
                       EngineKilled, RequestTooLarge,
                       TokenStreamDivergence)
from .decode import GPTStaticDecoder, SamplingParams, pack_sampling
from .kvcache import StaticKVCache
from .prefix import PrefixStore
from .spec import GPTSpecDecoder

_REQ_IDS = itertools.count(1)
_STREAM_END = object()


class GenerationRequest:
    """One queued generation: prompt + sampling params + result future.

    Duck-types the queue contract of :class:`InferenceRequest` (``expired``
    / ``fail_expired`` / ``future``) so the shared :class:`BatchQueue`
    admission and head-of-line deadline eviction apply unchanged. The
    future resolves to ``{"tokens": [...], "finish_reason": ...}``; with
    ``stream=True``, :meth:`iter_tokens` yields tokens as ticks produce
    them.
    """

    __slots__ = ("req_id", "prompt", "sampling", "deadline", "future",
                 "t_enqueue", "t_first_token", "tokens", "finish_reason",
                 "_stream_q", "_clock", "_prefix_entry", "_t_last",
                 "weights_version", "_replay_pos", "_resume_offset")

    def __init__(self, prompt, sampling: SamplingParams,
                 deadline: Optional[Deadline] = None, stream: bool = False,
                 clock=time.monotonic):
        arr = np.asarray(prompt, dtype=np.int32).reshape(-1)  # noqa: PTA002 -- admission-time conversion of the caller's host-side prompt (list/ndarray), not a device value
        if arr.size < 1:
            raise ValueError("prompt must contain at least one token")
        self.req_id = next(_REQ_IDS)
        self.prompt = arr
        self.sampling = sampling
        self.deadline = deadline
        from concurrent.futures import Future
        self.future = Future()
        self._clock = clock
        self.t_enqueue = clock()
        self.t_first_token: Optional[float] = None
        self.tokens: List[int] = []
        self.finish_reason: Optional[str] = None
        self._stream_q = _pyqueue.Queue() if stream else None
        # prefix-store pin held while this request is in flight (the
        # batcher unpins on release/evict/abort) + inter-token clock
        self._prefix_entry = None
        self._t_last: Optional[float] = None
        # stamped at admission from the batcher's weight generation; the
        # whole generation runs on that one generation (hot-swap waits
        # for slots to quiesce), so the result is bitwise old-or-new
        self.weights_version: Optional[int] = None
        # resume-dedup guard (docs/fault_tolerance.md "Zero-loss
        # serving"): after a migration/replay rebind, `_replay_pos`
        # marks the next already-streamed token the engine must
        # re-verify before any NEW token may flow; `_resume_offset`
        # counts generated tokens folded into the rebuilt prompt so
        # `seq_len` stays invariant across resumes.
        self._replay_pos: Optional[int] = None
        self._resume_offset = 0

    @property
    def prompt_len(self) -> int:
        return int(self.prompt.size)

    @property
    def seq_len(self) -> int:
        """Logical sequence length: ORIGINAL prompt + generated tokens.
        Invariant under resume (a replayed request's ``prompt`` holds
        already-generated tokens; ``_resume_offset`` backs them out), so
        capacity and length-budget checks never double-count."""
        return self.prompt_len - self._resume_offset + len(self.tokens)

    @property
    def expired(self) -> bool:
        return self.deadline is not None and self.deadline.expired()

    @property
    def nrows(self) -> int:          # queue/stats compatibility
        return 1

    def fail(self, exc: BaseException) -> bool:
        if self.future.done():
            return False
        self.future.set_exception(exc)
        if self._stream_q is not None:
            self._stream_q.put(exc)
            self._stream_q.put(_STREAM_END)
        return True

    def fail_expired(self) -> bool:
        return self.fail(DeadlineExceeded(
            f"generation request {self.req_id} exceeded its "
            f"{self.deadline.seconds}s deadline"))

    def begin_resume(self, n_resume: int) -> "GenerationRequest":
        """Rebind this request for resumption on another engine with
        ``n_resume`` generated tokens' worth of state restored (from a
        migrated KV splice or a journal replay). The prompt is rebuilt
        as ``original_prompt + tokens[:n_resume]`` so a plain prefill
        reconstructs the cache, and the dedup guard arms: every
        re-generated token in ``tokens[n_resume:]`` is VERIFIED against
        what the client already received and swallowed — the stream
        resumes at the exact next unseen token, or fails loudly with
        :class:`TokenStreamDivergence`. Raises (gap direction) when
        ``n_resume`` exceeds what the client has."""
        n = int(n_resume)
        if n < 0 or n > len(self.tokens):
            raise TokenStreamDivergence(
                f"request {self.req_id}: cannot resume at token {n}; "
                f"the client has {len(self.tokens)} — the restored "
                f"state is AHEAD of the stream and would emit a gap")
        base = self.prompt[:self.prompt.size - self._resume_offset]
        if n:
            self.prompt = np.concatenate(
                [base,
                 np.asarray(self.tokens[:n], np.int32)])  # noqa: PTA002 -- self.tokens is a host-side list of emitted ints, not a device value
        else:
            self.prompt = base
        self._resume_offset = n
        self._replay_pos = n if n < len(self.tokens) else None
        self._t_last = None
        return self

    def _emit(self, tok: int) -> bool:
        """Deliver one engine-produced token. During a resume replay the
        token is verified against the already-streamed transcript and
        swallowed (never re-delivered); a mismatch fails the request
        with :class:`TokenStreamDivergence` and returns False — the
        caller must then forget the slot without finishing."""
        if self._replay_pos is not None:
            pos = self._replay_pos
            if pos < len(self.tokens):
                if tok != self.tokens[pos]:
                    self.fail(TokenStreamDivergence(
                        f"request {self.req_id}: resumed stream produced "
                        f"token {tok} at position {pos} but the client "
                        f"already received {self.tokens[pos]} — refusing "
                        f"to corrupt the stream"))
                    return False
                self._replay_pos = pos + 1
                if self._replay_pos >= len(self.tokens):
                    self._replay_pos = None
                return True
            self._replay_pos = None
        if self.t_first_token is None:
            self.t_first_token = self._clock()
        self.tokens.append(tok)
        if self._stream_q is not None:
            self._stream_q.put(tok)
        return True

    def _finish(self, reason: str):
        self.finish_reason = reason
        if not self.future.done():
            self.future.set_result(
                {"tokens": list(self.tokens), "finish_reason": reason,
                 "req_id": self.req_id,
                 "weights_version": self.weights_version})
        if self._stream_q is not None:
            self._stream_q.put(_STREAM_END)

    def iter_tokens(self, timeout: Optional[float] = None):
        """Yield tokens as they are generated (``stream=True`` requests
        only); raises the failure exception on eviction/drain-abort."""
        if self._stream_q is None:
            raise ValueError("request was not submitted with stream=True")
        while True:
            item = self._stream_q.get(timeout=timeout)
            if item is _STREAM_END:
                return
            if isinstance(item, BaseException):
                raise item
            yield item

    def result(self, timeout: Optional[float] = None) -> dict:
        return self.future.result(timeout)


class LLMEngineConfig:
    """Tunables for the LLM serving engine (see docs/serving.md)."""

    def __init__(self,
                 num_slots: int = 8,
                 max_seq: int = 256,
                 prefill_buckets: Optional[Sequence[int]] = None,
                 max_queue: int = 256,
                 admission_block: bool = True,
                 admission_timeout: Optional[float] = 2.0,
                 default_deadline: Optional[float] = None,
                 default_max_new_tokens: int = 64,
                 max_top_k: int = 64,
                 idle_poll: float = 0.01,
                 warmup: bool = True,
                 seed: int = 0,
                 measure_mfu: bool = False,
                 prefix_cache: bool = False,
                 prefix_block: int = 16,
                 prefix_capacity_mb: float = 256.0,
                 spec_k: int = 0,
                 role: str = "mixed",
                 weight_dtype: str = "float32",
                 kv_dtype: str = "float32",
                 kv_layout: str = "slot",
                 page_size: int = 16,
                 num_pages: Optional[int] = None,
                 paged_attn_impl: str = "auto",
                 prefill_chunk: Optional[int] = None,
                 stat_prefix: str = "serving.llm"):
        self.num_slots = int(num_slots)
        self.max_seq = int(max_seq)
        if prefill_buckets is None:
            prefill_buckets = pow2_buckets(self.max_seq,
                                           start=min(8, self.max_seq))
        buckets = tuple(sorted(set(int(b) for b in prefill_buckets)))
        if not buckets or buckets[0] < 1 or buckets[-1] > self.max_seq:
            raise ValueError(
                f"prefill buckets must lie in [1, max_seq={self.max_seq}]; "
                f"got {buckets}")
        self.prefill_buckets = buckets
        self.max_queue = int(max_queue)
        self.admission_block = bool(admission_block)
        self.admission_timeout = admission_timeout
        self.default_deadline = default_deadline
        self.default_max_new_tokens = int(default_max_new_tokens)
        self.max_top_k = int(max_top_k)
        self.idle_poll = float(idle_poll)
        self.warmup = bool(warmup)
        self.seed = int(seed)
        # opt-in: publish `serving.llm.mfu` from XLA cost analysis of the
        # decode step (costs one extra compile at the first tick)
        self.measure_mfu = bool(measure_mfu)
        # disaggregated-fleet knobs (docs/serving.md "Disaggregated fleet")
        self.prefix_cache = bool(prefix_cache)
        self.prefix_block = int(prefix_block)
        self.prefix_capacity_mb = float(prefix_capacity_mb)
        if spec_k < 0:
            raise ValueError(f"spec_k must be >= 0, got {spec_k}")
        self.spec_k = int(spec_k)          # 0 disables speculative decode
        if role not in ("prefill", "decode", "mixed"):
            raise ValueError(
                f"role must be prefill/decode/mixed, got {role!r}")
        self.role = role
        # quantized serving (docs/quantization.md): int8 weights halve
        # parameter bytes; int8 KV halves cache bytes so slots-per-chip
        # doubles. Both dequantize inside the fused decode step.
        if weight_dtype not in ("float32", "int8"):
            raise ValueError(
                f"weight_dtype must be 'float32' or 'int8', got "
                f"{weight_dtype!r}")
        if kv_dtype not in ("float32", "int8"):
            raise ValueError(
                f"kv_dtype must be 'float32' or 'int8', got {kv_dtype!r}")
        if kv_dtype == "int8" and self.prefix_cache:
            raise ValueError(
                "prefix_cache requires a dense KV cache: the prefix "
                "export/insert path moves raw f32 rows between engines. "
                "Set kv_dtype='float32' or prefix_cache=False.")
        if kv_dtype == "int8" and self.spec_k > 0:
            raise ValueError(
                "speculative decoding (spec_k > 0) requires a dense KV "
                "cache: the verify/rollback path rewrites accepted rows "
                "in place. Set kv_dtype='float32' or spec_k=0.")
        self.weight_dtype = weight_dtype
        self.kv_dtype = kv_dtype
        # paged KV substrate (docs/serving.md "Paged KV cache"): fixed
        # page_size-token pages in one arena, admission on pages at
        # current lengths instead of worst-case max_seq slots
        if kv_layout not in ("slot", "paged"):
            raise ValueError(
                f"kv_layout must be 'slot' or 'paged', got {kv_layout!r}")
        if paged_attn_impl not in ("auto", "gather", "kernel"):
            raise ValueError(
                f"paged_attn_impl must be 'auto', 'gather' or 'kernel', "
                f"got {paged_attn_impl!r}")
        self.kv_layout = kv_layout
        self.page_size = int(page_size)
        self.num_pages = None if num_pages is None else int(num_pages)
        self.paged_attn_impl = paged_attn_impl
        # chunked prefill (paged layout, a decoder family that offers
        # ``chunk_prefill``): a prompt enters in chunks of this many tokens,
        # at most one chunk between two decode ticks; None admits every
        # prompt in one bucketed program
        if prefill_chunk is not None:
            if kv_layout != "paged":
                raise NotImplementedError(
                    "prefill_chunk needs kv_layout='paged'")
            if not 1 <= int(prefill_chunk) <= self.max_seq:
                raise ValueError(
                    f"prefill_chunk must lie in [1, max_seq={self.max_seq}],"
                    f" got {prefill_chunk}")
            prefill_chunk = int(prefill_chunk)
        self.prefill_chunk = prefill_chunk
        if kv_layout == "paged":
            if self.page_size < 1 or self.max_seq % self.page_size:
                raise ValueError(
                    f"page_size {self.page_size} must divide "
                    f"max_seq {self.max_seq} (the gather lane's bitwise "
                    f"parity relies on whole-page rows)")
            if self.num_pages is not None and self.num_pages < \
                    self.max_seq // self.page_size:
                raise ValueError(
                    f"num_pages {self.num_pages} cannot hold even one "
                    f"max_seq sequence "
                    f"({self.max_seq // self.page_size} pages)")
        self.stat_prefix = stat_prefix

    @property
    def max_prompt_len(self) -> int:
        """Longest admissible prompt: must fit a bucket (chunked prefill
        has none) AND leave room for at least one generated token in the
        slot."""
        if self.prefill_chunk is not None:
            return self.max_seq - 1
        return min(self.prefill_buckets[-1], self.max_seq - 1)

    def bucket_for(self, prompt_len: int) -> int:
        for b in self.prefill_buckets:
            if prompt_len <= b:
                return b
        raise RequestTooLarge(
            f"prompt of {prompt_len} tokens exceeds the largest prefill "
            f"bucket ({self.prefill_buckets[-1]})")


#: phases of the worker the batcher times: span name and counter (under the
#: engine's stat prefix); ``prefill``'s counter says what the span bounds,
#: the dispatch of the prefill program, not its device time
_PHASE_COUNTERS = {
    "loop": ("serving.llm/loop", "worker.loop_s"),
    "idle_wait": ("serving.llm/idle_wait", "worker.idle_wait_s"),
    "admit": ("serving.llm/admit", "worker.admit_s"),
    "admit_pages": ("serving.llm/admit_pages", "worker.admit_pages_s"),
    "prefill": ("serving.llm/prefill", "worker.prefill_dispatch_s"),
    "prefill_chunk": ("serving.llm/prefill_chunk", "worker.prefill_chunk_s"),
    "first_token_fetch": ("serving.llm/first_token_fetch",
                          "worker.first_token_fetch_s"),
    "tick_capacity": ("serving.llm/tick_capacity", "worker.tick_capacity_s"),
    "tick_dispatch": ("serving.llm/tick_dispatch", "worker.tick_dispatch_s"),
    "tick_fetch": ("serving.llm/tick_fetch", "worker.tick_fetch_s"),
    "tick_emit": ("serving.llm/tick_emit", "worker.tick_emit_s"),
}


class _Phase:
    """One timed phase of the worker: always adds its elapsed seconds to
    its counter, and is the tracer's span (the shared no-op while tracing
    is off) of the same extent."""

    __slots__ = ("_add", "_counter", "_span", "_t0")

    def __init__(self, add, counter: str, span):
        self._add = add
        self._counter = counter
        self._span = span

    def __enter__(self):
        self._span.__enter__()
        self._t0 = time.perf_counter()

    def __exit__(self, exc_type, exc, tb):
        self._add(self._counter, time.perf_counter() - self._t0)
        return self._span.__exit__(exc_type, exc, tb)


class _Step:
    """One decode step that has been dispatched and not fetched yet: who held
    each slot when it was dispatched (its tokens go to them, and to no one
    admitted since), the device value its fetch reads, when it was
    dispatched, and whether the step before it was still unfetched then."""

    __slots__ = ("reqs", "out", "packed", "t0", "overlapped")

    def __init__(self, reqs, out, packed: bool, t0: float, overlapped: bool):
        self.reqs = reqs
        self.out = out
        self.packed = packed
        self.t0 = t0
        self.overlapped = overlapped


class ContinuousBatcher:
    """Slot-level scheduling state + the per-tick device interaction.

    Owns the :class:`StaticKVCache`, the per-slot device vectors
    (``finished``, ``last_tokens``, packed sampling params), and the
    slot -> request table. ``admit`` prefms prefill + first-token
    delivery; ``tick`` advances every active sequence one token and
    retires finished/evicted slots. Single-threaded by design: only the
    engine worker calls into it.
    """

    def __init__(self, decoder: GPTStaticDecoder, config: LLMEngineConfig,
                 registry: _mon.StatRegistry, clock=time.monotonic,
                 prefix_store: Optional[PrefixStore] = None,
                 spec_decoder: Optional[GPTSpecDecoder] = None):
        self.decoder = decoder
        self.config = config
        self._registry = registry
        self._prefix = config.stat_prefix
        self._clock = clock
        self.kv = decoder.new_kv(config.num_slots, config.max_seq)
        self._params = decoder.params()
        self.prefix_store = prefix_store
        self.spec = spec_decoder
        self.kv_draft: Optional[StaticKVCache] = None
        self._draft_params = None
        if spec_decoder is not None:
            # draft cache mirrors the target's slot/position geometry; one
            # shared lengths vector advances both in lockstep
            self.kv_draft = spec_decoder.new_draft_kv(config.num_slots,
                                                      config.max_seq)
            self._draft_params = spec_decoder.draft_params()
        self._spec_proposed = 0
        self._spec_accepted = 0
        #: monotonically increasing weight generation; bumped by the
        #: engine's swap_weights AFTER slots quiesce, read at admission
        self.weights_version = 0
        self._reqs: Dict[int, GenerationRequest] = {}
        self._slot_samp: List[SamplingParams] = [
            SamplingParams() for _ in range(config.num_slots)]
        self._samp_vecs = pack_sampling(self._slot_samp)
        self._finished = jnp.zeros((config.num_slots,), jnp.bool_)
        self._last = jnp.zeros((config.num_slots,), jnp.int32)
        self._rng = jax.random.PRNGKey(config.seed)
        #: the decode step dispatched and not fetched yet: the tick runs one
        #: ahead (see :meth:`tick`)
        self._inflight: Optional[_Step] = None
        self._fetched_at = 0.0      # when the newest fetch of a step ended
        # decode-step FLOPs (measure_mfu): measured lazily at first tick
        self._decode_flops: Optional[float] = None
        self._peak_flops: Optional[float] = None

    # -- introspection -------------------------------------------------------
    @property
    def active(self) -> int:
        return len(self._reqs)

    @property
    def free_slots(self) -> int:
        return self.kv.free_slots

    def refresh_params(self):
        """Re-extract model parameters (after a checkpoint reload)."""
        self._params = self.decoder.params()

    # -- internals -----------------------------------------------------------
    def _next_key(self):
        self._rng, sub = jax.random.split(self._rng)
        return sub

    def _stat_add(self, name, v):
        self._registry.add(f"{self._prefix}.{name}", v)

    def _stat_set(self, name, v):
        self._registry.set(f"{self._prefix}.{name}", v)

    def _stat_observe(self, name, v):
        self._registry.observe(f"{self._prefix}.{name}", v)

    # -- worker phases -------------------------------------------------------
    def phase(self, name: str, attrs: Optional[Dict] = None) -> _Phase:
        """``with self.phase("tick_fetch"): ...`` times one phase of the
        worker (a key of ``_PHASE_COUNTERS``): its seconds always go to the
        ``worker.*_s`` counter, and with the tracer on it is also the span
        ``serving.llm/<name>``, so the counter and the span bound the same
        statements."""
        span_name, counter = _PHASE_COUNTERS[name]
        return _Phase(self._stat_add, counter, _otrace.span(span_name, attrs))

    # -- scheduling ----------------------------------------------------------
    def admit(self, req: GenerationRequest):
        """Prefill ``req`` into a free slot and deliver its first token.
        The caller guarantees ``free_slots > 0`` and a bucket-fitting
        prompt (``submit`` validated both)."""
        t0 = self._clock()
        # how long the request sat in the queue since it was enqueued
        self._stat_add("queue_wait_s", t0 - req.t_enqueue)
        with self.phase("admit_pages"):
            slot = self.kv.alloc()
            req.weights_version = self.weights_version
            self._reqs[slot] = req
            self._slot_samp[slot] = req.sampling
            self._samp_vecs = pack_sampling(self._slot_samp)
            samp1 = pack_sampling([req.sampling])
            slot_arr = jnp.asarray([slot], jnp.int32)
            entry, reuse_n = None, 0
            if self.prefix_store is not None:
                # cap: at least one prompt token must prefill (logits
                # source)
                entry, reuse_n = self.prefix_store.lookup(
                    req.prompt, req.prompt_len - 1,
                    self.decoder.prefix_sig(self.kv))
                # the PADDED tail bucket must fit behind the reused head —
                # dynamic_update_slice clamps out-of-range starts, which
                # would silently corrupt the reused rows. Shrink reuse
                # block-wise until offset + tail_bucket fits (rarely more
                # than one step).
                while reuse_n > 0 and reuse_n + self.config.bucket_for(
                        req.prompt_len - reuse_n) > self.config.max_seq:
                    reuse_n -= self.prefix_store.block_tokens
                if entry is not None and reuse_n <= 0:
                    self.prefix_store.unpin(entry)
                    entry, reuse_n = None, 0
        with self.phase("prefill", {"req": req.req_id}):
            if reuse_n > 0:
                # hit: bulk-copy the cached head, prefill only the tail
                # bucket
                self.decoder.insert_prefix(
                    self.kv, entry.k[:, :reuse_n], entry.v[:, :reuse_n],
                    slot)
                self.prefix_store.note_copied(
                    int(entry.k[:, :reuse_n].nbytes
                        + entry.v[:, :reuse_n].nbytes))
                req._prefix_entry = entry   # stays pinned until release
                tail = req.prompt[reuse_n:]
                lt = self.config.bucket_for(int(tail.size))
                padded = np.zeros((1, lt), np.int32)
                padded[0, :tail.size] = tail
                nxt, self._finished = self.decoder.tail_prefill(
                    self.kv, self._params, jnp.asarray(padded),
                    jnp.asarray([int(tail.size)], jnp.int32),
                    jnp.asarray([reuse_n], jnp.int32), slot_arr,
                    self._finished, samp1, self._next_key())
                self._stat_add("prefix.reused_tokens", reuse_n)
            else:
                lp = self.config.bucket_for(req.prompt_len)
                padded = np.zeros((1, lp), np.int32)
                padded[0, :req.prompt_len] = req.prompt
                nxt, self._finished = self.decoder.prefill(
                    self.kv, self._params, jnp.asarray(padded),
                    jnp.asarray([req.prompt_len], jnp.int32), slot_arr,
                    self._finished, samp1, self._next_key())
                if self.prefix_store is not None:
                    # miss: export the block-aligned head for future
                    # requests
                    blk = self.prefix_store.block_tokens
                    n = (req.prompt_len // blk) * blk
                    if n >= blk:
                        k_h, v_h = self.kv.host_slot_kv(slot, n)
                        ins = self.prefix_store.insert(
                            req.prompt[:n], k_h, v_h,
                            self.decoder.prefix_sig(self.kv))
                        if ins is not None:
                            req._prefix_entry = ins
            if self.spec is not None:
                self._draft_prefill(req, slot_arr, samp1)
            self._last = self._last.at[jnp.asarray([slot])].set(nxt)
        self._deliver_first_token(req, slot, nxt, t0)

    def _draft_prefill(self, req: GenerationRequest, slot_arr, samp1):
        # the draft cache never reuses prefixes (the draft is cheap and
        # its K/V is not stored); full-prompt prefill, keep K/V only
        lp = self.config.bucket_for(req.prompt_len)
        dpad = np.zeros((1, lp), np.int32)
        dpad[0, :req.prompt_len] = req.prompt
        self.spec.draft_prefill(
            self.kv_draft, self._draft_params, jnp.asarray(dpad),
            jnp.asarray([req.prompt_len], jnp.int32), slot_arr,
            self.kv.lengths, self._finished, samp1, self._next_key())

    def _deliver_first_token(self, req: GenerationRequest, slot: int, nxt,
                             t0: float):
        """The end of every admission, on either lane: fetch the first
        generated token (streaming TTFT requires it on host, and it doubles
        as the finish probe), record the admission and deliver."""
        with self.phase("first_token_fetch"):
            tok = int(np.asarray(jax.device_get(nxt))[0])  # noqa: PTA002 -- one [1]-token fetch per admission; first-token delivery (TTFT) needs the value on host
        now = self._clock()
        self._stat_observe("prefill_ms", (now - t0) * 1000.0)
        self._stat_observe("ttft_ms", (now - req.t_enqueue) * 1000.0)
        self._stat_add("prefills", 1)
        if not req._emit(tok):
            self._forget(slot, req)
            return
        req._t_last = now
        self._stat_add("tokens_generated", 1)
        self._maybe_finish(slot, req, tok)

    def tick(self) -> int:
        """One decode tick: every active sequence gets the tokens of one
        compiled step (1 token plain, 1..k+1 speculative), and finished
        slots retire. Returns the number of sequences advanced.

        The plain tick runs ONE STEP AHEAD. A step needs nothing from the
        host but to be dispatched: lengths, finished flags and last tokens
        are device arrays that each step hands to the next. So a call
        dispatches step t+1 first and only then fetches, delivers and
        finishes step t: the device has its next step queued while the
        worker's Python, the copy to the host and the wake-up run. A call
        that finds nothing in flight dispatches both steps. What follows
        from it:

        - a step's tokens go to the requests that held the slots when it
          was dispatched (``_Step.reqs``); one that has ended, was evicted
          or forgotten since gets nothing, and a request admitted into the
          slot since never sees the old occupant's token;
        - a request that ends at step t has one more row computed in t+1,
          which is dropped. That row writes the slot's own cache row (a page
          the slot alone holds, or the trash page), and ``kv.free`` and the
          next admission's prefill are dispatched after it: the device runs
          in order, so none of them waits;
        - whatever touches the slots from outside the tick calls
          :meth:`settle` first (the control plane, an export or import, an
          evacuation), and when the batch empties the step in flight is
          dropped without a wait. An admission leaves it in flight: its own
          first-token fetch waits for everything queued;
        - the speculative tick stays serial: how many tokens a verify step
          accepts, and so where the next one writes, is known only from
          its fetch. So does every tick of a paged engine that prefills in
          chunks, and any tick for whose row ahead the pool has no page
          (``PagedBatcher._room_ahead``)."""
        if not self._reqs:
            self._drop_inflight()
            return 0
        if self.spec is not None:
            if self._spec_room_ok():
                with _otrace.span("serving.llm/spec_tick"):
                    return self._spec_tick()
            # near the end of a slot row there is no room for k+1
            # candidate writes — run the plain one-token step instead
            self._stat_add("spec.fallback_ticks", 1)
        with _otrace.span("serving.llm/decode_tick"):
            return self._tick_inner()

    def _spec_room_ok(self) -> bool:
        """True when every ACTIVE slot can absorb k+1 candidate K/V rows:
        the next write position is ``prompt_len + len(tokens) - 1`` (the
        last emitted token is not yet in cache) and the verify step lands
        rows up to position + k."""
        k = self.spec.k
        for req in self._reqs.values():
            pos = req.seq_len - 1
            if pos + k + 1 > self.config.max_seq:
                return False
        return True

    def _spec_tick(self) -> int:
        t0 = self._clock()
        with self.phase("tick_dispatch"):
            self._finished, self._last, out_dev = self.spec.step(
                self.kv, self.kv_draft, self._params, self._draft_params,
                self._finished, self._last, self._samp_vecs,
                self._next_key())
        # THE one host fetch of the tick: the packed [S, k+2]
        # (count | tokens...) matrix — same budget as the plain tick's
        # next-token vector, just wider.
        with self.phase("tick_fetch"):
            out = np.asarray(jax.device_get(out_dev))  # noqa: PTA002 -- the single per-tick packed emit fetch; token streaming requires host delivery
        with self.phase("tick_emit"):
            n = len(self._reqs)
            dt = max(self._clock() - t0, 1e-9)
            total = 0
            for slot, req in list(self._reqs.items()):
                if req.expired:
                    self._evict(slot, req)
                    continue
                n_emit = int(out[slot, 0])
                toks = out[slot, 1:1 + n_emit]
                if not req.sampling.do_sample:
                    # acceptance accounting is a greedy-lane concept;
                    # sampling slots take one verified token per tick by
                    # construction
                    self._spec_proposed += self.spec.k
                    self._spec_accepted += n_emit - 1
                    self._stat_add("spec.proposed", self.spec.k)
                    self._stat_add("spec.accepted", n_emit - 1)
                total += self._emit_many(slot, req, toks)
            self._stat_observe("decode_tick_ms", dt * 1000.0)
            # per-token time: the tick advanced each slot by total/n tokens
            # on average, so normalize to stay comparable with the plain
            # tick
            self._stat_observe("tpot_ms", dt * 1000.0 * n / max(1, total))
            self._stat_add("tokens_generated", total)
            self._stat_add("spec.ticks", 1)
            if self._spec_proposed:
                self._stat_set("spec.acceptance_rate",
                               self._spec_accepted / self._spec_proposed)
        return n

    def _emit_many(self, slot: int, req: GenerationRequest, toks) -> int:
        """Deliver a spec tick's emitted tokens in order, stopping at the
        first finish condition (same eos-before-budget order as
        :meth:`_maybe_finish`; surplus device-side tokens are discarded —
        the slot is released, so the cache divergence is unobservable)."""
        emitted = 0
        now = self._clock()
        if req._t_last is not None:
            self._stat_observe("intertoken_ms",
                               (now - req._t_last) * 1000.0)
        req._t_last = now
        s = req.sampling
        for tok in toks:
            tok = int(tok)
            if not req._emit(tok):
                self._forget(slot, req)
                break
            emitted += 1
            if s.eos_token_id is not None and tok == int(s.eos_token_id):
                self._release(slot, req, "stop")
                break
            if len(req.tokens) >= s.max_new_tokens \
                    or req.seq_len >= self.config.max_seq:
                self._release(slot, req, "length")
                break
        return emitted

    def _tick_inner(self) -> int:
        if self.config.measure_mfu and self._decode_flops is None:
            self._measure_decode_flops()
        step = self._inflight
        if step is None:
            # nothing is ahead: this call's own step, then the one after it
            step = self._dispatch_step(None)
            run_ahead = self.spec is None and self._room_ahead()
        else:
            run_ahead = True
        self._inflight = self._dispatch_step(step) if run_ahead else None
        return self._finish_step(step)

    @staticmethod
    def _behind(ahead: Optional[_Step], slot: int,
                req: GenerationRequest) -> int:
        """1 where ``ahead``, a step not fetched yet, holds a row of
        ``req``: the host's ``req.seq_len`` is then one token behind the
        device's length of the slot."""
        return int(ahead is not None and ahead.reqs.get(slot) is req)

    def _room_ahead(self) -> bool:
        """Whether a second step may be dispatched behind the one just
        dispatched (the paged lane maps its rows first, if the pool has
        them to spare)."""
        return True

    def _note_step(self, ahead: Optional[_Step]):
        """A step is about to be dispatched behind ``ahead`` (the paged
        lane counts the pages its attention will walk)."""

    def _dispatch_step(self, ahead: Optional[_Step]) -> _Step:
        """Enqueue THE compiled step over every slot, behind ``ahead`` (the
        step still unfetched, or None)."""
        t0 = self._clock()
        with self.phase("tick_dispatch"):
            self._note_step(ahead)
            nxt, self._finished, *packed = self.decoder.decode_step(
                self.kv, self._params, self._finished, self._last,
                self._samp_vecs, self._next_key())
            self._last = nxt
        # a decoder family may pack its tick counters behind the tokens, so
        # that they ride the same fetch
        return _Step(dict(self._reqs), packed[0] if packed else nxt,
                     bool(packed), t0, ahead is not None)

    def _finish_step(self, step: _Step) -> int:
        """Fetch ``step``'s tokens, deliver them to the requests that held
        the slots when it was dispatched and still do, and retire what
        ended."""
        # THE one host fetch of the tick: the [num_slots] next-token
        # vector. Streaming delivery and host-side finish detection both
        # consume it, so this sync is the feature, not an accident.
        with self.phase("tick_fetch"):
            toks = np.asarray(jax.device_get(step.out))  # noqa: PTA002 -- the single per-tick [num_slots] fetch; token streaming requires host delivery
        with self.phase("tick_emit"):
            live = [(slot, req) for slot, req in step.reqs.items()
                    if self._reqs.get(slot) is req]
            n = len(live)
            if step.packed:
                self.decoder.note_tick(toks[self.config.num_slots:],
                                       len(step.reqs), self._stat_add)
            # the tick's period: since the fetch before it where the step
            # was dispatched ahead of that fetch, else since its dispatch
            now = self._clock()
            dt = max(now - (self._fetched_at if step.overlapped
                            else step.t0), 1e-9)
            self._fetched_at = now
            self._stat_observe("decode_tick_ms", dt * 1000.0)
            self._stat_observe("tpot_ms", dt * 1000.0)
            self._stat_add("tokens_generated", n)
            if step.overlapped:
                self._stat_add("ticks_overlapped", 1)
            if self._decode_flops and self._peak_flops:
                # the period includes the sanctioned token fetch, so this
                # is delivered MFU, not device-only MFU
                self._stat_set("mfu",
                               self._decode_flops / dt / self._peak_flops)
            for slot, req in live:
                if req.expired:
                    self._evict(slot, req)
                    continue
                tok = int(toks[slot])
                if not req._emit(tok):
                    self._forget(slot, req)
                    continue
                if req._t_last is not None:
                    self._stat_observe("intertoken_ms",
                                       (now - req._t_last) * 1000.0)
                req._t_last = now
                self._maybe_finish(slot, req, tok)
        if not self._reqs:
            self._drop_inflight()
        return n

    def settle(self):
        """Fetch, deliver and finish the step in flight, if there is one.
        Whoever touches the slots from outside the tick calls this first
        and then acts on a batcher whose host state and device state
        agree: ``_loop_once`` before the control plane's closures (an
        import among them), ``export_all``, ``evacuate``. A tick settled
        so is a ``decode_tick`` span (fetch and emit) like any other."""
        step, self._inflight = self._inflight, None
        if step is None:
            return
        self._stat_add("ticks_settled_early", 1)
        with _otrace.span("serving.llm/decode_tick"):
            self._finish_step(step)

    def _drop_inflight(self) -> Optional[_Step]:
        """Forget the step in flight without waiting for it: no request is
        left to take its tokens (the batch emptied, or is being failed)."""
        step, self._inflight = self._inflight, None
        if step is not None:
            self._stat_add("ticks_settled_early", 1)
        return step

    def _measure_decode_flops(self):
        """XLA cost analysis of THE decode step (once, at first tick when
        ``measure_mfu``): compiles the raw program a second time to read
        its flops without executing. Failure disables MFU, never decode."""
        from ...observability import stepmeter as _sm
        from .decode import build_decode_step
        raw = build_decode_step(self.decoder.spec, self.decoder.max_top_k)
        with _otrace.span("observability/cost_analysis"):
            flops = _sm.compiled_flops(
                raw, self._params, self.kv.k, self.kv.v, self.kv.lengths,
                self._finished, self._last, *self._samp_vecs,
                jax.random.PRNGKey(0))
        self._peak_flops = _sm.default_peak_flops()
        self._decode_flops = flops if flops else 0.0
        if flops:
            self._stat_set("decode_flops_per_tick", flops)

    def _maybe_finish(self, slot: int, req: GenerationRequest, tok: int):
        s = req.sampling
        if s.eos_token_id is not None and tok == int(s.eos_token_id):
            self._release(slot, req, "stop")
        elif len(req.tokens) >= s.max_new_tokens:
            self._release(slot, req, "length")
        elif req.seq_len >= self.config.max_seq:
            self._release(slot, req, "length")

    def _ends_by_length(self, req: GenerationRequest) -> bool:
        """True where ``req`` has a row in the step in flight and that row
        is known to be its last: :meth:`_maybe_finish`'s two length rules,
        one token on (a replayed request's count is not known from here)."""
        return req._replay_pos is None and (
            len(req.tokens) + 1 >= req.sampling.max_new_tokens
            or req.seq_len + 1 >= self.config.max_seq)

    def _unpin_prefix(self, req: GenerationRequest):
        """Drop the request's prefix-store pin (if any) the moment the
        request leaves the engine — eviction of its entry becomes legal
        again. Every exit path (release/evict/abort) funnels through
        this."""
        if req._prefix_entry is not None and self.prefix_store is not None:
            self.prefix_store.unpin(req._prefix_entry)
            req._prefix_entry = None

    def _release(self, slot: int, req: GenerationRequest, reason: str):
        del self._reqs[slot]
        self.kv.free(slot)
        self._unpin_prefix(req)
        req._finish(reason)
        self._stat_add("completed", 1)
        self._stat_observe("request_latency_ms",
                           (self._clock() - req.t_enqueue) * 1000.0)

    def _evict(self, slot: int, req: GenerationRequest):
        """Mid-stream deadline eviction: the slot is reclaimed and the
        future fails — a stalled consumer cannot pin a slot forever."""
        del self._reqs[slot]
        self.kv.free(slot)
        self._unpin_prefix(req)
        req.fail(DeadlineExceeded(
            f"generation request {req.req_id} exceeded its "
            f"{req.deadline.seconds}s deadline after "
            f"{len(req.tokens)} tokens"))
        self._stat_add("evicted_midstream", 1)

    def _forget(self, slot: int, req: GenerationRequest):
        """Reclaim a slot whose request already resolved (the dedup
        guard failed it mid-replay): free resources, touch neither the
        future nor the stream."""
        del self._reqs[slot]
        self.kv.free(slot)
        self._unpin_prefix(req)
        self._stat_add("stream_divergence", 1)

    def evacuate(self) -> List[GenerationRequest]:
        """Detach every in-flight request WITHOUT failing it — the
        zero-loss half of a hard kill. Slots and prefix pins are
        reclaimed; the futures stay pending for the router's recovery
        replay (docs/fault_tolerance.md "Zero-loss serving"). The step in
        flight is settled first: its tokens were computed, so they are
        delivered rather than computed again by the replay."""
        self.settle()
        out: List[GenerationRequest] = []
        for slot, req in list(self._reqs.items()):
            del self._reqs[slot]
            self.kv.free(slot)
            self._unpin_prefix(req)
            out.append(req)
        return out

    def abort_all(self, exc_factory):
        """Fail every in-flight sequence (forced shutdown, not drain). The
        step in flight is dropped, not settled: its tokens have no one to
        go to, and the worker's death handler calls this, where a fetch
        could raise again."""
        self._drop_inflight()
        for slot, req in list(self._reqs.items()):
            del self._reqs[slot]
            self.kv.free(slot)
            self._unpin_prefix(req)
            req.fail(exc_factory(req))

    # -- warmup --------------------------------------------------------------
    def warmup(self):
        """Compile the decode step and every prefill bucket up front so no
        request pays a trace. Runs dummy work through the real buffers,
        then resets slot state — junk K/V is masked by the zeroed
        lengths."""
        t0 = self._clock()
        samp = pack_sampling([SamplingParams()])
        slot0 = jnp.asarray([0], jnp.int32)
        chunk = self.config.prefill_chunk
        if chunk is not None:       # every admission is the chunk program
            self.decoder.chunk_prefill(
                self.kv, self._params, jnp.zeros((1, chunk), jnp.int32), 0,
                chunk, True, 0, self._finished, samp, self._next_key())
        for lp in (() if chunk is not None else self.config.prefill_buckets):
            self.decoder.prefill(
                self.kv, self._params, jnp.zeros((1, lp), jnp.int32),
                jnp.asarray([lp], jnp.int32), slot0,
                self._finished, samp, self._next_key())
            if self.prefix_store is not None:
                # one trace per tail bucket covers every reuse offset —
                # `starts` is a traced device argument, not a shape
                self.decoder.tail_prefill(
                    self.kv, self._params, jnp.zeros((1, lp), jnp.int32),
                    jnp.asarray([lp], jnp.int32),
                    jnp.zeros((1,), jnp.int32), slot0,
                    self._finished, samp, self._next_key())
            if self.spec is not None:
                self.spec.draft_prefill(
                    self.kv_draft, self._draft_params,
                    jnp.zeros((1, lp), jnp.int32),
                    jnp.asarray([lp], jnp.int32), slot0, self.kv.lengths,
                    self._finished, samp, self._next_key())
        nxt = self.decoder.decode_step(
            self.kv, self._params, self._finished, self._last,
            self._samp_vecs, self._next_key())[0]
        if self.spec is not None:
            # the spec step needs headroom for k+1 candidate rows; warmup
            # state after the bucket loop has lengths == largest bucket,
            # so reset first and trace against zeroed lengths
            self.kv.reset()
            self.kv_draft.reset()
            _, _, out = self.spec.step(
                self.kv, self.kv_draft, self._params, self._draft_params,
                jnp.zeros((self.config.num_slots,), jnp.bool_),
                jnp.zeros((self.config.num_slots,), jnp.int32),
                self._samp_vecs, self._next_key())
            out.block_until_ready()  # noqa: PTA002 -- warmup barrier: ensure compiles finish before serving starts
        nxt.block_until_ready()  # noqa: PTA002 -- warmup barrier: ensure compiles finish before serving starts
        self.kv.reset()
        if self.kv_draft is not None:
            self.kv_draft.reset()
        self._finished = jnp.zeros((self.config.num_slots,), jnp.bool_)
        self._last = jnp.zeros((self.config.num_slots,), jnp.int32)
        self._stat_set("warmup_ms", (self._clock() - t0) * 1000.0)


class LLMEngine(DrainableEngineBase):
    """submit()/drain() continuous-batching generation over one GPT model.

    Construction compiles (optionally) and starts the worker thread; from
    then on every decode tick reuses the one compiled step. Graceful
    drain — explicit, SIGTERM via :meth:`install_drain_signal_handler`,
    or preemption via :meth:`arm_preemption` — stops admission and
    finishes every in-flight AND queued sequence before the worker exits.
    """

    def __init__(self, model, config: Optional[LLMEngineConfig] = None,
                 registry: Optional[_mon.StatRegistry] = None,
                 cache: Optional[ExecutableCache] = None,
                 mesh=None, slot_axis: str = "model",
                 draft_model=None,
                 prefix_store: Optional[PrefixStore] = None):
        self._config = config or LLMEngineConfig()
        self._init_serving_base(registry, self._config.stat_prefix)
        # `is not None`, not truthiness: an empty ExecutableCache has
        # len() == 0 and is falsy, so `cache or ...` would drop it.
        # Default: the ONE process-wide cache (serving/cache.py) — the
        # LLM engine shares executables and counters with Predictors and
        # batch engines instead of holding a private per-engine cache.
        self._cache = cache if cache is not None else default_cache()
        if self._config.kv_layout == "paged":
            # lazy import: paged/batcher imports this module's classes
            from .paged import (GPTPagedSpecDecoder, PagedBatcher,
                                paged_decoder_class)
            if mesh is not None:
                raise NotImplementedError(
                    "kv_layout='paged' over a slot-sharded mesh is not "
                    "supported yet — use kv_layout='slot' with a mesh")
            if prefix_store is not None:
                raise NotImplementedError(
                    "paged engines share prefix pages inside their own "
                    "arena; an external PrefixStore cannot be attached "
                    "— set prefix_cache=True instead")
            # the decoder family comes from the model (GPT the default)
            self._decoder = paged_decoder_class(model)(
                model, max_top_k=self._config.max_top_k,
                exec_cache=self._cache,
                weight_dtype=self._config.weight_dtype,
                kv_dtype=self._config.kv_dtype,
                page_size=self._config.page_size,
                num_pages=self._config.num_pages,
                attn_impl=self._config.paged_attn_impl)
            self._decoder.check_config(self._config)
            if self._config.prefill_chunk is not None \
                    and not self._decoder.prefills_in_chunks:
                raise NotImplementedError(
                    f"{type(self._decoder).__name__} has no chunked "
                    f"prefill yet: leave prefill_chunk unset")
            spec_decoder = None
            if self._config.spec_k > 0:
                if draft_model is None:
                    raise ValueError(
                        "spec_k > 0 requires a draft_model (the small "
                        "GPT that proposes candidate tokens)")
                spec_decoder = GPTPagedSpecDecoder(
                    self._decoder, draft_model, k=self._config.spec_k,
                    exec_cache=self._cache)
            self._batcher = PagedBatcher(
                self._decoder, self._config, self._registry,
                spec_decoder=spec_decoder)
            # the batcher builds its PagedPrefixStore (it needs the live
            # arena); surface it on the engine like the host store
            self._prefix_store = self._batcher.prefix_store
        else:
            from .paged import GPTPagedDecoder, paged_decoder_class
            if paged_decoder_class(model) is not GPTPagedDecoder:
                raise NotImplementedError(
                    f"{type(model).__name__} is served on "
                    f"kv_layout='paged' only")
            self._decoder = GPTStaticDecoder(
                model, max_top_k=self._config.max_top_k,
                exec_cache=self._cache,
                mesh=mesh, slot_axis=slot_axis,
                weight_dtype=self._config.weight_dtype,
                kv_dtype=self._config.kv_dtype)
            # prefix reuse: an explicit store (the disaggregated fleet
            # shares ONE across replicas for the prefill->decode KV
            # handoff) enables it even when the config flag is off
            self._prefix_store = prefix_store
            if prefix_store is not None and self._config.kv_dtype == "int8":
                raise ValueError(
                    "a shared PrefixStore requires a dense KV cache "
                    "(kv_dtype='float32'): prefix export/insert moves raw "
                    "f32 rows between engines")
            if self._prefix_store is None and self._config.prefix_cache:
                self._prefix_store = PrefixStore(
                    capacity_bytes=int(
                        self._config.prefix_capacity_mb * (1 << 20)),
                    block_tokens=self._config.prefix_block,
                    registry=self._registry,
                    stat_prefix=f"{self._config.stat_prefix}.prefix")
            spec_decoder = None
            if self._config.spec_k > 0:
                if draft_model is None:
                    raise ValueError(
                        "spec_k > 0 requires a draft_model (the small GPT "
                        "that proposes candidate tokens)")
                spec_decoder = GPTSpecDecoder(
                    self._decoder, draft_model, k=self._config.spec_k,
                    exec_cache=self._cache)
            self._batcher = ContinuousBatcher(
                self._decoder, self._config, self._registry,
                prefix_store=self._prefix_store, spec_decoder=spec_decoder)
        self._queue = BatchQueue(max_size=self._config.max_queue)
        # between-tick control plane (docs/fault_tolerance.md "Zero-loss
        # serving"): closures queued here run ON the worker thread at the
        # top of its loop, after the tick in flight is settled. The
        # sequence export/import paths ride this so migration can touch
        # batcher state without a lock on the hot path.
        self._ctl: "collections.deque" = collections.deque()
        #: crash-recovery journal; armed by :meth:`enable_recovery`
        self.journal = None
        self._evacuated: List[GenerationRequest] = []
        if self._config.warmup:
            self._batcher.warmup()
        self._worker = threading.Thread(
            target=self._worker_loop, name="paddle-tpu-llm-worker",
            daemon=True)
        self._worker.start()

    # -- public API ----------------------------------------------------------
    @property
    def config(self) -> LLMEngineConfig:
        return self._config

    @property
    def cache(self) -> ExecutableCache:
        return self._cache

    @property
    def decoder(self) -> GPTStaticDecoder:
        return self._decoder

    @property
    def prefix_store(self) -> Optional[PrefixStore]:
        return self._prefix_store

    @property
    def role(self) -> str:
        return self._config.role

    def submit(self, prompt, *, max_new_tokens: Optional[int] = None,
               do_sample: bool = False, temperature: float = 1.0,
               top_k: int = 0, eos_token_id: Optional[int] = None,
               deadline: Optional[Union[Deadline, float]] = None,
               stream: bool = False) -> GenerationRequest:
        """Enqueue one prompt; returns the :class:`GenerationRequest`
        (``.future`` for the full result, ``.iter_tokens()`` when
        ``stream=True``)."""
        if self._killed.is_set():
            self._stat_add("rejected_killed", 1)
            raise EngineKilled(
                f"engine was hard-killed ({self._kill_reason}); "
                f"submit rejected")
        if self._draining.is_set():
            self._stat_add("rejected_draining", 1)
            raise EngineDraining("engine is draining; submit rejected")
        if self._admission_paused.is_set():
            self._stat_add("rejected_paused", 1)
            raise EngineDraining(
                "engine admission is paused (fleet control); "
                "submit rejected")
        arr = np.asarray(prompt, dtype=np.int32).reshape(-1)  # noqa: PTA002 -- admission-time conversion of the caller's host-side prompt, not a device value
        if arr.size > self._config.max_prompt_len:
            self._stat_add("rejected_oversize", 1)
            raise RequestTooLarge(
                f"prompt of {arr.size} tokens exceeds max_prompt_len="
                f"{self._config.max_prompt_len} (largest prefill bucket "
                f"capped at max_seq-1)")
        if top_k > self._decoder.max_top_k:
            raise ValueError(
                f"top_k={top_k} exceeds the engine's compiled "
                f"max_top_k={self._decoder.max_top_k}")
        if max_new_tokens is None:
            max_new_tokens = self._config.default_max_new_tokens
        if max_new_tokens < 1:
            raise ValueError("max_new_tokens must be >= 1")
        if deadline is None and self._config.default_deadline is not None:
            deadline = self._config.default_deadline
        if deadline is not None and not isinstance(deadline, Deadline):
            deadline = Deadline(float(deadline))
        samp = SamplingParams(
            do_sample=bool(do_sample), temperature=float(temperature),
            top_k=int(top_k), eos_token_id=eos_token_id,
            max_new_tokens=int(max_new_tokens))
        req = GenerationRequest(arr, samp, deadline=deadline, stream=stream)
        try:
            self._queue.put(req, block=self._config.admission_block,
                            timeout=self._config.admission_timeout)
        except Exception:
            self._stat_add("rejected_queue_full", 1)
            raise
        self._stat_set("queue_depth", len(self._queue))
        return req

    def generate(self, prompt, **kw) -> dict:
        """Synchronous convenience: submit + wait."""
        return self.submit(prompt, **kw).result()

    @property
    def weights_version(self) -> int:
        return self._batcher.weights_version

    def swap_weights(self, state_dict: dict, *, timeout: float = 30.0,
                     poll: float = 0.005) -> int:
        """Live weight hot-swap: install ``state_dict`` into the model and
        re-extract params, WITHOUT tearing down the engine or recompiling
        (the decode/prefill executables are keyed by spec + dtypes, not by
        parameter values, so the persistent cache serves them unchanged).

        The caller must :meth:`pause_admission` first; this method then
        waits until every in-flight slot retires and the queue is empty —
        the swap happens only on a quiesced engine, which is what makes it
        bitwise-safe: a generation is computed entirely by the old weights
        or entirely by the new ones, never a mix. Returns the new weights
        version (stamped into every subsequent request's result).
        """
        if not (self._admission_paused.is_set() or self._draining.is_set()):
            raise RuntimeError(
                "swap_weights requires pause_admission() first: in-flight "
                "sequences must quiesce before params change under them")
        deadline = time.monotonic() + timeout
        while self._batcher.active > 0 or len(self._queue) > 0:
            if self._killed.is_set():
                raise EngineKilled(
                    f"engine hard-killed ({self._kill_reason}) while "
                    f"quiescing for a weight swap")
            if time.monotonic() >= deadline:
                raise TimeoutError(
                    f"engine did not quiesce within {timeout}s "
                    f"(active={self._batcher.active}, "
                    f"queued={len(self._queue)}); weight swap aborted")
            time.sleep(poll)
        # engine is quiesced AND admission is closed: the worker cannot
        # touch params (admit/tick need a request) until we finish, so
        # mutating the model + re-extracting here is single-writer
        with _otrace.span("serving.llm/weight_swap"):
            misses_before = self._cache.stats()["misses"]
            self._decoder.model.set_state_dict(state_dict)
            self._batcher.refresh_params()
            self._batcher.weights_version += 1
        self._stat_add("weight_swaps", 1)
        self._stat_set("weights_version", self._batcher.weights_version)
        _flight.record_event(
            "weight_swap",
            {"engine": self._prefix,
             "version": self._batcher.weights_version,
             "cache_misses_before": misses_before})
        return self._batcher.weights_version

    # -- zero-loss serving: migration + crash recovery -----------------------
    # (docs/fault_tolerance.md "Zero-loss serving")
    def _run_on_worker(self, fn, timeout: float = 30.0):
        """Run ``fn`` on the engine worker at the top of its next loop
        iteration, after the tick in flight is settled (the worker calls
        ``batcher.settle()`` before any closure, so ``fn`` never sees a
        step dispatched and not delivered). Blocks the caller until
        serviced; re-raises whatever ``fn``
        raised. A worker that exits first fails the call with
        :class:`EngineKilled` instead of hanging it."""
        if self._stopped.is_set():
            raise EngineKilled(
                f"engine worker already stopped "
                f"({self._kill_reason or 'drained'})")
        box: Dict[str, object] = {}
        ev = threading.Event()
        self._ctl.append((fn, box, ev))
        if not ev.wait(timeout):
            raise TimeoutError(
                f"engine worker did not service the control call within "
                f"{timeout}s")
        if "exc" in box:
            raise box["exc"]
        return box.get("ret")

    @property
    def supports_migration(self) -> bool:
        """True when live sequences can be exported/imported as page
        payloads — the paged KV substrate only (slot-layout engines
        still get crash recovery via journal replay)."""
        return bool(getattr(self._batcher, "supports_export", False))

    def export_sequences(self, *, timeout: float = 30.0) -> List:
        """Snapshot-and-detach every live sequence — plus the engine's
        still-queued backlog, shipped cold — into host-side
        :class:`~paddle_tpu.serving.fleet.migrate.SequenceManifest`
        objects. The caller (migrator) should have paused admission
        first. Runs on the worker between ticks; on return the engine
        holds none of the exported requests and their futures are still
        pending — ownership transfers to the caller."""
        if not self.supports_migration:
            raise NotImplementedError(
                "sequence export requires the paged KV cache "
                "(kv_layout='paged') and a decoder family whose whole "
                "per-sequence state is pages")
        action = fault_injector().fire("seq_export")
        if action == "slow_io":
            time.sleep(float(os.environ.get(
                "PADDLE_TPU_FAULT_SLOW_IO_S", "1.0")))
        elif action is not None:
            raise RuntimeError(f"injected seq_export fault: {action}")
        from ..fleet.migrate import SequenceManifest

        def _export():
            mans = self._batcher.export_all()
            if len(self._queue):
                for req in self._queue.take_many(
                        len(self._queue), timeout=0.0):
                    mans.append(SequenceManifest.for_queued(req))
            return mans
        mans = self._run_on_worker(_export, timeout=timeout)
        self._stat_add("migrated_out", len(mans))
        self._stat_set("queue_depth", len(self._queue))
        return mans

    def import_sequence(self, manifest, *, timeout: float = 30.0) -> bool:
        """Splice a migrated sequence into this engine and resume it at
        the exact next token. Returns False when the engine cannot
        adopt it (manifest/weights-version mismatch, pool pressure,
        injected faults) — the migrator falls back to replay then."""
        if self._killed.is_set() or self._draining.is_set() \
                or self._stopped.is_set() or not self.supports_migration:
            return False
        from ..fleet.migrate import MANIFEST_VERSION
        if manifest.version != MANIFEST_VERSION or manifest.cold:
            return False
        if manifest.weights_version != self.weights_version:
            # KV computed under other weights must never continue under
            # these — the hot-swap bitwise contract is old OR new
            return False
        action = fault_injector().fire("seq_import")
        if action == "slow_io":
            time.sleep(float(os.environ.get(
                "PADDLE_TPU_FAULT_SLOW_IO_S", "1.0")))
        elif action is not None:
            return False
        ok = bool(self._run_on_worker(
            lambda: self._batcher.import_manifest(manifest),
            timeout=timeout))
        if ok:
            self._stat_add("migrated_in", 1)
        return ok

    def resubmit(self, req: GenerationRequest) -> bool:
        """Adopt a request that never started decoding on its donor (a
        migrated admission-queue entry): nothing was streamed, so it
        re-queues as if freshly submitted."""
        if self._killed.is_set() or self._draining.is_set() \
                or self._stopped.is_set():
            return False
        if req.tokens:       # defensive: partially-streamed → replay path
            return self.resubmit_for_recovery(req, req.tokens)
        self._queue.put(req, block=False)
        self._stat_set("queue_depth", len(self._queue))
        return True

    def resubmit_for_recovery(self, req: GenerationRequest,
                              resume_tokens) -> bool:
        """Adopt an evacuated request from a dead sibling by REPLAY:
        re-prefill ``original_prompt + resume_tokens`` (the journaled
        transcript, possibly a few tokens stale) and let the dedup
        guard verify-and-swallow the re-generated gap. Greedy streams
        come out bitwise-identical to an uninterrupted run; a sampled
        stream that diverges fails loudly instead of corrupting
        output."""
        if self._killed.is_set() or self._draining.is_set() \
                or self._stopped.is_set():
            return False
        resume = [int(t) for t in resume_tokens]
        n = min(len(resume), len(req.tokens))
        if resume[:n] != req.tokens[:n]:
            exc = TokenStreamDivergence(
                f"request {req.req_id}: journaled transcript diverges "
                f"from the client stream within the first {n} tokens")
            req.fail(exc)
            raise exc
        # the rebuilt prompt must stay admissible; shrinking the resume
        # point is always safe — the gap is re-generated and verified
        cap = self._config.max_prompt_len \
            - (req.prompt_len - req._resume_offset)
        req.begin_resume(max(0, min(n, cap)))
        self._queue.put(req, block=False)
        self._stat_add("recovered", 1)
        self._stat_set("queue_depth", len(self._queue))
        return True

    def enable_recovery(self, capacity: int = 1024):
        """Arm crash recovery (idempotent): the worker notes the live
        request set every tick into a :class:`~paddle_tpu.serving.
        fleet.migrate.SequenceJournal` (flushed off-thread), and a
        subsequent :meth:`kill` EVACUATES in-flight requests — futures
        left pending — instead of failing them, so the router can
        replay them onto survivors."""
        if self.journal is None:
            from ..fleet.migrate import SequenceJournal
            self.journal = SequenceJournal(
                capacity=capacity, registry=self._registry,
                stat_prefix=f"{self._prefix}.journal")
        return self.journal

    def take_evacuated(self) -> List[GenerationRequest]:
        """Hand over the requests the worker detached at kill time
        (futures still pending). Ownership transfers to the caller —
        anything not replayed or failed there would leak."""
        out, self._evacuated = self._evacuated, []
        return out

    def kill(self, reason: str = "killed") -> List[dict]:
        """Hard-kill, returning a snapshot record per affected request
        (id, phase, tokens emitted): queued requests fail retryably;
        in-flight generations are evacuated for replay when recovery is
        armed, aborted with :class:`EngineKilled` otherwise."""
        journaled = self.journal is not None
        inflight = [{"req_id": r.req_id, "phase": "decode",
                     "tokens": len(r.tokens), "evacuated": journaled}
                    for r in list(self._batcher._reqs.values())]
        return list(super().kill(reason)) + inflight

    def drain(self, timeout: Optional[float] = None) -> List:
        """Graceful drain: stop admission, finish every in-flight and
        queued sequence, stop the worker. Returns the requests that were
        in flight when the drain began (all resolved on return)."""
        inflight = list(self._batcher._reqs.values())
        self.begin_drain()
        self._stopped.wait(timeout)
        if self._signal_chain is not None:
            self._signal_chain.uninstall()
        if self.journal is not None:
            self.journal.close()
        self._stat_set("queue_depth", 0)
        return inflight

    close = drain

    def __enter__(self):
        return self

    def __exit__(self, *exc):
        self.drain()
        return False

    def stats(self) -> dict:
        """Scalar stats + histogram summaries + cache counters + slot
        occupancy (the ``/statsz`` payload for the LLM engine)."""
        # NB: trailing dot — a bare startswith(self._prefix) would leak a
        # sibling engine's "serving.llm.replica1.*" counters into the
        # "serving.llm.replica0" payload (and vice versa) when several
        # in-process replicas share one registry.
        pre = self._prefix + "."
        hists = self._registry.histograms_with_prefix(pre)
        ticks = hists.get(pre + "decode_tick_ms", {}).get("count", 0)
        table = self._registry.get(pre + "paged_attn.pages_table")
        sparse_live = self._registry.get(pre + "sparse_attn.pages_live")
        window_live = self._registry.get(pre + "window_attn.pages_live")
        window_unbounded = self._registry.get(
            pre + "kv_pages.window_unbounded")
        row_expanded = self._registry.get(pre + "kv_row_bytes_expanded")
        return {
            "stats": self._registry.stats_with_prefix(pre),
            "histograms": hists,
            "executable_cache": self._cache.stats(),
            "draining": self.draining,
            "queue_depth": len(self._queue),
            "slots": {"total": self._config.num_slots,
                      "in_use": self._batcher.active,
                      "free": self._batcher.free_slots},
            "role": self._config.role,
            "spec_k": self._config.spec_k,
            "prefix_store": (self._prefix_store.stats()
                             if self._prefix_store is not None else None),
            "kv_layout": self._config.kv_layout,
            # the lane "auto" resolved to (None on the slot plane)
            "paged_attn_impl": getattr(self._decoder, "attn_impl", None),
            # "mxu" / "vpu": what the kernel lane's plain walk runs over a
            # fetched page, chosen from the cache's shapes (None elsewhere)
            "paged_attn_recurrence": getattr(self._batcher,
                                             "attn_recurrence", None),
            "pages": ({"total": self._batcher.kv.pool.num_pages,
                       "free": self._batcher.kv.pool.free_pages,
                       "cow_splits": self._batcher.kv.cow_splits,
                       "pending": len(self._batcher._pending)}
                      if self._config.kv_layout == "paged" else None),
            # share of the ticks so far whose step was dispatched while the
            # step before it was still unfetched (0 on the speculative lane)
            "tick_overlap_share": (
                self._registry.get(pre + "ticks_overlapped") / ticks
                if ticks else None),
            # share of the block tables' pages that hold a live row, over
            # the ticks so far: what paged_attn's walk does not skip
            "paged_attn_live_page_share": (
                self._registry.get(pre + "paged_attn.pages_live") / table
                if table else None),
            # share of the live pages that the sparse layers' selections
            # read, over the ticks so far (1.0: the walk ignores them)
            "sparse_attn_selected_share": (
                self._registry.get(pre + "sparse_attn.pages_selected")
                / sparse_live if sparse_live else None),
            # share of the live pages that the window layers' walks read,
            # and of the pages one group would hold that the window group
            # holds mapped, over the ticks so far (1.0: the window ignored)
            "window_attn_walked_share": (
                self._registry.get(pre + "window_attn.pages_walked")
                / window_live if window_live else None),
            "window_held_page_share": (
                self._registry.get(pre + "kv_pages.window_held")
                / window_unbounded if window_unbounded else None),
            # what a token and layer hold in a latent cache, of what its
            # heads' keys and values would take (1.0: expanded rows; None:
            # no latent cache)
            "latent_cache_row_share": (
                self._registry.get(pre + "kv_row_bytes") / row_expanded
                if row_expanded else None),
        }

    # -- worker --------------------------------------------------------------
    def _worker_loop(self):
        batcher = self._batcher
        try:
            stop = False
            while not stop:
                with batcher.phase("loop"):
                    stop = self._loop_once()
        except BaseException as e:  # worker death must not strand futures
            _flight.record_event(
                "llm_worker_death",
                {"error": f"{type(e).__name__}: {e}",
                 "active": self._batcher.active,
                 "queued": len(self._queue)})
            _flight.dump_if_armed("llm_worker_death")
            self._batcher.abort_all(
                lambda req, e=e: RuntimeError(
                    f"LLM worker died while request {req.req_id} was in "
                    f"flight: {e!r}"))
            raise
        finally:
            # unblock any control-plane caller racing the worker's exit
            while self._ctl:
                fn, box, ev = self._ctl.popleft()
                box["exc"] = EngineKilled(
                    "engine worker exited before servicing the control "
                    "call")
                ev.set()
            if self._drain_signaled:
                _flight.record_event("sigterm_drain",
                                     {"engine": self._prefix})
                _flight.dump_if_armed("sigterm_drain")
            self._stopped.set()

    def _loop_once(self) -> bool:
        """One iteration of the worker: control plane, admission, one
        decode tick. True when the worker is to exit (killed, or drained
        dry)."""
        # between-tick control plane: migration export/import closures run
        # here, on the worker, after the tick in flight is settled: a
        # closure finds every token delivered that the device has computed,
        # and the host's lengths equal to the device's
        if self._ctl:
            self._batcher.settle()
        while self._ctl:
            fn, box, ev = self._ctl.popleft()
            try:
                box["ret"] = fn()
            except BaseException as e:  # noqa: BLE001 -- boxed and re-raised on the calling thread
                box["exc"] = e
            finally:
                ev.set()
        if self._killed.is_set():
            # hard-kill: queued requests were failed by kill() itself.
            # With recovery armed, in-flight sequences are EVACUATED
            # (futures pending, for the router's replay); otherwise aborted
            # as before. Either way this is a commanded death, not a worker
            # crash, so no re-raise / no noisy daemon-thread traceback.
            n = self._batcher.active
            if self.journal is not None:
                self._evacuated.extend(self._batcher.evacuate())
            else:
                self._batcher.abort_all(
                    lambda req: EngineKilled(
                        f"engine hard-killed ({self._kill_reason}) "
                        f"with request {req.req_id} in flight after "
                        f"{len(req.tokens)} tokens"))
            _flight.record_event(
                "engine_killed",
                {"engine": self._prefix,
                 "reason": self._kill_reason,
                 "aborted": 0 if self.journal is not None else n,
                 "evacuated": n if self.journal is not None else 0})
            return True
        if self._guard is not None and self._guard.preempted \
                and not self._draining.is_set():
            self._stat_add("preemption_drains", 1)
            self.begin_drain()
        elif self._draining.is_set() and not self._queue.closed:
            # flag set by the async-signal-safe handler; complete the
            # drain outside signal context
            self._queue.close()
        free = self._batcher.free_slots
        if free > 0:
            if self._batcher.active:
                reqs = self._queue.take_many(free, timeout=0.0)
            else:   # nothing to tick: this take may block, for a request
                with self._batcher.phase("idle_wait"):
                    reqs = self._queue.take_many(
                        free, timeout=self._config.idle_poll)
            for req in reqs:
                with self._batcher.phase(
                        "admit", {"req": req.req_id,
                                  "prompt_len": req.prompt_len}):
                    self._batcher.admit(req)
        self._stat_set("queue_depth", len(self._queue))
        self._stat_set("deadline_evicted_queued",
                       self._queue.evicted_expired)
        self._stat_set("slots_in_use", self._batcher.active)
        if self._batcher.active:
            self._batcher.tick()
            if self.journal is not None and self._batcher.active:
                # O(1) reference enqueue; the journal's flush thread does
                # the copying (async-dispatch discipline: the tick never
                # pays for durability)
                self.journal.note(self._batcher._reqs.values())
        elif self._draining.is_set() and len(self._queue) == 0:
            return True
        self._publish_cache_stats()
        return False

    def _publish_cache_stats(self):
        s = self._cache.stats()
        self._stat_set("cache.hits", s["hits"])
        self._stat_set("cache.misses", s["misses"])
        self._stat_set("recompiles", s["misses"])
