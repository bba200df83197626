"""Paged prefill + decode step builders, the GPTPagedDecoder façade, and what
every other served family shares: the protocol the engine asks of a decoder
(:class:`PagedDecoderProtocol`), the one façade the families inherit
(:class:`PagedFamilyDecoder`) and the helpers their programs have in common.

Same contracts as ``serving/llm/decode.py`` with ONE extra device input
threaded through every program: the ``[num_slots, pages_per_seq]`` block
table. The forward math is untouched — only where K/V rows live changes
(scatter into the page arena instead of ``dynamic_update_slice`` into a
slot row; gather back through the block table instead of reading the
slot row directly).

The arena is written where it lies. Every program threads the whole
``[P+1, L, page, H, D]`` arrays through its layer loop as ONE value:
rows scatter in by ``(page, layer, offset)``, attention reads by
``(page, layer)``, and no layer is cut out of the arena or stacked back
into it. The raw builders stay pure functions of their arguments; the
jitted getters donate both arenas, so XLA updates them in place and a
tick moves the rows it writes, not the arena (the arrays passed in are
deleted: ``PagedKVCache.swap`` installs the outputs).

Bitwise parity with the slot path (the acceptance contract): the cache
enforces ``max_seq % page_size == 0``, so ``paged_gather_rows``
reconstructs a ``[S, max_seq, H, D]`` tensor shape-identical to a slot
buffer's layer view. Valid rows hold identical values (same projections,
same int8 quantization granularity), junk rows differ but carry the same
``-1e9`` additive mask, whose softmax weight is exactly 0.0 in f32 —
identical shapes, identical reduction order, bitwise-equal logits. The
greedy-lane parity test pins it.

Two attention implementations sit behind ``attn_impl``:

- ``"gather"`` — materialize the gathered rows in-graph and run the
  slot path's exact matmul/softmax (the parity lane; default off-TPU).
- ``"kernel"`` — the Pallas paged-attention kernel
  (``ops/paged_attention.py``) walks the block table inside the grid,
  never materializing the gather (the TPU fast path; float-equal, not
  bitwise — blocked online-softmax sums in a different order).
"""
from __future__ import annotations

import functools
from typing import Optional

import numpy as np

import jax
import jax.numpy as jnp

from ....models.gpt import FullSequence, gpt_hidden, stack_kv
from ....ops import moe as _moe
from ...cache import default_cache
from ..decode import (GPTDecodeSpec, GPTStaticDecoder, _AUDIT_SPEC,
                      _AUDIT_TOP_K, _audit_params, jit_program, last_rows,
                      sample_next)
from ..kvcache import TailRows, is_quantized_kv, valid_mask
from .pool import (PagedKVCache, PagedRows, paged_gather_rows,
                   paged_write_prompts, pages_for_tokens)


# -- the compiled programs ---------------------------------------------------

def _token_major(kv):
    """A view's per-layer ``(k, v)`` records as ``(K, V)``
    ``[B, T, L, H, D]``, the rows :func:`~.pool.paged_write_prompts`
    scatters. Stacked by layer and then swapped, not stacked on axis 2:
    the compiled prefill then keeps each layer's rows as one block, as the
    slot plane's ``[B, L, T, H, D]`` does."""
    return tuple(jnp.swapaxes(z, 1, 2) for z in stack_kv(kv, 1))


def build_paged_decode_step(spec: GPTDecodeSpec, max_top_k: int,
                            page_size: int, attn_impl: str = "gather"):
    """The RAW (un-jitted) paged decode step — the auditable program
    (PTA009 entrypoint ``llm_paged_decode_step``).

    step(params, kbuf, vbuf, block_tables, lengths, finished,
         last_tokens, temperature, top_k, do_sample, eos, key)
      -> (kbuf, vbuf, lengths+1, finished, next_tokens)

    The block table is read-only inside the step (page mapping is host
    policy, applied between ticks). The raw step is a pure function;
    the arenas run through the layer loop as one value each
    (:class:`~.pool.PagedRows`), written by scatter only, so the jitted,
    donating step (``get_paged_decode_step``) updates them in place.
    """
    if attn_impl not in ("gather", "kernel"):
        raise ValueError(f"attn_impl must be 'gather' or 'kernel', got "
                         f"{attn_impl!r}")

    def _step(params, kbuf, vbuf, block_tables, lengths, finished,
              last_tokens, temperature, top_k, do_sample, eos, key):
        # a slot's write position is its length
        view = PagedRows(kbuf, vbuf, block_tables, lengths, page_size,
                         attn_impl, params["tok"].dtype)
        h = gpt_hidden(spec, params, last_tokens, lengths, view)  # [S, E]
        nxt, finished = sample_next(params, h, finished, temperature,
                                    top_k, do_sample, eos, key, max_top_k)
        return view.kbuf, view.vbuf, lengths + 1, finished, nxt

    return _step


@functools.lru_cache(maxsize=64)
def get_paged_decode_step(spec: GPTDecodeSpec, max_top_k: int,
                          page_size: int, attn_impl: str):
    """Jitted paged decode step; ``trace_counter`` contract matches
    ``get_decode_step`` (one trace per (num_pages, num_slots) shape).
    Donates ``kbuf`` and ``vbuf``."""
    return jit_program(
        build_paged_decode_step(spec, max_top_k, page_size, attn_impl),
        donate=(1, 2))


def build_paged_prefill_fn(spec: GPTDecodeSpec, max_top_k: int,
                           page_size: int):
    """The RAW paged prefill: identical forward math to
    ``build_prefill_fn`` (so the sampled first token is bitwise equal);
    the K/V rows scatter through each request's block-table row, with
    right-padding junk routed to the trash page instead of parked past
    the slot length."""

    def _prefill(params, tokens, true_lens, kbuf, vbuf, block_tables,
                 lengths, finished, slot_ids, temperature, top_k,
                 do_sample, eos, key):
        view = FullSequence()
        pos = jnp.arange(tokens.shape[1], dtype=jnp.int32)[None]
        h = gpt_hidden(spec, params, tokens, pos, view)        # [B, L, E]
        k_new, v_new = _token_major(view.kv)           # [B, Lp, L, H, D]
        starts = jnp.zeros_like(true_lens)
        kbuf = paged_write_prompts(kbuf, k_new, block_tables, slot_ids,
                                   starts, true_lens, page_size)
        vbuf = paged_write_prompts(vbuf, v_new, block_tables, slot_ids,
                                   starts, true_lens, page_size)
        lengths = lengths.at[slot_ids].set(true_lens)
        nxt, fin = sample_next(params, last_rows(h, true_lens), False,
                               temperature, top_k, do_sample, eos, key,
                               max_top_k)
        return kbuf, vbuf, lengths, finished.at[slot_ids].set(fin), nxt

    return _prefill


@functools.lru_cache(maxsize=64)
def get_paged_prefill_fn(spec: GPTDecodeSpec, max_top_k: int,
                         page_size: int):
    return jit_program(
        build_paged_prefill_fn(spec, max_top_k, page_size), donate=(3, 4))


def build_paged_tail_prefill_fn(spec: GPTDecodeSpec, max_top_k: int,
                                page_size: int):
    """The RAW paged *tail* prefill — prefill a prompt suffix into a
    slot whose first ``starts[i]`` rows arrived as SHARED prefix pages
    (block-table splices, zero bytes copied — contrast the slot path,
    which bulk-copied them first). Attention gathers the slot's full
    logical row (shared pages + the fresh tail spliced in) under the
    same offset-causal mask, so the first sampled token is bitwise what
    a full prefill would produce. The arenas are written once, after the
    layer loop."""

    def _tail(params, tokens, tail_lens, starts, kbuf, vbuf,
              block_tables, lengths, finished, slot_ids, temperature,
              top_k, do_sample, eos, key):
        if is_quantized_kv(kbuf):
            raise NotImplementedError(
                "tail prefill (prefix reuse) over int8 pages is "
                "unsupported; LLMEngineConfig gates prefix_cache off "
                "for kv_dtype='int8'")
        max_seq = block_tables.shape[1] * page_size
        pos = starts[:, None] + jnp.arange(tokens.shape[1],
                                           dtype=jnp.int32)[None]
        bt_sel = block_tables[slot_ids]                    # [B, PP]
        view = TailRows(
            lambda buf, li: paged_gather_rows(buf, bt_sel, li), kbuf, vbuf,
            starts, valid_mask(pos, max_seq, params["tok"].dtype))
        h = gpt_hidden(spec, params, tokens, pos, view)    # [B, Lt, E]
        k_new, v_new = _token_major(view.kv)           # [B, Lt, L, H, D]
        kbuf = paged_write_prompts(kbuf, k_new, block_tables, slot_ids,
                                   starts, tail_lens, page_size)
        vbuf = paged_write_prompts(vbuf, v_new, block_tables, slot_ids,
                                   starts, tail_lens, page_size)
        lengths = lengths.at[slot_ids].set(starts + tail_lens)
        nxt, fin = sample_next(params, last_rows(h, tail_lens), False,
                               temperature, top_k, do_sample, eos, key,
                               max_top_k)
        return kbuf, vbuf, lengths, finished.at[slot_ids].set(fin), nxt

    return _tail


@functools.lru_cache(maxsize=64)
def get_paged_tail_prefill_fn(spec: GPTDecodeSpec, max_top_k: int,
                              page_size: int):
    return jit_program(
        build_paged_tail_prefill_fn(spec, max_top_k, page_size),
        donate=(4, 5))


# -- what the served families share ------------------------------------------

def _largest_divisor(n: int, cap: int) -> int:
    """The largest divisor of ``n`` that is at most ``cap``."""
    return max(p for p in range(1, min(cap, n) + 1) if n % p == 0)


def _sample(params, hidden, frozen, samp, key, max_top_k):
    """``sample_next`` against a family's own head (``[hidden, V]``: the
    transpose of a transpose folds away)."""
    return sample_next({"tok": params["head"].T}, hidden, frozen, *samp, key,
                       max_top_k)


def _tick_counters(counts):
    """``[2]`` int32: the held experts that received a token, summed over
    the expert layers, and the fullest one's tokens in any layer."""
    counts = jnp.stack(counts) if counts else jnp.zeros((1, 1), jnp.int32)
    return jnp.stack([jnp.sum(counts > 0), jnp.max(counts)]).astype(jnp.int32)


def _window_walks(counts, num_tokens: int, top_k: int, num_experts: int):
    """``[2]`` int32: the windows of their row buffers a program's expert
    layers walked, summed, and the calls that had a window to walk
    (``ops/moe.py:window_passes``; 0 and 0 where a call of these sizes takes
    its whole buffer)."""
    passes = [p for p in (_moe.window_passes(c, num_tokens, top_k,
                                             num_experts) for c in counts)
              if p is not None]
    return jnp.stack([sum(passes, jnp.int32(0)), jnp.int32(len(passes))])


def note_window_walks(pending: list, stat_add):
    """Count the ``_window_walks`` of the chunks dispatched before the tick
    whose fetch has just ended (the lane is serial: they are done), and
    forget them."""
    for passes, calls in jax.device_get(pending):  # noqa: PTA002 -- two int32 a chunk that ended before the tick's fetch did: one copy of ready values, no wait
        stat_add("moe.window_passes", int(passes))
        stat_add("moe.window_calls", int(calls))
    pending.clear()


def note_expert_tick(spec, extras, n_active: int, stat_add):
    """A tick's expert counters, from the ``_tick_counters`` fetched behind
    its tokens and the pairs ``n_active`` slots routed."""
    stat_add("moe_experts_active", int(extras[0]))
    stat_add("moe_load_max", int(extras[1]))
    stat_add("moe_pairs_routed", n_active * spec.num_experts_per_tok
             * spec.num_expert_layers)


def grouped_walk(q_heads: int, kv):
    """``tuner.space.paged_recurrence``'s arguments for the plain walk over
    an arena ``[P+1, layers, page, KV heads, row]``: the query heads that
    share a KV head, on that head's rows."""
    _, _, page, kv_heads, row = kv.k.shape
    return (q_heads // kv_heads, kv_heads, page, row, kv.k.dtype.itemsize,
            1 if kv.fused_kv else 2)


class PagedDecoderProtocol:
    """What ``PagedBatcher`` and ``LLMEngine`` ask of a paged decoder beside
    its programs (``new_kv``, ``params``, ``prefix_sig``, ``check_config``,
    ``prefill``, ``decode_step`` and, where ``prefills_in_chunks``,
    ``chunk_prefill``): abilities as class attributes, hooks that do nothing
    until a family has something to say. Nobody probes a decoder with
    ``hasattr``: what is not declared here is not asked."""

    kv_layout = "paged"
    #: sequences can be shipped as page payloads (``export_sequence`` /
    #: ``import_sequence``): the family's whole per-sequence state is pages
    supports_export = False
    #: the family has a chunk program, so ``LLMEngineConfig.prefill_chunk``
    #: may be set and every admission is ``chunk_prefill``
    prefills_in_chunks = False

    def plain_walk(self, kv):
        """The arguments of ``tuner.space.paged_recurrence`` for the plain
        walk ``paged_attn`` runs over this family's arena on the kernel
        lane, or None where none runs (every walk over selected pages)."""
        return None

    def publish_gauges(self, kv, stat_set):
        """The family's own gauges, set once when the batcher is built."""

    def note_lengths(self, seq_lens, stat_add):
        """A decode tick over sequences of ``seq_lens`` tokens (the new one
        included) is about to be dispatched: the family's own walks."""

    def note_chunk(self, start: int, n_valid: int, pages_per_seq: int,
                   stat_add):
        """A chunk of ``n_valid`` tokens behind ``start`` was dispatched."""

    def note_tick(self, extras, n_active: int, stat_add):
        """A tick's fetch has ended: ``extras`` is what the step packed
        behind its tokens (called only where it packed something)."""


class PagedFamilyDecoder(PagedDecoderProtocol):
    """The façade ``PagedBatcher`` drives, for every family but GPT: the
    constructor and its refusals, ``check_config``, the compiled programs'
    cache and the three calls that run them (``prefill``, the chunk at
    offset 0; ``chunk_prefill``; ``decode_step``), written once over what a
    family declares:

    - ``family`` (its name in messages and in the cache key), ``vocab`` (the
      configuration field ``max_top_k`` is clipped to), ``unserved_why``
      (the sentence ``check_config`` gives for ``unserved``), ``tick_fetch``
      / ``chunk_walks`` (whether a step packs counters behind its tokens and
      a chunk returns its experts' window walks);
    - ``setup()`` for what only it checks or keeps, ``new_kv``,
      ``prefix_sig``, the protocol's hooks;
    - ``step_program()`` / ``chunk_program()``: the jitted ``get_*`` it runs
      (``admits_chunk`` refuses a chunk length the program cannot take);
    - ``cache_arrays(kv)``: what a program takes of the cache, in the
      program's own order (the block tables last), and ``install(kv,
      arrays, lengths)``: how the arrays it returns go back."""

    family = ""
    vocab = "vocab_held"
    #: what ``kv_dtype`` is the dtype of, in the refusal's words
    holds = "KV"
    unserved = (("prefix_cache", False), ("spec_k", 0))
    unserved_why = ""
    tick_fetch = True
    chunk_walks = True

    def __init__(self, model, max_top_k: int = 64, exec_cache=None,
                 mesh=None, weight_dtype: str = "float32",
                 kv_dtype: str = "float32", page_size: int = 16,
                 num_pages: Optional[int] = None,
                 attn_impl: str = "auto"):
        if mesh is not None:
            raise NotImplementedError(
                f"the {self.family} paged decoder does not serve over a "
                f"mesh yet")
        if weight_dtype != "float32" or kv_dtype != "float32":
            raise NotImplementedError(
                f"the {self.family} paged decoder serves float32 weights "
                f"and {self.holds} only (got weight_dtype={weight_dtype!r}, "
                f"kv_dtype={kv_dtype!r})")
        if attn_impl not in ("auto", "gather", "kernel"):
            raise ValueError(
                f"attn_impl must be 'auto', 'gather' or 'kernel', got "
                f"{attn_impl!r}")
        self.spec = model.config
        self._model = model
        self.max_top_k = max(0, min(int(max_top_k),
                                    getattr(self.spec, self.vocab)))
        self.exec_cache = (exec_cache if exec_cache is not None
                           else default_cache())
        if attn_impl == "auto":
            attn_impl = ("kernel" if jax.default_backend() == "tpu"
                         else "gather")
        self.attn_impl = attn_impl
        self.page_size = int(page_size)
        self.num_pages = None if num_pages is None else int(num_pages)
        #: the window walks of the chunks no tick has counted yet
        self._walks = []
        self._key = (self.family.lower().replace("-", "") + "-paged",
                     self.spec, self.max_top_k, self.page_size,
                     self.attn_impl)
        self.setup()

    def setup(self):
        """What only this family checks or keeps (the constructor's last
        step: ``spec``, ``page_size`` and ``attn_impl`` are set)."""

    def check_config(self, config):
        """The engine options this family does not serve yet."""
        for name, off in self.unserved:
            if getattr(config, name) != off:
                raise NotImplementedError(
                    f"the {self.family} paged decoder does not support "
                    f"{name} yet ({self.unserved_why})")
        chunk = config.prefill_chunk
        if self.prefills_in_chunks and chunk is not None \
                and chunk % config.page_size:
            raise ValueError(
                f"prefill_chunk {chunk} must be a multiple of the page "
                f"size {config.page_size}: a chunk starts on a page")

    @property
    def model(self):
        return self._model

    def params(self):
        return self._model.param_tree()

    def check_max_seq(self, max_seq: int):
        """``new_kv``'s refusal of more rows than the model has positions."""
        if max_seq > self.spec.max_position_embeddings:
            raise ValueError(
                f"max_seq {max_seq} exceeds the model's "
                f"{self.spec.max_position_embeddings} positions")

    # -- compiled-program access --------------------------------------------
    def step_program(self):
        raise NotImplementedError

    def chunk_program(self):
        raise NotImplementedError

    def admits_chunk(self, chunk_len: int):
        """Raises for a chunk length the chunk program cannot take."""

    def decode_fn(self, num_slots: int, max_seq: int):
        return self.exec_cache.get_or_compile(
            self._key + ("decode", num_slots, max_seq), self.step_program)

    def chunk_fn(self, chunk_len: int):
        self.admits_chunk(chunk_len)
        return self.exec_cache.get_or_compile(
            self._key + ("chunk", chunk_len), self.chunk_program)

    # -- the calls the batcher makes ----------------------------------------
    def cache_arrays(self, kv: PagedKVCache) -> tuple:
        raise NotImplementedError

    def install(self, kv: PagedKVCache, arrays, lengths):
        raise NotImplementedError

    def chunk_prefill(self, kv: PagedKVCache, params, tokens, start: int,
                      n_valid: int, is_last: bool, slot: int, finished,
                      samp_vecs, key):
        """Run ``tokens`` ``[1, T]`` (the first ``n_valid`` real) of slot
        ``slot`` behind its ``start`` cached tokens: ``(next token [1],
        finished)``."""
        out = self.chunk_fn(tokens.shape[1])(
            params, tokens, jnp.asarray(start, jnp.int32),
            jnp.asarray(n_valid, jnp.int32), jnp.asarray(is_last, bool),
            *self.cache_arrays(kv), kv.lengths, finished,
            jnp.asarray(slot, jnp.int32), *samp_vecs, key)
        n = len(out) - self.chunk_walks
        *arrays, lengths, finished, nxt = out[:n]
        self.install(kv, arrays, lengths)
        self._walks.extend(out[n:])
        return nxt, finished

    def prefill(self, kv: PagedKVCache, params, tokens, true_lens,
                slot_ids, finished, samp_vecs, key):
        """A whole prompt: the chunk at offset 0 (one request a call)."""
        if tokens.shape[0] != 1:
            raise NotImplementedError(
                f"the {self.family} paged decoder prefills one request a "
                f"call")
        return self.chunk_prefill(kv, params, tokens, 0, true_lens[0], True,
                                  slot_ids[0], finished, samp_vecs, key)

    def decode_step(self, kv: PagedKVCache, params, finished, last_tokens,
                    samp_vecs, key):
        """Advance every slot one token: ``(next tokens, finished)`` and,
        where ``tick_fetch``, the tokens with the tick's counters behind
        them (what the host fetches in their place)."""
        out = self.decode_fn(kv.num_slots, kv.max_seq)(
            params, *self.cache_arrays(kv), kv.lengths, finished,
            last_tokens, *samp_vecs, key)
        n = len(out) - self.tick_fetch
        *arrays, lengths, finished, nxt = out[:n]
        self.install(kv, arrays, lengths)
        return (nxt, finished, *out[n:])


#: (model class, decoder class) of the families served on pages beside GPT
_PAGED_DECODERS = []


def register_paged_decoder(model_cls, decoder_cls):
    """Serve instances of ``model_cls`` through ``decoder_cls`` on the
    paged engine. A decoder class takes ``GPTPagedDecoder``'s constructor
    arguments and is a :class:`PagedDecoderProtocol`; a
    :class:`PagedFamilyDecoder` brings its views, its cache and its counters
    and inherits the rest."""
    _PAGED_DECODERS.append((model_cls, decoder_cls))


def plain_walk_recurrence(decoder, kv) -> Optional[str]:
    """``"mxu"`` or ``"vpu"``: the recurrence ``paged_attn``'s plain walk
    runs over this cache's pages (``tuner.space.paged_recurrence``, from
    the call's shapes alone, so one answer an engine); None where no plain
    walk runs: on the gather lane, and for a family that declares none."""
    from ....tuner.space import paged_recurrence
    walk = decoder.plain_walk(kv) if decoder.attn_impl == "kernel" else None
    return None if walk is None else paged_recurrence(*walk)


def paged_decoder_class(model):
    """The decoder family of ``model``: a registered one, GPT by default."""
    for model_cls, decoder_cls in _PAGED_DECODERS:
        if isinstance(model, model_cls):
            return decoder_cls
    return GPTPagedDecoder


class GPTPagedDecoder(GPTStaticDecoder, PagedDecoderProtocol):
    """GPTStaticDecoder with the KV substrate swapped for pages: same
    model façade, same ExecutableCache accounting, but ``new_kv``
    returns a :class:`PagedKVCache` and every compiled program threads
    its block table. ``attn_impl``: ``"auto"`` picks the Pallas kernel
    on TPU (dense arenas) and the gather lane elsewhere; the lane taken
    is ``self.attn_impl`` and the engine's ``stats()["paged_attn_impl"]``."""

    #: a sequence's whole state is K and V pages
    supports_export = True

    def __init__(self, model, max_top_k: int = 64, exec_cache=None,
                 mesh=None, slot_axis: str = "model",
                 weight_dtype: str = "float32",
                 kv_dtype: str = "float32", page_size: int = 16,
                 num_pages: Optional[int] = None,
                 attn_impl: str = "auto"):
        if mesh is not None:
            raise NotImplementedError(
                "paged KV over a slot-sharded mesh is not supported yet "
                "— the arena would need a page-granular GSPMD "
                "partitioning; use kv_layout='slot' with a mesh")
        super().__init__(model, max_top_k=max_top_k,
                         exec_cache=exec_cache, mesh=None,
                         slot_axis=slot_axis, weight_dtype=weight_dtype,
                         kv_dtype=kv_dtype)
        if page_size < 1:
            raise ValueError(f"page_size must be >= 1, got {page_size}")
        if attn_impl not in ("auto", "gather", "kernel"):
            raise ValueError(
                f"attn_impl must be 'auto', 'gather' or 'kernel', got "
                f"{attn_impl!r}")
        if attn_impl == "kernel" and kv_dtype == "int8":
            raise ValueError(
                "the paged kernel lane reads dense arenas; int8 pages "
                "use attn_impl='gather' (dequantize in-graph)")
        if attn_impl == "auto":
            on_tpu = jax.default_backend() == "tpu"
            attn_impl = ("kernel" if on_tpu and kv_dtype != "int8"
                         else "gather")
        self.attn_impl = attn_impl
        self.page_size = int(page_size)
        self.num_pages = None if num_pages is None else int(num_pages)
        self._key = self._key + ("paged", self.page_size, self.attn_impl)

    @staticmethod
    def check_config(config):
        """Raises for an engine option this family does not serve (GPT
        serves them all)."""

    def plain_walk(self, kv):
        return grouped_walk(self.spec.num_heads, kv)

    def new_kv(self, num_slots: int, max_seq: int) -> PagedKVCache:
        if max_seq > self.spec.max_position_embeddings:
            raise ValueError(
                f"max_seq {max_seq} exceeds the model's "
                f"{self.spec.max_position_embeddings} positions")
        dtype = self._model.gpt.word_embeddings.weight._data.dtype
        return PagedKVCache(num_slots, self.spec.num_layers, max_seq,
                            self.spec.num_heads, self.spec.head_dim,
                            dtype=dtype,
                            kv_dtype=("int8" if self.kv_dtype == "int8"
                                      else None),
                            page_size=self.page_size,
                            num_pages=self.num_pages)

    # -- compiled-program access --------------------------------------------
    def decode_fn(self, num_slots: int, max_seq: int):
        return self.exec_cache.get_or_compile(
            self._key + ("decode", num_slots, max_seq),
            lambda: get_paged_decode_step(self.spec, self.max_top_k,
                                          self.page_size, self.attn_impl))

    def prefill_fn(self, batch: int, prompt_len: int):
        return self.exec_cache.get_or_compile(
            self._key + ("prefill", batch, prompt_len),
            lambda: get_paged_prefill_fn(self.spec, self.max_top_k,
                                         self.page_size))

    def tail_prefill_fn(self, batch: int, tail_len: int):
        return self.exec_cache.get_or_compile(
            self._key + ("tail_prefill", batch, tail_len),
            lambda: get_paged_tail_prefill_fn(self.spec, self.max_top_k,
                                              self.page_size))

    def insert_prefix_fn(self, prefix_len: int):
        raise NotImplementedError(
            "paged prefix reuse shares pages via the block table "
            "(PagedPrefixStore) — there is no bulk copy to compile")

    def insert_prefix(self, kv, k_pre, v_pre, slot: int):
        raise NotImplementedError(
            "paged prefix reuse shares pages via the block table "
            "(PagedPrefixStore.lookup + PagedKVCache.adopt_shared_page)"
            " — bulk-copying would defeat the zero-copy contract")

    def prefix_sig(self, kv: PagedKVCache):
        """Paged prefix entries are page-id lists into THIS cache's
        arena, so the signature also pins the page size (a different
        page size re-buckets every row)."""
        return (self.spec.num_layers, self.spec.num_heads,
                self.spec.head_dim, str(kv.dtype), self.page_size)

    # -- convenience wrappers (same signatures as the slot decoder) ----------
    def prefill(self, kv: PagedKVCache, params, tokens, true_lens,
                slot_ids, finished, samp_vecs, key):
        fn = self.prefill_fn(tokens.shape[0], tokens.shape[1])
        k, v, lengths, finished, nxt = fn(
            params, tokens, true_lens, kv.k, kv.v, kv.block_tables,
            kv.lengths, finished, slot_ids, *samp_vecs, key)
        kv.swap(k, v, lengths)
        return nxt, finished

    def tail_prefill(self, kv: PagedKVCache, params, tokens, tail_lens,
                     starts, slot_ids, finished, samp_vecs, key):
        if kv.quantized:
            raise NotImplementedError(
                "tail_prefill over int8 pages is unsupported; "
                "LLMEngineConfig gates prefix_cache off for "
                "kv_dtype='int8'")
        fn = self.tail_prefill_fn(tokens.shape[0], tokens.shape[1])
        k, v, lengths, finished, nxt = fn(
            params, tokens, tail_lens, starts, kv.k, kv.v,
            kv.block_tables, kv.lengths, finished, slot_ids, *samp_vecs,
            key)
        kv.swap(k, v, lengths)
        return nxt, finished

    def decode_step(self, kv: PagedKVCache, params, finished,
                    last_tokens, samp_vecs, key):
        fn = self.decode_fn(kv.num_slots, kv.max_seq)
        k, v, lengths, finished, nxt = fn(
            params, kv.k, kv.v, kv.block_tables, kv.lengths, finished,
            last_tokens, *samp_vecs, key)
        kv.swap(k, v, lengths)
        return nxt, finished

    # -- live sequence migration (docs/fault_tolerance.md) -------------------
    def export_sequence(self, kv: PagedKVCache, slot: int, n_tokens: int):
        """Snapshot the device half of a live sequence: host copies of
        the arena pages backing logical rows ``[0, n_tokens)``. Returns
        ``(page_ids, k_pages, v_pages)`` — the payload the migrator
        wraps into a :class:`~paddle_tpu.serving.fleet.migrate.
        SequenceManifest`. The sampling/progress half (tokens, RNG
        discipline, position) is host-derivable and assembled by the
        batcher; only the KV rows need a device fetch. Runs between
        decode ticks (engine worker), never inside one."""
        n_pages = pages_for_tokens(n_tokens, self.page_size)
        pids = kv.slot_page_ids(slot)[:n_pages]
        if len(pids) < n_pages:
            raise ValueError(
                f"slot {slot} maps {len(pids)} pages but {n_pages} are "
                f"needed for {n_tokens} cached tokens")
        k_pages, v_pages = kv.read_pages(pids)
        return pids, k_pages, v_pages

    def import_sequence(self, kv: PagedKVCache, slot: int, n_tokens: int,
                        k_pages, v_pages, shared_pages: int = 0):
        """Splice an exported sequence into ``slot``: pages
        ``[0, shared_pages)`` were already adopted zero-copy from this
        engine's prefix store (the chain-hash path); the remaining tail
        pages are allocated here and filled from the shipped payload.
        Installs the resume position so the next decode tick writes the
        exact next token."""
        total = pages_for_tokens(n_tokens, self.page_size)
        if not (0 <= shared_pages <= total):
            raise ValueError(
                f"shared_pages {shared_pages} out of range for "
                f"{total} total pages")
        kv.ensure_pages(slot, n_tokens)
        pids = kv.slot_page_ids(slot)
        tmap = jax.tree_util.tree_map
        for i in range(shared_pages, total):
            kv.write_page(pids[i],
                          tmap(lambda x, i=i: x[i], k_pages),
                          tmap(lambda x, i=i: x[i], v_pages))
        kv.set_length(slot, n_tokens)
        return total - shared_pages


# -- trace-audit registration (tools/analyze/trace, PTA009/PTA012) -----------

def _audit_paged_decode_spec():
    """Tiny paged geometry: 2 slots, max_seq 16 over 4-token pages, an
    8-page pool (+trash), both block tables fully pre-mapped. Proves the
    paged tick stays one fused zero-host-transfer program — the block
    table rides as a device input, never as host control flow."""
    from ....core import audit
    spec = _AUDIT_SPEC
    slots, page, phys = 2, 4, 8

    def make_args(variant):
        rng = np.random.default_rng(8642 + variant)
        arena = (phys + 1, spec.num_layers, page, spec.num_heads,
                 spec.head_dim)
        return (_audit_params(rng),
                jnp.zeros(arena, jnp.float32),
                jnp.zeros(arena, jnp.float32),
                jnp.asarray([[0, 1, 2, 3], [4, 5, 6, 7]], jnp.int32),
                jnp.asarray([3, 1], jnp.int32),           # lengths
                jnp.zeros((slots,), bool),                # finished
                jnp.asarray(rng.integers(0, spec.vocab_size, slots),
                            jnp.int32),                   # last_tokens
                jnp.ones((slots,), jnp.float32),          # temperature
                jnp.zeros((slots,), jnp.int32),           # top_k
                jnp.zeros((slots,), bool),                # do_sample
                jnp.full((slots,), -1, jnp.int32),        # eos
                jax.random.PRNGKey(variant))
    return audit.AuditSpec(
        fn=build_paged_decode_step(spec, _AUDIT_TOP_K, 4, "gather"),
        make_args=make_args)


def _register_audit_entrypoints():
    from ....core import audit
    audit.register_entrypoint("llm_paged_decode_step",
                              _audit_paged_decode_spec,
                              tags=("serving", "decode", "paged",
                                    "bench"))


_register_audit_entrypoints()
