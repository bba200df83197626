"""Paged prefill + decode step builders and the GPTPagedDecoder façade.

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


#: (model class, decoder class) of the families served on pages beside GPT
_PAGED_DECODERS = []


def register_paged_decoder(model_cls, decoder_cls):
    """Serve instances of ``model_cls`` through ``decoder_cls`` on the
    paged engine. A decoder class takes ``GPTPagedDecoder``'s constructor
    arguments and answers the calls ``PagedBatcher`` makes (``new_kv``,
    ``prefill``, ``decode_step``, ``params``, ``prefix_sig``,
    ``check_config``)."""
    _PAGED_DECODERS.append((model_cls, decoder_cls))


def plain_walk_recurrence(decoder, kv) -> Optional[str]:
    """``"mxu"`` or ``"vpu"``: the recurrence ``paged_attn``'s plain walk
    runs over this cache's pages (``tuner.space.paged_recurrence``, from
    the call's shapes alone, so one answer an engine); None where no plain
    walk runs: on the gather lane, and for a family whose every walk is
    over selected pages of a head-major arena."""
    from ....tuner.space import paged_recurrence
    if getattr(decoder, "attn_impl", None) != "kernel":
        return None
    spec = decoder.spec
    if getattr(decoder, "latent", None) is not None:
        # latent rows: every query head on the one row a token keeps
        _, _, page, row = kv.k.shape
        return paged_recurrence(spec.num_attention_heads, 1, page, row,
                                kv.k.dtype.itemsize, 1)
    if getattr(decoder, "head_major_walk", False):
        # a head-major arena walked plainly: a KV head a call, its query
        # heads on the head's own fused rows
        _, _, page, row = kv.k.shape
        return paged_recurrence(
            spec.num_attention_heads // spec.num_key_value_heads, 1, page,
            row, kv.k.dtype.itemsize, 1)
    if getattr(kv.k, "ndim", 0) != 5:
        return None
    q_heads = getattr(spec, "num_attention_heads", None) or spec.num_heads
    _, _, page, kv_heads, row = kv.k.shape
    return paged_recurrence(q_heads // kv_heads, kv_heads, page, row,
                            kv.k.dtype.itemsize, 1 if kv.fused_kv else 2)


def paged_decoder_class(model):
    """The decoder family of ``model``: a registered one, GPT by default."""
    for model_cls, decoder_cls in _PAGED_DECODERS:
        if isinstance(model, model_cls):
            return decoder_cls
    return GPTPagedDecoder


class GPTPagedDecoder(GPTStaticDecoder):
    """GPTStaticDecoder with the KV substrate swapped for pages: same
    model façade, same ExecutableCache accounting, but ``new_kv``
    returns a :class:`PagedKVCache` and every compiled program threads
    its block table. ``attn_impl``: ``"auto"`` picks the Pallas kernel
    on TPU (dense arenas) and the gather lane elsewhere; the lane taken
    is ``self.attn_impl`` and the engine's ``stats()["paged_attn_impl"]``."""

    kv_layout = "paged"

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
