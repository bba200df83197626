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

from ..decode import (GPTDecodeSpec, GPTStaticDecoder, _AUDIT_SPEC,
                      _AUDIT_TOP_K, _audit_params, _block_prefill,
                      _layer_norm, _mm, _sample)
from ..kvcache import dequantize_kv, is_quantized_kv, valid_mask
from .pool import (PagedKVCache, paged_gather_rows,
                   paged_write_prompt_rows, paged_write_rows,
                   pages_for_tokens)


def _write_page_index(block_tables, positions, page_size):
    """(physical page, in-page offset) of each slot's write position.
    Out-of-range positions (inactive slots whose lengths keep advancing)
    clip to the last table entry, which for a freed slot is the trash
    page — the paged analogue of the slot path's clamped
    ``dynamic_update_slice`` on inactive rows."""
    idx = jnp.clip(positions // page_size, 0,
                   block_tables.shape[1] - 1)
    pid = jnp.take_along_axis(block_tables, idx[:, None], axis=1)[:, 0]
    return pid, positions % page_size


def _paged_block_decode(spec, lp, h, kbuf, vbuf, li, block_tables, pid,
                        ppos, positions, mask, scale, attn_impl):
    """One pre-norm block for a single new token per slot — the paged
    twin of ``decode._block_decode``. ``kbuf``/``vbuf``: the whole
    ``[P+1, L, page, H, D]`` arenas; the token's K/V is scattered at
    (``pid``, ``li``, ``ppos``) before layer ``li`` attends."""
    s = h.shape[0]
    x = _layer_norm(h, lp["n1w"], lp["n1b"], spec.ln_epsilon)
    q = (_mm(x, lp["qw"]) + lp["qb"]).reshape(s, spec.num_heads,
                                              spec.head_dim)
    kn = (_mm(x, lp["kw"]) + lp["kb"]).reshape(s, spec.num_heads,
                                               spec.head_dim)
    vn = (_mm(x, lp["vw"]) + lp["vb"]).reshape(s, spec.num_heads,
                                               spec.head_dim)
    kbuf = paged_write_rows(kbuf, kn, pid, ppos, li)
    vbuf = paged_write_rows(vbuf, vn, pid, ppos, li)
    if attn_impl == "kernel":
        from ....ops.paged_attention import paged_attention
        out = paged_attention(q, kbuf, vbuf, block_tables, positions,
                              layer=li,
                              scale=scale).reshape(s, spec.hidden_size)
    else:
        kd = dequantize_kv(paged_gather_rows(kbuf, block_tables, li),
                           h.dtype)
        vd = dequantize_kv(paged_gather_rows(vbuf, block_tables, li),
                           h.dtype)
        qh = (q * scale)[:, :, None, :]                   # [S, H, 1, D]
        kt = jnp.transpose(kd, (0, 2, 1, 3))              # [S, H, max, D]
        vt = jnp.transpose(vd, (0, 2, 1, 3))
        prod = jnp.matmul(qh, jnp.swapaxes(kt, -1, -2))   # [S, H, 1, max]
        weights = jax.nn.softmax(prod + mask, axis=-1)
        out = jnp.matmul(weights, vt)                     # [S, H, 1, D]
        out = jnp.transpose(out, (0, 2, 1, 3)).reshape(s,
                                                       spec.hidden_size)
    h = h + (_mm(out, lp["ow"]) + lp["ob"])
    x = _layer_norm(h, lp["n2w"], lp["n2b"], spec.ln_epsilon)
    ffn = jax.nn.gelu(_mm(x, lp["w1"]) + lp["b1"], approximate=False)
    return h + (_mm(ffn, lp["w2"]) + lp["b2"]), kbuf, vbuf


# -- the compiled programs ---------------------------------------------------

def jit_donating_arenas(raw, arenas):
    """``jax.jit`` of a raw paged program, donating the positional
    arguments ``arenas`` (its ``kbuf`` and ``vbuf``): with the in-place
    row writes this is what lets XLA alias each arena to its output
    instead of copying it. Carries the getters' ``trace_counter``; the
    compiled module keeps the raw program's name (``jit__step``,
    ``jit__prefill``, ``jit__tail``), which dumps and traces are read by."""
    counter = {"traces": 0}

    @functools.wraps(raw)
    def _fn(*args):
        counter["traces"] += 1
        return raw(*args)

    fn = jax.jit(_fn, donate_argnums=arenas)
    fn.trace_counter = counter
    return fn


def build_paged_decode_step(spec: GPTDecodeSpec, max_top_k: int,
                            page_size: int, attn_impl: str = "gather"):
    """The RAW (un-jitted) paged decode step — the auditable program
    (PTA009 entrypoint ``llm_paged_decode_step``).

    step(params, kbuf, vbuf, block_tables, lengths, finished,
         last_tokens, temperature, top_k, do_sample, eos, key)
      -> (kbuf, vbuf, lengths+1, finished, next_tokens)

    The block table is read-only inside the step (page mapping is host
    policy, applied between ticks). The raw step is a pure function;
    the arenas run through the layer loop as one value each, written by
    scatter only, so the jitted, donating step
    (``get_paged_decode_step``) updates them in place.
    """
    if attn_impl not in ("gather", "kernel"):
        raise ValueError(f"attn_impl must be 'gather' or 'kernel', got "
                         f"{attn_impl!r}")
    scale = 1.0 / np.sqrt(spec.head_dim)
    max_pos = spec.max_position_embeddings

    def _step(params, kbuf, vbuf, block_tables, lengths, finished,
              last_tokens, temperature, top_k, do_sample, eos, key):
        max_seq = block_tables.shape[1] * page_size
        positions = lengths                   # write position per slot
        posc = jnp.clip(positions, 0, max_pos - 1)
        h = params["tok"][last_tokens] + params["pos"][posc]      # [S, E]
        mask = (valid_mask(positions, max_seq, h.dtype)
                if attn_impl == "gather" else None)
        pid, ppos = _write_page_index(block_tables, positions, page_size)
        for li, lp in enumerate(params["layers"]):
            h, kbuf, vbuf = _paged_block_decode(
                spec, lp, h, kbuf, vbuf, li, block_tables, pid, ppos,
                positions, mask, scale, attn_impl)
        h = _layer_norm(h, params["fnw"], params["fnb"], spec.ln_epsilon)
        lraw = (h @ params["tok"].T).astype(jnp.float32)          # [S, V]
        nxt = _sample(lraw, temperature, top_k, do_sample, key, max_top_k)
        nxt = jnp.where(finished & (eos >= 0), eos, nxt)
        finished = finished | ((nxt == eos) & (eos >= 0))
        return kbuf, vbuf, lengths + 1, finished, nxt

    return _step


@functools.lru_cache(maxsize=64)
def get_paged_decode_step(spec: GPTDecodeSpec, max_top_k: int,
                          page_size: int, attn_impl: str):
    """Jitted paged decode step; ``trace_counter`` contract matches
    ``get_decode_step`` (one trace per (num_pages, num_slots) shape).
    Donates ``kbuf`` and ``vbuf``."""
    return jit_donating_arenas(
        build_paged_decode_step(spec, max_top_k, page_size, attn_impl),
        arenas=(1, 2))


def build_paged_prefill_fn(spec: GPTDecodeSpec, max_top_k: int,
                           page_size: int):
    """The RAW paged prefill: identical forward math to
    ``build_prefill_fn`` (so the sampled first token is bitwise equal);
    the K/V rows scatter through each request's block-table row, with
    right-padding junk routed to the trash page instead of parked past
    the slot length."""
    scale = 1.0 / np.sqrt(spec.head_dim)

    def _prefill(params, tokens, true_lens, kbuf, vbuf, block_tables,
                 lengths, finished, slot_ids, temperature, top_k,
                 do_sample, eos, key):
        b, lp_len = tokens.shape
        trash = jax.tree_util.tree_leaves(kbuf)[0].shape[0] - 1
        pos = jnp.arange(lp_len, dtype=jnp.int32)
        h = params["tok"][tokens] + params["pos"][pos][None]   # [B, L, E]
        mask = jnp.triu(jnp.full((lp_len, lp_len), -1e9, h.dtype),
                        1)[None, None]
        kcs, vcs = [], []
        for lp in params["layers"]:
            h, k, v = _block_prefill(spec, lp, h, mask, scale)
            kcs.append(k)
            vcs.append(v)
        k_new = jnp.stack(kcs, axis=1)                 # [B, L, Lp, H, D]
        v_new = jnp.stack(vcs, axis=1)
        ppos = pos % page_size
        page_idx = pos // page_size                    # < PP: buckets
        for i in range(b):                             # fit in max_seq
            bt_row = block_tables[slot_ids[i]]         # [PP]
            pid = jnp.where(pos < true_lens[i], bt_row[page_idx], trash)
            kbuf = paged_write_prompt_rows(
                kbuf, jnp.transpose(k_new[i], (1, 0, 2, 3)), pid, ppos)
            vbuf = paged_write_prompt_rows(
                vbuf, jnp.transpose(v_new[i], (1, 0, 2, 3)), pid, ppos)
        lengths = lengths.at[slot_ids].set(true_lens)
        h = _layer_norm(h, params["fnw"], params["fnb"], spec.ln_epsilon)
        last = jnp.take_along_axis(
            h, (true_lens - 1)[:, None, None].astype(jnp.int32),
            axis=1)[:, 0]                                      # [B, E]
        lraw = (last @ params["tok"].T).astype(jnp.float32)
        nxt = _sample(lraw, temperature, top_k, do_sample, key, max_top_k)
        finished = finished.at[slot_ids].set((nxt == eos) & (eos >= 0))
        return kbuf, vbuf, lengths, finished, nxt

    return _prefill


@functools.lru_cache(maxsize=64)
def get_paged_prefill_fn(spec: GPTDecodeSpec, max_top_k: int,
                         page_size: int):
    return jit_donating_arenas(
        build_paged_prefill_fn(spec, max_top_k, page_size), arenas=(3, 4))


def build_paged_tail_prefill_fn(spec: GPTDecodeSpec, max_top_k: int,
                                page_size: int):
    """The RAW paged *tail* prefill — prefill a prompt suffix into a
    slot whose first ``starts[i]`` rows arrived as SHARED prefix pages
    (block-table splices, zero bytes copied — contrast the slot path,
    which bulk-copied them first). Attention gathers the slot's full
    logical row (shared pages + the fresh tail spliced in) under the
    same offset-causal mask, so the first sampled token is bitwise what
    a full prefill would produce."""
    scale = 1.0 / np.sqrt(spec.head_dim)
    max_pos = spec.max_position_embeddings

    def _tail(params, tokens, tail_lens, starts, kbuf, vbuf,
              block_tables, lengths, finished, slot_ids, temperature,
              top_k, do_sample, eos, key):
        if is_quantized_kv(kbuf):
            raise NotImplementedError(
                "tail prefill (prefix reuse) over int8 pages is "
                "unsupported; LLMEngineConfig gates prefix_cache off "
                "for kv_dtype='int8'")
        b, lt = tokens.shape
        pp_n = block_tables.shape[1]
        max_seq = pp_n * page_size
        trash = kbuf.shape[0] - 1
        pos = starts[:, None] + jnp.arange(lt, dtype=jnp.int32)[None]
        posc = jnp.clip(pos, 0, max_pos - 1)
        h = params["tok"][tokens] + params["pos"][posc]    # [B, Lt, E]
        j = jnp.arange(max_seq, dtype=jnp.int32)[None, None]
        mask = jnp.where(j <= pos[:, :, None], 0.0,
                         -1e9).astype(h.dtype)[:, None]    # [B,1,Lt,max]
        bt_sel = block_tables[slot_ids]                    # [B, PP]
        kcs, vcs = [], []
        for li, lp in enumerate(params["layers"]):
            x = _layer_norm(h, lp["n1w"], lp["n1b"], spec.ln_epsilon)

            def heads(t):
                return t.reshape(b, lt, spec.num_heads, spec.head_dim)

            q = heads(_mm(x, lp["qw"]) + lp["qb"])
            kn = heads(_mm(x, lp["kw"]) + lp["kb"])
            vn = heads(_mm(x, lp["vw"]) + lp["vb"])
            # attention reads the gathered logical rows with the fresh
            # tail spliced in; the arenas are written once, after the
            # layer loop
            row_k = paged_gather_rows(kbuf, bt_sel, li)
            row_v = paged_gather_rows(vbuf, bt_sel, li)

            def _splice(row, new, st):
                return jax.lax.dynamic_update_slice(row, new, (st, 0, 0))

            row_k = jax.vmap(_splice)(row_k, kn, starts)
            row_v = jax.vmap(_splice)(row_v, vn, starts)
            qh = jnp.transpose(q * scale, (0, 2, 1, 3))    # [B,H,Lt,D]
            kt = jnp.transpose(row_k, (0, 2, 1, 3))        # [B,H,max,D]
            vt = jnp.transpose(row_v, (0, 2, 1, 3))
            prod = jnp.matmul(qh, jnp.swapaxes(kt, -1, -2))
            weights = jax.nn.softmax(prod + mask, axis=-1)
            out = jnp.matmul(weights, vt)                  # [B,H,Lt,D]
            out = jnp.transpose(out, (0, 2, 1, 3)).reshape(
                b, lt, spec.hidden_size)
            h = h + (_mm(out, lp["ow"]) + lp["ob"])
            x = _layer_norm(h, lp["n2w"], lp["n2b"], spec.ln_epsilon)
            ffn = jax.nn.gelu(_mm(x, lp["w1"]) + lp["b1"],
                              approximate=False)
            h = h + (_mm(ffn, lp["w2"]) + lp["b2"])
            kcs.append(kn)
            vcs.append(vn)
        k_new = jnp.stack(kcs, axis=1)                 # [B, L, Lt, H, D]
        v_new = jnp.stack(vcs, axis=1)
        t = jnp.arange(lt, dtype=jnp.int32)
        for i in range(b):
            pos_i = starts[i] + t
            page_idx = jnp.clip(pos_i // page_size, 0, pp_n - 1)
            pid = jnp.where(t < tail_lens[i], bt_sel[i][page_idx], trash)
            kbuf = paged_write_prompt_rows(
                kbuf, jnp.transpose(k_new[i], (1, 0, 2, 3)), pid,
                pos_i % page_size)
            vbuf = paged_write_prompt_rows(
                vbuf, jnp.transpose(v_new[i], (1, 0, 2, 3)), pid,
                pos_i % page_size)
        lengths = lengths.at[slot_ids].set(starts + tail_lens)
        h = _layer_norm(h, params["fnw"], params["fnb"], spec.ln_epsilon)
        last = jnp.take_along_axis(
            h, (tail_lens - 1)[:, None, None].astype(jnp.int32),
            axis=1)[:, 0]                                      # [B, E]
        lraw = (last @ params["tok"].T).astype(jnp.float32)
        nxt = _sample(lraw, temperature, top_k, do_sample, key, max_top_k)
        finished = finished.at[slot_ids].set((nxt == eos) & (eos >= 0))
        return kbuf, vbuf, lengths, finished, nxt

    return _tail


@functools.lru_cache(maxsize=64)
def get_paged_tail_prefill_fn(spec: GPTDecodeSpec, max_top_k: int,
                              page_size: int):
    return jit_donating_arenas(
        build_paged_tail_prefill_fn(spec, max_top_k, page_size),
        arenas=(4, 5))


#: (model class, decoder class) of the families served on pages beside GPT
_PAGED_DECODERS = []


def register_paged_decoder(model_cls, decoder_cls):
    """Serve instances of ``model_cls`` through ``decoder_cls`` on the
    paged engine. A decoder class takes ``GPTPagedDecoder``'s constructor
    arguments and answers the calls ``PagedBatcher`` makes (``new_kv``,
    ``prefill``, ``decode_step``, ``params``, ``prefix_sig``,
    ``check_config``)."""
    _PAGED_DECODERS.append((model_cls, decoder_cls))


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
