"""The paged programs of the LFM2-MoE family and ``LFM2PagedDecoder``.

A second decoder family behind the same ``PagedBatcher``: the block is
``models.lfm2.lfm2_block`` and only the cache views differ. The prefill runs
whole prompts through ``FullSequence`` and stores what it recorded; the
decode step's view (:class:`PagedStep`) keeps two kinds of per-sequence
state side by side in one ``PagedKVCache``:

- the KV arena ``[P+1, attention layers, page, KV heads, 2 * D]``: its
  layer axis counts attention layers only, its head axis KV heads, and a
  row holds the head's key and value side by side (``fused_kv`` of
  ``paged/pool.py``: with D = 64 a K-only row would be half a lane row and
  the device would lay the arena out in another order than the kernel
  reads, a whole-arena copy in and out of every program);
- the convolution state ``state["conv"]`` ``[slots, conv layers, L-1,
  hidden]`` (a named row per slot of the cache's ``state``): the last
  ``L-1`` inputs of each short convolution (hidden on the lane axis). The
  prefill writes a slot's whole row from its own prompt (zeros where the
  prompt is shorter than ``L-1``), the decode step rolls it in place, and
  it is donated and returned with the arenas.

The decode step also returns, packed behind the next tokens so that the
tick's one fetch brings them, two counters: the distinct experts that
received a token (summed over the expert layers) and the fullest expert's
tokens in any layer.

The façade is ``paged/decode.py:PagedFamilyDecoder``, which also refuses what
no family but GPT serves yet (a mesh, int8, ``prefix_cache``, ``spec_k``,
sequence export); the class here declares what is this family's own.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from ....ops.paged_attention import paged_attention
from ....models.lfm2 import (FullSequence, LFM2Config, LFM2ForCausalLM,
                             lfm2_hidden)
from ..decode import jit_program, last_rows, sample_next
from .decode import (PagedFamilyDecoder, grouped_walk, note_expert_tick,
                     register_paged_decoder)
from .pool import (PagedKVCache, paged_gather_rows, paged_row_index,
                   paged_write_prompts, paged_write_rows)


class PagedStep:
    """The cache view of one decode step: one new token per slot, the past
    in the KV pages and the convolution state. Holds the (traced) arenas
    and state and replaces them as layers write: read them back when the
    layers are done."""

    def __init__(self, kvbuf, state, block_tables, positions,
                 page_size: int, attn_impl: str):
        self.kvbuf, self.state = kvbuf, state
        self.block_tables, self.positions = block_tables, positions
        self.attn_impl = attn_impl
        self.pid, self.ppos = paged_row_index(block_tables, positions,
                                              page_size)
        max_seq = block_tables.shape[1] * page_size
        self.mask = None if attn_impl == "kernel" else jnp.where(
            jnp.arange(max_seq)[None] <= positions[:, None], 0.0, -1e9)

    def conv(self, ci, z, kern):
        window = jnp.concatenate([self.state[:, ci], z], axis=1)  # [S, L, h]
        self.state = self.state.at[:, ci].set(window[:, 1:])
        return sum(kern[:, j] * window[:, j]
                   for j in range(kern.shape[1]))[:, None]

    def attend(self, ai, q, k, v, scale):
        s, _, hq, d = q.shape
        self.kvbuf = paged_write_rows(
            self.kvbuf, jnp.concatenate([k[:, 0], v[:, 0]], axis=-1),
            self.pid, self.ppos, ai)
        if self.attn_impl == "kernel":
            return paged_attention(q[:, 0], self.kvbuf, None,
                                   self.block_tables, self.positions,
                                   layer=ai, scale=scale)[:, None]
        kd, vd = jnp.split(paged_gather_rows(self.kvbuf, self.block_tables,
                                             ai), 2, axis=-1)
        qg = (q[:, 0] * scale).reshape(s, kd.shape[2], -1, d)  # [S,Hkv,G,D]
        prod = jnp.einsum("skgd,smkd->skgm", qg, kd)
        weights = jax.nn.softmax(prod + self.mask[:, None, None], axis=-1)
        return jnp.einsum("skgm,smkd->skgd", weights, vd).reshape(
            s, 1, hq, d)


def build_lfm2_paged_decode_step(cfg: LFM2Config, max_top_k: int,
                                 page_size: int, attn_impl: str = "gather"):
    """The RAW paged decode step of this family.

    step(params, kvbuf, state, block_tables, lengths, finished,
         last_tokens, temperature, top_k, do_sample, eos, key)
      -> (kvbuf, state, lengths+1, finished, next_tokens, fetch)

    ``fetch`` is ``[S + 2]`` int32: the next tokens, then the experts that
    received a token (summed over the expert layers) and the fullest
    expert's tokens in any layer."""
    if attn_impl not in ("gather", "kernel"):
        raise ValueError(f"attn_impl must be 'gather' or 'kernel', got "
                         f"{attn_impl!r}")

    def _step(params, kvbuf, state, block_tables, lengths, finished,
              last_tokens, temperature, top_k, do_sample, eos, key):
        view = PagedStep(kvbuf, state, block_tables, lengths,
                         page_size, attn_impl)
        h, counts = lfm2_hidden(cfg, params, last_tokens[:, None],
                                lengths[:, None], view)
        nxt, finished = sample_next(params, h[:, 0], finished, temperature,
                                    top_k, do_sample, eos, key, max_top_k)
        counts = jnp.stack(counts) if counts else jnp.zeros((1, 1), jnp.int32)
        fetch = jnp.concatenate([nxt, jnp.stack(
            [jnp.sum(counts > 0), jnp.max(counts)]).astype(jnp.int32)])
        return view.kvbuf, view.state, lengths + 1, finished, nxt, fetch

    return _step


@functools.lru_cache(maxsize=64)
def get_lfm2_paged_decode_step(cfg: LFM2Config, max_top_k: int,
                               page_size: int, attn_impl: str):
    return jit_program(
        build_lfm2_paged_decode_step(cfg, max_top_k, page_size, attn_impl),
        donate=(1, 2))


def build_lfm2_paged_prefill_fn(cfg: LFM2Config, max_top_k: int,
                                page_size: int):
    """The RAW paged prefill: whole right-padded prompts through the block
    with no past; the attention layers' K/V rows scatter through each
    request's block-table row (padding to the trash page) and the
    convolution layers' last inputs overwrite the slots' state rows."""

    def _prefill(params, tokens, true_lens, kvbuf, state, block_tables,
                 lengths, finished, slot_ids, temperature, top_k,
                 do_sample, eos, key):
        pos = jnp.arange(tokens.shape[1], dtype=jnp.int32)
        view = FullSequence(true_lens)
        h, _ = lfm2_hidden(cfg, params, tokens, pos[None], view)
        kv_new = jnp.stack([jnp.concatenate(kv, axis=-1) for kv in view.kv],
                           axis=2)                      # [B, Lp, La, H, 2D]
        kvbuf = paged_write_prompts(kvbuf, kv_new, block_tables, slot_ids,
                                    jnp.zeros_like(true_lens), true_lens,
                                    page_size)
        state = state.at[slot_ids].set(jnp.stack(view.conv_tails, axis=1))
        lengths = lengths.at[slot_ids].set(true_lens)
        nxt, fin = sample_next(params, last_rows(h, true_lens), False,
                               temperature, top_k, do_sample, eos, key,
                               max_top_k)
        finished = finished.at[slot_ids].set(fin)
        return kvbuf, state, lengths, finished, nxt

    return _prefill


@functools.lru_cache(maxsize=64)
def get_lfm2_paged_prefill_fn(cfg: LFM2Config, max_top_k: int,
                              page_size: int):
    return jit_program(
        build_lfm2_paged_prefill_fn(cfg, max_top_k, page_size),
        donate=(3, 4))


class LFM2PagedDecoder(PagedFamilyDecoder):
    """``PagedFamilyDecoder`` for an ``LFM2ForCausalLM``: whole prompts
    through its own prefill program (no chunk program), the convolution
    state beside the pages."""

    family = "LFM2"
    vocab = "vocab_size"
    unserved_why = ("the convolution state has no prefix-reuse or rollback "
                    "path")

    def setup(self):
        if not (self.spec.attn_layers and self.spec.conv_layers):
            raise NotImplementedError(
                "the LFM2 paged decoder needs at least one attention and "
                "one convolution layer")

    def new_kv(self, num_slots: int, max_seq: int) -> PagedKVCache:
        c = self.spec
        self.check_max_seq(max_seq)
        return PagedKVCache(
            num_slots, len(c.attn_layers), max_seq, c.num_key_value_heads,
            c.head_dim, dtype=self.params()["tok"].dtype,
            page_size=self.page_size, num_pages=self.num_pages,
            state_rows={"conv": ("slot", (len(c.conv_layers),
                                          c.conv_L_cache - 1,
                                          c.hidden_size))}, fused_kv=True)

    def plain_walk(self, kv: PagedKVCache):
        return grouped_walk(self.spec.num_attention_heads, kv)

    def publish_gauges(self, kv: PagedKVCache, stat_set):
        stat_set("conv_state_bytes", kv.state_bytes())

    def note_tick(self, extras, n_active: int, stat_add):
        note_expert_tick(self.spec, extras, n_active, stat_add)

    def prefix_sig(self, kv: PagedKVCache):
        c = self.spec
        return (len(c.attn_layers), c.num_key_value_heads, c.head_dim,
                str(kv.dtype), self.page_size)

    # -- its programs and what they take of the cache ------------------------
    def step_program(self):
        return get_lfm2_paged_decode_step(self.spec, self.max_top_k,
                                          self.page_size, self.attn_impl)

    def prefill_fn(self, batch: int, prompt_len: int):
        return self.exec_cache.get_or_compile(
            self._key + ("prefill", batch, prompt_len),
            lambda: get_lfm2_paged_prefill_fn(self.spec, self.max_top_k,
                                              self.page_size))

    def cache_arrays(self, kv: PagedKVCache):
        return kv.k, kv.state["conv"], kv.block_tables

    def install(self, kv: PagedKVCache, arrays, lengths):
        k, conv = arrays
        kv.swap(k, kv.v, lengths, {"conv": conv})

    def prefill(self, kv: PagedKVCache, params, tokens, true_lens,
                slot_ids, finished, samp_vecs, key):
        fn = self.prefill_fn(tokens.shape[0], tokens.shape[1])
        *arrays, lengths, finished, nxt = fn(
            params, tokens, true_lens, *self.cache_arrays(kv), kv.lengths,
            finished, slot_ids, *samp_vecs, key)
        self.install(kv, arrays, lengths)
        return nxt, finished


register_paged_decoder(LFM2ForCausalLM, LFM2PagedDecoder)
