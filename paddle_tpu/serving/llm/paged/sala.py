"""The paged programs of the MiniCPM-SALA family and ``SALAPagedDecoder``.

A third decoder family behind the same ``PagedBatcher``: the block is
``models.sala.sala_block`` and only the cache views differ. One
``PagedKVCache`` holds three kinds of per-sequence state:

- the KV arena ``[P+1, sparse layers * Hkv, page, 2 * D]``, for the sparse
  layers only. It is head-major with fused rows: arena row ``ai * Hkv + h``
  is sparse layer ``ai``'s KV head ``h``, and a row of a page holds that
  head's key and value side by side. Block-sparse attention gives every KV
  head pages of its own to read, so a head's rows of a page lie together
  and one copy of the kernel brings them (``ops/paged_attention.py``,
  ``selected``). A selection block is a page (``page_size`` must equal the
  configuration's ``sparse_block_size``);
- the compressed keys ``state["ckey"]`` ``[P+1, sparse layers, kernels a
  page, Hkv, D]``: the selection's index, kept by PAGE so that it is mapped,
  freed and evicted with its page. Kernel ``j`` (rows ``[stride * j, stride
  * j + kernel)``) is stored with the page its first row lies in; the last
  kernels of a page reach into the next page, so they are written when the
  next page's first rows arrive;
- the linear states ``state["lin"]`` ``[slots, linear layers, H, D, D]``
  (float32), a row a slot: a prompt's first chunk starts from zeros, every
  later chunk and every decode step from what the last one left.

Two views. :class:`PagedStep` is one decode step (one token a slot): it
writes the token's K|V row, completes a compressed key where the token ends
a kernel, selects (every live page while the context is at most
``dense_len``) and reads the selected pages, through the kernel or, on the
gather lane, through a gather of the slot's rows under the selection's mask;
the linear layers roll their states. A slot whose ``finished`` flag is set
(free, or still being prefilled) is left alone: its row goes to the trash
page and its states stay. :class:`PagedChunk` is up to ``T`` tokens of ONE
slot behind what is already cached, the program of every prefill (a whole
prompt is the chunk at offset 0): it writes the chunk's rows, completes the
compressed keys that end inside it, selects per query token, and attends
blockwise over ALL the slot's pages up to the chunk's end under the
selection's mask (the result is the sparse one; what the blockwise lane
computes and what the selection needs are both counted, see
``SALAPagedDecoder.note_chunk``). Offset and true length are arguments: one
compiled program serves every chunk of every prompt.

The façade is ``paged/decode.py:PagedFamilyDecoder``, which also refuses what
no family but GPT serves yet (a mesh, int8, ``prefix_cache``, ``spec_k``,
sequence export); the class here declares what is this family's own.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np

from ....models.sala import (MiniCPMSALAForCausalLM, SALAConfig,
                             block_sparse_attention, sala_hidden,
                             selected_blocks)
from ....ops.linear_attention import decayed_linear_attention
from ....ops.paged_attention import paged_attention
from ..decode import jit_program
from .decode import (PagedFamilyDecoder, _largest_divisor, _sample,
                     register_paged_decoder)
from .pool import PagedKVCache, paged_row_index

#: query rows of the chunk's sparse attention computed at once, and the most
#: pages of one step of its walk over the slot's pages (scores of ``Q_ROWS x
#: heads x TILE_PAGES * page`` floats)
Q_ROWS, TILE_PAGES = 256, 32
_NEG = -1e30


def _head_rows(cfg: SALAConfig, ai: int):
    """Arena rows of sparse layer ``ai``'s KV heads."""
    return ai * cfg.num_key_value_heads + jnp.arange(cfg.num_key_value_heads)


def table_width(cfg: SALAConfig, pages_per_seq: int) -> int:
    """Columns of a selected-pages table: the selection's ``topk``, or the
    pages of a context still dense, whichever is more."""
    dense = -(-cfg.sparse_dense_len // cfg.sparse_block_size)
    return min(max(cfg.sparse_topk, dense), pages_per_seq)


def _page_tables(blocks, block_tables, width: int):
    """The selection as the kernel reads it: ``blocks`` bool ``[S, Hkv,
    PP]`` -> (physical pages ``[S, Hkv, width]`` in ascending logical
    order, counts ``[S, Hkv]``)."""
    pp = blocks.shape[-1]
    order = jnp.sort(jnp.where(blocks, jnp.arange(pp), pp), axis=-1)
    order = jnp.minimum(order[..., :width], pp - 1)
    tables = jnp.take_along_axis(
        jnp.broadcast_to(block_tables[:, None], blocks.shape), order, axis=-1)
    return tables, jnp.minimum(jnp.sum(blocks, axis=-1), width)


class PagedStep:
    """The cache view of one decode step: one new token per slot, the past
    in the KV pages, the compressed keys and the linear states. Holds the
    (traced) arrays and replaces them as layers write: read them back when
    the layers are done."""

    def __init__(self, cfg: SALAConfig, kvbuf, state, block_tables,
                 positions, frozen, attn_impl: str):
        self.cfg, self.kvbuf = cfg, kvbuf
        self.ckey, self.lin = state["ckey"], state["lin"]
        self.lin_new = [None] * self.lin.shape[1]
        self.block_tables, self.positions = block_tables, positions
        self.frozen, self.attn_impl = frozen, attn_impl
        self.page = kvbuf.shape[2]
        self.trash = kvbuf.shape[0] - 1
        pid, self.ppos = paged_row_index(block_tables, positions, self.page)
        self.pid = jnp.where(frozen, self.trash, pid)

    @property
    def state(self):
        """The states after the step: the linear layers' are laid together
        once (a layer's update in place of its own would copy the whole
        array a layer, compiled for a v5e)."""
        return {"ckey": self.ckey, "lin": jnp.stack(self.lin_new, axis=1)}

    def _complete_kernel(self, ai, rows):
        """Token ``positions`` ends the kernel that starts ``kernel - 1``
        rows before it, where that start is on the stride: its mean goes to
        the page the kernel starts in."""
        cfg = self.cfg
        ks, st, r = (cfg.sparse_kernel_size, cfg.sparse_kernel_stride,
                     cfg.kernels_per_block)
        n = self.positions + 1
        j = jnp.maximum(n - ks, 0) // st
        done = (n >= ks) & ((n - ks) % st == 0) & ~self.frozen
        back = jnp.maximum(n[:, None] - ks + jnp.arange(ks)[None], 0)
        bpid, bpos = paged_row_index(self.block_tables, back, self.page)
        d = cfg.head_dim
        window = self.kvbuf[bpid[:, None], rows[None], bpos[:, None], :d]
        mean = jnp.mean(window.reshape(-1, ks, rows.shape[0], d), axis=1)
        first = jnp.take_along_axis(
            self.block_tables, jnp.clip(j // r, 0, self.block_tables.shape[1]
                                        - 1)[:, None], axis=1)[:, 0]
        self.ckey = self.ckey.at[jnp.where(done, first, self.trash), ai,
                                 j % r].set(mean)

    def sparse(self, ai, q, k, v, scale):
        cfg = self.cfg
        s, _, hq, d = q.shape
        hkv = cfg.num_key_value_heads
        rows = _head_rows(cfg, ai)
        self.kvbuf = self.kvbuf.at[
            self.pid[:, None], rows[None], self.ppos[:, None]].set(
                jnp.concatenate([k[:, 0], v[:, 0]], axis=-1))
        self._complete_kernel(ai, rows)
        ck = self.ckey[self.block_tables, ai]     # [S, PP, r, Hkv, D]
        qg = q[:, 0].reshape(s, hkv, hq // hkv, d)
        blocks = selected_blocks(
            cfg, qg, ck.reshape(s, -1, hkv, d), self.positions + 1, scale)
        if self.attn_impl == "kernel":
            tables, counts = _page_tables(
                blocks, self.block_tables,
                table_width(cfg, self.block_tables.shape[1]))
            return paged_attention(
                q[:, 0], self.kvbuf, None, self.block_tables, self.positions,
                layer=ai, scale=scale, selected=(tables, counts))[:, None]
        # the gather lane: the slot's rows, whole, under the selection's mask
        g = self.kvbuf[self.block_tables[:, :, None], rows[None, None]]
        g = jnp.moveaxis(g, 2, 1).reshape(s, hkv, -1, 2 * d)   # [S,Hkv,R,2D]
        live = jnp.arange(g.shape[2])[None] <= self.positions[:, None]
        mask = jnp.repeat(blocks, self.page, axis=-1) & live[:, None]
        out = jax.vmap(lambda qs, gs, ms: block_sparse_attention(
            qs[None], jnp.moveaxis(gs[..., :d], 0, 1),
            jnp.moveaxis(gs[..., d:], 0, 1), ms[None], scale)[0])(qg, g, mask)
        return out.reshape(s, 1, hq, d)

    def linear(self, li, q, k, v, decay):
        y, new = decayed_linear_attention(
            q, k, v, decay, self.lin[:, li],
            jnp.ones((q.shape[0],), jnp.int32))
        self.lin_new[li] = jnp.where(self.frozen[:, None, None, None],
                                     self.lin[:, li], new)
        return y


class PagedChunk:
    """The cache view of one chunk: ``T`` tokens of slot ``slot`` at
    positions ``start ..``, of which the first ``n_valid`` are real (the
    rest is right padding, routed to the trash page). ``start`` is a
    multiple of the page size."""

    def __init__(self, cfg: SALAConfig, kvbuf, state, block_tables, slot,
                 start, n_valid):
        self.cfg, self.kvbuf = cfg, kvbuf
        self.ckey, self.lin = state["ckey"], state["lin"]
        self.lin_new = [None] * self.lin.shape[1]
        self.slot, self.start, self.n_valid = slot, start, n_valid
        self.bt_row = block_tables[slot]                       # [PP]
        self.page = kvbuf.shape[2]
        self.trash = kvbuf.shape[0] - 1

    @property
    def state(self):
        return {"ckey": self.ckey, "lin": self.lin.at[self.slot].set(
            jnp.stack(self.lin_new))}

    def _page_of(self, pos):
        return self.bt_row[jnp.clip(pos // self.page, 0,
                                    self.bt_row.shape[0] - 1)]

    def _complete_kernels(self, ai, rows, k):
        """The kernels that end inside the chunk: kernel ``j`` covers rows
        ``[stride * j, stride * j + kernel)``, so those that end here start
        up to ``kernel - stride`` rows before it, rows the last chunk
        wrote."""
        cfg = self.cfg
        ks, st, r = (cfg.sparse_kernel_size, cfg.sparse_kernel_stride,
                     cfg.kernels_per_block)
        t, d = k.shape[0], cfg.head_dim
        tail = ks - st
        before = jnp.maximum(self.start - tail + jnp.arange(tail), 0)
        kcat = jnp.concatenate([
            self.kvbuf[self._page_of(before)[:, None], rows[None],
                       (before % self.page)[:, None], :d], k])
        count, o = t // st, ks // st
        parts = kcat.reshape(count + o - 1, st, rows.shape[0], d).sum(1)
        mean = sum(parts[i:i + count] for i in range(o)) / ks
        j = (self.start - tail) // st + jnp.arange(count)
        whole = (j >= 0) & (st * j + ks <= self.start + self.n_valid)
        j = jnp.maximum(j, 0)
        self.ckey = self.ckey.at[
            jnp.where(whole, self._page_of(st * j), self.trash), ai,
            j % r].set(mean)

    def sparse(self, ai, q, k, v, scale):
        cfg = self.cfg
        _, t, hq, d = q.shape
        hkv = cfg.num_key_value_heads
        rows = _head_rows(cfg, ai)
        pos = self.start + jnp.arange(t)
        real = jnp.arange(t) < self.n_valid
        self.kvbuf = self.kvbuf.at[
            jnp.where(real, self._page_of(pos), self.trash)[:, None],
            rows[None], (pos % self.page)[:, None]].set(
                jnp.concatenate([k[0], v[0]], axis=-1))
        self._complete_kernels(ai, rows, k[0])
        ck = self.ckey[self.bt_row, ai].reshape(-1, hkv, d)    # [J, Hkv, D]
        pp = self.bt_row.shape[0]
        tile = _largest_divisor(pp, TILE_PAGES)
        tile_rows = tile * self.page
        n_tiles = (self.start + self.n_valid + tile_rows - 1) // tile_rows
        kvbuf, bt_row, page = self.kvbuf, self.bt_row, self.page

        def queries(qb, posb):
            """``Q_ROWS`` query tokens: their selection, then the walk over
            the slot's pages up to the chunk's end, a tile a step."""
            blocks = selected_blocks(cfg, qb, ck, posb + 1, scale)

            def walk(c, carry):
                m, l, acc = carry
                pages = jax.lax.dynamic_slice_in_dim(bt_row, c * tile, tile)
                kv = kvbuf[pages[:, None], rows[None]]   # [tile,Hkv,page,2D]
                kv = jnp.moveaxis(kv, 1, 0).reshape(hkv, tile_rows, 2 * d)
                scores = jnp.einsum("qkgd,krd->qkgr", qb, kv[..., :d]) * scale
                at = c * tile_rows + jnp.arange(tile_rows)
                mask = jnp.repeat(jax.lax.dynamic_slice_in_dim(
                    blocks, c * tile, tile, axis=-1), page, axis=-1) \
                    & (at[None] <= posb[:, None])[:, None]      # [Q,Hkv,R]
                scores = jnp.where(mask[:, :, None], scores, _NEG)
                m_new = jnp.maximum(m, jnp.max(scores, -1, keepdims=True))
                alpha = jnp.exp(m - m_new)
                p = jnp.where(mask[:, :, None], jnp.exp(scores - m_new), 0.0)
                return (m_new, l * alpha + jnp.sum(p, -1, keepdims=True),
                        acc * alpha + jnp.einsum("qkgr,krd->qkgd", p,
                                                 kv[..., d:]))

            shape = qb.shape[:3]
            with jax.named_scope("sala/chunk_walk"):
                m, l, acc = jax.lax.fori_loop(0, n_tiles, walk, (
                    jnp.full(shape + (1,), _NEG, jnp.float32),
                    jnp.zeros(shape + (1,), jnp.float32),
                    jnp.zeros(shape + (d,), jnp.float32)))
            return acc / jnp.maximum(l, 1e-30)

        qg = q[0].reshape(t, hkv, hq // hkv, d)
        qrows = _largest_divisor(t, Q_ROWS)
        out = jax.lax.map(
            lambda xs: queries(*xs),
            (qg.reshape(t // qrows, qrows, hkv, hq // hkv, d),
             pos.reshape(t // qrows, qrows)))
        return out.reshape(1, t, hq, d)

    def linear(self, li, q, k, v, decay):
        carried = jnp.where(self.start > 0, self.lin[self.slot, li], 0.0)
        with jax.named_scope("sala/linear_scan"):
            y, new = decayed_linear_attention(
                q, k, v, decay, carried[None], self.n_valid[None])
        self.lin_new[li] = new[0]
        return y


def build_sala_paged_decode_step(cfg: SALAConfig, max_top_k: int,
                                 attn_impl: str = "gather"):
    """The RAW paged decode step of this family.

    step(params, kvbuf, state, block_tables, lengths, finished,
         last_tokens, temperature, top_k, do_sample, eos, key)
      -> (kvbuf, state, lengths+1, finished, next_tokens)"""
    if attn_impl not in ("gather", "kernel"):
        raise ValueError(f"attn_impl must be 'gather' or 'kernel', got "
                         f"{attn_impl!r}")

    def _step(params, kvbuf, state, block_tables, lengths, finished,
              last_tokens, temperature, top_k, do_sample, eos, key):
        max_pos = block_tables.shape[1] * kvbuf.shape[2] - 1
        view = PagedStep(cfg, kvbuf, state, block_tables,
                         jnp.clip(lengths, 0, max_pos), finished, attn_impl)
        h = sala_hidden(cfg, params, last_tokens[:, None], lengths[:, None],
                        view)
        nxt, finished = _sample(params, h[:, 0], finished,
                                (temperature, top_k, do_sample, eos), key,
                                max_top_k)
        return view.kvbuf, view.state, lengths + 1, finished, nxt

    return _step


def build_sala_paged_chunk_fn(cfg: SALAConfig, max_top_k: int):
    """The RAW chunk program: ``T`` tokens of one slot behind ``start``
    cached ones.

    chunk(params, tokens [1, T], start, n_valid, is_last, kvbuf, state,
          block_tables, lengths, finished, slot, temperature, top_k,
          do_sample, eos, key)
      -> (kvbuf, state, lengths, finished, next_token [1])

    ``lengths[slot]`` becomes ``start + n_valid``; the token sampled from
    the last real row is the prompt's first generated one when ``is_last``
    (and then the slot's ``finished`` flag is the sample's; before that it
    stays set, which keeps the decode step off the slot)."""

    def _chunk(params, tokens, start, n_valid, is_last, kvbuf, state,
               block_tables, lengths, finished, slot, temperature, top_k,
               do_sample, eos, key):
        t = tokens.shape[1]
        view = PagedChunk(cfg, kvbuf, state, block_tables, slot, start,
                          n_valid)
        positions = (start + jnp.arange(t, dtype=jnp.int32))[None]
        h = sala_hidden(cfg, params, tokens, positions, view)
        last = jax.lax.dynamic_index_in_dim(
            h[0], jnp.maximum(n_valid - 1, 0), axis=0)         # [1, hidden]
        nxt, fin = _sample(params, last, False,
                           (temperature, top_k, do_sample, eos), key,
                           max_top_k)
        lengths = lengths.at[slot].set(start + n_valid)
        finished = finished.at[slot].set(jnp.where(is_last, fin[0], True))
        return view.kvbuf, view.state, lengths, finished, nxt

    return _chunk


@functools.lru_cache(maxsize=64)
def get_sala_paged_decode_step(cfg: SALAConfig, max_top_k: int,
                               attn_impl: str):
    return jit_program(
        build_sala_paged_decode_step(cfg, max_top_k, attn_impl),
        donate=(1, 2))


@functools.lru_cache(maxsize=64)
def get_sala_paged_chunk_fn(cfg: SALAConfig, max_top_k: int):
    return jit_program(build_sala_paged_chunk_fn(cfg, max_top_k),
                       donate=(5, 6))


class SALAPagedDecoder(PagedFamilyDecoder):
    """``PagedFamilyDecoder`` for a ``MiniCPMSALAForCausalLM``: pages for the
    sparse layers, their compressed keys by page and a linear state a slot;
    every walk is over selected pages, so it declares no plain one."""

    family = "SALA"
    vocab = "vocab_size"
    prefills_in_chunks = True
    unserved_why = ("the linear states and the compressed keys have no "
                    "prefix-reuse or rollback path")
    tick_fetch = chunk_walks = False

    def setup(self):
        if not (self.spec.sparse_layers and self.spec.linear_layers):
            raise NotImplementedError(
                "the SALA paged decoder needs at least one sparse and one "
                "linear layer")
        if self.page_size != self.spec.sparse_block_size:
            raise ValueError(
                f"page_size {self.page_size} must be the selection block "
                f"({self.spec.sparse_block_size}): a selected block is a "
                f"page")

    def new_kv(self, num_slots: int, max_seq: int) -> PagedKVCache:
        c = self.spec
        self.check_max_seq(max_seq)
        hkv, d = c.num_key_value_heads, c.head_dim
        return PagedKVCache(
            num_slots, len(c.sparse_layers) * hkv, max_seq, 1, d,
            dtype=self.params()["tok"].dtype, page_size=self.page_size,
            num_pages=self.num_pages, fused_kv=True, row_shape=(2 * d,),
            state_rows={
                "lin": ("slot", (len(c.linear_layers), c.lightning_nh,
                                 c.lightning_head_dim,
                                 c.lightning_head_dim)),
                "ckey": ("page", (len(c.sparse_layers), c.kernels_per_block,
                                  hkv, d))})

    def publish_gauges(self, kv: PagedKVCache, stat_set):
        stat_set("linear_state_bytes", kv.state_bytes("lin"))
        stat_set("ckey_bytes", kv.state_bytes("ckey"))

    # -- what the selection reads, from lengths the host holds ---------------
    def _selected_pages(self, n):
        """Pages one KV head of one sparse layer reads for contexts ``n``
        (an array): ``(selected, live)``."""
        c = self.spec
        n = np.asarray(n, np.int64)  # noqa: PTA002 -- host-side lengths the batcher holds (ints), never a device value
        live = -(-n // self.page_size)
        return (np.where(n > c.sparse_dense_len,
                         np.minimum(live, c.sparse_topk), live), live)

    def note_lengths(self, seq_lens, stat_add):
        """A decode tick over sequences of ``seq_lens`` tokens (the new one
        included): the pages its sparse walks read, and the live ones."""
        heads = len(self.spec.sparse_layers) * self.spec.num_key_value_heads
        selected, live = self._selected_pages(list(seq_lens))
        stat_add("sparse_attn.pages_selected", int(selected.sum()) * heads)
        stat_add("sparse_attn.pages_live", int(live.sum()) * heads)

    def note_chunk(self, start: int, n_valid: int, pages_per_seq: int,
                   stat_add):
        """A chunk's sparse attention, in (query token, KV head, block)
        triples: what the selection admits, and what the blockwise lane
        computes (every block of the tiles up to the chunk's end)."""
        heads = len(self.spec.sparse_layers) * self.spec.num_key_value_heads
        selected, _ = self._selected_pages(start + 1 + np.arange(n_valid))
        tile = _largest_divisor(pages_per_seq, TILE_PAGES)
        walked = -(-(start + n_valid) // (tile * self.page_size)) * tile
        stat_add("sparse_prefill.blocks_selected",
                 int(selected.sum()) * heads)
        stat_add("sparse_prefill.blocks_computed",
                 int(n_valid) * walked * heads)

    def prefix_sig(self, kv: PagedKVCache):
        c = self.spec
        return (len(c.sparse_layers), c.num_key_value_heads, c.head_dim,
                str(kv.dtype), self.page_size)

    # -- its programs and what they take of the cache ------------------------
    def step_program(self):
        return get_sala_paged_decode_step(self.spec, self.max_top_k,
                                          self.attn_impl)

    def chunk_program(self):
        return get_sala_paged_chunk_fn(self.spec, self.max_top_k)

    def admits_chunk(self, chunk_len: int):
        st = self.spec.sparse_kernel_stride
        if chunk_len % st or (chunk_len > 128 and chunk_len % 128):
            raise ValueError(
                f"a chunk of {chunk_len} tokens is no multiple of the "
                f"compression stride {st}, or is over 128 and no multiple "
                f"of 128 (the linear layers' sub-chunk)")

    def cache_arrays(self, kv: PagedKVCache):
        return kv.k, kv.state, kv.block_tables

    def install(self, kv: PagedKVCache, arrays, lengths):
        k, state = arrays
        kv.swap(k, kv.v, lengths, state)


register_paged_decoder(MiniCPMSALAForCausalLM, SALAPagedDecoder)
