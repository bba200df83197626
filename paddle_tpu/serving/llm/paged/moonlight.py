"""The paged programs of the Moonlight family and ``MoonlightPagedDecoder``.

A fifth decoder family behind the same ``PagedBatcher``: the block is
``models.moonlight.moonlight_block`` and only the cache views differ. The
family's attention is LATENT: what a token leaves behind for later queries is
one row a layer for all its heads, ``[c_t | r_t]`` (a latent of
``kv_lora_rank`` numbers after its norm, one rotary key part after its
positions), so the cache is ONE arena ``[pages + 1, layers, page, row]``
(``paged/pool.py``, "Latent rows"), one page group, no window, no state
beside the pages. ``row`` is the 576 numbers padded to whole 128-lane tiles
(:func:`latent_row_width`; PERF.md section 4 has the row layouts' readings).

Two views over that one cache, the same mathematics in two orders:

:class:`PagedStep` is one decode step (one token a slot) and ABSORBS: it
writes the token's row, multiplies the query's position-free part by
``W_UK^T`` (``q~``, 512 a head), hands ``[q~ | qr]`` to
``paged_attention(latent=...)``, which walks the slot's pages once for both
products (a score is one 576-long dot with a cached row, the result the
weighted sum of the rows' first 512 columns), and multiplies the result by
``W_UV``: a tick reads the latent rows and never a per-head key or value. On
the gather lane the same products run over a gather of the slot's rows. A
slot whose ``finished`` flag is set (free, or still being prefilled) is left
alone: its row goes to the trash page and its walk is one page long.

:class:`PagedChunk` is up to ``T`` tokens of ONE slot behind what is already
cached, the program of every prefill (a whole prompt is the chunk at offset
0), and EXPANDS: it writes the chunk's rows, takes the chunk's own keys and
values from its own latents, and reads the prefix's pages a tile at a time,
multiplying each tile by ``W_UKV`` into per-head keys and values that live
for that tile only; online softmax over the tiles, then the chunk's own
block under the causal mask. A chunk of 1,024 behind ``P`` rows costs 4.2 +
10.5 MFLOP x ``P`` a layer this way and 35.7 the absorbed way; a tick would
read 8.9 times the bytes expanded. Offset and true length are arguments: one
compiled program serves every chunk of every prompt.

The decode step also returns, packed behind the next tokens so that the
tick's one fetch brings them, the expert layer's two counters over the HELD
experts (as the Trinity step does).

The façade is ``paged/decode.py:PagedFamilyDecoder``, which also refuses what
no family but GPT serves yet (a mesh, int8, ``prefix_cache``, ``spec_k``,
sequence export); the class here declares what is this family's own.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from ....models.moonlight import (MoonlightConfig, MoonlightForCausalLM,
                                  moonlight_hidden, split_ukv)
from ....ops.paged_attention import paged_attention
from ..decode import jit_program
from .decode import (PagedFamilyDecoder, _largest_divisor, _sample,
                     _tick_counters, _window_walks, note_expert_tick,
                     note_window_walks, register_paged_decoder)
from .pool import PagedKVCache, paged_row_index, paged_write_rows

#: the most pages of one step of the chunk's walk over the prefix (a tile's
#: scores are ``T x heads x TILE_PAGES * page`` floats: 67 MB at 1,024 x 16
#: x 16 x 64, its expanded keys and values 16 MB)
TILE_PAGES = 16
#: lanes of a tile of the device's memory: a row is padded to whole tiles
LANES = 128
_NEG = -1e30


def latent_row_width(cfg: MoonlightConfig) -> int:
    """Columns of a cached row as the arena holds it: ``[c | r]`` and zeros
    up to the next whole lane tile (576 -> 640)."""
    return -(-cfg.latent_row // LANES) * LANES


def _row(c, r, width: int):
    """``[c | r | zeros]`` of ``width`` columns."""
    row = jnp.concatenate([c, r], axis=-1)
    return jnp.pad(row, [(0, 0)] * (row.ndim - 1)
                   + [(0, width - row.shape[-1])])


def latent_gather_attention(q, arena, block_tables, positions, layer, scale,
                            latent):
    """``paged_attention(latent=...)`` by a gather of the slots' rows: the
    kernel's oracle, and the decode step's lane off the chip."""
    value, rotary = latent
    rows = arena[block_tables, layer]                # [S, PP, page, row]
    rows = rows.reshape(rows.shape[0], -1, rows.shape[-1])
    seen = jnp.arange(rows.shape[1])[None] <= positions[:, None]     # [S, M]
    # past a sequence's end may lie anything: 0 * NaN is NaN
    rows = jnp.where(seen[..., None], rows, 0.0)
    scores = jnp.einsum("shc,smc->shm", q * scale, rows[..., :value + rotary])
    weights = jax.nn.softmax(jnp.where(seen[:, None], scores, _NEG), axis=-1)
    return jnp.einsum("shm,smc->shc", weights, rows[..., :value])


class PagedStep:
    """The cache view of one decode step: one new token per slot, the past in
    the slot's pages; the absorbed order."""

    def __init__(self, cfg, arena, tables, positions, frozen, attn_impl):
        self.cfg, self.arena, self.tables = cfg, arena, tables
        self.attn_impl = attn_impl
        # a slot nobody decodes for reads one page and writes the trash page
        self.positions = jnp.where(frozen, 0, positions)
        pid, self.ppos = paged_row_index(tables, positions, arena.shape[2])
        self.pid = jnp.where(frozen, arena.shape[0] - 1, pid)
        self.latent = (cfg.kv_lora_rank, cfg.qk_rope_head_dim)

    def attend(self, i, qn, qr, c, r, w_ukv, scale):
        wuk, wuv = split_ukv(self.cfg, w_ukv)
        with jax.named_scope("latent"):
            self.arena = paged_write_rows(
                self.arena, _row(c[:, 0], r[:, 0], self.arena.shape[-1]),
                self.pid, self.ppos, i)
        with jax.named_scope("absorb"):
            # [q~ | qr]: what one dot with a cached row takes
            q = jnp.concatenate(
                [jnp.einsum("shd,chd->shc", qn[:, 0], wuk), qr[:, 0]], -1)
        with jax.named_scope("attn"):
            if self.attn_impl == "kernel":
                out = paged_attention(q, self.arena, None, self.tables,
                                      self.positions, layer=i, scale=scale,
                                      latent=self.latent)
            else:
                out = latent_gather_attention(
                    q, self.arena, self.tables, self.positions, i, scale,
                    self.latent)
        with jax.named_scope("absorb"):
            return jnp.einsum("shc,chd->shd", out, wuv)[:, None]


class PagedChunk:
    """The cache view of one chunk: ``T`` tokens of slot ``slot`` at
    positions ``start ..``, of which the first ``n_valid`` are real (the
    rest is right padding, routed to the trash page); the expanded order."""

    def __init__(self, cfg, arena, tables, slot, start, n_valid):
        self.cfg, self.arena = cfg, arena
        self.start, self.n_valid = start, n_valid
        self.bt_row = tables[slot]                              # [PP]

    def attend(self, i, qn, qr, c, r, w_ukv, scale):
        cfg, bt_row = self.cfg, self.bt_row
        rank, rot = cfg.kv_lora_rank, cfg.qk_rope_head_dim
        t, page = qn.shape[1], self.arena.shape[2]
        wuk, wuv = split_ukv(cfg, w_ukv)
        with jax.named_scope("latent"):
            pos = self.start + jnp.arange(t)
            pid = jnp.where(
                jnp.arange(t) < self.n_valid,
                bt_row[jnp.clip(pos // page, 0, bt_row.shape[0] - 1)],
                self.arena.shape[0] - 1)
            self.arena = self.arena.at[pid, i, pos % page].set(
                _row(c[0], r[0], self.arena.shape[-1]))
        arena = self.arena
        tile = _largest_divisor(bt_row.shape[0], TILE_PAGES)
        tile_rows = tile * page
        q_n, q_r = qn[0] * scale, qr[0] * scale                 # [T, H, .]

        def scores_of(kn, rk):
            return jnp.einsum("qhd,rhd->qhr", q_n, kn) \
                + jnp.einsum("qhd,rd->qhr", q_r, rk)

        def fold(carry, scores, seen, v):
            """One online-softmax step over the rows ``seen``."""
            m, l, acc = carry
            scores = jnp.where(seen, scores, _NEG)
            m_new = jnp.maximum(m, jnp.max(scores, -1, keepdims=True))
            alpha = jnp.exp(m - m_new)
            p = jnp.where(seen, jnp.exp(scores - m_new), 0.0)
            return (m_new, l * alpha + jnp.sum(p, -1, keepdims=True),
                    acc * alpha + jnp.einsum("qhr,rhd->qhd", p, v))

        def walk(ci, carry):
            """A tile of the prefix's pages: expanded, attended, dropped."""
            with jax.named_scope("expand"):
                pages = jax.lax.dynamic_slice_in_dim(bt_row, ci * tile, tile)
                rows = arena[pages, i].reshape(tile_rows, -1)
                before = ci * tile_rows + jnp.arange(tile_rows) < self.start
                # the tile may reach into the chunk's own or unwritten pages
                rows = jnp.where(before[:, None], rows, 0.0)
                ct = rows[:, :rank]
                kn = jnp.einsum("rc,chd->rhd", ct, wuk)
                v = jnp.einsum("rc,chd->rhd", ct, wuv)
            with jax.named_scope("chunk_walk"):
                return fold(carry, scores_of(kn, rows[:, rank:rank + rot]),
                            before[None, None], v)

        heads = q_n.shape[:2]
        carry = jax.lax.fori_loop(
            0, (self.start + tile_rows - 1) // tile_rows, walk, (
                jnp.full(heads + (1,), _NEG, jnp.float32),
                jnp.zeros(heads + (1,), jnp.float32),
                jnp.zeros(heads + (cfg.v_head_dim,), jnp.float32)))
        with jax.named_scope("expand"):     # the chunk's own, from its own c
            kn = jnp.einsum("rc,chd->rhd", c[0], wuk)
            v = jnp.einsum("rc,chd->rhd", c[0], wuv)
        with jax.named_scope("chunk_walk"):
            own = jnp.arange(t)[None, :] <= jnp.arange(t)[:, None]
            _, l, acc = fold(carry, scores_of(kn, r[0]), own[:, None], v)
            return (acc / jnp.maximum(l, 1e-30))[None]


def tiles_expanded(start: int, pages_per_seq: int, page_size: int) -> int:
    """Prefix rows a chunk behind ``start`` cached rows multiplies by
    ``W_UKV`` in one layer: whole tiles of its walk."""
    tile_rows = _largest_divisor(pages_per_seq, TILE_PAGES) * page_size
    return -(-start // tile_rows) * tile_rows


def build_moonlight_paged_decode_step(cfg: MoonlightConfig, max_top_k: int,
                                      attn_impl: str = "gather"):
    """The RAW paged decode step of this family.

    step(params, arena, tables, lengths, finished, last_tokens,
         temperature, top_k, do_sample, eos, key)
      -> (arena, lengths+1, finished, next_tokens, fetch)

    ``fetch`` is ``[S + 2]`` int32: the next tokens, then the expert layer's
    two counters."""
    if attn_impl not in ("gather", "kernel"):
        raise ValueError(f"attn_impl must be 'gather' or 'kernel', got "
                         f"{attn_impl!r}")

    def _step(params, arena, tables, lengths, finished, last_tokens,
              temperature, top_k, do_sample, eos, key):
        max_pos = tables.shape[1] * arena.shape[2] - 1
        view = PagedStep(cfg, arena, tables, jnp.clip(lengths, 0, max_pos),
                         finished, attn_impl)
        h, counts = moonlight_hidden(cfg, params, last_tokens[:, None],
                                     lengths[:, None], view)
        nxt, finished = _sample(params, h[:, 0], finished,
                                (temperature, top_k, do_sample, eos), key,
                                max_top_k)
        fetch = jnp.concatenate([nxt, _tick_counters(counts)])
        return view.arena, lengths + 1, finished, nxt, fetch

    return _step


def build_moonlight_paged_chunk_fn(cfg: MoonlightConfig, max_top_k: int):
    """The RAW chunk program: ``T`` tokens of one slot behind ``start``
    cached ones.

    chunk(params, tokens [1, T], start, n_valid, is_last, arena, tables,
          lengths, finished, slot, temperature, top_k, do_sample, eos, key)
      -> (arena, lengths, finished, next_token [1], window walks [2])

    ``lengths[slot]`` becomes ``start + n_valid``; the token sampled from
    the last real row is the prompt's first generated one when ``is_last``
    (and then the slot's ``finished`` flag is the sample's; before that it
    stays set, which keeps the decode step off the slot). The walks are
    ``_window_walks`` of the chunk's expert layers."""

    def _chunk(params, tokens, start, n_valid, is_last, arena, tables,
               lengths, finished, slot, temperature, top_k, do_sample, eos,
               key):
        t = tokens.shape[1]
        view = PagedChunk(cfg, arena, tables, slot, start, n_valid)
        positions = (start + jnp.arange(t, dtype=jnp.int32))[None]
        h, counts = moonlight_hidden(cfg, params, tokens, positions, view)
        walks = _window_walks(counts, t, cfg.num_experts_per_tok,
                              cfg.n_routed_experts)
        last = jax.lax.dynamic_index_in_dim(
            h[0], jnp.maximum(n_valid - 1, 0), axis=0)         # [1, hidden]
        nxt, fin = _sample(params, last, False,
                           (temperature, top_k, do_sample, eos), key,
                           max_top_k)
        lengths = lengths.at[slot].set(start + n_valid)
        finished = finished.at[slot].set(jnp.where(is_last, fin[0], True))
        return view.arena, lengths, finished, nxt, walks

    return _chunk


@functools.lru_cache(maxsize=64)
def get_moonlight_paged_decode_step(cfg: MoonlightConfig, max_top_k: int,
                                    attn_impl: str):
    return jit_program(
        build_moonlight_paged_decode_step(cfg, max_top_k, attn_impl),
        donate=(1,))


@functools.lru_cache(maxsize=64)
def get_moonlight_paged_chunk_fn(cfg: MoonlightConfig, max_top_k: int):
    return jit_program(build_moonlight_paged_chunk_fn(cfg, max_top_k),
                       donate=(5,))


class MoonlightPagedDecoder(PagedFamilyDecoder):
    """``PagedFamilyDecoder`` for a ``MoonlightForCausalLM``: ONE arena of
    latent rows, all the heads' (``pool.py``, "Latent rows")."""

    family = "Moonlight"
    holds = "latent rows"
    prefills_in_chunks = True
    unserved_why = ("a shared or rolled-back page would hold latent rows, "
                    "which the prefix store and the speculative step read "
                    "as K and V pages")

    def setup(self):
        #: ``paged_attention``'s argument: the value's and the rotary width
        self.latent = (self.spec.kv_lora_rank, self.spec.qk_rope_head_dim)
        self.row_width = latent_row_width(self.spec)

    def new_kv(self, num_slots: int, max_seq: int) -> PagedKVCache:
        self.check_max_seq(max_seq)
        return PagedKVCache(
            num_slots, self.spec.num_hidden_layers, max_seq, 1,
            self.row_width, dtype=self.params()["tok"].dtype,
            page_size=self.page_size, num_pages=self.num_pages,
            fused_kv=True, row_shape=(self.row_width,))

    def plain_walk(self, kv: PagedKVCache):
        # every query head on the one row a token keeps
        _, _, page, row = kv.k.shape
        return (self.spec.num_attention_heads, 1, page, row,
                kv.k.dtype.itemsize, 1)

    def expanded_row_nbytes(self, itemsize: int = 4) -> int:
        """What a token and layer would hold as per-head keys and values."""
        c = self.spec
        return c.num_attention_heads * (c.qk_head_dim + c.v_head_dim) \
            * itemsize

    def publish_gauges(self, kv: PagedKVCache, stat_set):
        stat_set("kv_row_bytes", kv.row_nbytes())
        stat_set("kv_row_bytes_expanded",
                 self.expanded_row_nbytes(kv.dtype.itemsize))

    def note_tick(self, extras, n_active: int, stat_add):
        note_expert_tick(self.spec, extras, n_active, stat_add)
        note_window_walks(self._walks, stat_add)

    def note_lengths(self, seq_lens, stat_add):
        """A decode tick over sequences of ``seq_lens`` tokens (the new one
        included): the latent rows its walks read, every layer's."""
        stat_add("latent_attn.rows_live",
                 int(sum(seq_lens)) * self.spec.num_hidden_layers)

    def note_chunk(self, start: int, n_valid: int, pages_per_seq: int,
                   stat_add):
        """The prefix rows this chunk expanded, every layer's."""
        stat_add("latent_prefill.rows_expanded",
                 tiles_expanded(start, pages_per_seq, self.page_size)
                 * self.spec.num_hidden_layers)

    def prefix_sig(self, kv: PagedKVCache):
        return ("latent", self.latent, self.row_width,
                self.spec.num_hidden_layers, str(kv.dtype), self.page_size)

    # -- its programs and what they take of the cache ------------------------
    def step_program(self):
        return get_moonlight_paged_decode_step(self.spec, self.max_top_k,
                                               self.attn_impl)

    def chunk_program(self):
        return get_moonlight_paged_chunk_fn(self.spec, self.max_top_k)

    def cache_arrays(self, kv: PagedKVCache):
        return kv.k, kv.block_tables

    def install(self, kv: PagedKVCache, arrays, lengths):
        kv.swap(*arrays, kv.v, lengths)


register_paged_decoder(MoonlightForCausalLM, MoonlightPagedDecoder)
