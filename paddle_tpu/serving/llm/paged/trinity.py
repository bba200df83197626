"""The paged programs of the Trinity family and ``TrinityPagedDecoder``.

A fourth decoder family behind the same ``PagedBatcher``: the block is
``models.trinity.trinity_block`` and only the cache views differ. The
family's layers are of two kinds that keep DIFFERENT pages of one sequence,
so its ``PagedKVCache`` has two page groups (``paged/pool.py``): the full
attention layers' group keeps every page, the sliding-window layers' group
the pages that hold the last ``sliding_window`` rows, and gives the others
back as the sequence grows. Each group has its own K and V arenas
``[pages + 1, its layers, page, Hkv, D]`` and block tables; the full group is
the first, so ``kv.pool`` and the batcher's admission math are about the
pages a prompt holds for good.

Two views. :class:`PagedStep` is one decode step (one token a slot): layer
``i`` writes the token's K and V row into its group's arenas and reads
through ``paged_attention`` with the group's tables and the layer's window
(the walk begins at the window's first page), or, on the gather lane, through
a gather of the slot's rows under the same mask. A slot whose ``finished``
flag is set (free, or still being prefilled) is left alone: its row goes to
the trash page and its walk is one page long. :class:`PagedChunk` is up to
``T`` tokens of ONE slot behind what is already cached, the program of every
prefill (a whole prompt is the chunk at offset 0): it writes the chunk's rows
to both groups, then attends blockwise over the slot's pages, a block of
query rows at a time and from the first tile that block's window reaches to
the last its rows reach. Offset and true length are arguments: one compiled
program serves every chunk of every prompt.

The decode step also returns, packed behind the next tokens so that the
tick's one fetch brings them, the expert layer's two counters over the HELD
experts: those that received a token (summed over the expert layers) and the
fullest one's tokens in any layer.

The façade is ``paged/decode.py:PagedFamilyDecoder``, which also refuses what
no family but GPT serves yet (a mesh, int8, ``prefix_cache``, ``spec_k``,
sequence export); the class here declares what is this family's own.
"""
from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
import numpy as np

from ....models.trinity import (TrinityConfig, TrinityForCausalLM,
                                trinity_hidden)
from ....ops.paged_attention import paged_attention
from ..decode import jit_program
from .decode import (PagedFamilyDecoder, _largest_divisor, _sample,
                     _tick_counters, _window_walks, grouped_walk,
                     note_expert_tick, note_window_walks,
                     register_paged_decoder)
from .pool import (PagedKVCache, PageGroup, paged_gather_rows,
                   paged_row_index, paged_write_rows, window_page_bound)

#: query rows of the chunk's attention computed at once, and the most pages
#: of one step of its walk over the slot's pages (scores of ``Q_ROWS x heads
#: x TILE_PAGES * page`` floats: 71 MB at 256 x 32 x 17 x 64)
Q_ROWS, TILE_PAGES = 256, 32
_NEG = -1e30


def page_groups(cfg: TrinityConfig):
    """``(layers, window)`` of the family's page groups, the full attention
    layers' first; a kind the configuration has no layer of has no group."""
    kinds = ((cfg.full_layers, None),
             (cfg.window_layers, cfg.sliding_window))
    return tuple(k for k in kinds if k[0])


class _Groups:
    """The (traced) arenas and tables of a cache's groups inside a program,
    and where each model layer lies in them."""

    def __init__(self, cfg: TrinityConfig, ks, vs, tables):
        self.ks, self.vs, self.tables = list(ks), list(vs), tables
        self.where = {i: (g, li) for g, (layers, _) in enumerate(
            page_groups(cfg)) for li, i in enumerate(layers)}
        self.page = self.ks[0].shape[2]


class PagedStep(_Groups):
    """The cache view of one decode step: one new token per slot, the past in
    the groups' pages."""

    def __init__(self, cfg, ks, vs, tables, positions, frozen, attn_impl):
        super().__init__(cfg, ks, vs, tables)
        self.frozen, self.attn_impl = frozen, attn_impl
        # a slot nobody decodes for reads one page and writes the trash page
        self.positions = jnp.where(frozen, 0, positions)
        self.rows = []
        for k, bt in zip(self.ks, tables):
            pid, ppos = paged_row_index(bt, positions, self.page)
            self.rows.append((jnp.where(frozen, k.shape[0] - 1, pid), ppos))

    def attend(self, i, q, k, v, scale, window):
        g, li = self.where[i]
        s, _, hq, d = q.shape
        pid, ppos = self.rows[g]
        self.ks[g] = paged_write_rows(self.ks[g], k[:, 0], pid, ppos, li)
        self.vs[g] = paged_write_rows(self.vs[g], v[:, 0], pid, ppos, li)
        if self.attn_impl == "kernel":
            return paged_attention(
                q[:, 0], self.ks[g], self.vs[g], self.tables[g],
                self.positions, layer=li, scale=scale, window=window)[:, None]
        kd = paged_gather_rows(self.ks[g], self.tables[g], li)
        vd = paged_gather_rows(self.vs[g], self.tables[g], li)
        at = jnp.arange(kd.shape[1])[None]
        seen = at <= self.positions[:, None]
        if window is not None:
            seen &= at > self.positions[:, None] - window
        qg = (q[:, 0] * scale).reshape(s, kd.shape[2], -1, d)  # [S,Hkv,G,D]
        prod = jnp.einsum("skgd,smkd->skgm", qg, kd)
        weights = jax.nn.softmax(
            jnp.where(seen[:, None, None], prod, _NEG), axis=-1)
        # what lies behind a window may be a released page: 0 * NaN is NaN
        vd = jnp.where(seen[:, :, None, None], vd, 0.0)
        return jnp.einsum("skgm,smkd->skgd", weights, vd).reshape(
            s, 1, hq, d)


class PagedChunk(_Groups):
    """The cache view of one chunk: ``T`` tokens of slot ``slot`` at
    positions ``start ..``, of which the first ``n_valid`` are real (the
    rest is right padding, routed to the trash page)."""

    def __init__(self, cfg, ks, vs, tables, slot, start, n_valid):
        super().__init__(cfg, ks, vs, tables)
        self.start, self.n_valid = start, n_valid
        self.bt_rows = [bt[slot] for bt in tables]             # [PP] each

    def attend(self, i, q, k, v, scale, window):
        g, li = self.where[i]
        _, t, hq, d = q.shape
        hkv = k.shape[2]
        bt_row, page = self.bt_rows[g], self.page
        pos = self.start + jnp.arange(t)
        real = jnp.arange(t) < self.n_valid
        pid = jnp.where(real, bt_row[jnp.clip(pos // page, 0,
                                              bt_row.shape[0] - 1)],
                        self.ks[g].shape[0] - 1)
        self.ks[g] = self.ks[g].at[pid, li, pos % page].set(k[0])
        self.vs[g] = self.vs[g].at[pid, li, pos % page].set(v[0])
        kbuf, vbuf = self.ks[g], self.vs[g]
        tile = _largest_divisor(bt_row.shape[0], TILE_PAGES)
        tile_rows = tile * page
        end = self.start + self.n_valid

        def queries(qb, posb):
            """``Q_ROWS`` query tokens: the walk over the slot's pages, a
            tile a step, from the tile the first row's window reaches to
            the one the last row lies in."""
            first = 0 if window is None else jnp.maximum(
                posb[0] - (window - 1), 0) // tile_rows
            last = (jnp.minimum(posb[-1] + 1, end) + tile_rows - 1) \
                // tile_rows

            def walk(c, carry):
                m, l, acc = carry
                pages = jax.lax.dynamic_slice_in_dim(bt_row, c * tile, tile)
                kt = kbuf[pages, li].reshape(tile_rows, hkv, d)
                vt = vbuf[pages, li].reshape(tile_rows, hkv, d)
                at = c * tile_rows + jnp.arange(tile_rows)
                seen = at[None] <= posb[:, None]                # [Q, R]
                if window is not None:
                    seen &= at[None] > posb[:, None] - window
                scores = jnp.einsum("qkgd,rkd->qkgr", qb, kt) * scale
                scores = jnp.where(seen[:, None, None], scores, _NEG)
                m_new = jnp.maximum(m, jnp.max(scores, -1, keepdims=True))
                alpha = jnp.exp(m - m_new)
                p = jnp.where(seen[:, None, None], jnp.exp(scores - m_new),
                              0.0)
                # a tile may reach into released or unwritten pages
                vt = jnp.where(jnp.any(seen, axis=0)[:, None, None], vt, 0.0)
                return (m_new, l * alpha + jnp.sum(p, -1, keepdims=True),
                        acc * alpha + jnp.einsum("qkgr,rkd->qkgd", p, vt))

            shape = qb.shape[:3]
            with jax.named_scope("trinity/chunk_walk"):
                m, l, acc = jax.lax.fori_loop(first, last, walk, (
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


def build_trinity_paged_decode_step(cfg: TrinityConfig, max_top_k: int,
                                    attn_impl: str = "gather"):
    """The RAW paged decode step of this family.

    step(params, ks, vs, tables, lengths, finished, last_tokens,
         temperature, top_k, do_sample, eos, key)
      -> (ks, vs, lengths+1, finished, next_tokens, fetch)

    ``ks``/``vs``/``tables`` are tuples, an entry a page group; ``fetch`` is
    ``[S + 2]`` int32: the next tokens, then the expert layer's two
    counters."""
    if attn_impl not in ("gather", "kernel"):
        raise ValueError(f"attn_impl must be 'gather' or 'kernel', got "
                         f"{attn_impl!r}")

    def _step(params, ks, vs, tables, lengths, finished, last_tokens,
              temperature, top_k, do_sample, eos, key):
        max_pos = tables[0].shape[1] * ks[0].shape[2] - 1
        view = PagedStep(cfg, ks, vs, tables, jnp.clip(lengths, 0, max_pos),
                         finished, attn_impl)
        h, counts = trinity_hidden(cfg, params, last_tokens[:, None],
                                   lengths[:, None], view)
        nxt, finished = _sample(params, h[:, 0], finished,
                                (temperature, top_k, do_sample, eos), key,
                                max_top_k)
        fetch = jnp.concatenate([nxt, _tick_counters(counts)])
        return (tuple(view.ks), tuple(view.vs), lengths + 1, finished, nxt,
                fetch)

    return _step


def build_trinity_paged_chunk_fn(cfg: TrinityConfig, max_top_k: int):
    """The RAW chunk program: ``T`` tokens of one slot behind ``start``
    cached ones.

    chunk(params, tokens [1, T], start, n_valid, is_last, ks, vs, tables,
          lengths, finished, slot, temperature, top_k, do_sample, eos, key)
      -> (ks, vs, lengths, finished, next_token [1], window walks [2])

    ``lengths[slot]`` becomes ``start + n_valid``; the token sampled from
    the last real row is the prompt's first generated one when ``is_last``
    (and then the slot's ``finished`` flag is the sample's; before that it
    stays set, which keeps the decode step off the slot). The walks are
    ``_window_walks`` of the chunk's expert layers."""

    def _chunk(params, tokens, start, n_valid, is_last, ks, vs, tables,
               lengths, finished, slot, temperature, top_k, do_sample, eos,
               key):
        t = tokens.shape[1]
        view = PagedChunk(cfg, ks, vs, tables, slot, start, n_valid)
        positions = (start + jnp.arange(t, dtype=jnp.int32))[None]
        h, counts = trinity_hidden(cfg, params, tokens, positions, view)
        walks = _window_walks(counts, t, cfg.num_experts_per_tok,
                              cfg.num_experts)
        last = jax.lax.dynamic_index_in_dim(
            h[0], jnp.maximum(n_valid - 1, 0), axis=0)         # [1, hidden]
        nxt, fin = _sample(params, last, False,
                           (temperature, top_k, do_sample, eos), key,
                           max_top_k)
        lengths = lengths.at[slot].set(start + n_valid)
        finished = finished.at[slot].set(jnp.where(is_last, fin[0], True))
        return (tuple(view.ks), tuple(view.vs), lengths, finished, nxt,
                walks)

    return _chunk


@functools.lru_cache(maxsize=64)
def get_trinity_paged_decode_step(cfg: TrinityConfig, max_top_k: int,
                                  attn_impl: str):
    return jit_program(
        build_trinity_paged_decode_step(cfg, max_top_k, attn_impl),
        donate=(1, 2))


@functools.lru_cache(maxsize=64)
def get_trinity_paged_chunk_fn(cfg: TrinityConfig, max_top_k: int):
    return jit_program(build_trinity_paged_chunk_fn(cfg, max_top_k),
                       donate=(5, 6))


class TrinityPagedDecoder(PagedFamilyDecoder):
    """``PagedFamilyDecoder`` for a ``TrinityForCausalLM``: two page groups
    in one cache, so its programs take and return the groups' tuples."""

    family = "Trinity"
    prefills_in_chunks = True
    unserved_why = ("two page groups hold different pages of one prefix, "
                    "and a rollback would have to re-map released pages")

    def setup(self):
        #: rows one program writes at most (``check_config`` sets it from
        #: the engine's chunk or largest bucket): a window group's bound
        self.span: Optional[int] = None

    def check_config(self, config):
        """And what the engine's prefill writes at once, which sizes the
        window group."""
        super().check_config(config)
        chunk = config.prefill_chunk
        self.span = chunk if chunk is not None else max(
            config.prefill_buckets)

    def window_pages(self, num_slots: int, max_seq: int) -> int:
        """Pages of the window group's pool: every slot at its bound, and a
        spare page a slot."""
        bound = window_page_bound(self.spec.sliding_window,
                                  self.span or max_seq, self.page_size)
        return num_slots * (min(bound, max_seq // self.page_size) + 1)

    def new_kv(self, num_slots: int, max_seq: int) -> PagedKVCache:
        c = self.spec
        self.check_max_seq(max_seq)
        groups = [PageGroup(layers, window,
                            self.num_pages if window is None
                            else self.window_pages(num_slots, max_seq))
                  for layers, window in page_groups(c)]
        return PagedKVCache(
            num_slots, c.num_hidden_layers, max_seq, c.num_key_value_heads,
            c.head_dim, dtype=self.params()["tok"].dtype,
            page_size=self.page_size, groups=groups)

    def plain_walk(self, kv: PagedKVCache):
        return grouped_walk(self.spec.num_attention_heads, kv)

    def publish_gauges(self, kv: PagedKVCache, stat_set):
        stat_set("kv_group_bytes.full", kv.group_bytes(windowed=False))
        stat_set("kv_group_bytes.window", kv.group_bytes(windowed=True))

    def note_tick(self, extras, n_active: int, stat_add):
        note_expert_tick(self.spec, extras, n_active, stat_add)
        note_window_walks(self._walks, stat_add)

    def note_lengths(self, seq_lens, stat_add):
        """A decode tick over sequences of ``seq_lens`` tokens (the new one
        included): the pages its window layers' walks read, and what a walk
        bounded by the length alone would."""
        n = np.asarray(list(seq_lens), np.int64)  # noqa: PTA002 -- host-side lengths the batcher holds (ints), never a device value
        live = (n - 1) // self.page_size + 1
        first = np.maximum(n - self.spec.sliding_window, 0) // self.page_size
        layers = len(self.spec.window_layers)
        stat_add("window_attn.pages_walked",
                 int((live - first).sum()) * layers)
        stat_add("window_attn.pages_live", int(live.sum()) * layers)

    def prefix_sig(self, kv: PagedKVCache):
        c = self.spec
        return (page_groups(c), c.num_key_value_heads, c.head_dim,
                str(kv.dtype), self.page_size)

    # -- its programs and what they take of the cache ------------------------
    def step_program(self):
        return get_trinity_paged_decode_step(self.spec, self.max_top_k,
                                             self.attn_impl)

    def chunk_program(self):
        return get_trinity_paged_chunk_fn(self.spec, self.max_top_k)

    @staticmethod
    def cache_arrays(kv: PagedKVCache):
        return (tuple(g.k for g in kv.groups), tuple(g.v for g in kv.groups),
                tuple(g.block_tables for g in kv.groups))

    _arenas = cache_arrays      # the name tests/test_serving_trinity.py uses

    def install(self, kv: PagedKVCache, arrays, lengths):
        kv.swap_groups(*arrays, lengths)


register_paged_decoder(TrinityForCausalLM, TrinityPagedDecoder)
