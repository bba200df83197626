"""The paged programs of the Qwen3-Next family and ``Qwen3NextPagedDecoder``.

A sixth decoder family behind the same ``PagedBatcher``: the block is
``models.qwen3next.qwen3next_block`` and only the cache views differ. Three
layers in four are Gated-DeltaNet mixers that keep a state of constant size,
the fourth is full attention, so one ``PagedKVCache`` holds three kinds of
per-sequence state:

- the KV arena ``[P+1, full layers * Hkv, page, 2 * D]``, for the full
  layers only. It is head-major with fused rows, as ``paged/sala.py``'s is:
  arena row ``ai * Hkv + h`` is full layer ``ai``'s KV head ``h``, and a row
  of a page holds that head's key and value side by side (a page is fetched
  once and serves both products). ``paged_attention``'s plain grouped walk
  runs once a KV head, its ``Hq / Hkv`` query heads on the head's own pages:
  with 2 KV heads a ``[page, Hkv, 2 * D]`` row layout is tiled two sublanes
  deep on the device, and the walk's flat ``[page * Hkv, 2 * D]`` view of it
  was a copy of the whole arena a layer and tick (4.2 GB, read from a
  compile for a described v5e: PERF.md section 4, PR 40);
- the rule's states ``state["gdn<li>"]`` ``[slots, Hv, Dk, Dv]`` (float32),
  an array a linear layer and a row a slot: a prompt's first chunk starts
  from zeros, every later chunk and every decode step from what the last one
  left. (One array a layer, so that a program updates each in place: laid
  together as ``[slots, layers, ...]`` the compiled step copied all of them
  once more a tick.)
- the convolution's last inputs ``state["conv<li>"]`` ``[slots, K - 1,
  channels]``: the ``K - 1`` rows of ``[q | k | v]`` before the next token,
  as they were BEFORE the convolution.

Two views. :class:`PagedStep` is one decode step (one token a slot): a full
layer writes the token's K|V row and reads its pages through
``paged_attention`` (or, on the gather lane, a gather of the slot's rows); a
linear layer rolls its convolution window and its state by one
``gated_delta_step``. A slot whose ``finished`` flag is set (free, or still
being prefilled) is left alone: its row goes to the trash page, its walk is
one page long, and its step runs with ``g = 0`` and ``beta = 0``, which
leaves a state exactly as it was. :class:`PagedChunk` is up to ``T`` tokens
of ONE slot behind what is already cached, the program of every prefill (a
whole prompt is the chunk at offset 0): the full layers write the chunk's
rows and attend blockwise over the slot's pages (``paged/trinity.py``'s walk,
over fused rows), the linear layers run ``gated_delta_chunked`` from the
slot's carried state (zeros where the chunk is the prompt's first) and leave
the state after the chunk's last real token. Offset and true length are
arguments: one compiled program serves every chunk of every prompt.

The decode step also returns, packed behind the next tokens so that the
tick's one fetch brings them, the expert layers' two counters over the HELD
experts (as the Trinity step does).

The façade is ``paged/decode.py:PagedFamilyDecoder``, which also refuses what
no family but GPT serves yet (a mesh, int8, ``prefix_cache``, ``spec_k``,
sequence export); the class here declares what is this family's own.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from ....models.qwen3next import (Qwen3NextConfig, Qwen3NextForCausalLM,
                                  causal_conv, qwen3next_hidden)
from ....ops.gated_delta import CHUNK, gated_delta_chunked, gated_delta_step
from ....ops.paged_attention import paged_attention
from ..decode import jit_program
from .decode import (PagedFamilyDecoder, _largest_divisor, _sample,
                     _tick_counters, _window_walks, note_expert_tick,
                     note_window_walks, register_paged_decoder)
from .pool import PagedKVCache, paged_row_index

#: query rows of the chunk's attention computed at once, and the most pages
#: of one step of its walk over the slot's pages (scores of ``Q_ROWS x heads
#: x TILE_PAGES * page`` floats: 34 MB at 256 x 16 x 32 x 64)
Q_ROWS, TILE_PAGES = 256, 32
_NEG = -1e30


def _head_rows(cfg: Qwen3NextConfig, ai: int):
    """Arena rows of full layer ``ai``'s KV heads."""
    return ai * cfg.num_key_value_heads + jnp.arange(cfg.num_key_value_heads)


def state_rows(cfg: Qwen3NextConfig) -> dict:
    """``PagedKVCache(state_rows=...)`` of the family: a state and a
    convolution window a linear layer, each a row a slot."""
    rows = {}
    for li in range(len(cfg.linear_layers)):
        rows[f"gdn{li}"] = ("slot", (cfg.linear_num_value_heads,
                                     cfg.linear_key_head_dim,
                                     cfg.linear_value_head_dim))
        rows[f"conv{li}"] = ("slot", (cfg.linear_conv_kernel_dim - 1,
                                      cfg.conv_width))
    return rows


class PagedStep:
    """The cache view of one decode step: one new token per slot, the past in
    the KV pages, the rule's states and the convolution windows. Holds the
    (traced) arrays and replaces them as layers write: read them back when
    the layers are done."""

    def __init__(self, cfg, kvbuf, state, tables, positions, frozen,
                 attn_impl):
        self.cfg, self.kvbuf, self.tables = cfg, kvbuf, tables
        self.state = dict(state)
        self.frozen, self.attn_impl = frozen, attn_impl
        # a slot nobody decodes for reads one page and writes the trash page
        self.positions = jnp.where(frozen, 0, positions)
        pid, self.ppos = paged_row_index(tables, positions, kvbuf.shape[2])
        self.pid = jnp.where(frozen, kvbuf.shape[0] - 1, pid)

    def attend(self, ai, q, k, v, scale):
        s, _, hq, d = q.shape
        hkv = k.shape[2]
        rows = _head_rows(self.cfg, ai)
        self.kvbuf = self.kvbuf.at[
            self.pid[:, None], rows[None], self.ppos[:, None]].set(
                jnp.concatenate([k[:, 0], v[:, 0]], axis=-1))
        qg = q[:, 0].reshape(s, hkv, hq // hkv, d)
        if self.attn_impl == "kernel":
            # the plain walk, a KV head a call: its query heads on its pages
            arena = self.kvbuf[:, :, :, None]       # [P+1, L*Hkv, page, 1, 2D]
            return jnp.stack([
                paged_attention(qg[:, h], arena, None, self.tables,
                                self.positions, layer=ai * hkv + h,
                                scale=scale)
                for h in range(hkv)], axis=1).reshape(s, 1, hq, d)
        g = self.kvbuf[self.tables[:, :, None], rows[None, None]]
        g = jnp.moveaxis(g, 2, 1).reshape(s, hkv, -1, 2 * d)   # [S,Hkv,R,2D]
        seen = (jnp.arange(g.shape[2])[None]
                <= self.positions[:, None])[:, None, None]      # [S,1,1,R]
        prod = jnp.einsum("skgd,skrd->skgr", qg * scale, g[..., :d])
        weights = jax.nn.softmax(jnp.where(seen, prod, _NEG), axis=-1)
        # past a sequence's end may lie anything: 0 * NaN is NaN
        vd = jnp.where(jnp.moveaxis(seen, 3, 2), g[..., d:], 0.0)
        return jnp.einsum("skgr,skrd->skgd", weights, vd).reshape(
            s, 1, hq, d)

    def conv(self, li, x, kern):
        old = self.state[f"conv{li}"]
        window = jnp.concatenate([old, x], axis=1)              # [S, K, C]
        self.state[f"conv{li}"] = jnp.where(self.frozen[:, None, None], old,
                                            window[:, 1:])
        return causal_conv(window, kern, 1)

    def delta(self, li, q, k, v, g, beta):
        live = ~self.frozen[:, None]
        with jax.named_scope("gdn_step"):
            # g = 0 and beta = 0 leave a state exactly as it was
            o, self.state[f"gdn{li}"] = gated_delta_step(
                q[:, 0], k[:, 0], v[:, 0], jnp.where(live, g[:, 0], 0.0),
                jnp.where(live, beta[:, 0], 0.0), self.state[f"gdn{li}"])
        return o[:, None]


class PagedChunk:
    """The cache view of one chunk: ``T`` tokens of slot ``slot`` at
    positions ``start ..``, of which the first ``n_valid`` are real (the
    rest is right padding, routed to the trash page and kept out of the
    states)."""

    def __init__(self, cfg, kvbuf, state, tables, slot, start, n_valid):
        self.cfg, self.kvbuf, self.state = cfg, kvbuf, dict(state)
        self.slot, self.start, self.n_valid = slot, start, n_valid
        self.bt_row = tables[slot]                              # [PP]

    def attend(self, ai, q, k, v, scale):
        _, t, hq, d = q.shape
        hkv = k.shape[2]
        rows = _head_rows(self.cfg, ai)
        bt_row, page = self.bt_row, self.kvbuf.shape[2]
        pos = self.start + jnp.arange(t)
        pid = jnp.where(jnp.arange(t) < self.n_valid,
                        bt_row[jnp.clip(pos // page, 0, bt_row.shape[0] - 1)],
                        self.kvbuf.shape[0] - 1)
        self.kvbuf = self.kvbuf.at[
            pid[:, None], rows[None], (pos % page)[:, None]].set(
                jnp.concatenate([k[0], v[0]], axis=-1))
        kvbuf = self.kvbuf
        tile = _largest_divisor(bt_row.shape[0], TILE_PAGES)
        tile_rows = tile * page
        end = self.start + self.n_valid

        def queries(qb, posb):
            """``Q_ROWS`` query tokens: the walk over the slot's pages, a
            tile a step, up to the one the last row lies in."""
            last = (jnp.minimum(posb[-1] + 1, end) + tile_rows - 1) \
                // tile_rows

            def walk(c, carry):
                m, l, acc = carry
                pages = jax.lax.dynamic_slice_in_dim(bt_row, c * tile, tile)
                kv = kvbuf[pages[:, None], rows[None]]  # [tile,Hkv,page,2D]
                kv = jnp.moveaxis(kv, 1, 0).reshape(hkv, tile_rows, 2 * d)
                at = c * tile_rows + jnp.arange(tile_rows)
                seen = (at[None] <= posb[:, None])[:, None, None]  # [Q,1,1,R]
                scores = jnp.einsum("qkgd,krd->qkgr", qb, kv[..., :d]) * scale
                scores = jnp.where(seen, scores, _NEG)
                m_new = jnp.maximum(m, jnp.max(scores, -1, keepdims=True))
                alpha = jnp.exp(m - m_new)
                p = jnp.where(seen, jnp.exp(scores - m_new), 0.0)
                # a tile may reach into unwritten pages
                vt = jnp.where((at <= posb[-1])[None, :, None], kv[..., d:],
                               0.0)
                return (m_new, l * alpha + jnp.sum(p, -1, keepdims=True),
                        acc * alpha + jnp.einsum("qkgr,krd->qkgd", p, vt))

            shape = qb.shape[:3]
            with jax.named_scope("chunk_walk"):
                m, l, acc = jax.lax.fori_loop(0, last, walk, (
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

    def conv(self, li, x, kern):
        held = self.state[f"conv{li}"]
        carried = jnp.where(self.start > 0, held[self.slot], 0.0)
        window = jnp.concatenate([carried[None], x], axis=1)  # [1, K-1+T, C]
        # the K - 1 inputs before row n_valid: window rows n_valid ..
        self.state[f"conv{li}"] = held.at[self.slot].set(
            jax.lax.dynamic_slice_in_dim(window[0], self.n_valid,
                                         kern.shape[1] - 1, axis=0))
        return causal_conv(window, kern, x.shape[1])

    def delta(self, li, q, k, v, g, beta):
        held = self.state[f"gdn{li}"]
        carried = jnp.where(self.start > 0, held[self.slot], 0.0)
        with jax.named_scope("gdn_scan"):
            o, new = gated_delta_chunked(q, k, v, g, beta, carried[None],
                                         self.n_valid[None])
        self.state[f"gdn{li}"] = held.at[self.slot].set(new[0])
        return o


def build_qwen3next_paged_decode_step(cfg: Qwen3NextConfig, max_top_k: int,
                                      attn_impl: str = "gather"):
    """The RAW paged decode step of this family.

    step(params, kvbuf, state, tables, lengths, finished, last_tokens,
         temperature, top_k, do_sample, eos, key)
      -> (kvbuf, state, lengths+1, finished, next_tokens, fetch)

    ``fetch`` is ``[S + 2]`` int32: the next tokens, then the expert layers'
    two counters."""
    if attn_impl not in ("gather", "kernel"):
        raise ValueError(f"attn_impl must be 'gather' or 'kernel', got "
                         f"{attn_impl!r}")

    def _step(params, kvbuf, state, tables, lengths, finished, last_tokens,
              temperature, top_k, do_sample, eos, key):
        max_pos = tables.shape[1] * kvbuf.shape[2] - 1
        view = PagedStep(cfg, kvbuf, state, tables,
                         jnp.clip(lengths, 0, max_pos), finished, attn_impl)
        h, counts = qwen3next_hidden(cfg, params, last_tokens[:, None],
                                     lengths[:, None], view)
        nxt, finished = _sample(params, h[:, 0], finished,
                                (temperature, top_k, do_sample, eos), key,
                                max_top_k)
        fetch = jnp.concatenate([nxt, _tick_counters(counts)])
        return view.kvbuf, view.state, lengths + 1, finished, nxt, fetch

    return _step


def build_qwen3next_paged_chunk_fn(cfg: Qwen3NextConfig, max_top_k: int):
    """The RAW chunk program: ``T`` tokens of one slot behind ``start``
    cached ones.

    chunk(params, tokens [1, T], start, n_valid, is_last, kvbuf, state,
          tables, lengths, finished, slot, temperature, top_k, do_sample,
          eos, key)
      -> (kvbuf, state, lengths, finished, next_token [1], window walks [2])

    ``lengths[slot]`` becomes ``start + n_valid``; the token sampled from
    the last real row is the prompt's first generated one when ``is_last``
    (and then the slot's ``finished`` flag is the sample's; before that it
    stays set, which keeps the decode step off the slot). The walks are
    ``_window_walks`` of the chunk's expert layers."""

    def _chunk(params, tokens, start, n_valid, is_last, kvbuf, state, tables,
               lengths, finished, slot, temperature, top_k, do_sample, eos,
               key):
        t = tokens.shape[1]
        view = PagedChunk(cfg, kvbuf, state, tables, slot, start, n_valid)
        positions = (start + jnp.arange(t, dtype=jnp.int32))[None]
        h, counts = qwen3next_hidden(cfg, params, tokens, positions, view)
        walks = _window_walks(counts, t, cfg.num_experts_per_tok,
                              cfg.num_experts)
        last = jax.lax.dynamic_index_in_dim(
            h[0], jnp.maximum(n_valid - 1, 0), axis=0)         # [1, hidden]
        nxt, fin = _sample(params, last, False,
                           (temperature, top_k, do_sample, eos), key,
                           max_top_k)
        lengths = lengths.at[slot].set(start + n_valid)
        finished = finished.at[slot].set(jnp.where(is_last, fin[0], True))
        return view.kvbuf, view.state, lengths, finished, nxt, walks

    return _chunk


@functools.lru_cache(maxsize=64)
def get_qwen3next_paged_decode_step(cfg: Qwen3NextConfig, max_top_k: int,
                                    attn_impl: str):
    return jit_program(
        build_qwen3next_paged_decode_step(cfg, max_top_k, attn_impl),
        donate=(1, 2))


@functools.lru_cache(maxsize=64)
def get_qwen3next_paged_chunk_fn(cfg: Qwen3NextConfig, max_top_k: int):
    return jit_program(build_qwen3next_paged_chunk_fn(cfg, max_top_k),
                       donate=(5, 6))


class Qwen3NextPagedDecoder(PagedFamilyDecoder):
    """``PagedFamilyDecoder`` for a ``Qwen3NextForCausalLM``: head-major
    pages for the full layers, a delta-rule state and a convolution window a
    slot and linear layer."""

    family = "Qwen3-Next"
    prefills_in_chunks = True
    unserved_why = ("a shared prefix or a rolled-back draft needs a "
                    "snapshot of the linear layers' states at that row, and "
                    "nothing keeps one")

    def setup(self):
        if not (self.spec.full_layers and self.spec.linear_layers):
            raise NotImplementedError(
                "the Qwen3-Next paged decoder needs at least one full and "
                "one linear layer")

    def new_kv(self, num_slots: int, max_seq: int) -> PagedKVCache:
        c = self.spec
        self.check_max_seq(max_seq)
        hkv, d = c.num_key_value_heads, c.head_dim
        return PagedKVCache(
            num_slots, len(c.full_layers) * hkv, max_seq, 1, d,
            dtype=self.params()["tok"].dtype, page_size=self.page_size,
            num_pages=self.num_pages, fused_kv=True, row_shape=(2 * d,),
            state_rows=state_rows(c))

    def plain_walk(self, kv: PagedKVCache):
        # the head-major arena walked plainly: a KV head a call, its query
        # heads on the head's own fused rows
        _, _, page, row = kv.k.shape
        c = self.spec
        return (c.num_attention_heads // c.num_key_value_heads, 1, page, row,
                kv.k.dtype.itemsize, 1)

    def publish_gauges(self, kv: PagedKVCache, stat_set):
        stat_set("gdn_state_bytes", kv.state_bytes())
        # a token and full layer: every KV head's [K | V]
        stat_set("kv_row_bytes",
                 kv.row_nbytes() * self.spec.num_key_value_heads)

    def note_tick(self, extras, n_active: int, stat_add):
        """Every active slot stepped every linear layer's state."""
        note_expert_tick(self.spec, extras, n_active, stat_add)
        stat_add("gdn.step_rows", n_active * len(self.spec.linear_layers))
        note_window_walks(self._walks, stat_add)

    def note_chunk(self, start: int, n_valid: int, pages_per_seq: int,
                   stat_add):
        """The (token, linear layer) pairs this chunk scanned."""
        stat_add("gdn.chunk_rows",
                 int(n_valid) * len(self.spec.linear_layers))

    def prefix_sig(self, kv: PagedKVCache):
        c = self.spec
        return ("gdn", len(c.full_layers), len(c.linear_layers),
                c.num_key_value_heads, c.head_dim, str(kv.dtype),
                self.page_size)

    # -- its programs and what they take of the cache ------------------------
    def step_program(self):
        return get_qwen3next_paged_decode_step(self.spec, self.max_top_k,
                                               self.attn_impl)

    def chunk_program(self):
        return get_qwen3next_paged_chunk_fn(self.spec, self.max_top_k)

    def admits_chunk(self, chunk_len: int):
        if chunk_len > CHUNK and chunk_len % CHUNK:
            raise ValueError(
                f"a chunk of {chunk_len} tokens is over {CHUNK} and no "
                f"multiple of it (the gated delta rule's chunk)")

    def cache_arrays(self, kv: PagedKVCache):
        return kv.k, kv.state, kv.block_tables

    def install(self, kv: PagedKVCache, arrays, lengths):
        k, state = arrays
        kv.swap(k, kv.v, lengths, state)


register_paged_decoder(Qwen3NextForCausalLM, Qwen3NextPagedDecoder)
