"""Speculative decoding over a paged target cache.

Same draft+verify tick as ``serving/llm/spec.py`` — the draft model
keeps its own small slot-layout :class:`StaticKVCache` (draft contexts
are tiny; paging them buys nothing), only the TARGET's K/V moves through
the page arena. The verify step scatters all ``k+1`` candidate rows per
slot through the block table (``[S*(k+1)]`` flattened physical indices)
into the whole target arena, in place (the jitted step donates it), and
gathers the full logical rows back for the multi-query attention,
so greedy output stays bitwise identical to the slot spec step, which is
itself bitwise the plain decoder (the composed parity test pins the
chain: paged-spec == slot-spec == plain slot decode on greedy).

The scheduler's room check must cover the SPECULATIVE horizon in pages:
a tick can advance a slot ``k+1`` positions, so ``PagedBatcher`` maps
pages for ``lengths + k + 1`` before a spec tick (its
``_ensure_decode_capacity``), exactly where the slot engine checked
``lengths + k + 1 <= max_seq``.
"""
from __future__ import annotations

import functools

import numpy as np

import jax
import jax.numpy as jnp

from ..decode import _block_decode, _layer_norm, _sample
from ..kvcache import valid_mask
from ..spec import GPTDecodeSpec, GPTSpecDecoder
from .decode import GPTPagedDecoder, jit_donating_arenas
from .pool import PagedKVCache, paged_gather_rows, paged_write_rows


def _paged_block_verify(spec, lp, h, kbuf, vbuf, li, block_tables,
                        pid_flat, ppos_flat, mask, scale):
    """``spec._block_verify`` with the K/V substrate paged: all T
    candidate rows scatter into layer ``li`` of the whole arenas
    through (``pid_flat``, ``ppos_flat``) — the [S*T] physical
    coordinates of ``positions..positions+T-1`` — then the full logical
    rows gather back for the attention. Dense only (the spec engine
    path never runs over int8 KV; the config gate predates paging)."""
    s, t = h.shape[0], h.shape[1]
    x = _layer_norm(h, lp["n1w"], lp["n1b"], spec.ln_epsilon)

    def heads(z):                                          # [S, T, H, D]
        return z.reshape(s, t, spec.num_heads, spec.head_dim)

    q = heads(x @ lp["qw"] + lp["qb"])
    kn = heads(x @ lp["kw"] + lp["kb"])
    vn = heads(x @ lp["vw"] + lp["vb"])
    flat = (s * t, spec.num_heads, spec.head_dim)
    kbuf = paged_write_rows(kbuf, kn.reshape(flat), pid_flat, ppos_flat,
                            li)
    vbuf = paged_write_rows(vbuf, vn.reshape(flat), pid_flat, ppos_flat,
                            li)
    kg = paged_gather_rows(kbuf, block_tables, li)         # [S, max, H, D]
    vg = paged_gather_rows(vbuf, block_tables, li)
    qh = jnp.transpose(q * scale, (0, 2, 1, 3))            # [S, H, T, D]
    kt = jnp.transpose(kg, (0, 2, 1, 3))                   # [S, H, max, D]
    vt = jnp.transpose(vg, (0, 2, 1, 3))
    prod = jnp.matmul(qh, jnp.swapaxes(kt, -1, -2))        # [S, H, T, max]
    weights = jax.nn.softmax(prod + mask, axis=-1)
    out = jnp.matmul(weights, vt)                          # [S, H, T, D]
    out = jnp.transpose(out, (0, 2, 1, 3)).reshape(s, t, spec.hidden_size)
    h = h + (out @ lp["ow"] + lp["ob"])
    x = _layer_norm(h, lp["n2w"], lp["n2b"], spec.ln_epsilon)
    ffn = jax.nn.gelu(x @ lp["w1"] + lp["b1"], approximate=False)
    return h + (ffn @ lp["w2"] + lp["b2"]), kbuf, vbuf


def build_paged_spec_decode_step(tspec: GPTDecodeSpec,
                                 dspec: GPTDecodeSpec, k: int,
                                 max_top_k: int, page_size: int):
    """The RAW paged speculative step; same signature as
    ``build_spec_decode_step`` with the target block table threaded
    after the draft buffers:

    step(params_t, params_d, kbuf_t, vbuf_t, kbuf_d, vbuf_d,
         block_tables, lengths, finished, last_tokens, temperature,
         top_k, do_sample, eos, key)
      -> (kbuf_t, vbuf_t, kbuf_d, vbuf_d, lengths + n, finished,
          new_last, out[S, k+2])

    The caller guarantees every ACTIVE slot has pages mapped through
    position ``lengths + k`` (PagedBatcher's pre-tick capacity pass).
    """
    if k < 1:
        raise ValueError(f"speculation depth k must be >= 1, got {k}")
    t_scale = 1.0 / np.sqrt(tspec.head_dim)
    d_scale = 1.0 / np.sqrt(dspec.head_dim)
    t_max_pos = tspec.max_position_embeddings
    d_max_pos = dspec.max_position_embeddings

    def _step(params_t, params_d, kbuf_t, vbuf_t, kbuf_d, vbuf_d,
              block_tables, lengths, finished, last_tokens, temperature,
              top_k, do_sample, eos, key):
        s = lengths.shape[0]
        pp_n = block_tables.shape[1]
        max_seq = pp_n * page_size
        d_max_seq = kbuf_d.shape[2]
        # -- 1. draft proposes k tokens greedily (slot-layout cache) -----
        # identical to the slot spec step, k+1 micro-steps (the last one
        # only deposits the final proposal's K/V row)
        d_last = last_tokens
        drafts = []
        for i in range(k + 1):
            pos_i = lengths + i
            posc = jnp.clip(pos_i, 0, d_max_pos - 1)
            h = params_d["tok"][d_last] + params_d["pos"][posc]
            mask = valid_mask(pos_i, d_max_seq, h.dtype)
            new_k, new_v = [], []
            for li, lp in enumerate(params_d["layers"]):
                h, kb, vb = _block_decode(dspec, lp, h, kbuf_d[:, li],
                                          vbuf_d[:, li], pos_i, mask,
                                          d_scale)
                new_k.append(kb)
                new_v.append(vb)
            kbuf_d = jnp.stack(new_k, axis=1)
            vbuf_d = jnp.stack(new_v, axis=1)
            if i == k:
                break
            h = _layer_norm(h, params_d["fnw"], params_d["fnb"],
                            dspec.ln_epsilon)
            lraw_d = (h @ params_d["tok"].T).astype(jnp.float32)
            d_i = jnp.argmax(lraw_d, axis=-1).astype(jnp.int32)
            drafts.append(d_i)
            d_last = d_i
        drafts_arr = jnp.stack(drafts, axis=1)                 # [S, k]

        # -- 2. target verifies through the page arena -------------------
        t_len = k + 1
        u = jnp.concatenate([last_tokens[:, None], drafts_arr], axis=1)
        pos_mat = lengths[:, None] + jnp.arange(t_len, dtype=jnp.int32)
        posc = jnp.clip(pos_mat, 0, t_max_pos - 1)
        h = params_t["tok"][u] + params_t["pos"][posc]         # [S, T, E]
        j = jnp.arange(max_seq, dtype=jnp.int32)[None, None]
        vmask = jnp.where(j <= pos_mat[:, :, None], 0.0,
                          -1e9).astype(h.dtype)[:, None]       # [S,1,T,max]
        # physical coordinates of all S*T candidate rows; out-of-range
        # positions (inactive slots) clip to the last table entry — the
        # trash page for freed slots
        page_idx = jnp.clip(pos_mat // page_size, 0, pp_n - 1)
        pid_flat = jnp.take_along_axis(block_tables, page_idx,
                                       axis=1).reshape(-1)     # [S*T]
        ppos_flat = (pos_mat % page_size).reshape(-1)
        for li, lp in enumerate(params_t["layers"]):
            h, kbuf_t, vbuf_t = _paged_block_verify(
                tspec, lp, h, kbuf_t, vbuf_t, li, block_tables,
                pid_flat, ppos_flat, vmask, t_scale)
        h = _layer_norm(h, params_t["fnw"], params_t["fnb"],
                        tspec.ln_epsilon)
        lraw = (h @ params_t["tok"].T).astype(jnp.float32)     # [S, T, V]
        t_greedy = jnp.argmax(lraw, axis=-1).astype(jnp.int32)

        # -- 3. accept-prefix + bonus (identical to the slot step) -------
        match = (drafts_arr == t_greedy[:, :k]).astype(jnp.int32)
        m = jnp.sum(jnp.cumprod(match, axis=1), axis=1)        # [S], 0..k
        m = jnp.where(do_sample | finished, 0, m)
        bonus = jnp.take_along_axis(t_greedy, m[:, None], axis=1)[:, 0]
        samp_tok = _sample(lraw[:, 0], temperature, top_k, do_sample,
                           key, max_top_k)
        step_tok = jnp.where(do_sample, samp_tok, bonus)
        step_tok = jnp.where(finished & (eos >= 0), eos, step_tok)
        idx = jnp.arange(t_len, dtype=jnp.int32)[None]         # [1, T]
        ext_drafts = jnp.concatenate(
            [drafts_arr, jnp.zeros((s, 1), jnp.int32)], axis=1)
        emit = jnp.where(idx < m[:, None], ext_drafts,
                         jnp.where(idx == m[:, None], step_tok[:, None],
                                   0))
        n_emit = m + 1
        hit_eos = ((emit == eos[:, None]) & (eos >= 0)[:, None]
                   & (idx < n_emit[:, None])).any(axis=1)
        finished = finished | hit_eos
        out = jnp.concatenate([n_emit[:, None], emit],
                              axis=1).astype(jnp.int32)        # [S, k+2]
        return (kbuf_t, vbuf_t, kbuf_d, vbuf_d, lengths + n_emit,
                finished, step_tok, out)

    return _step


@functools.lru_cache(maxsize=32)
def get_paged_spec_decode_step(tspec: GPTDecodeSpec,
                               dspec: GPTDecodeSpec, k: int,
                               max_top_k: int, page_size: int):
    """Jitted paged speculative step. Donates the TARGET arenas (the
    paged ones); the draft's slot-layout buffers are the slot plane's."""
    return jit_donating_arenas(
        build_paged_spec_decode_step(tspec, dspec, k, max_top_k,
                                     page_size), arenas=(2, 3))


class GPTPagedSpecDecoder(GPTSpecDecoder):
    """GPTSpecDecoder whose TARGET is a :class:`GPTPagedDecoder` —
    the draft cache stays slot-layout (``new_draft_kv`` inherited
    unchanged), only the verify step is swapped for the paged one."""

    def __init__(self, target: GPTPagedDecoder, draft_model, k: int = 4,
                 exec_cache=None):
        if not isinstance(target, GPTPagedDecoder):
            raise TypeError(
                "GPTPagedSpecDecoder needs a GPTPagedDecoder target; "
                "use GPTSpecDecoder for slot-layout targets")
        super().__init__(target, draft_model, k=k, exec_cache=exec_cache)
        self._key = self._key + ("paged", target.page_size)

    def spec_step_fn(self, num_slots: int, max_seq: int):
        return self.exec_cache.get_or_compile(
            self._key + ("spec_step", num_slots, max_seq),
            lambda: get_paged_spec_decode_step(
                self.target.spec, self.dspec, self.k,
                self.target.max_top_k, self.target.page_size))

    def step(self, kv: PagedKVCache, kv_draft, params_t, params_d,
             finished, last_tokens, samp_vecs, key):
        fn = self.spec_step_fn(kv.num_slots, kv.max_seq)
        (kt, vt, kd, vd, lengths, finished, last_new, out) = fn(
            params_t, params_d, kv.k, kv.v, kv_draft.k, kv_draft.v,
            kv.block_tables, kv.lengths, finished, last_tokens,
            *samp_vecs, key)
        kv.swap(kt, vt, lengths)
        kv_draft.swap(kd, vd, lengths)
        return finished, last_new, out
