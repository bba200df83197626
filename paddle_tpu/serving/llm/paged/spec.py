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

from ....models.gpt import gpt_hidden
from ..decode import jit_program
from ..spec import (GPTDecodeSpec, GPTSpecDecoder, accept_prefix,
                    draft_proposals, verify_inputs)
from .decode import GPTPagedDecoder
from .pool import PagedKVCache, PagedRows


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

    def _step(params_t, params_d, kbuf_t, vbuf_t, kbuf_d, vbuf_d,
              block_tables, lengths, finished, last_tokens, temperature,
              top_k, do_sample, eos, key):
        # the draft and the accept-prefix are the slot spec step's; only
        # the verify view differs: all k+1 candidate rows per slot scatter
        # through the block table, then the full logical rows gather back
        kbuf_d, vbuf_d, drafts = draft_proposals(
            dspec, k, params_d, kbuf_d, vbuf_d, lengths, last_tokens)
        u, pos = verify_inputs(lengths, last_tokens, drafts)
        view = PagedRows(kbuf_t, vbuf_t, block_tables, pos, page_size,
                         "gather", params_t["tok"].dtype)
        h = gpt_hidden(tspec, params_t, u, pos, view)          # [S, T, E]
        return (view.kbuf, view.vbuf, kbuf_d, vbuf_d) + accept_prefix(
            params_t, h, drafts, lengths, finished, temperature, top_k,
            do_sample, eos, key, max_top_k)

    return _step


@functools.lru_cache(maxsize=32)
def get_paged_spec_decode_step(tspec: GPTDecodeSpec,
                               dspec: GPTDecodeSpec, k: int,
                               max_top_k: int, page_size: int):
    """Jitted paged speculative step. Donates the TARGET arenas (the
    paged ones); the draft's slot-layout buffers are the slot plane's."""
    return jit_program(
        build_paged_spec_decode_step(tspec, dspec, k, max_top_k,
                                     page_size), donate=(2, 3))


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
