"""Paged KV cache: block-table memory manager, paged attention, COW
prefix sharing (docs/serving.md "Paged KV cache").

The vLLM/PagedAttention design grafted under the static-slot LLM stack:
``pool`` owns the page arena + refcounted free list, ``decode``/``spec``
mirror the slot decode programs with the block table threaded through,
``prefix`` shares prefix pages by refcount (COW on divergence), and
``batcher`` admits on pages-at-current-lengths. Select with
``LLMEngineConfig(kv_layout="paged")``. The engine picks the decoder family
from the model it is given (``paged_decoder_class``): GPT by default,
``lfm2`` for the LFM2-MoE family, whose cache also holds a per-slot
convolution state beside the pages, ``sala`` for MiniCPM-SALA, whose cache
holds pages for its sparse layers only, their compressed keys by page and a
linear-attention state a slot, ``trinity`` for the Trinity family, whose
window and full attention layers keep different pages of one sequence (two
page groups in one cache), ``moonlight`` for the Moonlight family, whose
latent attention keeps ONE row a token and layer for all its heads (one arena,
an absorbed decode step and an expanded chunk program over it), ``qwen3next``
for the Qwen3-Next family, whose cache holds pages for its full-attention
layers only (one layer in four) and, a slot and linear layer, a gated
delta-rule state and a short convolution's last inputs.
"""
from .batcher import PagedBatcher
from .decode import (GPTPagedDecoder, paged_decoder_class,
                     register_paged_decoder, build_paged_decode_step,
                     build_paged_prefill_fn, build_paged_tail_prefill_fn,
                     get_paged_decode_step, get_paged_prefill_fn,
                     get_paged_tail_prefill_fn)
from .pool import (PagedKVCache, PageGroup, PagePool, PagesExhausted,
                   paged_gather_rows, paged_write_prompt_rows,
                   paged_write_rows, pages_for_tokens)
from .prefix import PagedPrefixEntry, PagedPrefixStore
from .lfm2 import LFM2PagedDecoder
from .sala import SALAPagedDecoder
from .trinity import TrinityPagedDecoder
from .moonlight import MoonlightPagedDecoder
from .qwen3next import Qwen3NextPagedDecoder
from .spec import (GPTPagedSpecDecoder, build_paged_spec_decode_step,
                   get_paged_spec_decode_step)

__all__ = [
    "PageGroup",
    "PagePool",
    "PagedKVCache",
    "PagesExhausted",
    "pages_for_tokens",
    "paged_write_rows",
    "paged_write_prompt_rows",
    "paged_gather_rows",
    "build_paged_decode_step",
    "build_paged_prefill_fn",
    "build_paged_tail_prefill_fn",
    "get_paged_decode_step",
    "get_paged_prefill_fn",
    "get_paged_tail_prefill_fn",
    "GPTPagedDecoder",
    "LFM2PagedDecoder",
    "SALAPagedDecoder",
    "TrinityPagedDecoder",
    "MoonlightPagedDecoder",
    "Qwen3NextPagedDecoder",
    "paged_decoder_class",
    "register_paged_decoder",
    "build_paged_spec_decode_step",
    "get_paged_spec_decode_step",
    "GPTPagedSpecDecoder",
    "PagedPrefixEntry",
    "PagedPrefixStore",
    "PagedBatcher",
]
