"""Paged KV cache: block-table memory manager, paged attention, COW
prefix sharing (docs/serving.md "Paged KV cache").

The vLLM/PagedAttention design grafted under the static-slot LLM stack:
``pool`` owns the page arena + refcounted free list, ``decode``/``spec``
mirror the slot decode programs with the block table threaded through,
``prefix`` shares prefix pages by refcount (COW on divergence), and
``batcher`` admits on pages-at-current-lengths. Select with
``LLMEngineConfig(kv_layout="paged")``. The engine picks the decoder family
from the model it is given (``paged_decoder_class``), GPT by default, and asks
it a declared protocol (``decode.PagedDecoderProtocol``: abilities as class
attributes, counter hooks that default to nothing), never ``hasattr``. Every
family but GPT is a ``decode.PagedFamilyDecoder``, which owns the
constructor, the refusals, ``check_config`` and the calls that run the
programs; a family's module brings its two cache views, its two programs and
a class that declares its cache (``new_kv``, the arrays a program takes and
how they go back), its counters and its ``prefix_sig``: ``lfm2`` (a
convolution state a slot beside the pages), ``sala`` (pages for the sparse
layers only, their compressed keys by page, a linear state a slot),
``trinity`` (window and full layers keep different pages of one sequence: two
page groups), ``moonlight`` (latent attention: ONE row a token and layer for
all heads, absorbed in the step and expanded in the chunk), ``qwen3next``
(pages for one layer in four and, a slot and linear layer, a gated delta-rule
state and a short convolution's last inputs). A new family registers itself
(``register_paged_decoder``) and is imported here; no other file changes.
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
