"""Draft-model speculative decoding inside the jitted decode step.

Decode is memory-bound: each tick streams the whole KV cache to produce
ONE token per slot. Speculative decoding spends the idle FLOPs — a small
draft GPT proposes ``k`` tokens per tick (k cheap micro-steps over its
own small cache), then the target model verifies all ``k`` in ONE
multi-query step (k+1 queries over the full cache — barely more
expensive than the single-query tick it replaces) and the accept-prefix
selection happens on device. A tick emits 1..k+1 tokens.

Greedy acceptance math (``build_spec_decode_step``): with per-slot
position ``p`` and last emitted token ``x0`` (not yet in cache, same
convention as the plain step),

1. the draft greedily proposes ``d[0..k-1]`` (k+1 micro-steps share the
   tick; K/V rows for all of ``[x0, d0, .., d_{k-1}]`` land at
   ``p..p+k`` in the DRAFT cache, so a fully-accepted tick leaves the
   draft self-consistent);
2. the target runs queries ``u = [x0, d0, .., d_{k-1}]`` at positions
   ``p..p+k`` under an offset-causal mask, writing all k+1 K/V rows,
   producing greedy verdicts ``t[0..k]`` — ``t[i]`` is exactly what the
   plain decoder would emit after ``u[0..i]``;
3. ``m = |longest prefix with d[i] == t[i]|`` tokens of the draft are
   accepted and the bonus token ``t[m]`` rides along free: the tick emits
   ``d[0..m-1], t[m]`` (``m+1`` tokens) and advances lengths by ``m+1``.

Because each query's attention sees exactly the rows the plain decoder
would have seen (extra candidate rows are masked at -1e9 → exactly-0.0
softmax weight in f32), greedy output is **bitwise identical** to the
non-speculative static decoder — the regression test asserts it.
Sampling slots fall back to one verified token per tick (the position-0
logits ARE the plain step's logits, drawn with the tick key); note the
key-per-tick schedule means a sampling request's draw sequence matches
the plain engine only when tick counts align — greedy is the bitwise
contract, sampling stays distribution-correct.

The per-tick host traffic stays ONE fetch: the step packs
``[n_emitted | tokens...]`` per slot into a single ``[S, k+2]`` int32
array (LazyTensor async-dispatch discipline, arxiv 2102.13267 — the
fetch-counter test pins it).
"""
from __future__ import annotations

import functools
from typing import Optional, Tuple

import numpy as np

import jax
import jax.numpy as jnp

from ...models.gpt import gpt_hidden
from ..cache import ExecutableCache
from .decode import (GPTDecodeSpec, GPTStaticDecoder, _sample,
                     extract_gpt_params, get_prefill_fn, jit_program)
from .kvcache import SlotRows, StaticKVCache


def draft_proposals(dspec: GPTDecodeSpec, k: int, params_d, kbuf_d, vbuf_d,
                    lengths, last_tokens):
    """Part 1 of a speculative tick: the draft proposes ``k`` tokens
    greedily over its own small slot cache. Returns ``(kbuf_d, vbuf_d,
    drafts[S, k])``.

    k+1 micro-steps, not k: when every draft is accepted the tick's valid
    rows extend to position p+k, so the draft cache needs the LAST
    proposal's K/V row too — without it the next tick's draft attends a
    garbage row and acceptance collapses. The extra step only deposits
    that row; its logits are never formed."""
    d_last = last_tokens
    drafts = []
    for i in range(k + 1):
        pos_i = lengths + i
        view = SlotRows(kbuf_d, vbuf_d, pos_i, params_d["tok"].dtype)
        h = gpt_hidden(dspec, params_d, d_last, pos_i, view)      # [S, E]
        kbuf_d, vbuf_d = view.buffers()
        if i == k:
            break
        lraw_d = (h @ params_d["tok"].T).astype(jnp.float32)
        d_last = jnp.argmax(lraw_d, axis=-1).astype(jnp.int32)
        drafts.append(d_last)
    return kbuf_d, vbuf_d, jnp.stack(drafts, axis=1)


def verify_inputs(lengths, last_tokens, drafts):
    """Part 2's queries: ``u = [x0, d0, .., d_{k-1}]`` ``[S, k+1]`` at
    positions ``p..p+k``."""
    u = jnp.concatenate([last_tokens[:, None], drafts], axis=1)
    pos = lengths[:, None] + jnp.arange(u.shape[1], dtype=jnp.int32)
    return u, pos


def accept_prefix(params_t, h, drafts, lengths, finished, temperature,
                  top_k, do_sample, eos, key, max_top_k):
    """Part 3, all on device: from the target's final-norm hidden states
    ``h`` ``[S, k+1, E]`` of the verify queries, the accepted prefix of
    ``drafts`` plus the bonus token. Returns ``(lengths + n, finished,
    new_last, out[S, k+2])``."""
    s, k = drafts.shape
    lraw = (h @ params_t["tok"].T).astype(jnp.float32)         # [S, T, V]
    t_greedy = jnp.argmax(lraw, axis=-1).astype(jnp.int32)
    match = (drafts == t_greedy[:, :k]).astype(jnp.int32)
    m = jnp.sum(jnp.cumprod(match, axis=1), axis=1)            # [S], 0..k
    # sampling slots take one verified token per tick; finished slots
    # freeze (the host released them already — mirror the plain step)
    m = jnp.where(do_sample | finished, 0, m)
    bonus = jnp.take_along_axis(t_greedy, m[:, None], axis=1)[:, 0]
    samp_tok = _sample(lraw[:, 0], temperature, top_k, do_sample, key,
                       max_top_k)
    step_tok = jnp.where(do_sample, samp_tok, bonus)
    step_tok = jnp.where(finished & (eos >= 0), eos, step_tok)
    idx = jnp.arange(k + 1, dtype=jnp.int32)[None]             # [1, T]
    ext_drafts = jnp.concatenate(
        [drafts, jnp.zeros((s, 1), jnp.int32)], axis=1)
    emit = jnp.where(idx < m[:, None], ext_drafts,
                     jnp.where(idx == m[:, None], step_tok[:, None], 0))
    n_emit = m + 1
    hit_eos = ((emit == eos[:, None]) & (eos >= 0)[:, None]
               & (idx < n_emit[:, None])).any(axis=1)
    out = jnp.concatenate([n_emit[:, None], emit],
                          axis=1).astype(jnp.int32)            # [S, k+2]
    return lengths + n_emit, finished | hit_eos, step_tok, out


def build_spec_decode_step(tspec: GPTDecodeSpec, dspec: GPTDecodeSpec,
                           k: int, max_top_k: int):
    """The RAW (un-jitted) speculative decode step — the auditable
    program (registered as PTA009 entrypoint ``llm_spec_decode_step``).

    step(params_t, params_d, kbuf_t, vbuf_t, kbuf_d, vbuf_d, lengths,
         finished, last_tokens, temperature, top_k, do_sample, eos, key)
      -> (kbuf_t, vbuf_t, kbuf_d, vbuf_d, lengths + n, finished,
          new_last, out[S, k+2])

    ``out[s] = [n_emitted, tok_0, .., tok_{n-1}, 0...]`` — the single
    per-tick host fetch. The caller guarantees every ACTIVE slot has
    ``lengths + k + 1 <= max_seq`` (the scheduler's room check; it falls
    back to the plain tick otherwise).
    """
    if k < 1:
        raise ValueError(f"speculation depth k must be >= 1, got {k}")

    def _step(params_t, params_d, kbuf_t, vbuf_t, kbuf_d, vbuf_d, lengths,
              finished, last_tokens, temperature, top_k, do_sample, eos,
              key):
        kbuf_d, vbuf_d, drafts = draft_proposals(
            dspec, k, params_d, kbuf_d, vbuf_d, lengths, last_tokens)
        # the target verifies all k (+ the carried last token) at once:
        # all k+1 candidate rows are written before attending, query i's
        # own row is visible to it, mirroring the single-token step
        u, pos = verify_inputs(lengths, last_tokens, drafts)
        view = SlotRows(kbuf_t, vbuf_t, pos, params_t["tok"].dtype)
        h = gpt_hidden(tspec, params_t, u, pos, view)          # [S, T, E]
        kbuf_t, vbuf_t = view.buffers()
        return (kbuf_t, vbuf_t, kbuf_d, vbuf_d) + accept_prefix(
            params_t, h, drafts, lengths, finished, temperature, top_k,
            do_sample, eos, key, max_top_k)

    return _step


@functools.lru_cache(maxsize=32)
def get_spec_decode_step(tspec: GPTDecodeSpec, dspec: GPTDecodeSpec,
                         k: int, max_top_k: int):
    """THE speculative decode step: jitted once per (target spec, draft
    spec, k, max_top_k); one trace per (num_slots, max_seq) shape pair
    (``trace_counter`` pins it, same contract as ``get_decode_step``)."""
    return jit_program(build_spec_decode_step(tspec, dspec, k, max_top_k))


class GPTSpecDecoder:
    """Draft+verify façade over one target :class:`GPTStaticDecoder` and
    a small draft GPT model: draft parameter extraction, the draft's own
    :class:`StaticKVCache` (same slots/positions, smaller heads), and
    ExecutableCache-audited access to the compiled spec step and draft
    prefill. The draft cache advances in lockstep with the target's —
    they share ONE lengths vector."""

    def __init__(self, target: GPTStaticDecoder, draft_model, k: int = 4,
                 exec_cache: Optional[ExecutableCache] = None):
        if k < 1:
            raise ValueError(f"speculation depth k must be >= 1, got {k}")
        if target.mesh is not None:
            raise NotImplementedError(
                "speculative decoding over a slot-sharded (mesh) decoder "
                "is not supported yet — the draft cache would need the "
                "same GSPMD partitioning")
        self.target = target
        self.k = int(k)
        self.dspec = GPTDecodeSpec.from_model(draft_model)
        if self.dspec.vocab_size != target.spec.vocab_size:
            raise ValueError(
                f"draft vocab {self.dspec.vocab_size} != target vocab "
                f"{target.spec.vocab_size} — speculative verification "
                f"compares token ids, the vocabularies must be shared")
        self._draft_model = draft_model
        # `is not None`, not truthiness: an empty ExecutableCache is falsy
        self.exec_cache = (exec_cache if exec_cache is not None
                           else target.exec_cache)
        self._key = ("gpt-spec", target.spec, self.dspec, self.k,
                     target.max_top_k)
        #: tuned (block_q, block_k) for the verify attention shape, when
        #: the autotuner knows this (q=k+1, kv=max_seq) flash family — the
        #: dense CPU lane ignores it; the TPU flash-verify lane consumes
        #: it (resolved lazily per max_seq in :meth:`verify_blocks`)
        self._verify_blocks: Optional[Tuple[int, int]] = None

    def draft_params(self):
        return extract_gpt_params(self._draft_model)

    def new_draft_kv(self, num_slots: int, max_seq: int) -> StaticKVCache:
        dtype = self._draft_model.gpt.word_embeddings.weight._data.dtype
        return StaticKVCache(num_slots, self.dspec.num_layers, max_seq,
                             self.dspec.num_heads, self.dspec.head_dim,
                             dtype=dtype)

    def verify_blocks(self, max_seq: int) -> Optional[Tuple[int, int]]:
        """Tuned Pallas blocks for the verify-step attention — the
        (q = k+1, kv = max_seq) causal flash shape — from the autotuner's
        winner memo (``paddle_tpu.tuner``). None when untuned (the dense
        verify lane needs no blocks; a TPU flash-verify lane would)."""
        if self._verify_blocks is None:
            from ...tuner import get_spec_verify_blocks
            self._verify_blocks = get_spec_verify_blocks(
                self.k, max_seq, self.target.spec.head_dim, "float32")
        return self._verify_blocks

    # -- compiled-program access ---------------------------------------------
    def spec_step_fn(self, num_slots: int, max_seq: int):
        return self.exec_cache.get_or_compile(
            self._key + ("spec_step", num_slots, max_seq),
            lambda: get_spec_decode_step(self.target.spec, self.dspec,
                                         self.k, self.target.max_top_k))

    def draft_prefill_fn(self, batch: int, prompt_len: int):
        # draft prefill is greedy-only (drafts are proposals): top-k 0
        return self.exec_cache.get_or_compile(
            self._key + ("draft_prefill", batch, prompt_len),
            lambda: get_prefill_fn(self.dspec, 0))

    # -- convenience wrappers ------------------------------------------------
    def draft_prefill(self, kv_draft: StaticKVCache, params_d, tokens,
                      true_lens, slot_ids, lengths, finished, samp_vecs,
                      key):
        """Prefill the DRAFT cache for a newly admitted prompt. Only the
        K/V outputs are kept — lengths/finished/first-token are the
        target prefill's business (both prefills would compute identical
        lengths; the draft's sampled token is discarded)."""
        fn = self.draft_prefill_fn(tokens.shape[0], tokens.shape[1])
        kd, vd, _lens, _fin, _nxt = fn(
            params_d, tokens, true_lens, kv_draft.k, kv_draft.v, lengths,
            finished, slot_ids, *samp_vecs, key)
        kv_draft.k, kv_draft.v = kd, vd

    def step(self, kv: StaticKVCache, kv_draft: StaticKVCache, params_t,
             params_d, finished, last_tokens, samp_vecs, key):
        """Advance every slot 1..k+1 tokens; swaps BOTH caches and
        returns (finished[S] device, new_last[S] device, out[S, k+2]
        device) — the caller performs the tick's single host fetch on
        ``out``."""
        fn = self.spec_step_fn(kv.num_slots, kv.max_seq)
        (kt, vt, kd, vd, lengths, finished, last_new, out) = fn(
            params_t, params_d, kv.k, kv.v, kv_draft.k, kv_draft.v,
            kv.lengths, finished, last_tokens, *samp_vecs, key)
        kv.swap(kt, vt, lengths)
        kv_draft.swap(kd, vd, lengths)
        return finished, last_new, out


# -- trace-audit registration (tools/analyze/trace, PTA009/PTA010) -----------

_AUDIT_TSPEC = GPTDecodeSpec(vocab_size=32, hidden_size=8, num_layers=1,
                             num_heads=2, max_position_embeddings=64)
_AUDIT_DSPEC = GPTDecodeSpec(vocab_size=32, hidden_size=4, num_layers=1,
                             num_heads=1, max_position_embeddings=64)
_AUDIT_K = 2
_AUDIT_TOP_K = 4


def _audit_spec_step():
    from ...core import audit
    from .decode import _audit_params
    slots, max_seq = 2, 16
    tkv = (slots, _AUDIT_TSPEC.num_layers, max_seq,
           _AUDIT_TSPEC.num_heads, _AUDIT_TSPEC.head_dim)
    dkv = (slots, _AUDIT_DSPEC.num_layers, max_seq,
           _AUDIT_DSPEC.num_heads, _AUDIT_DSPEC.head_dim)

    def make_args(variant):
        rng = np.random.default_rng(777 + variant)
        return (_audit_params(rng, _AUDIT_TSPEC),
                _audit_params(rng, _AUDIT_DSPEC),
                jnp.zeros(tkv, jnp.float32),
                jnp.zeros(tkv, jnp.float32),
                jnp.zeros(dkv, jnp.float32),
                jnp.zeros(dkv, jnp.float32),
                jnp.asarray([3, 1], jnp.int32),           # lengths
                jnp.zeros((slots,), bool),                # finished
                jnp.asarray(rng.integers(0, 32, slots), jnp.int32),
                jnp.ones((slots,), jnp.float32),          # temperature
                jnp.zeros((slots,), jnp.int32),           # top_k
                jnp.zeros((slots,), bool),                # do_sample
                jnp.full((slots,), -1, jnp.int32),        # eos
                jax.random.PRNGKey(variant))
    return audit.AuditSpec(
        fn=build_spec_decode_step(_AUDIT_TSPEC, _AUDIT_DSPEC, _AUDIT_K,
                                  _AUDIT_TOP_K),
        make_args=make_args)


def _register_audit_entrypoints():
    from ...core import audit
    audit.register_entrypoint("llm_spec_decode_step", _audit_spec_step,
                              tags=("serving", "decode", "speculative",
                                    "bench"))


_register_audit_entrypoints()
