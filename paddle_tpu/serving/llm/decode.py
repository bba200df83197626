"""Pure-jax prefill + single-compile decode step for GPT models.

The concat-cache ``generate`` retraces every token because the KV shapes
grow; here the whole decode tick is one jitted function over fixed
``[num_slots, ...]`` shapes — greedy/temperature/top-k sampling and eos
masking included — so XLA fuses it once and reuses it for every token of
every request ("Operator Fusion in XLA", arxiv 2301.13062). Parameters
are passed as a pytree argument (not baked into the trace), so training
and serving can share one executable across checkpoint reloads.

Every program here and under ``paged/`` is the same three steps: build the
cache view of where its K/V rows live (``kvcache.SlotRows`` / ``TailRows``,
``models.gpt.FullSequence``, ``paged.pool.PagedRows``), run
``models.gpt.gpt_hidden`` (the block, written once) through it, and end in
:func:`sample_next`. The math mirrors the framework's dense eval path
operation-for-operation (``nn.transformer.MultiHeadAttention`` dense
branch, ``F.layer_norm``, ``F.gelu(approximate=False)``, tied-embedding
logits, and the sampling recipe of ``models.gpt._gpt_generate``), so
static-slot decode emits the same tokens as the reference concat-cache
path — the equivalence test in ``tests/test_llm_serving.py`` asserts it
token-for-token.

Per-slot sampling state travels as device vectors (``temperature``,
``top_k``, ``do_sample``, ``eos``; eos < 0 means "no eos"), so requests
with different sampling settings share the single compiled step.
"""
from __future__ import annotations

import functools
from dataclasses import dataclass
from typing import Any, Dict, Optional, Sequence, Tuple

import numpy as np

import jax
import jax.numpy as jnp

from ...models.gpt import (FullSequence, GPTDecodeSpec, extract_gpt_params,
                           gpt_hidden, stack_kv)
from ...observability import opscope
from ..cache import ExecutableCache, default_cache
from .kvcache import SlotRows, StaticKVCache, TailRows, is_quantized_kv, \
    valid_mask, write_prompt_kv, write_prompt_kv_at


@dataclass
class SamplingParams:
    """Per-request decode settings (host side; the scheduler packs them
    into the per-slot device vectors)."""
    do_sample: bool = False
    temperature: float = 1.0
    top_k: int = 0
    eos_token_id: Optional[int] = None
    max_new_tokens: int = 32

    def clamped_temperature(self) -> float:
        # same guard the reference generate applies host-side
        return max(float(self.temperature), 1e-6)


#: per-layer weight matrices that quantize to int8 (biases/norms stay f32
#: — they are O(E) bytes and scale-sensitive)
_QUANT_WEIGHT_KEYS = ("qw", "kw", "vw", "ow", "w1", "w2")


def quantize_gpt_params(params: Dict[str, Any]) -> Dict[str, Any]:
    """Per-out-channel int8 quantization of the GPT weight pytree: each
    matmul weight becomes ``{"q": int8 [in, out], "s": f32 [out]}`` with
    ``w ≈ q * s`` (scale = absmax/127 per column). The embedding tables
    stay f32: ``tok`` doubles as the logit head, where a per-row scale
    would perturb the argmax ordering the accuracy budget is measured on.
    Layout matches :func:`extract_gpt_params`, so the same step builders
    serve both — ``_mm`` dispatches on the leaf type."""
    from ...quantization import quantize_weight_int8

    def _q(w):
        q, s = quantize_weight_int8(w, quant_axis=1)
        return {"q": q, "s": s}

    layers = tuple(
        {k: (_q(v) if k in _QUANT_WEIGHT_KEYS else v)
         for k, v in lp.items()}
        for lp in params["layers"])
    return dict(params, layers=layers)


def _sample(lraw, temperature, top_k, do_sample, key, max_top_k):
    """Greedy argmax / temperature+top-k categorical, vectorized per slot.

    ``lraw``: [S, V] float32 last-token logits. Mirrors the reference
    ``_gpt_generate`` recipe: greedy ignores temperature; sampling divides
    by (pre-clamped) temperature, masks everything below the k-th logit to
    -1e9 when ``top_k > 0``, then draws ``jax.random.categorical(key, ·)``.
    ``max_top_k`` is the static top-k width; per-slot ``top_k`` selects the
    effective threshold inside it.
    """
    greedy = jnp.argmax(lraw, axis=-1).astype(jnp.int32)
    lt = lraw / temperature[:, None]
    if max_top_k > 0:
        vals = jax.lax.top_k(lt, max_top_k)[0]            # [S, maxK] desc
        kidx = jnp.clip(top_k, 1, max_top_k) - 1
        kth = jnp.take_along_axis(vals, kidx[:, None], axis=-1)
        filtered = jnp.where(lt < kth, -1e9, lt)
        lt = jnp.where((top_k > 0)[:, None], filtered, lt)
    sampled = jax.random.categorical(key, lt, axis=-1).astype(jnp.int32)
    return jnp.where(do_sample, sampled, greedy)


def last_rows(h, lens):
    """Row ``lens - 1`` of each right-padded sequence: ``[B, T, E]`` ->
    ``[B, E]``."""
    return jnp.take_along_axis(
        h, (lens - 1)[:, None, None].astype(jnp.int32), axis=1)[:, 0]


def sample_next(params, last, frozen, temperature, top_k, do_sample, eos,
                key, max_top_k):
    """The tail of every program: logits of the ``last`` hidden rows
    ``[N, E]`` against the tied embedding, :func:`_sample`, and the eos
    bookkeeping of the reference generate. ``frozen``: rows that had
    finished before this call and keep emitting their eos (``False`` for
    rows that were only just admitted). Returns ``(next tokens, finished)``
    per row."""
    with jax.named_scope("decode/head"):
        lraw = (last @ params["tok"].T).astype(jnp.float32)       # [N, V]
    with jax.named_scope("decode/sample"):
        nxt = _sample(lraw, temperature, top_k, do_sample, key, max_top_k)
        nxt = jnp.where(frozen & (eos >= 0), eos, nxt)
        return nxt, frozen | ((nxt == eos) & (eos >= 0))


# -- the compiled programs ---------------------------------------------------

def jit_program(raw, donate=()):
    """``jax.jit`` of a raw program, donating the positional arguments
    ``donate`` (the paged programs' arenas: with the in-place row writes
    this is what lets XLA alias each arena to its output instead of
    copying it). The attached ``trace_counter["traces"]`` counts
    Python-body executions == XLA traces (the compile-counter tests assert
    it stays flat after warmup); the compiled module keeps the raw
    program's name (``jit__step``, ``jit__prefill``, ``jit__tail``), which
    dumps and traces are read by; under that name the trace is noted for
    ``observability.opscope``, which maps a device event back to the scope
    it came from (the body runs only while JAX traces, so a call pays
    nothing for it)."""
    counter = {"traces": 0}

    @functools.wraps(raw)
    def _fn(*args):
        counter["traces"] += 1
        opscope.note("jit_" + raw.__name__, fn, args)
        return raw(*args)

    fn = jax.jit(_fn, donate_argnums=donate)
    fn.trace_counter = counter
    return fn


def build_decode_step(spec: GPTDecodeSpec, max_top_k: int):
    """The RAW (un-jitted) decode step — the auditable program.

    Split out of :func:`get_decode_step` so the trace auditor
    (tools/analyze/trace, PTA009/PTA010) can wrap the same function in its
    own counting jit without disturbing the production lru-cached wrapper.
    """

    def _step(params, kbuf, vbuf, lengths, finished, last_tokens,
              temperature, top_k, do_sample, eos, key):
        # a slot's write position is its length
        view = SlotRows(kbuf, vbuf, lengths, params["tok"].dtype)
        h = gpt_hidden(spec, params, last_tokens, lengths, view)  # [S, E]
        nxt, finished = sample_next(params, h, finished, temperature,
                                    top_k, do_sample, eos, key, max_top_k)
        kbuf, vbuf = view.buffers()
        return kbuf, vbuf, lengths + 1, finished, nxt

    return _step


@functools.lru_cache(maxsize=64)
def get_decode_step(spec: GPTDecodeSpec, max_top_k: int):
    """THE decode step: jitted once per (spec, max_top_k); each distinct
    (num_slots, max_seq) shape pair traces exactly once.

    step(params, kbuf, vbuf, lengths, finished, last_tokens,
         temperature, top_k, do_sample, eos, key)
      -> (kbuf, vbuf, lengths+1, finished, next_tokens)

    All slots advance unconditionally (inactive slots compute masked
    garbage that the scheduler discards — uniform shapes are what keep the
    program unique); per-slot eos semantics match the reference generate:
    finished rows keep emitting their eos token.
    """
    return jit_program(build_decode_step(spec, max_top_k))


def build_prefill_fn(spec: GPTDecodeSpec, max_top_k: int):
    """The RAW (un-jitted) prefill — see :func:`build_decode_step`."""

    def _prefill(params, tokens, true_lens, kbuf, vbuf, lengths, finished,
                 slot_ids, temperature, top_k, do_sample, eos, key):
        view = FullSequence()
        pos = jnp.arange(tokens.shape[1], dtype=jnp.int32)[None]
        h = gpt_hidden(spec, params, tokens, pos, view)        # [B, L, E]
        kbuf, vbuf = write_prompt_kv(kbuf, vbuf, *stack_kv(view.kv, 1),
                                     slot_ids)
        lengths = lengths.at[slot_ids].set(true_lens)
        nxt, fin = sample_next(params, last_rows(h, true_lens), False,
                               temperature, top_k, do_sample, eos, key,
                               max_top_k)
        return kbuf, vbuf, lengths, finished.at[slot_ids].set(fin), nxt

    return _prefill


@functools.lru_cache(maxsize=64)
def get_prefill_fn(spec: GPTDecodeSpec, max_top_k: int):
    """Bucketed prefill: run the whole (right-padded) prompt batch through
    the causal stack, write its K/V into the target slots, set their
    lengths, and sample the first generated token. One trace per
    (batch, prompt_bucket) shape — a small closed set when prompts are
    padded to buckets.

    prefill(params, tokens[B, Lp], true_lens[B], kbuf, vbuf, lengths,
            finished, slot_ids[B], temperature[B], top_k[B], do_sample[B],
            eos[B], key)
      -> (kbuf, vbuf, lengths, finished, next_tokens[B])

    Right-padding is safe under the causal mask: real position i only
    attends j <= i < true_len, and the junk K/V written at
    [true_len, Lp) is masked by the slot length until later tokens
    overwrite it.
    """
    return jit_program(build_prefill_fn(spec, max_top_k))


def build_tail_prefill_fn(spec: GPTDecodeSpec, max_top_k: int):
    """The RAW (un-jitted) tail prefill — prefill a prompt *suffix* into a
    slot whose first ``starts[i]`` rows were bulk-copied from the prefix
    store. Queries attend over the slot's FULL cache row (cached prefix +
    the fresh tail spliced in: :class:`~.kvcache.TailRows`) under an
    offset-causal mask, so the produced hidden states — and therefore the
    first sampled token — are bitwise what a full prefill of the whole
    prompt would produce: masked positions contribute exactly-0.0 softmax
    weight (same -1e9 additive mask as the dense path), and row-wise dot
    products contract in the same order regardless of the extra
    zero-weight columns. The buffers are written once, after the layer
    loop, via ONE update per request.
    """

    def _tail(params, tokens, tail_lens, starts, kbuf, vbuf, lengths,
              finished, slot_ids, temperature, top_k, do_sample, eos, key):
        # tokens: [B, Lt] right-padded tails; tail_lens: [B] true tail
        # counts; starts: [B] reuse offsets (block multiples).
        if is_quantized_kv(kbuf):
            raise NotImplementedError(
                "tail prefill (prefix reuse) over an int8 KV cache is "
                "unsupported; LLMEngineConfig gates prefix_cache off for "
                "kv_dtype='int8'")
        pos = starts[:, None] + jnp.arange(tokens.shape[1],
                                           dtype=jnp.int32)[None]
        view = TailRows(lambda buf, li: buf[slot_ids, li], kbuf, vbuf,
                        starts, valid_mask(pos, kbuf.shape[2],
                                           params["tok"].dtype))
        h = gpt_hidden(spec, params, tokens, pos, view)        # [B, Lt, E]
        kbuf, vbuf = write_prompt_kv_at(kbuf, vbuf, *stack_kv(view.kv, 1),
                                        slot_ids, starts)
        lengths = lengths.at[slot_ids].set(starts + tail_lens)
        nxt, fin = sample_next(params, last_rows(h, tail_lens), False,
                               temperature, top_k, do_sample, eos, key,
                               max_top_k)
        return kbuf, vbuf, lengths, finished.at[slot_ids].set(fin), nxt

    return _tail


@functools.lru_cache(maxsize=64)
def get_tail_prefill_fn(spec: GPTDecodeSpec, max_top_k: int):
    """Bucketed *tail* prefill for prefix-cache hits: same contract as
    :func:`get_prefill_fn` plus a per-request ``starts`` offset vector.
    One trace per (batch, tail_bucket) shape.

    tail_prefill(params, tokens[B, Lt], tail_lens[B], starts[B], kbuf,
                 vbuf, lengths, finished, slot_ids[B], temperature[B],
                 top_k[B], do_sample[B], eos[B], key)
      -> (kbuf, vbuf, lengths, finished, next_tokens[B])
    """
    return jit_program(build_tail_prefill_fn(spec, max_top_k))


def build_insert_prefix_fn():
    """The RAW prefix bulk-copy: land a cached ``[L, n, H, D]`` prefix
    into one slot's rows [0, n) — ONE batched ``dynamic_update_slice``
    per buffer across all layers (the tentpole's no-per-layer-host-loop
    invariant lives here)."""

    def _insert(kbuf, vbuf, k_pre, v_pre, slot):
        return write_prompt_kv_at(kbuf, vbuf, k_pre[None], v_pre[None],
                                  jnp.asarray([slot], jnp.int32),
                                  jnp.asarray([0], jnp.int32))

    return _insert


@functools.lru_cache(maxsize=8)
def get_insert_prefix_fn():
    """Jitted prefix bulk-copy; retraces only per distinct prefix-row
    count (block multiples — a small closed set)."""
    return jit_program(build_insert_prefix_fn())


def pack_sampling(params_list: Sequence[SamplingParams]):
    """Host-side SamplingParams -> the per-slot device vectors the compiled
    step consumes (eos -1 disables eos handling for that slot)."""
    temps = [p.clamped_temperature() for p in params_list]
    eoses = [-1 if p.eos_token_id is None else int(p.eos_token_id)
             for p in params_list]
    temp = np.asarray(temps, np.float32)  # noqa: PTA002 -- packs host-side SamplingParams fields (python scalars), no device value involved
    topk = np.asarray([int(p.top_k) for p in params_list], np.int32)  # noqa: PTA002 -- host python scalars
    do_s = np.asarray([bool(p.do_sample) for p in params_list], np.bool_)  # noqa: PTA002 -- host python scalars
    eos = np.asarray(eoses, np.int32)  # noqa: PTA002 -- host python scalars
    return (jnp.asarray(temp), jnp.asarray(topk), jnp.asarray(do_s),
            jnp.asarray(eos))


class GPTStaticDecoder:
    """Object façade over the compiled prefill/decode programs for one
    GPT model: parameter extraction, KV-cache construction, and
    ExecutableCache-audited access to the jitted functions (a cache miss
    marks the first time a shape signature is seen == one XLA trace, the
    same accounting the classifier Engine uses)."""

    def __init__(self, model, max_top_k: int = 64,
                 exec_cache: Optional[ExecutableCache] = None,
                 mesh=None, slot_axis: str = "model",
                 weight_dtype: str = "float32",
                 kv_dtype: str = "float32"):
        self.spec = GPTDecodeSpec.from_model(model)
        self._model = model
        self.max_top_k = max(0, min(int(max_top_k), self.spec.vocab_size))
        if weight_dtype not in ("float32", "int8"):
            raise ValueError(
                f"weight_dtype must be 'float32' or 'int8', got "
                f"{weight_dtype!r}")
        if kv_dtype not in ("float32", "int8"):
            raise ValueError(
                f"kv_dtype must be 'float32' or 'int8', got {kv_dtype!r}")
        self.weight_dtype = weight_dtype
        self.kv_dtype = kv_dtype
        # NOT `exec_cache or ...`: an empty ExecutableCache has len() == 0
        # and is falsy, which would silently orphan the engine's cache.
        # Default is the ONE process-wide cache (serving/cache.py), shared
        # with Predictors and batch engines; the spec-based key below
        # keeps decoders from colliding in it.
        self.exec_cache = (exec_cache if exec_cache is not None
                           else default_cache())
        # GSPMD: with a mesh, params are replicated onto it and KV slots
        # shard over `slot_axis` (see StaticKVCache). The mesh token —
        # axis names + shape + device ids — joins the cache key so two
        # replica decoders over different device subsets sharing one
        # ExecutableCache never collide (and neither collides with the
        # unsharded key).
        self.mesh = mesh
        self.slot_axis = slot_axis
        self._key = ("gpt-static", self.spec, self.max_top_k,
                     self.weight_dtype, self.kv_dtype)
        self._param_sharding = None
        if mesh is not None:
            from jax.sharding import NamedSharding, PartitionSpec
            from ..sharding import mesh_token
            self._key = self._key + (mesh_token(mesh),)
            self._param_sharding = NamedSharding(mesh, PartitionSpec())

    @property
    def model(self):
        """The live model object (weight hot-swap mutates it in place via
        ``set_state_dict``, then re-extracts params)."""
        return self._model

    def params(self):
        p = extract_gpt_params(self._model)
        if self.weight_dtype == "int8":
            p = quantize_gpt_params(p)
        if self._param_sharding is not None:
            sh = self._param_sharding
            p = jax.tree_util.tree_map(
                lambda a: jax.device_put(a, sh), p)
        return p

    def new_kv(self, num_slots: int, max_seq: int) -> StaticKVCache:
        if max_seq > self.spec.max_position_embeddings:
            raise ValueError(
                f"max_seq {max_seq} exceeds the model's "
                f"{self.spec.max_position_embeddings} positions")
        dtype = self._model.gpt.word_embeddings.weight._data.dtype
        return StaticKVCache(num_slots, self.spec.num_layers, max_seq,
                             self.spec.num_heads, self.spec.head_dim,
                             dtype=dtype, mesh=self.mesh,
                             slot_axis=self.slot_axis,
                             kv_dtype=("int8" if self.kv_dtype == "int8"
                                       else None))

    # -- compiled-program access --------------------------------------------
    def decode_fn(self, num_slots: int, max_seq: int):
        """The single decode step; the ExecutableCache key carries the
        shape pair so its miss counter mirrors XLA traces."""
        return self.exec_cache.get_or_compile(
            self._key + ("decode", num_slots, max_seq),
            lambda: get_decode_step(self.spec, self.max_top_k))

    def prefill_fn(self, batch: int, prompt_len: int):
        return self.exec_cache.get_or_compile(
            self._key + ("prefill", batch, prompt_len),
            lambda: get_prefill_fn(self.spec, self.max_top_k))

    def tail_prefill_fn(self, batch: int, tail_len: int):
        return self.exec_cache.get_or_compile(
            self._key + ("tail_prefill", batch, tail_len),
            lambda: get_tail_prefill_fn(self.spec, self.max_top_k))

    def insert_prefix_fn(self, prefix_len: int):
        return self.exec_cache.get_or_compile(
            self._key + ("insert_prefix", prefix_len),
            lambda: get_insert_prefix_fn())

    def prefix_sig(self, kv: StaticKVCache):
        """The shape signature a PrefixStore entry must match to be
        copyable into this decoder's cache (max_seq deliberately NOT part
        of it — a prefix exported from a larger-max_seq engine reuses
        fine in a smaller slot as long as it fits, which the scheduler's
        reuse cap guarantees)."""
        return (self.spec.num_layers, self.spec.num_heads,
                self.spec.head_dim, str(kv.dtype))

    # -- convenience wrappers ------------------------------------------------
    def prefill(self, kv: StaticKVCache, params, tokens, true_lens,
                slot_ids, finished, samp_vecs, key):
        """Run bucketed prefill for ``tokens`` [B, Lp] into ``slot_ids``;
        updates ``kv`` in place (functionally) and returns
        (next_tokens[B] device, finished[S] device)."""
        fn = self.prefill_fn(tokens.shape[0], tokens.shape[1])
        k, v, lengths, finished, nxt = fn(
            params, tokens, true_lens, kv.k, kv.v, kv.lengths, finished,
            slot_ids, *samp_vecs, key)
        kv.swap(k, v, lengths)
        return nxt, finished

    def tail_prefill(self, kv: StaticKVCache, params, tokens, tail_lens,
                     starts, slot_ids, finished, samp_vecs, key):
        """Prefill prompt *tails* at per-request offsets (after an
        :meth:`insert_prefix` landed the cached head); same return shape
        as :meth:`prefill`."""
        if kv.quantized:
            raise NotImplementedError(
                "tail_prefill over an int8 KV cache is unsupported; "
                "LLMEngineConfig gates prefix_cache off for "
                "kv_dtype='int8'")
        fn = self.tail_prefill_fn(tokens.shape[0], tokens.shape[1])
        k, v, lengths, finished, nxt = fn(
            params, tokens, tail_lens, starts, kv.k, kv.v, kv.lengths,
            finished, slot_ids, *samp_vecs, key)
        kv.swap(k, v, lengths)
        return nxt, finished

    def insert_prefix(self, kv: StaticKVCache, k_pre, v_pre, slot: int):
        """Bulk-copy a cached host prefix ``[L, n, H, D]`` into ``slot``'s
        rows [0, n) — one batched device update across all layers. The
        slot's length is set by the tail prefill that follows."""
        if kv.quantized:
            raise NotImplementedError(
                "insert_prefix into an int8 KV cache is unsupported; "
                "LLMEngineConfig gates prefix_cache off for "
                "kv_dtype='int8'")
        fn = self.insert_prefix_fn(int(k_pre.shape[1]))
        k, v = fn(kv.k, kv.v, jnp.asarray(k_pre, dtype=kv.dtype),
                  jnp.asarray(v_pre, dtype=kv.dtype), slot)
        kv.swap(k, v, kv.lengths)

    def decode_step(self, kv: StaticKVCache, params, finished, last_tokens,
                    samp_vecs, key):
        """Advance every slot one token; updates ``kv`` and returns
        (next_tokens[S] device, finished[S] device)."""
        fn = self.decode_fn(kv.num_slots, kv.max_seq)
        k, v, lengths, finished, nxt = fn(
            params, kv.k, kv.v, kv.lengths, finished, last_tokens,
            *samp_vecs, key)
        kv.swap(k, v, lengths)
        return nxt, finished


# -- trace-audit registration (tools/analyze/trace, PTA009/PTA010) -----------

_AUDIT_SPEC = GPTDecodeSpec(vocab_size=32, hidden_size=8, num_layers=1,
                            num_heads=2, max_position_embeddings=64)
_AUDIT_TOP_K = 4


def _audit_params(rng, spec: GPTDecodeSpec = _AUDIT_SPEC):
    """A synthetic tiny GPT parameter pytree matching extract_gpt_params'
    layout; values vary with the rng so PTA010's perturbed variants share
    shapes but not data. ``spec`` must be single-layer (the audit
    entrypoints all are); spec.py reuses this for its draft pytree."""
    e, v, p = spec.hidden_size, spec.vocab_size, spec.max_position_embeddings

    def arr(*shape):
        return jnp.asarray(rng.standard_normal(shape) * 0.02, jnp.float32)

    layer = {
        "qw": arr(e, e), "qb": arr(e), "kw": arr(e, e), "kb": arr(e),
        "vw": arr(e, e), "vb": arr(e), "ow": arr(e, e), "ob": arr(e),
        "w1": arr(e, 4 * e), "b1": arr(4 * e), "w2": arr(4 * e, e),
        "b2": arr(e), "n1w": arr(e), "n1b": arr(e), "n2w": arr(e),
        "n2b": arr(e),
    }
    return {"tok": arr(v, e), "pos": arr(p, e), "fnw": arr(e),
            "fnb": arr(e), "layers": (layer,)}


def _audit_decode_spec():
    from ...core import audit
    spec = _AUDIT_SPEC
    slots, max_seq, layers = 2, 16, spec.num_layers
    hd = spec.head_dim

    def make_args(variant):
        rng = np.random.default_rng(1234 + variant)
        kv_shape = (slots, layers, max_seq, spec.num_heads, hd)
        return (_audit_params(rng),
                jnp.zeros(kv_shape, jnp.float32),
                jnp.zeros(kv_shape, jnp.float32),
                jnp.asarray([3, 1], jnp.int32),           # lengths
                jnp.zeros((slots,), bool),                # finished
                jnp.asarray(rng.integers(0, spec.vocab_size, slots),
                            jnp.int32),                   # last_tokens
                jnp.ones((slots,), jnp.float32),          # temperature
                jnp.zeros((slots,), jnp.int32),           # top_k
                jnp.zeros((slots,), bool),                # do_sample
                jnp.full((slots,), -1, jnp.int32),        # eos
                jax.random.PRNGKey(variant))
    return audit.AuditSpec(fn=build_decode_step(spec, _AUDIT_TOP_K),
                           make_args=make_args)


def _audit_int8_decode_spec():
    """Same decode step, int8 weights + int8 KV: the serving-memory
    tentpole's executable. Proves the quantized hot path keeps PTA009's
    zero-host-transfer invariant (dequantization is fused in-graph)."""
    from ...core import audit
    spec = _AUDIT_SPEC
    slots, max_seq, layers = 2, 16, spec.num_layers
    hd = spec.head_dim

    def make_args(variant):
        rng = np.random.default_rng(5678 + variant)
        q_shape = (slots, layers, max_seq, spec.num_heads, hd)
        s_shape = (slots, layers, max_seq)

        def qbuf():
            return {"q": jnp.zeros(q_shape, jnp.int8),
                    "s": jnp.zeros(s_shape, jnp.float32)}

        return (quantize_gpt_params(_audit_params(rng)),
                qbuf(), qbuf(),
                jnp.asarray([3, 1], jnp.int32),           # lengths
                jnp.zeros((slots,), bool),                # finished
                jnp.asarray(rng.integers(0, spec.vocab_size, slots),
                            jnp.int32),                   # last_tokens
                jnp.ones((slots,), jnp.float32),          # temperature
                jnp.zeros((slots,), jnp.int32),           # top_k
                jnp.zeros((slots,), bool),                # do_sample
                jnp.full((slots,), -1, jnp.int32),        # eos
                jax.random.PRNGKey(variant))
    return audit.AuditSpec(fn=build_decode_step(spec, _AUDIT_TOP_K),
                           make_args=make_args)


def _audit_prefill_spec():
    from ...core import audit
    spec = _AUDIT_SPEC
    slots, max_seq, layers, b, lp = 2, 16, spec.num_layers, 2, 4
    hd = spec.head_dim

    def make_args(variant):
        rng = np.random.default_rng(4321 + variant)
        kv_shape = (slots, layers, max_seq, spec.num_heads, hd)
        return (_audit_params(rng),
                jnp.asarray(rng.integers(0, spec.vocab_size, (b, lp)),
                            jnp.int32),                   # tokens
                jnp.asarray([lp, lp - 1], jnp.int32),     # true_lens
                jnp.zeros(kv_shape, jnp.float32),
                jnp.zeros(kv_shape, jnp.float32),
                jnp.zeros((slots,), jnp.int32),           # lengths
                jnp.zeros((slots,), bool),                # finished
                jnp.asarray([0, 1], jnp.int32),           # slot_ids
                jnp.ones((b,), jnp.float32),
                jnp.zeros((b,), jnp.int32),
                jnp.zeros((b,), bool),
                jnp.full((b,), -1, jnp.int32),
                jax.random.PRNGKey(100 + variant))
    return audit.AuditSpec(fn=build_prefill_fn(spec, _AUDIT_TOP_K),
                           make_args=make_args)


def _register_audit_entrypoints():
    from ...core import audit
    audit.register_entrypoint("llm_decode_step", _audit_decode_spec,
                              tags=("serving", "decode"))
    audit.register_entrypoint("llm_int8_decode_step",
                              _audit_int8_decode_spec,
                              tags=("serving", "decode", "quantized",
                                    "bench"))
    audit.register_entrypoint("llm_prefill", _audit_prefill_spec,
                              tags=("serving", "prefill"))


_register_audit_entrypoints()
