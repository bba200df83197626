"""GPT: decoder-only causal language model — the flagship training workload
(BASELINE config 5: "Fleet hybrid-parallel GPT-3 1.3B pp+dp").

Built from the framework's own transformer layers (the reference builds GPT
the same way on python/paddle/nn/layer/transformer.py MultiHeadAttention /
TransformerEncoder; the 1.3B fleet example lives in the PaddleNLP repo, its
parallel form in fleet/meta_parallel/parallel_layers/mp_layers.py).

TPU notes:
- pre-norm (normalize_before=True) transformer blocks, bf16-friendly.
- the causal mask is a static additive mask folded into attention — XLA fuses
  it; no dynamic masking code path.
- `tp_partition_specs()` returns the tensor-parallel PartitionSpec plan for
  every parameter (Megatron-style column/row split over the "mp" mesh axis:
  reference mp_layers.py:96 ColumnParallelLinear / :169 RowParallelLinear /
  :29 VocabParallelEmbedding) — consumed by fleet's planner and the
  multi-chip dryrun.

Serving runs the block as ONE function of a parameter pytree and a *cache
view* (:func:`gpt_block`, :func:`gpt_hidden`; the twin of
``models/lfm2.py``). A view answers what depends on where the sequence's
past lives: ``view.attend(li, q, k, v, scale)`` is the attention of ``q``
for layer ``li`` over the keys and values so far, ``k``/``v`` included,
and the view keeps whatever it wrote. :class:`FullSequence` is the view
with no past; the views over slot rows and pages live beside their caches
(``serving/llm/kvcache.py``, ``serving/llm/paged/pool.py``). The ``Layer``
graph above keeps its own forward (autocast, recompute and the flash
kernels live there).
"""
from __future__ import annotations

import re
from dataclasses import dataclass
from typing import Any, Dict, Optional

import numpy as np

import jax
import jax.numpy as jnp

from ..core.tensor import Tensor
from .. import ops
from ..nn.layer_base import Layer
from ..nn import (Embedding, LayerNorm, Linear, Dropout, TransformerEncoder,
                  TransformerEncoderLayer)
from ..nn import functional as F


@dataclass
class GPTConfig:
    vocab_size: int = 50304
    hidden_size: int = 768
    num_layers: int = 12
    num_heads: int = 12
    intermediate_size: Optional[int] = None
    max_position_embeddings: int = 1024
    hidden_dropout_prob: float = 0.1
    attention_dropout_prob: float = 0.1
    # "auto": Pallas flash attention above the measured S>=4096 crossover
    # (nn/transformer.py FLASH_CROSSOVER), dense below; "flash"/"dense"
    # force either. Training with attention_dropout_prob > 0 stays dense
    # (the fused kernel never materialises the prob matrix to drop).
    attn_impl: str = "auto"
    # explicit (block_q, block_k) for the flash kernel; None = ask the
    # paddle_tpu.tuner winner cache for this (shape, dtype, platform)
    attn_blocks: Optional[tuple] = None

    @property
    def ffn_size(self):
        return self.intermediate_size or 4 * self.hidden_size

    def draft(self, scale: int = 4, *, hidden_size: Optional[int] = None,
              num_layers: Optional[int] = None,
              num_heads: Optional[int] = None) -> "GPTConfig":
        """A small draft-model config for speculative decoding against
        this target: SAME vocab and positions (the verify step compares
        token ids and shares the position range), everything else shrunk
        by ``scale`` unless given explicitly. Heads are reduced until they
        divide the draft hidden size."""
        h = hidden_size if hidden_size is not None \
            else max(1, self.hidden_size // scale)
        nl = num_layers if num_layers is not None \
            else max(1, self.num_layers // scale)
        nh = num_heads if num_heads is not None \
            else max(1, self.num_heads // scale)
        while h % nh:
            nh -= 1
        return GPTConfig(
            vocab_size=self.vocab_size, hidden_size=h, num_layers=nl,
            num_heads=nh,
            max_position_embeddings=self.max_position_embeddings,
            hidden_dropout_prob=0.0, attention_dropout_prob=0.0,
            attn_impl=self.attn_impl)


class GPTModel(Layer):
    """Token + position embedding → pre-norm decoder stack → final norm."""

    def __init__(self, config: GPTConfig):
        super().__init__()
        self.config = config
        c = config
        from ..nn.layer_base import ParamAttr
        from ..nn import initializer as I
        emb_attr = ParamAttr(initializer=I.Normal(0.0, 0.02))
        self.word_embeddings = Embedding(c.vocab_size, c.hidden_size,
                                         weight_attr=emb_attr)
        self.position_embeddings = Embedding(c.max_position_embeddings,
                                             c.hidden_size,
                                             weight_attr=ParamAttr(
                                                 initializer=I.Normal(0.0, 0.02)))
        self.embedding_dropout = Dropout(c.hidden_dropout_prob)
        layer = TransformerEncoderLayer(
            c.hidden_size, c.num_heads, c.ffn_size,
            dropout=c.hidden_dropout_prob, activation="gelu",
            attn_dropout=c.attention_dropout_prob, normalize_before=True,
            attn_impl=getattr(c, "attn_impl", "auto"),
            attn_blocks=getattr(c, "attn_blocks", None))
        self.decoder = TransformerEncoder(layer, c.num_layers,
                                          norm=LayerNorm(c.hidden_size))

    def gen_cache(self, input_ids):
        """Per-layer incremental KV caches for autoregressive decoding
        (reference: TransformerEncoder.gen_cache). The layer gen_cache
        reads only batch size and dtype, so seed it from a single-token
        embedding slice instead of embedding the whole prompt."""
        h0 = self.word_embeddings(input_ids[:, :1])
        return self.decoder.gen_cache(h0)

    def forward(self, input_ids, position_ids=None, cache=None):
        seq_len = input_ids.shape[1]
        if position_ids is None:
            # with a KV cache the new tokens sit AFTER the cached prefix
            offset = int(cache[0].k.shape[2]) if cache is not None else 0
            position_ids = ops.arange(offset, offset + seq_len,
                                      dtype="int32")
            position_ids = ops.expand(ops.unsqueeze(position_ids, 0),
                                      [input_ids.shape[0], seq_len])
        with jax.named_scope("gpt/embed"):
            h = (self.word_embeddings(input_ids)
                 + self.position_embeddings(position_ids))
            h = self.embedding_dropout(h)
        # causal mask as the CAUSAL_MASK sentinel: the flash path applies
        # causality inside the kernel, the dense path materialises the
        # additive triu lazily with the cached-prefix offset
        # (nn/transformer.py MultiHeadAttention)
        from ..nn.transformer import CAUSAL_MASK
        if cache is None:
            return self.decoder(h, src_mask=CAUSAL_MASK)
        return self.decoder(h, src_mask=CAUSAL_MASK, cache=cache)


class GPTForCausalLM(Layer):
    """LM head tied to the word embedding (reference GPT convention)."""

    def __init__(self, config: GPTConfig):
        super().__init__()
        self.gpt = GPTModel(config)

    def forward(self, input_ids, position_ids=None, cache=None):
        out = self.gpt(input_ids, position_ids, cache=cache)
        h, new_cache = out if cache is not None else (out, None)
        # logits = h @ E^T with the tied embedding matrix
        with jax.named_scope("gpt/loss_head"):
            logits = ops.matmul(h, self.gpt.word_embeddings.weight,
                                transpose_y=True)
        return logits if cache is None else (logits, new_cache)


class GPTPretrainingCriterion(Layer):
    """Shifted next-token cross entropy: position t is scored against
    label t+1 and the last position is ignored.

    The shift is applied to the labels, never to the logits: slicing
    ``logits[:, :-1]`` first turns the ``[B, S-1, V] -> [B*(S-1), V]``
    merge into a relayout (S-1 is no multiple of the sublane tile), and at
    B=4, S=4096, V=50304 the TPU compiler spent ~427 s on it against ~4 s
    for this form (PERF.md, PR 21). Same mean over the same B*(S-1)
    positions.

    Kept for the backward pass (``F.cross_entropy``, PR 48): the logits as
    the head's product gave them (bf16 ``[16384, 50304]`` under the train
    cell's autocast) and one float32 ``lse`` a row, not a float32 table of
    log-probabilities, 3.07 GiB at that size and a pass of its own."""

    def forward(self, logits, labels):
        v = logits.shape[-1]
        with jax.named_scope("gpt/loss_head"):
            tgt = ops.concat([labels[:, 1:],
                              ops.full_like(labels[:, :1], -100)], axis=1)
            return F.cross_entropy(ops.reshape(logits, [-1, v]),
                                   ops.reshape(tgt, [-1]), ignore_index=-100)


# -- the block as a function of a cache view (what serving runs) --------------

@dataclass(frozen=True)
class GPTDecodeSpec:
    """The static facts the compiled decode program is specialized on.

    Frozen + hashable: it keys the process-wide jit-function caches, so
    two engines (or ``generate`` calls) over same-shaped models share one
    traced program family.
    """
    vocab_size: int
    hidden_size: int
    num_layers: int
    num_heads: int
    max_position_embeddings: int
    ln_epsilon: float = 1e-5

    @property
    def head_dim(self) -> int:
        return self.hidden_size // self.num_heads

    @classmethod
    def from_model(cls, model) -> "GPTDecodeSpec":
        c = model.gpt.config
        return cls(vocab_size=c.vocab_size, hidden_size=c.hidden_size,
                   num_layers=c.num_layers, num_heads=c.num_heads,
                   max_position_embeddings=c.max_position_embeddings)


def extract_gpt_params(model) -> Dict[str, Any]:
    """The GPT parameter pytree as raw jnp arrays (references, not copies —
    re-extract after an optimizer step to pick up new values)."""
    gpt = model.gpt
    layers = []
    for lyr in gpt.decoder.layers:
        a = lyr.self_attn
        layers.append({
            "qw": a.q_proj.weight._data, "qb": a.q_proj.bias._data,
            "kw": a.k_proj.weight._data, "kb": a.k_proj.bias._data,
            "vw": a.v_proj.weight._data, "vb": a.v_proj.bias._data,
            "ow": a.out_proj.weight._data, "ob": a.out_proj.bias._data,
            "w1": lyr.linear1.weight._data, "b1": lyr.linear1.bias._data,
            "w2": lyr.linear2.weight._data, "b2": lyr.linear2.bias._data,
            "n1w": lyr.norm1.weight._data, "n1b": lyr.norm1.bias._data,
            "n2w": lyr.norm2.weight._data, "n2b": lyr.norm2.bias._data,
        })
    return {
        "tok": gpt.word_embeddings.weight._data,
        "pos": gpt.position_embeddings.weight._data,
        "fnw": gpt.decoder.norm.weight._data,
        "fnb": gpt.decoder.norm.bias._data,
        "layers": tuple(layers),
    }


def _mm(x, w):
    """``x @ w`` for a dense f32 weight or an int8 ``{"q", "s"}`` leaf.
    The int8 path multiplies against the raw codes and applies the
    per-out-channel scale to the product — exactly equal to dequantizing
    first (scales distribute over the contraction), but the weight reads
    stay int8, which is the memory-bandwidth win."""
    if isinstance(w, dict):
        return (x @ w["q"].astype(x.dtype)) * w["s"].astype(x.dtype)
    return x @ w


def _layer_norm(x, w, b, eps):
    # mirrors F.layer_norm: mean/var over the last axis, rsqrt, scale+shift
    mean = jnp.mean(x, axis=-1, keepdims=True)
    var = jnp.var(x, axis=-1, keepdims=True)
    return (x - mean) * jax.lax.rsqrt(var + eps) * w + b


def masked_attention(q, k, v, mask, scale):
    """Dense attention of ``q`` ``[B, T, H, D]`` (or ``[B, H, D]``: one
    query a row) over ``k``/``v`` ``[B, M, H, D]`` under an additive
    ``mask`` that broadcasts to ``[B, H, T, M]`` (-1e9 where a row is not
    to be seen: its softmax weight is exactly 0.0 in f32). Returns the
    shape of ``q``. Mirrors the dense branch of
    ``nn.transformer.MultiHeadAttention`` operation for operation: every
    view that reads whole rows attends through here, which is what keeps
    them bitwise equal to one another."""
    one = q.ndim == 3
    if one:
        q = q[:, None]
    qh = jnp.transpose(q * scale, (0, 2, 1, 3))            # [B, H, T, D]
    kt = jnp.transpose(k, (0, 2, 1, 3))                    # [B, H, M, D]
    vt = jnp.transpose(v, (0, 2, 1, 3))
    prod = jnp.matmul(qh, jnp.swapaxes(kt, -1, -2))        # [B, H, T, M]
    weights = jax.nn.softmax(prod + mask, axis=-1)
    out = jnp.transpose(jnp.matmul(weights, vt), (0, 2, 1, 3))
    return out[:, 0] if one else out


class FullSequence:
    """The view with no past: whole right-padded prompts from position 0
    under the additive causal triu the dense path materialises. Records
    each layer's ``(k, v)`` ``[B, T, H, D]`` in ``kv``: what a cache has to
    keep."""

    def __init__(self):
        self.kv, self.mask = [], None

    def attend(self, li, q, k, v, scale):
        self.kv.append((k, v))
        if self.mask is None:
            t = q.shape[1]
            self.mask = jnp.triu(jnp.full((t, t), -1e9, q.dtype),
                                 1)[None, None]
        return masked_attention(q, k, v, self.mask, scale)


def stack_kv(kv, axis: int):
    """A view's per-layer ``[(k, v), ...]`` records as ``(K, V)``, the
    layers stacked on a new ``axis``."""
    ks, vs = zip(*kv)
    return jnp.stack(ks, axis=axis), jnp.stack(vs, axis=axis)


def gpt_block(spec: GPTDecodeSpec, lp, h, view, li: int):
    """Pre-norm layer ``li`` on ``h`` ``[B, T, E]``, or ``[S, E]`` for one
    token per slot: the decode tick has no ``T`` axis, because the chip's
    compiler lays ``[S, 1, E]`` out in other tiles than ``[S, E]`` and
    fuses the tick differently. Must mirror the framework's eval ops
    exactly: ``F.layer_norm``, the dense attention branch,
    ``F.gelu(approximate=False)``. The projections go through :func:`_mm`,
    so ``lp`` may hold int8 weight leaves."""

    def heads(z):                              # [B, T, H, D] or [S, H, D]
        return z.reshape(h.shape[:-1] + (spec.num_heads, spec.head_dim))

    with jax.named_scope("gpt/norm"):
        x = _layer_norm(h, lp["n1w"], lp["n1b"], spec.ln_epsilon)
    with jax.named_scope("gpt/qkv"):
        q = heads(_mm(x, lp["qw"]) + lp["qb"])
        k = heads(_mm(x, lp["kw"]) + lp["kb"])
        v = heads(_mm(x, lp["vw"]) + lp["vb"])
    with jax.named_scope("gpt/attn"):
        out = view.attend(li, q, k, v, 1.0 / np.sqrt(spec.head_dim))
    with jax.named_scope("gpt/proj"):
        h = h + (_mm(out.reshape(h.shape), lp["ow"]) + lp["ob"])
    with jax.named_scope("gpt/norm"):
        x = _layer_norm(h, lp["n2w"], lp["n2b"], spec.ln_epsilon)
    with jax.named_scope("gpt/mlp"):
        ffn = jax.nn.gelu(_mm(x, lp["w1"]) + lp["b1"], approximate=False)
        return h + (_mm(ffn, lp["w2"]) + lp["b2"])


def gpt_hidden(spec: GPTDecodeSpec, params, tokens, positions, view):
    """Final-norm hidden states ``[B, T, E]`` of ``tokens`` ``[B, T]`` at
    ``positions`` (``[B, T]`` or ``[1, T]``; clipped into the learned
    position table), the past read and written through ``view``; ``[S, E]``
    of ``[S]`` tokens at ``[S]`` positions for the decode tick."""
    with jax.named_scope("gpt/embed"):
        posc = jnp.clip(positions, 0, spec.max_position_embeddings - 1)
        h = params["tok"][tokens] + params["pos"][posc]
    for li, lp in enumerate(params["layers"]):
        h = gpt_block(spec, lp, h, view, li)
    with jax.named_scope("gpt/norm"):
        return _layer_norm(h, params["fnw"], params["fnb"], spec.ln_epsilon)


# -- tensor-parallel plan -----------------------------------------------------

_TP_RULES = (
    # Megatron split: qkv + ffn-in are column-parallel (shard output dim),
    # attn-out + ffn-out are row-parallel (shard input dim), embeddings are
    # vocab/position-sharded on the table dim.
    (r"\.(q_proj|k_proj|v_proj|linear1)\.weight$", (None, "mp")),
    (r"\.(q_proj|k_proj|v_proj|linear1)\.bias$", ("mp",)),
    (r"\.(out_proj|linear2)\.weight$", ("mp", None)),
    (r"word_embeddings\.weight$", ("mp", None)),
)


def tp_partition_specs(model: Layer) -> Dict[str, tuple]:
    """Per-parameter PartitionSpec axes (as tuples; () = replicated) for
    tensor parallelism over the "mp" mesh axis."""
    specs = {}
    for name, p in model.named_parameters():
        spec = ()
        for pat, s in _TP_RULES:
            if re.search(pat, name):
                spec = s
                break
        specs[name] = spec
    return specs


# -- pipeline plan ------------------------------------------------------------

def gpt_pipeline_fns(model: "GPTForCausalLM", num_stages: int):
    """Decompose a GPTForCausalLM into (embed_fn, block_fn, head_fn) pure
    functions + their param trees for the compiled heterogeneous pipeline
    engine (fleet.pipeline_engine.gpipe_blocks): embedding runs as stage
    0's preamble, each stage applies num_layers/num_stages decoder blocks
    (params stacked [S, k, ...] and sharded over "pp"), and the head (final
    norm + tied-embedding logits + shifted CE loss) runs on the last stage.

    The reference schedules these heterogeneous stage signatures with a
    runtime handshake (fleet/meta_parallel/pipeline_parallel.py:272
    _send_meta); here they are fixed at build time. Dropout must be 0 (the
    engine threads no RNG through the schedule).
    """
    from ..jit.functionalize import build_pure

    cfg = model.gpt.config
    if cfg.hidden_dropout_prob or cfg.attention_dropout_prob:
        raise ValueError("gpt_pipeline_fns requires dropout 0")
    L, S = cfg.num_layers, int(num_stages)
    if L % S != 0:
        raise ValueError(f"{L} layers not divisible by {S} stages")
    k = L // S

    emb = model.gpt.word_embeddings.weight._data
    pos = model.gpt.position_embeddings.weight._data
    dec_layers = list(model.gpt.decoder.layers)
    final_norm = model.gpt.decoder.norm

    # one pure fn traced from a representative block; per-stage params are
    # the per-layer raw lists, stacked [S, k, ...]
    layer0_params = [p for _, p in dec_layers[0].named_parameters()]
    block_pure, _ = build_pure(dec_layers[0].forward, layer0_params)
    per_layer_raws = [[p._data for _, p in lyr.named_parameters()]
                     for lyr in dec_layers]
    stacked = jax.tree_util.tree_map(
        lambda *xs: jnp.stack(xs),
        *[jax.tree_util.tree_map(
            lambda *ys: jnp.stack(ys), *per_layer_raws[s * k:(s + 1) * k])
          for s in range(S)])

    norm_params = [p for _, p in final_norm.named_parameters()]
    norm_pure, _ = build_pure(final_norm.forward, norm_params)
    norm_raws = [p._data for p in norm_params]

    key = jax.random.PRNGKey(0)  # unused: dropout is 0

    def _mask(h):
        L_seq = h.shape[1]
        m = jnp.triu(jnp.full((L_seq, L_seq), -1e4, h.dtype), 1)
        return m[None, None]

    def embed_fn(p, ids):
        seq = ids.shape[1]
        return p["tok"][ids] + p["pos"][None, :seq, :]

    def block_fn(stage_params, h):
        for i in range(k):
            lp = jax.tree_util.tree_map(lambda a: a[i], stage_params)
            h = block_pure(lp, (h, _mask(h)), key, None)[0]
        return h

    def head_fn(p, h, xy):
        ids = xy if not isinstance(xy, tuple) else xy[0]
        h = norm_pure(p["norm"], (h,), key, None)[0]
        logits = h @ p["tok"].T
        lo = jax.nn.log_softmax(logits[:, :-1, :].astype(jnp.float32))
        tgt = ids[:, 1:]
        nll = -jnp.take_along_axis(lo, tgt[..., None].astype(jnp.int32),
                                   axis=-1)
        return jnp.mean(nll)

    embed_params = {"tok": emb, "pos": pos}
    head_params = {"tok": emb, "norm": norm_raws}
    block_tensors = [[p for _, p in lyr.named_parameters()]
                     for lyr in dec_layers]
    return {
        "embed_fn": embed_fn, "block_fn": block_fn, "head_fn": head_fn,
        "embed_params": embed_params, "stacked_block_params": stacked,
        "head_params": head_params,
        "param_tensors": {
            "embed": [model.gpt.word_embeddings.weight,
                      model.gpt.position_embeddings.weight],
            "blocks": block_tensors, "norm": norm_params,
        },
        "stages": S, "layers_per_stage": k,
    }


#: how many tokens each decode runs between host-side "all rows hit eos?"
#: probes — the probe is a device->host sync, so amortizing it keeps decode
#: device-bound; frozen rows keep emitting eos, so the only cost of a late
#: stop is trimmed-off work, never wrong tokens.
_EOS_CHECK_EVERY = 8


def _next_pow2(n: int) -> int:
    p = 1
    while p < n:
        p *= 2
    return p


def _trim_generated(gen: "np.ndarray", eos_token_id) -> int:
    """Columns of the generated block to keep: the step at which every row
    had emitted eos, plus one — exactly where the per-token-checking loop
    used to break. Rows that never emit eos keep the full budget."""
    import numpy as np
    if eos_token_id is None or gen.shape[1] == 0:
        return gen.shape[1]
    hits = gen == eos_token_id
    if not hits.any(axis=1).all():
        return gen.shape[1]
    return int(hits.argmax(axis=1).max()) + 1


def _gpt_generate_static(model, ids, max_length, decode_strategy, top_k,
                         temperature, eos_token_id):
    """Static-slot decode: prefill once, then ONE compiled decode step per
    token over fixed [B, max_seq] shapes (paddle_tpu.serving.llm.decode) —
    no per-token retrace, no per-token host sync. Token-for-token
    equivalent to the concat-cache path (same math, same sampling recipe,
    same per-step generator keys)."""
    import numpy as np
    from ..core import generator as _gen
    from ..core.tensor import Tensor
    from ..serving.llm.decode import (GPTStaticDecoder, SamplingParams,
                                      pack_sampling)

    b, lin = int(ids.shape[0]), int(ids.shape[1])
    max_pos = model.gpt.config.max_position_embeddings
    # pow2-rounded shapes so repeat calls with nearby lengths reuse the
    # compiled step (the shape pair keys the executable)
    max_seq = min(_next_pow2(lin + int(max_length)), max_pos)
    lp = min(_next_pow2(lin), max_seq)
    do_sample = decode_strategy == "sampling" and top_k != 1
    dec = GPTStaticDecoder(
        model, max_top_k=int(top_k) if do_sample and top_k else 0)
    kv = dec.new_kv(b, max_seq)
    params = dec.params()
    samp = SamplingParams(
        do_sample=do_sample, temperature=float(temperature),
        top_k=int(top_k) if do_sample else 0, eos_token_id=eos_token_id,
        max_new_tokens=int(max_length))
    svecs = pack_sampling([samp] * b)
    fixed_key = jax.random.PRNGKey(0)   # greedy consumes no generator keys

    padded = np.zeros((b, lp), np.int32)
    padded[:, :lin] = np.asarray(jax.device_get(ids))  # noqa: PTA002 -- one prompt download to build the padded prefill batch (admission-time, not per-token)
    finished = jnp.zeros((b,), jnp.bool_)
    key = _gen.next_key() if do_sample else fixed_key
    nxt, finished = dec.prefill(
        kv, params, jnp.asarray(padded),
        jnp.full((b,), lin, jnp.int32), jnp.arange(b, dtype=jnp.int32),
        finished, svecs, key)
    gen = jnp.zeros((b, int(max_length)), jnp.int32).at[:, 0].set(nxt)
    last = nxt
    steps = 1
    for t in range(1, int(max_length)):
        key = _gen.next_key() if do_sample else fixed_key
        nxt, finished = dec.decode_step(kv, params, finished, last, svecs,
                                        key)
        last = nxt
        gen = gen.at[:, t].set(nxt)
        steps = t + 1
        if eos_token_id is not None and t % _EOS_CHECK_EVERY == 0:
            # the amortized finish probe: one [B]-bool reduce every
            # _EOS_CHECK_EVERY tokens instead of a sync per token
            if bool(np.asarray(jax.device_get(jnp.all(finished)))):  # noqa: PTA002 -- deliberate amortized early-exit probe; frozen rows emit eos so late detection only trims work
                break
    gen_h = np.asarray(jax.device_get(gen[:, :steps]))  # noqa: PTA002 -- single end-of-generate download of the token matrix (the return value)
    keep = _trim_generated(gen_h, eos_token_id)
    out = np.concatenate(
        [np.asarray(jax.device_get(ids)), gen_h[:, :keep]], axis=1)  # noqa: PTA002 -- stitching the host return value
    return Tensor(jnp.asarray(out, jnp.int32))


def _gpt_generate(model, input_ids, max_length=32, decode_strategy="greedy",
                  top_k=1, temperature=1.0, eos_token_id=None,
                  use_cache=True):
    """Autoregressive decoding for GPTForCausalLM (reference capability:
    PaddleNLP GenerationMixin.generate — greedy / top-k sampling; the
    beam form lives in nn.BeamSearchDecoder/dynamic_decode).

    ``use_cache=True`` (default) decodes through the static-slot KV cache:
    prefill writes the prompt K/V into preallocated ``[B, max_seq]``
    buffers and every token then reuses ONE compiled decode step — no
    shape growth, no per-token retrace. ``use_cache="concat"`` keeps the
    legacy concat-grown MHA cache (incremental but retraces per length);
    ``use_cache=False`` recomputes the full prefix each step (O(T^2), the
    testing reference). All three are token-identical. Returns ids
    [B, input_len + n_generated] (n_generated < max_length only when
    every row emitted ``eos_token_id``)."""
    import numpy as np
    from ..core import generator as _gen
    from ..core.tensor import Tensor

    if decode_strategy not in ("greedy", "sampling"):
        raise ValueError(
            f"decode_strategy {decode_strategy!r} not in "
            f"('greedy', 'sampling'); beam search = "
            f"nn.BeamSearchDecoder + dynamic_decode")
    ids = input_ids._data if isinstance(input_ids, Tensor) else \
        jnp.asarray(np.asarray(input_ids), jnp.int32)
    c = model.gpt.config
    if use_cache is True:
        # static-slot fast path needs the deterministic eval math (the
        # compiled step has no dropout) and room in the position table;
        # otherwise fall through to the concat cache below
        dropout_off = (not getattr(model, "training", False)) or (
            c.hidden_dropout_prob == 0.0 and c.attention_dropout_prob == 0.0)
        if dropout_off and ids.shape[1] + int(max_length) <= \
                c.max_position_embeddings and int(max_length) >= 1:
            return _gpt_generate_static(
                model, ids, max_length, decode_strategy, top_k,
                temperature, eos_token_id)
        use_cache = "concat"
    finished = jnp.zeros((ids.shape[0],), jnp.bool_)
    cache = None
    if use_cache:
        cache = model.gpt.gen_cache(Tensor(ids))
    step_input = ids
    n_steps = int(max_length)
    for step in range(n_steps):
        if use_cache:
            logits, cache = model(Tensor(step_input), cache=cache)
        else:
            logits = model(Tensor(ids))
        lraw = logits._data[:, -1, :].astype(jnp.float32)
        if decode_strategy == "greedy" or top_k == 1:
            nxt = jnp.argmax(lraw, axis=-1).astype(jnp.int32)
        else:   # sampling
            lraw = lraw / max(float(temperature), 1e-6)
            if top_k and top_k > 0:
                kth = jax.lax.top_k(lraw, int(top_k))[0][:, -1:]
                lraw = jnp.where(lraw < kth, -1e9, lraw)
            nxt = jax.random.categorical(_gen.next_key(), lraw,
                                         axis=-1).astype(jnp.int32)
        if eos_token_id is not None:
            # rows that already emitted eos are frozen to eos (reference
            # GenerationMixin per-row finished semantics)
            nxt = jnp.where(finished, jnp.asarray(eos_token_id,
                                                  nxt.dtype), nxt)
            finished = finished | (nxt == eos_token_id)
        ids = jnp.concatenate([ids, nxt[:, None]], axis=1)
        step_input = nxt[:, None]          # cache path: one new token
        if eos_token_id is not None and step % _EOS_CHECK_EVERY == \
                _EOS_CHECK_EVERY - 1:
            # amortized early-exit probe (was a per-token host sync);
            # overshoot columns are frozen eos and trimmed below
            if bool(jnp.all(finished)):  # noqa: PTA002 -- deliberate amortized device->host probe, every _EOS_CHECK_EVERY tokens
                break
    if eos_token_id is not None and n_steps > 0:
        lin = int(ids.shape[1]) - (step + 1)   # step = last loop index run
        full = np.asarray(jax.device_get(ids))  # noqa: PTA002 -- end-of-generate download to trim frozen-eos overshoot (the return value is host-bound anyway)
        keep = _trim_generated(full[:, lin:], eos_token_id)
        return Tensor(jnp.asarray(full[:, :lin + keep], jnp.int32))
    return Tensor(ids)


GPTForCausalLM.generate = _gpt_generate
