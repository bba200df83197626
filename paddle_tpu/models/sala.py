"""MiniCPM-SALA: block-sparse softmax attention in a few layers, decayed
linear attention in the rest, SwiGLU feed-forwards, the MiniCPM family's
three scalings and an untied head (openbmb MiniCPM-SALA, ``model_type``
``minicpm_sala``).

The block is written ONCE, as a function of a parameter pytree and a *cache
view* (:func:`sala_block`), as ``models.lfm2`` does. A view answers the two
questions whose answer depends on where the sequence's past lives:

- ``view.sparse(ai, q, k, v, scale)``: the attention of ``q`` for sparse
  layer ``ai`` over the rows its selection admits, ``k``/``v`` included;
- ``view.linear(li, q, k, v, decay)``: the decayed linear attention of
  linear layer ``li``, given whatever state came before.

:class:`FullSequence` is the view with no past (whole sequences from
position 0; the ``Layer``'s forward). The serving views
(``serving/llm/paged/sala.py``) keep KV pages with a compressed-key index
beside them and a linear state a slot. The equations, per layer on the
residual stream ``h`` (``rms`` in float32 with a learned weight, no biases;
``a = scale_depth / sqrt(residual_depth)``):

    h0 = scale_emb * E[token]
    x = rms(h; n1)
    sparse:  q, k, v = x Wq, x Wk, x Wv   (Hq heads on Hkv KV heads, NoPE)
             q, k = rms(q; qn), rms(k; kn) per head
             context n <= dense_len: causal softmax over all n rows
             else per KV head (its G = Hq / Hkv query heads share it):
               c_j = mean(k[stride * j : stride * j + kernel])
               p_g = softmax_j(q_g . c_j / sqrt(D)), j visible (whole in n)
               s_j = sum_g p_g[j];  B_b = max(s_j, j overlapping block b)
               selected = block 0, the blocks of the last `window` rows,
                          then the best B_b up to `topk` blocks in all
               causal softmax over the rows of the selected blocks
             op = (sigmoid(x Wgate) * concat(heads)) Wo
    linear:  q, k, v = x Wq, x Wk, x Wv   (H heads)
             q, k = rope(rms(q; qn)), rope(rms(k; kn))
             S_t = lam_h S_{t-1} + k_t v_t^T;  y_t = S_t^T q_t / sqrt(D)
             op = (sigmoid(x Wz) * rms(y; on) per head) Wo
    h = h + a * op;  f = rms(h; n2)
    h = h + a * (silu(f W1) * (f W3)) W2
    logits = (rms(h; fnw) / (hidden / dim_model_base)) W_head

``state_dict`` names follow the published checkpoint's layout
(``model.layers.<i>.self_attn.q_proj.weight``, ``...mlp.w1.weight``,
``lm_head.weight``); matrices are ``[in, out]`` as everywhere here.
"""
from __future__ import annotations

import functools
import math
from dataclasses import dataclass
from typing import Tuple

import jax
import jax.numpy as jnp

from ..nn import Embedding, LayerList, RMSNorm
from ..nn import initializer as I
from ..nn.layer_base import Layer, ParamAttr
from ..nn.moe import rms_norm
from ..ops.dispatch import apply
from ..ops.linear_attention import decayed_linear_attention
from .lfm2 import SwiGLU, _leaf, _proj, rope, swiglu

SPARSE, LINEAR = "minicpm4", "lightning-attn"


@dataclass(frozen=True)
class SALAConfig:
    """Every key of the published ``config.json`` (hashable: it keys the
    compiled programs), then the sizes it leaves out, with the defaults of
    the family's convention (``sparse_*``: MiniCPM4's ``sparse_config``;
    ``residual_depth``: the PUBLISHED depth, which scales the residual
    branches in a model cut in depth too)."""
    vocab_size: int = 73448
    hidden_size: int = 4096
    intermediate_size: int = 16384
    num_hidden_layers: int = 32
    mixer_types: Tuple[str, ...] = ()
    num_attention_heads: int = 32
    num_key_value_heads: int = 2
    head_dim: int = 128
    lightning_nh: int = 32
    lightning_nkv: int = 32
    lightning_head_dim: int = 128
    lightning_scale: str = "1/sqrt(d)"
    lightning_use_rope: bool = True
    attn_use_rope: bool = False
    attention_bias: bool = False
    qk_norm: bool = True
    use_output_gate: bool = True
    use_output_norm: bool = True
    attn_use_output_gate: bool = True
    hidden_act: str = "silu"
    rms_norm_eps: float = 1e-6
    rope_theta: float = 10000.0
    scale_emb: float = 12.0
    scale_depth: float = 1.4
    dim_model_base: int = 256
    mup_denominator: int = 32          # initialisation only: not read
    rand_init: bool = False            # initialisation only: not read
    max_position_embeddings: int = 524288
    tie_word_embeddings: bool = False
    model_type: str = "minicpm_sala"
    # -- not in the published config ----------------------------------------
    sparse_kernel_size: int = 32
    sparse_kernel_stride: int = 16
    sparse_block_size: int = 64
    sparse_topk: int = 64
    sparse_init_blocks: int = 1
    sparse_window_size: int = 2048
    sparse_dense_len: int = 8192
    residual_depth: int = 32

    def __post_init__(self):
        object.__setattr__(self, "mixer_types", tuple(self.mixer_types))
        if len(self.mixer_types) != self.num_hidden_layers:
            raise ValueError(
                f"{len(self.mixer_types)} mixer_types for "
                f"{self.num_hidden_layers} layers")
        bad = set(self.mixer_types) - {SPARSE, LINEAR}
        if bad:
            raise ValueError(f"unknown mixer types {sorted(bad)}")
        for name, wanted in (
                ("attention_bias", False), ("attn_use_rope", False),
                ("lightning_use_rope", True), ("qk_norm", True),
                ("use_output_gate", True), ("use_output_norm", True),
                ("attn_use_output_gate", True), ("hidden_act", "silu"),
                ("lightning_scale", "1/sqrt(d)"),
                ("tie_word_embeddings", False)):
            if getattr(self, name) != wanted:
                raise NotImplementedError(
                    f"{name}={getattr(self, name)!r} is not implemented "
                    f"(this family runs {name}={wanted!r})")
        if self.lightning_nh != self.lightning_nkv:
            raise NotImplementedError(
                "lightning_nkv != lightning_nh (grouped linear-attention "
                "heads) is not implemented")
        if self.num_attention_heads % self.num_key_value_heads:
            raise ValueError("query heads must be a multiple of KV heads")
        k, s, b = (self.sparse_kernel_size, self.sparse_kernel_stride,
                   self.sparse_block_size)
        if k % s or b % s or k > b:
            raise ValueError(
                f"the compression kernel ({k}) and the selection block "
                f"({b}) must be multiples of the stride ({s}), the kernel "
                f"no longer than the block")
        if self.sparse_window_size < k or self.sparse_dense_len < \
                self.sparse_topk * b:
            raise ValueError(
                "the forced window must hold a kernel, and dense_len at "
                "least topk blocks (a sparse context always has topk "
                "blocks to choose)")

    @property
    def sparse_layers(self) -> Tuple[int, ...]:
        return tuple(i for i, t in enumerate(self.mixer_types)
                     if t == SPARSE)

    @property
    def linear_layers(self) -> Tuple[int, ...]:
        return tuple(i for i, t in enumerate(self.mixer_types)
                     if t == LINEAR)

    @property
    def groups(self) -> int:
        return self.num_attention_heads // self.num_key_value_heads

    @property
    def residual_scale(self) -> float:
        return self.scale_depth / math.sqrt(self.residual_depth)

    @property
    def logit_divisor(self) -> float:
        return self.hidden_size / self.dim_model_base

    @property
    def kernels_per_block(self) -> int:
        return self.sparse_block_size // self.sparse_kernel_stride

    def decay_rates(self):
        """``s_h`` of ``lam_h = exp(-s_h)``: ``2^(-8 (h + 1) / H)``."""
        h = self.lightning_nh
        return jnp.asarray([2.0 ** (-8.0 * (i + 1) / h) for i in range(h)],
                           jnp.float32)


# -- the selection (shared by every view) --------------------------------------

def compressed_keys(cfg: SALAConfig, k, num_blocks: int):
    """``c_j = mean(k[stride * j : stride * j + kernel])`` of whole
    sequences: ``k`` ``[B, T, Hkv, D]`` -> ``[B, num_blocks *
    kernels_per_block, Hkv, D]`` (kernels past the rows are junk; they are
    never visible)."""
    ks, st = cfg.sparse_kernel_size, cfg.sparse_kernel_stride
    j_n = num_blocks * cfg.kernels_per_block
    rows = (j_n - 1) * st + ks
    kp = jnp.pad(k, ((0, 0), (0, max(rows - k.shape[1], 0)), (0, 0),
                     (0, 0)))[:, :rows]
    parts = kp.reshape(k.shape[0], rows // st, st, *k.shape[2:]).sum(2)
    return sum(parts[:, i:i + j_n] for i in range(ks // st)) / ks


def selected_blocks(cfg: SALAConfig, q, ckeys, n, scale: float):
    """Which blocks each query reads. ``q`` ``[N, Hkv, G, D]`` (one query a
    row, its heads grouped by KV head), ``ckeys`` ``[N, J, Hkv, D]``, or ``[J,
    Hkv, D]`` where the queries share them (``J = num_blocks *
    kernels_per_block``), ``n`` ``[N]`` the query's context
    (its position + 1) -> bool ``[N, Hkv, num_blocks]``. A context of at
    most ``dense_len`` reads every block it has rows in."""
    with jax.named_scope("sala/select"):
        ks, st, bs = (cfg.sparse_kernel_size, cfg.sparse_kernel_stride,
                      cfg.sparse_block_size)
        r, o = bs // st, ks // st
        j_n = ckeys.shape[-3]
        nb = j_n // r
        n = n.astype(jnp.int32)[:, None]
        visible = (st * jnp.arange(j_n)[None] + ks <= n)[:, None]  # [N,1,J]
        logits = jnp.einsum("nkgd,njkd->nkgj" if ckeys.ndim == 4
                            else "nkgd,jkd->nkgj", q, ckeys) * scale
        logits = jnp.where(visible[:, :, None], logits, -jnp.inf)
        top = jnp.max(logits, axis=-1, keepdims=True)
        e = jnp.where(visible[:, :, None],
                      jnp.exp(logits - jnp.where(jnp.isfinite(top), top,
                                                 0.0)), 0.0)
        p = e / jnp.maximum(jnp.sum(e, axis=-1, keepdims=True), 1e-30)
        s = jnp.where(visible, jnp.sum(p, axis=2), -jnp.inf)      # [N,Hkv,J]
        # block b is overlapped by kernels b*r - (o-1) .. b*r + r - 1
        sp = jnp.pad(s, ((0, 0), (0, 0), (o - 1, 0)),
                     constant_values=-jnp.inf)
        score = functools.reduce(jnp.maximum, [
            sp[..., d::r][..., :nb] for d in range(r + o - 1)])
        b = jnp.arange(nb)[None]
        live = b * bs < n                                          # [N, nb]
        forced = live & ((b < cfg.sparse_init_blocks)
                         | ((b + 1) * bs > n - cfg.sparse_window_size))
        ranked = jnp.where(forced[:, None], jnp.inf,
                           jnp.where(live[:, None], score, -jnp.inf))
        k_sel = min(cfg.sparse_topk, nb)
        _, idx = jax.lax.top_k(ranked, k_sel)                      # [N,Hkv,k]
        chosen = jnp.zeros(ranked.shape, bool).at[
            jnp.arange(ranked.shape[0])[:, None, None],
            jnp.arange(ranked.shape[1])[None, :, None], idx].set(True)
        return jnp.where((n <= cfg.sparse_dense_len)[:, None],
                         live[:, None], chosen & live[:, None])


def block_sparse_attention(q, k, v, mask_rows, scale: float):
    """Softmax attention of ``q`` ``[T, Hkv, G, D]`` over ``k``/``v`` ``[R,
    Hkv, D]`` under ``mask_rows`` bool ``[T, Hkv, R]`` (every query has a
    row to read) -> ``[T, Hkv, G, D]``."""
    scores = jnp.einsum("tkgd,rkd->tkgr", q, k) * scale
    probs = jax.nn.softmax(
        jnp.where(mask_rows[:, :, None], scores, -jnp.inf), axis=-1)
    return jnp.einsum("tkgr,rkd->tkgd", probs, v)


class FullSequence:
    """The view with no past: whole sequences from position 0."""

    def __init__(self, cfg: SALAConfig):
        self.cfg = cfg

    def sparse(self, ai, q, k, v, scale):
        cfg = self.cfg
        bsz, t, hq, d = q.shape
        bs = cfg.sparse_block_size
        nb = -(-t // bs)
        ck = compressed_keys(cfg, k, nb)
        qg = q.reshape(bsz, t, cfg.num_key_value_heads, cfg.groups, d)
        n = jnp.arange(1, t + 1)
        rows = jnp.arange(nb * bs)
        out = []
        for i in range(bsz):
            blocks = selected_blocks(cfg, qg[i], ck[i], n, scale)  # [T,Hkv,nb]
            mask = jnp.repeat(blocks, bs, axis=-1) \
                & (rows[None] < n[:, None])[:, None]
            out.append(block_sparse_attention(qg[i], k[i], v[i],
                                              mask[..., :t], scale))
        return jnp.stack(out).reshape(bsz, t, hq, d)

    def linear(self, li, q, k, v, decay):
        bsz, t, h, d = q.shape
        pad = (-t) % 128 if t > 128 else 0
        if pad:
            q, k, v = (jnp.pad(x, ((0, 0), (0, pad), (0, 0), (0, 0)))
                       for x in (q, k, v))
        y, _ = decayed_linear_attention(
            q, k, v, decay, jnp.zeros((bsz, h, d, v.shape[-1]), jnp.float32),
            jnp.full((bsz,), t, jnp.int32))
        return y[:, :t]


# -- the block -------------------------------------------------------------------

def _heads(y, heads: int, d: int):
    """A projection's result ``[B, T, heads * d]`` as heads. The barrier
    keeps the product and the split apart: merged, the compiler contracts a
    one-token step's product over a TRANSPOSED copy of the weight, made
    again every tick (20 copies of 64 MB in the 8-layer decode step
    compiled for a v5e, PERF.md PR 30)."""
    return jax.lax.optimization_barrier(y).reshape(
        y.shape[0], y.shape[1], heads, d)


def _merged(y):
    """Heads ``[B, T, heads, d]`` back to channels, held apart from the
    output projection as :func:`_heads` holds the split."""
    return jax.lax.optimization_barrier(
        y.reshape(y.shape[0], y.shape[1], -1))


def _sparse_mixer(cfg, lp, x, view, ai):
    d = cfg.head_dim
    q = _heads(x @ lp["qw"], cfg.num_attention_heads, d)
    k = _heads(x @ lp["kw"], cfg.num_key_value_heads, d)
    v = _heads(x @ lp["vw"], cfg.num_key_value_heads, d)
    q = rms_norm(q, lp["qn"], cfg.rms_norm_eps)
    k = rms_norm(k, lp["kn"], cfg.rms_norm_eps)
    out = _merged(view.sparse(ai, q, k, v, d ** -0.5))
    return (jax.nn.sigmoid(x @ lp["gw"]) * out) @ lp["ow"]


def _linear_mixer(cfg, lp, x, positions, view, li):
    h, d = cfg.lightning_nh, cfg.lightning_head_dim
    q = _heads(x @ lp["qw"], h, d)
    k = _heads(x @ lp["kw"], h, d)
    v = _heads(x @ lp["vw"], h, d)
    q = rope(rms_norm(q, lp["qn"], cfg.rms_norm_eps), positions,
             cfg.rope_theta)
    k = rope(rms_norm(k, lp["kn"], cfg.rms_norm_eps), positions,
             cfg.rope_theta)
    y = view.linear(li, q * d ** -0.5, k, v, cfg.decay_rates())
    y = _merged(rms_norm(y, lp["on"], cfg.rms_norm_eps))
    return (jax.nn.sigmoid(x @ lp["zw"]) * y) @ lp["ow"]


def sala_block(cfg: SALAConfig, i: int, lp, h, positions, view):
    """Layer ``i`` on ``h`` ``[B, T, hidden]`` at ``positions`` ``[B, T]``."""
    a = cfg.residual_scale
    x = rms_norm(h, lp["n1"], cfg.rms_norm_eps)
    if cfg.mixer_types[i] == SPARSE:
        with jax.named_scope("sala/sparse"):
            op = _sparse_mixer(cfg, lp, x, view, cfg.sparse_layers.index(i))
    else:
        with jax.named_scope("sala/linear"):
            op = _linear_mixer(cfg, lp, x, positions, view,
                               cfg.linear_layers.index(i))
    h = h + a * op
    with jax.named_scope("sala/ffn"):
        f = rms_norm(h, lp["n2"], cfg.rms_norm_eps)
        return h + a * swiglu(f, lp["w1"], lp["w3"], lp["w2"])


def sala_hidden(cfg: SALAConfig, params, tokens, positions, view):
    """Final-norm hidden states ``[B, T, hidden]``, already divided for the
    head (``hidden / dim_model_base``)."""
    h = cfg.scale_emb * params["tok"][tokens]
    for i, lp in enumerate(params["layers"]):
        h = sala_block(cfg, i, lp, h, positions, view)
    return rms_norm(h, params["fnw"], cfg.rms_norm_eps) / cfg.logit_divisor


def sala_logits(cfg: SALAConfig, params, tokens):
    """Logits ``[B, T, V]`` of whole sequences (no cache)."""
    positions = jnp.arange(tokens.shape[1], dtype=jnp.int32)[None]
    h = sala_hidden(cfg, params, tokens, positions, FullSequence(cfg))
    return h @ params["head"]


# -- the Layer graph -------------------------------------------------------------

class SparseAttention(Layer):
    def __init__(self, c: SALAConfig):
        super().__init__()
        width = c.num_attention_heads * c.head_dim
        kv = c.num_key_value_heads * c.head_dim
        self.q_proj = _proj(c.hidden_size, width)
        self.k_proj = _proj(c.hidden_size, kv)
        self.v_proj = _proj(c.hidden_size, kv)
        self.o_gate = _proj(c.hidden_size, width)
        self.o_proj = _proj(width, c.hidden_size)
        self.q_norm = RMSNorm(c.head_dim, c.rms_norm_eps)
        self.k_norm = RMSNorm(c.head_dim, c.rms_norm_eps)

    def param_tree(self, leaf):
        return {"qw": leaf(self.q_proj.weight), "kw": leaf(self.k_proj.weight),
                "vw": leaf(self.v_proj.weight), "gw": leaf(self.o_gate.weight),
                "ow": leaf(self.o_proj.weight), "qn": leaf(self.q_norm.weight),
                "kn": leaf(self.k_norm.weight)}


class LightningAttention(Layer):
    def __init__(self, c: SALAConfig):
        super().__init__()
        width = c.lightning_nh * c.lightning_head_dim
        self.q_proj = _proj(c.hidden_size, width)
        self.k_proj = _proj(c.hidden_size, width)
        self.v_proj = _proj(c.hidden_size, width)
        self.z_proj = _proj(c.hidden_size, width)
        self.o_proj = _proj(width, c.hidden_size)
        self.q_norm = RMSNorm(c.lightning_head_dim, c.rms_norm_eps)
        self.k_norm = RMSNorm(c.lightning_head_dim, c.rms_norm_eps)
        self.o_norm = RMSNorm(c.lightning_head_dim, c.rms_norm_eps)

    def param_tree(self, leaf):
        return {"qw": leaf(self.q_proj.weight), "kw": leaf(self.k_proj.weight),
                "vw": leaf(self.v_proj.weight), "zw": leaf(self.z_proj.weight),
                "ow": leaf(self.o_proj.weight), "qn": leaf(self.q_norm.weight),
                "kn": leaf(self.k_norm.weight), "on": leaf(self.o_norm.weight)}


class SALADecoderLayer(Layer):
    def __init__(self, c: SALAConfig, i: int):
        super().__init__()
        self.input_layernorm = RMSNorm(c.hidden_size, c.rms_norm_eps)
        self.self_attn = (SparseAttention(c) if c.mixer_types[i] == SPARSE
                          else LightningAttention(c))
        self.post_attention_layernorm = RMSNorm(c.hidden_size,
                                                c.rms_norm_eps)
        self.mlp = SwiGLU(c.hidden_size, c.intermediate_size)

    def param_tree(self, raw: bool):
        leaf = functools.partial(_leaf, raw=raw)
        out = {"n1": leaf(self.input_layernorm.weight),
               "n2": leaf(self.post_attention_layernorm.weight),
               "w1": leaf(self.mlp.w1.weight), "w3": leaf(self.mlp.w3.weight),
               "w2": leaf(self.mlp.w2.weight)}
        out.update(self.self_attn.param_tree(leaf))
        return out


class SALAModel(Layer):
    def __init__(self, config: SALAConfig):
        super().__init__()
        self.embed_tokens = Embedding(
            config.vocab_size, config.hidden_size,
            weight_attr=ParamAttr(initializer=I.Normal(0.0, 0.02)))
        self.layers = LayerList([SALADecoderLayer(config, i)
                                 for i in range(config.num_hidden_layers)])
        self.norm = RMSNorm(config.hidden_size, config.rms_norm_eps)


class MiniCPMSALAForCausalLM(Layer):
    """``forward`` runs whole sequences with no cache; the serving engine
    reads :meth:`param_tree` and runs the same block through its caches.
    The output head is a matrix of its own."""

    def __init__(self, config: SALAConfig):
        super().__init__()
        self.config = config
        self.model = SALAModel(config)
        self.lm_head = _proj(config.hidden_size, config.vocab_size)

    def param_tree(self, raw: bool = True):
        """``{"tok", "fnw", "head", "layers": (per-layer dicts)}``:
        references to the parameters' arrays, not copies."""
        m = self.model
        leaf = functools.partial(_leaf, raw=raw)
        return {"tok": leaf(m.embed_tokens.weight),
                "fnw": leaf(m.norm.weight),
                "head": leaf(self.lm_head.weight),
                "layers": tuple(lyr.param_tree(raw) for lyr in m.layers)}

    def forward(self, input_ids):
        cfg = self.config
        return apply("sala_forward",
                     lambda params, ids: sala_logits(cfg, params, ids),
                     self.param_tree(raw=False), input_ids)
