"""LFM2-MoE: gated short convolutions and grouped-query attention as the
operators, SwiGLU feed-forwards, sparse experts after the leading dense
layers (LiquidAI LFM2-8B-A1B's family, ``model_type`` ``lfm2_moe``).

The block is written ONCE, as a function of a parameter pytree and a
*cache view* (:func:`lfm2_block`). A view answers the two questions whose
answer depends on where the sequence's past lives:

- ``view.conv(ci, z, kern)``: the causal depthwise convolution of ``z`` for
  convolution layer ``ci``, given whatever came before;
- ``view.attend(ai, q, k, v)``: causal attention of ``q`` for attention
  layer ``ai`` over the keys and values so far, ``k``/``v`` included.

:class:`FullSequence` is the view with no past (whole sequences from
position 0): the ``Layer``'s forward uses it, and the serving prefill uses
it and then stores what it recorded. The paged decode step's view
(``serving/llm/paged/lfm2.py``) reads and writes the KV pages and the
per-slot convolution state. The equations, per layer ``i`` on the residual
stream ``h`` (``rms`` in float32, no biases anywhere):

    u = rms(h; operator_norm)
    conv:  B, C, x = split(u @ W_in, 3);  z = B * x
           c_t = sum_j k[:, j] * z_{t-(L-1)+j};  op = (C * c) @ W_out
    attn:  q, k, v = u @ W_q, W_k, W_v;  q, k = rope(rms(q), rms(k)) per head
           op = softmax(q k^T / sqrt(D), causal; head j reads KV head j // g)
                @ v @ W_o
    h = h + op;  f = rms(h; ffn_norm)
    dense (i < num_dense_layers):  ffn = (silu(f @ W1) * (f @ W3)) @ W2
    experts:  nn.MoEFeedForward (sigmoid scores, bias selects, top k)
    h = h + ffn
    logits = rms(h; embedding_norm) @ E^T        (tied to the embedding)

``state_dict`` names follow the published checkpoint's
(``model.layers.<i>.conv.in_proj.weight``, ``...self_attn.q_layernorm.
weight``, ``...feed_forward.experts...``); matrices are ``[in, out]`` as
everywhere in this framework, and a layer's experts are stacked on a
leading axis (``feed_forward.experts.w1`` ``[n, h, f]``), which is what the
grouped product reads (:func:`stack_checkpoint_experts` converts).
"""
from __future__ import annotations

import functools
from dataclasses import dataclass
from typing import Optional, Tuple

import jax
import jax.numpy as jnp

from ..nn import Embedding, LayerList, Linear, MoEFeedForward, RMSNorm
from ..nn import initializer as I
from ..nn.layer_base import Layer, ParamAttr
from ..nn.moe import rms_norm, swiglu  # noqa: F401 -- re-exported
from ..ops import moe as _moe
from ..ops.dispatch import apply

CONV, ATTN = "conv", "full_attention"


@dataclass(frozen=True)
class LFM2Config:
    """Every key of the published ``config.json`` (hashable: it keys the
    compiled programs). ``experts_held = (lo, n)`` is the share of each
    layer's experts that lives here (default: all of them)."""
    vocab_size: int = 65536
    hidden_size: int = 2048
    intermediate_size: int = 7168
    moe_intermediate_size: int = 1792
    num_hidden_layers: int = 24
    layer_types: Tuple[str, ...] = ()
    num_attention_heads: int = 32
    num_key_value_heads: int = 8
    num_dense_layers: int = 2
    num_experts: int = 32
    num_experts_per_tok: int = 4
    norm_topk_prob: bool = True
    routed_scaling_factor: float = 1.0
    use_expert_bias: bool = True
    conv_L_cache: int = 3
    conv_bias: bool = False
    norm_eps: float = 1e-5
    rope_theta: float = 1000000.0
    max_position_embeddings: int = 128000
    tie_word_embeddings: bool = True
    model_type: str = "lfm2_moe"
    experts_held: Optional[Tuple[int, int]] = None

    def __post_init__(self):
        object.__setattr__(self, "layer_types", tuple(self.layer_types))
        if len(self.layer_types) != self.num_hidden_layers:
            raise ValueError(
                f"{len(self.layer_types)} layer_types for "
                f"{self.num_hidden_layers} layers")
        bad = set(self.layer_types) - {CONV, ATTN}
        if bad:
            raise ValueError(f"unknown layer types {sorted(bad)}")
        if self.conv_bias or not self.tie_word_embeddings:
            raise NotImplementedError(
                "conv_bias and an untied output head are not implemented")
        if self.num_attention_heads % self.num_key_value_heads:
            raise ValueError("query heads must be a multiple of KV heads")

    @property
    def head_dim(self) -> int:
        return self.hidden_size // self.num_attention_heads

    @property
    def conv_layers(self) -> Tuple[int, ...]:
        return tuple(i for i, t in enumerate(self.layer_types) if t == CONV)

    @property
    def attn_layers(self) -> Tuple[int, ...]:
        return tuple(i for i, t in enumerate(self.layer_types) if t == ATTN)

    @property
    def num_expert_layers(self) -> int:
        return max(self.num_hidden_layers - self.num_dense_layers, 0)


# -- the arithmetic (raw arrays; shared by forward, prefill and decode) -------

def rope(x, positions, theta: float, rotary_dim: Optional[int] = None):
    """Rotate-half RoPE over the first ``rotary_dim`` columns of a head (the
    whole head by default; the other columns pass unrotated: partial
    rotary). ``x``: ``[B, T, heads, D]``; ``positions``: ``[B, T]`` (or
    ``[T]``) int32."""
    if rotary_dim is not None and rotary_dim != x.shape[-1]:
        return jnp.concatenate(
            [rope(x[..., :rotary_dim], positions, theta),
             x[..., rotary_dim:]], axis=-1)
    d = x.shape[-1]
    inv = theta ** (-jnp.arange(0, d, 2, dtype=jnp.float32) / d)
    ang = positions.astype(jnp.float32)[..., None] * inv          # [.., T, D/2]
    cos = jnp.concatenate([jnp.cos(ang), jnp.cos(ang)], -1)[..., None, :]
    sin = jnp.concatenate([jnp.sin(ang), jnp.sin(ang)], -1)[..., None, :]
    rot = jnp.concatenate([-x[..., d // 2:], x[..., :d // 2]], -1)
    return x * cos + rot * sin


def grouped_causal_attention(q, k, v, scale: float, window=None):
    """Causal attention within whole sequences: ``q`` ``[B, T, Hq, D]``,
    ``k``/``v`` ``[B, T, Hkv, D]``, query head ``j`` reading KV head
    ``j // (Hq // Hkv)``. With ``window`` a query at row ``t`` reads the
    rows ``t - window < j <= t`` only."""
    b, t, hq, d = q.shape
    hkv = k.shape[2]
    qg = q.reshape(b, t, hkv, hq // hkv, d)
    scores = jnp.einsum("bqkgd,bskd->bkgqs", qg, k) * scale
    causal = jnp.tril(jnp.ones((t, t), bool))
    if window is not None:
        causal &= ~jnp.tril(jnp.ones((t, t), bool), -window)
    probs = jax.nn.softmax(jnp.where(causal, scores, -jnp.inf), axis=-1)
    return jnp.einsum("bkgqs,bskd->bqkgd", probs, v).reshape(b, t, hq, d)


class FullSequence:
    """The view with no past: whole sequences from position 0. Records what
    a cache would have to keep: each attention layer's ``(k, v)`` and, given
    ``true_lens`` (right-padded rows), each convolution layer's last
    ``L - 1`` columns of ``z`` before ``true_len`` (zeros where the row is
    shorter than that)."""

    def __init__(self, true_lens=None):
        self.true_lens = true_lens
        self.kv, self.conv_tails = [], []

    def conv(self, ci, z, kern):
        width = kern.shape[1]
        zp = jnp.pad(z, ((0, 0), (width - 1, 0), (0, 0)))
        t = z.shape[1]
        if self.true_lens is not None:
            # zp row true_len + j is z row true_len - (L-1) + j
            rows = self.true_lens[:, None] + jnp.arange(width - 1)[None]
            self.conv_tails.append(
                jnp.take_along_axis(zp, rows[..., None], axis=1))
        return sum(kern[:, j] * zp[:, j:j + t] for j in range(width))

    def attend(self, ai, q, k, v, scale):
        self.kv.append((k, v))
        return grouped_causal_attention(q, k, v, scale)


def _short_conv(cfg, lp, u, view, ci):
    b, c, x = jnp.split(u @ lp["in"], 3, axis=-1)
    return (c * view.conv(ci, b * x, lp["k"])) @ lp["out"]


def _attention(cfg, lp, u, positions, view, ai):
    bsz, t, _ = u.shape
    d = cfg.head_dim
    q = (u @ lp["qw"]).reshape(bsz, t, cfg.num_attention_heads, d)
    k = (u @ lp["kw"]).reshape(bsz, t, cfg.num_key_value_heads, d)
    v = (u @ lp["vw"]).reshape(bsz, t, cfg.num_key_value_heads, d)
    q = rope(rms_norm(q, lp["qn"], cfg.norm_eps), positions, cfg.rope_theta)
    k = rope(rms_norm(k, lp["kn"], cfg.norm_eps), positions, cfg.rope_theta)
    out = view.attend(ai, q, k, v, d ** -0.5)
    return out.reshape(bsz, t, cfg.hidden_size) @ lp["ow"]


def lfm2_block(cfg: LFM2Config, i: int, lp, h, positions, view):
    """Layer ``i`` on ``h`` ``[B, T, hidden]``: ``(h', counts)`` with
    ``counts`` the pairs each held expert received (None in a dense
    layer)."""
    kind = cfg.layer_types[i]
    u = rms_norm(h, lp["n1"], cfg.norm_eps)
    if kind == CONV:
        with jax.named_scope("lfm2/conv"):
            op = _short_conv(cfg, lp, u, view, cfg.conv_layers.index(i))
    else:
        with jax.named_scope("lfm2/attn"):
            op = _attention(cfg, lp, u, positions, view,
                            cfg.attn_layers.index(i))
    h = h + op
    f = rms_norm(h, lp["n2"], cfg.norm_eps)
    if i < cfg.num_dense_layers:
        return h + swiglu(f, lp["w1"], lp["w3"], lp["w2"]), None
    lo = cfg.experts_held[0] if cfg.experts_held else 0
    out, counts = _moe.moe_feed_forward(
        f.reshape(-1, f.shape[-1]), lp["gate"], lp["bias"], lp["w1"],
        lp["w3"], lp["w2"], top_k=cfg.num_experts_per_tok,
        norm_topk=cfg.norm_topk_prob, scale=cfg.routed_scaling_factor,
        expert_lo=lo)
    return h + out.reshape(h.shape), counts


def lfm2_hidden(cfg: LFM2Config, params, tokens, positions, view):
    """Final-norm hidden states ``[B, T, hidden]`` and the expert layers'
    ``counts`` (a list, one ``[n]`` per expert layer)."""
    h = params["tok"][tokens]
    all_counts = []
    for i, lp in enumerate(params["layers"]):
        h, counts = lfm2_block(cfg, i, lp, h, positions, view)
        if counts is not None:
            all_counts.append(counts)
    return rms_norm(h, params["fnw"], cfg.norm_eps), all_counts


def lfm2_logits(cfg: LFM2Config, params, tokens):
    """Logits ``[B, T, V]`` of whole sequences (no cache)."""
    positions = jnp.arange(tokens.shape[1], dtype=jnp.int32)[None]
    h, _ = lfm2_hidden(cfg, params, tokens, positions, FullSequence())
    return h @ params["tok"].T


# -- the Layer graph -----------------------------------------------------------

def _leaf(p, raw: bool):
    """A parameter's array (``raw``) or the Parameter itself."""
    return p._data if raw else p


def _proj(n_in: int, n_out: int) -> Linear:
    return Linear(n_in, n_out, weight_attr=ParamAttr(
        initializer=I.Normal(0.0, 0.02)), bias_attr=False)


class ShortConv(Layer):
    def __init__(self, c: LFM2Config):
        super().__init__()
        self.in_proj = _proj(c.hidden_size, 3 * c.hidden_size)
        self.conv = Layer()         # depthwise kernel, [hidden, L]
        self.conv.weight = self.create_parameter(
            [c.hidden_size, c.conv_L_cache],
            default_initializer=I.Normal(0.0, 0.3))
        self.out_proj = _proj(c.hidden_size, c.hidden_size)


class GroupedQueryAttention(Layer):
    def __init__(self, c: LFM2Config):
        super().__init__()
        kv = c.num_key_value_heads * c.head_dim
        self.q_proj = _proj(c.hidden_size, c.hidden_size)
        self.k_proj = _proj(c.hidden_size, kv)
        self.v_proj = _proj(c.hidden_size, kv)
        self.out_proj = _proj(c.hidden_size, c.hidden_size)
        self.q_layernorm = RMSNorm(c.head_dim, c.norm_eps)
        self.k_layernorm = RMSNorm(c.head_dim, c.norm_eps)


class SwiGLU(Layer):
    def __init__(self, hidden: int, width: int):
        super().__init__()
        self.w1 = _proj(hidden, width)
        self.w3 = _proj(hidden, width)
        self.w2 = _proj(width, hidden)


class LFM2DecoderLayer(Layer):
    def __init__(self, c: LFM2Config, i: int):
        super().__init__()
        self.operator_norm = RMSNorm(c.hidden_size, c.norm_eps)
        if c.layer_types[i] == CONV:
            self.conv = ShortConv(c)
        else:
            self.self_attn = GroupedQueryAttention(c)
        self.ffn_norm = RMSNorm(c.hidden_size, c.norm_eps)
        if i < c.num_dense_layers:
            self.feed_forward = SwiGLU(c.hidden_size, c.intermediate_size)
        else:
            self.feed_forward = MoEFeedForward(
                c.hidden_size, c.moe_intermediate_size, c.num_experts,
                c.num_experts_per_tok, c.norm_topk_prob,
                c.routed_scaling_factor, held=c.experts_held)

    def param_tree(self, raw: bool):
        """This layer's leaves under the short keys :func:`lfm2_block`
        reads: raw arrays (``raw``) or the Parameters themselves."""
        leaf = functools.partial(_leaf, raw=raw)
        out = {"n1": leaf(self.operator_norm.weight),
               "n2": leaf(self.ffn_norm.weight)}
        if hasattr(self, "conv"):
            c = self.conv
            out.update({"in": leaf(c.in_proj.weight),
                        "k": leaf(c.conv.weight),
                        "out": leaf(c.out_proj.weight)})
        else:
            a = self.self_attn
            out.update({"qw": leaf(a.q_proj.weight),
                        "kw": leaf(a.k_proj.weight),
                        "vw": leaf(a.v_proj.weight),
                        "ow": leaf(a.out_proj.weight),
                        "qn": leaf(a.q_layernorm.weight),
                        "kn": leaf(a.k_layernorm.weight)})
        ff = self.feed_forward
        if isinstance(ff, SwiGLU):
            out.update({"w1": leaf(ff.w1.weight), "w3": leaf(ff.w3.weight),
                        "w2": leaf(ff.w2.weight)})
        else:
            out.update({"gate": leaf(ff.gate.weight),
                        "bias": leaf(ff.expert_bias),
                        "w1": leaf(ff.experts.w1), "w3": leaf(ff.experts.w3),
                        "w2": leaf(ff.experts.w2)})
        return out


class LFM2Model(Layer):
    def __init__(self, config: LFM2Config):
        super().__init__()
        self.config = config
        self.embed_tokens = Embedding(
            config.vocab_size, config.hidden_size,
            weight_attr=ParamAttr(initializer=I.Normal(0.0, 0.02)))
        self.layers = LayerList([LFM2DecoderLayer(config, i)
                                 for i in range(config.num_hidden_layers)])
        self.embedding_norm = RMSNorm(config.hidden_size, config.norm_eps)


class LFM2ForCausalLM(Layer):
    """Output head tied to the token embedding. ``forward`` runs whole
    sequences with no cache; the serving engine reads :meth:`param_tree`
    and runs the same block through its caches."""

    def __init__(self, config: LFM2Config):
        super().__init__()
        self.config = config
        self.model = LFM2Model(config)

    def param_tree(self, raw: bool = True):
        """``{"tok", "fnw", "layers": (per-layer dicts)}``: references to
        the parameters' arrays, not copies."""
        m = self.model
        leaf = functools.partial(_leaf, raw=raw)
        return {"tok": leaf(m.embed_tokens.weight),
                "fnw": leaf(m.embedding_norm.weight),
                "layers": tuple(lyr.param_tree(raw) for lyr in m.layers)}

    def forward(self, input_ids):
        cfg = self.config
        return apply("lfm2_forward",
                     lambda params, ids: lfm2_logits(cfg, params, ids),
                     self.param_tree(raw=False), input_ids)


def stack_checkpoint_experts(state: dict, num_experts: int) -> dict:
    """A published checkpoint's per-expert ``...feed_forward.experts.<e>.
    w1.weight`` (``[out, in]``) entries, stacked into this model's
    ``...feed_forward.experts.w1`` (``[n, in, out]``); other entries pass
    through."""
    out, stacks = {}, {}
    for name, value in state.items():
        head, sep, tail = name.partition(".feed_forward.experts.")
        parts = tail.split(".")
        if not sep or not parts[0].isdigit():
            out[name] = value
            continue
        stacks.setdefault((head, parts[1]), {})[int(parts[0])] = value
    for (head, mat), by_expert in stacks.items():
        out[f"{head}.feed_forward.experts.{mat}"] = jnp.stack(
            [jnp.asarray(by_expert[e]).T for e in range(num_experts)])
    return out
