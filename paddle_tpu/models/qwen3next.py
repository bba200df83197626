"""Qwen3-Next (``model_type`` ``qwen3_next``, Qwen's Qwen3-Next-80B-A3B
family): Gated-DeltaNet linear attention in three layers of four, gated
softmax attention with partial rotary in the fourth, sparse experts routed by
a softmax beside a gated shared expert in every layer, an untied head.

The block is written ONCE, as a function of a parameter pytree and a *cache
view* (:func:`qwen3next_block`), as the other families' are. A view answers
the three questions whose answer depends on where the sequence's past lives:

- ``view.attend(ai, q, k, v, scale)``: causal attention of full-attention
  layer ``ai``'s queries ``[B, T, Hq, D]`` over the keys and values so far,
  ``k``/``v`` ``[B, T, Hkv, D]`` included -> ``[B, T, Hq, D]``;
- ``view.conv(li, x, kern)``: the causal depthwise convolution of linear
  layer ``li``'s ``[q | k | v]`` channels ``[B, T, C]`` (before the SiLU),
  given whatever came before;
- ``view.delta(li, q, k, v, g, beta)``: the gated delta rule of linear layer
  ``li`` (``ops/gated_delta.py``), from whatever state came before -> ``[B, T,
  Hv, Dv]``.

:class:`FullSequence` is the view with no past (whole sequences from position
0; the ``Layer``'s forward). The serving views
(``serving/llm/paged/qwen3next.py``) keep KV pages for the full layers and, a
slot and linear layer, the rule's state and the convolution's last inputs.
The equations, per layer on the residual stream ``h`` (no biases anywhere;
``N(x; w) = x / sqrt(mean(x^2) + eps) * (1 + w)`` is the family's
ZERO-CENTRED RMSNorm; (a) marks what ``config.json`` does not say and the
public modelling code of the model type does, see
``benchmark/configs/qwen3-next-80b-a3b.json``):

    h0 = E[token]                                          (unscaled; a)
    x = N(h; n1)
    full (layer i with (i + 1) % full_attention_interval == 0):
        [q | gate] = x Wq per head (2 D columns a head; a);  k = x Wk;  v = x Wv
        q, k = N(q; qn), N(k; kn) per head                          (a)
        q, k = rope over the first partial_rotary_factor * D columns
        o = softmax(q k^T / sqrt(D), causal; head j reads KV head j // G) v
        op = (concat(o) * sigmoid(gate)) Wo                         (a)
    linear (Gated DeltaNet; Hk key heads, Hv value heads; a throughout):
        [q | k | v | z] = x W_qkvz;  [b | a] = x W_ba
        [q | k | v] = silu(causal depthwise conv, kernel 4, of [q | k | v])
        beta = sigmoid(b);  g = -exp(A_log) * softplus(a + dt_bias)
        q, k = x / sqrt(sum(x^2) + 1e-6) per head;  q = q / sqrt(Dk)
        per value head h (key head h // (Hv / Hk)), S [Dk, Dv] from 0:
            S <- exp(g_t) S;  r_t = v_t - S^T k_t
            S <- S + beta_t k_t r_t^T;  o_t = S^T q_t
        y = (gn * o / sqrt(mean(o^2) + eps)) * silu(z) per head   (plain weight)
        op = concat(y) W_out
    h = h + op;  f = N(h; n2)
    p = softmax(f Wr) over all the experts;  chosen = top k of p
    w_e = p_e / sum of the chosen p                          (norm_topk_prob)
    h = h + sum over the chosen e of w_e Expert_e(f)
          + sigmoid(f . w_sg) * Shared(f)                           (a)
    logits = N(h; final) W_head

The published ``in_proj_qkvz`` / ``in_proj_ba`` interleave their columns by
key head; here they are concatenated as written above, a fixed permutation of
columns that a checkpoint's matrices take when they are loaded.

**A chip's share.** ``experts_held = (lo, n)`` and ``vocab_rows = (lo, n)`` as
``models/trinity.py`` has them: routing is over all ``num_experts`` and the
layer's result is the gated shared expert + the held experts' part (what the
absent ones would add is left out, and that partial result goes on to the
next layer); token ids index the held rows of the embedding and logits are
over the held columns of the head.

Not implemented (the configuration raises): rope scaling, a tied head,
``mlp_only_layers`` or a ``decoder_sparse_step`` other than 1, attention
biases, a sliding window, and the published next-token-prediction (MTP) head,
which the ``config.json`` has no key for and this module leaves out.

``state_dict`` names follow the published checkpoint's where it has them;
matrices are ``[in, out]``; a layer's experts are stacked on a leading axis.
"""
from __future__ import annotations

import functools
from dataclasses import dataclass
from typing import Optional, Tuple

import jax
import jax.numpy as jnp

from ..nn import Embedding, LayerList, MoEFeedForward, RMSNorm
from ..nn import initializer as I
from ..nn.layer_base import Layer, ParamAttr
from ..ops import moe as _moe
from ..ops.dispatch import apply
from ..ops.gated_delta import CHUNK, gated_delta_chunked
from .lfm2 import (_leaf, _proj, grouped_causal_attention, rms_norm, rope,
                   swiglu)
from .sala import _heads

#: the epsilon under the square root of the linear layers' q and k norms
L2_EPS = 1e-6


@dataclass(frozen=True)
class Qwen3NextConfig:
    """Every key of the published ``config.json`` (hashable: it keys the
    compiled programs), then the share held here."""
    vocab_size: int = 151936
    hidden_size: int = 2048
    intermediate_size: int = 5120       # of mlp_only_layers: there are none
    moe_intermediate_size: int = 512
    shared_expert_intermediate_size: int = 512
    num_hidden_layers: int = 48
    full_attention_interval: int = 4
    num_attention_heads: int = 16
    num_key_value_heads: int = 2
    head_dim: int = 256
    partial_rotary_factor: float = 0.25
    linear_conv_kernel_dim: int = 4
    linear_key_head_dim: int = 128
    linear_value_head_dim: int = 128
    linear_num_key_heads: int = 16
    linear_num_value_heads: int = 32
    num_experts: int = 512
    num_experts_per_tok: int = 10
    norm_topk_prob: bool = True
    decoder_sparse_step: int = 1
    mlp_only_layers: Tuple[int, ...] = ()
    attention_bias: bool = False
    use_sliding_window: bool = False
    hidden_act: str = "silu"
    rms_norm_eps: float = 1e-6
    rope_theta: float = 10000000.0
    rope_scaling: Optional[str] = None
    max_position_embeddings: int = 262144
    tie_word_embeddings: bool = False
    num_nextn_predict_layers: int = 0
    model_type: str = "qwen3_next"
    # -- the share held here -------------------------------------------------
    experts_held: Optional[Tuple[int, int]] = None
    vocab_rows: Optional[Tuple[int, int]] = None

    def __post_init__(self):
        object.__setattr__(self, "mlp_only_layers",
                           tuple(self.mlp_only_layers))
        for name in ("experts_held", "vocab_rows"):
            if getattr(self, name) is not None:
                object.__setattr__(self, name, tuple(getattr(self, name)))
        for name, wanted in (
                ("rope_scaling", None), ("tie_word_embeddings", False),
                ("mlp_only_layers", ()), ("decoder_sparse_step", 1),
                ("num_nextn_predict_layers", 0), ("attention_bias", False),
                ("use_sliding_window", False), ("hidden_act", "silu")):
            if getattr(self, name) != wanted:
                raise NotImplementedError(
                    f"{name}={getattr(self, name)!r} is not implemented "
                    f"(this family runs {name}={wanted!r})")
        if self.num_attention_heads % self.num_key_value_heads \
                or self.linear_num_value_heads % self.linear_num_key_heads:
            raise ValueError(
                "query heads must be a multiple of KV heads, and the linear "
                "layers' value heads of their key heads")
        if self.rotary_dim % 2 or not 0 < self.rotary_dim <= self.head_dim:
            raise ValueError(
                f"partial_rotary_factor {self.partial_rotary_factor} of a "
                f"head of {self.head_dim} is no even number of columns")
        if self.shared_expert_intermediate_size % self.moe_intermediate_size:
            raise NotImplementedError(
                "a shared expert that is no whole number of routed experts "
                "wide is not implemented")
        lo, n = self.vocab_rows or (0, self.vocab_size)
        if not (0 <= lo and n >= 1 and lo + n <= self.vocab_size):
            raise ValueError(
                f"vocab_rows {self.vocab_rows} outside 0..{self.vocab_size}")

    def is_full(self, i: int) -> bool:
        return (i + 1) % self.full_attention_interval == 0

    @property
    def full_layers(self) -> Tuple[int, ...]:
        return tuple(i for i in range(self.num_hidden_layers)
                     if self.is_full(i))

    @property
    def linear_layers(self) -> Tuple[int, ...]:
        return tuple(i for i in range(self.num_hidden_layers)
                     if not self.is_full(i))

    @property
    def num_expert_layers(self) -> int:
        return self.num_hidden_layers

    @property
    def vocab_held(self) -> int:
        """Rows of the embedding (columns of the head) held here."""
        return self.vocab_rows[1] if self.vocab_rows else self.vocab_size

    @property
    def rotary_dim(self) -> int:
        """Columns of a head that rotate: the first of them."""
        return int(self.head_dim * self.partial_rotary_factor)

    @property
    def key_width(self) -> int:
        return self.linear_num_key_heads * self.linear_key_head_dim

    @property
    def value_width(self) -> int:
        return self.linear_num_value_heads * self.linear_value_head_dim

    @property
    def conv_width(self) -> int:
        """Channels the short convolution runs over: ``[q | k | v]``."""
        return 2 * self.key_width + self.value_width


# -- the arithmetic (raw arrays; shared by forward, chunk and decode) ---------

def zc_norm(x, w, eps: float):
    """The family's zero-centred RMSNorm: the learned weight is ``1 + w``."""
    return rms_norm(x, 1.0 + w, eps)


def causal_conv(window, kern, t: int):
    """``y_t = sum_j kern[:, j] * window[:, t + j]`` for ``t`` rows of a
    window ``[B, K - 1 + t, C]`` whose first ``K - 1`` rows came before."""
    return sum(kern[:, j] * window[:, j:j + t] for j in range(kern.shape[1]))


class FullSequence:
    """The view with no past: whole sequences from position 0. Records each
    full layer's ``(k, v)``, what a cache would have to keep."""

    def __init__(self):
        self.kv = []

    def attend(self, ai, q, k, v, scale):
        self.kv.append((k, v))
        return grouped_causal_attention(q, k, v, scale)

    def conv(self, li, x, kern):
        width = kern.shape[1]
        return causal_conv(jnp.pad(x, ((0, 0), (width - 1, 0), (0, 0))),
                           kern, x.shape[1])

    def delta(self, li, q, k, v, g, beta):
        bsz, t, hv, dv = v.shape
        pad = (-t) % CHUNK if t > CHUNK else 0
        if pad:
            q, k, v, g, beta = (jnp.pad(
                x, ((0, 0), (0, pad)) + ((0, 0),) * (x.ndim - 2))
                for x in (q, k, v, g, beta))
        with jax.named_scope("gdn_scan"):
            o, _ = gated_delta_chunked(
                q, k, v, g, beta,
                jnp.zeros((bsz, hv, q.shape[-1], dv), jnp.float32),
                jnp.full((bsz,), t, jnp.int32))
        return o[:, :t]


def _full_attention(cfg: Qwen3NextConfig, lp, x, positions, view, ai):
    bsz, t, _ = x.shape
    d, heads = cfg.head_dim, cfg.num_attention_heads
    with jax.named_scope("proj"):
        # held apart from the split: merged, a one-token step contracts over
        # a transposed copy of the weight, made again every tick
        both = _heads(x @ lp["qw"], heads, 2 * d)
        q, gate = both[..., :d], both[..., d:]
        k = (x @ lp["kw"]).reshape(bsz, t, cfg.num_key_value_heads, d)
        v = (x @ lp["vw"]).reshape(bsz, t, cfg.num_key_value_heads, d)
        q = rope(zc_norm(q, lp["qn"], cfg.rms_norm_eps), positions,
                 cfg.rope_theta, cfg.rotary_dim)
        k = rope(zc_norm(k, lp["kn"], cfg.rms_norm_eps), positions,
                 cfg.rope_theta, cfg.rotary_dim)
    out = view.attend(ai, q, k, v, d ** -0.5)
    with jax.named_scope("out"):
        out = out.reshape(bsz, t, -1) * jax.nn.sigmoid(
            gate.reshape(bsz, t, -1))
        return out @ lp["ow"]


def _l2(x):
    x32 = x.astype(jnp.float32)
    return (x32 * jax.lax.rsqrt(jnp.sum(x32 * x32, axis=-1, keepdims=True)
                                + L2_EPS)).astype(x.dtype)


def delta_gates(lp, b, a):
    """``(g, beta)`` of the projections ``b`` and ``a`` ``[..., Hv]``: the
    log of the decay (``<= 0``) and the step size."""
    g = -jnp.exp(lp["alog"]) * jax.nn.softplus(a + lp["dtb"])
    return g, jax.nn.sigmoid(b)


def _delta_mixer(cfg: Qwen3NextConfig, lp, x, view, li):
    bsz, t, _ = x.shape
    hk, hv = cfg.linear_num_key_heads, cfg.linear_num_value_heads
    dk, dv = cfg.linear_key_head_dim, cfg.linear_value_head_dim
    kw = cfg.key_width
    with jax.named_scope("proj"):
        proj = x @ lp["qkvz"]
        mixed, z = proj[..., :cfg.conv_width], proj[..., cfg.conv_width:]
        ba = x @ lp["ba"]
        g, beta = delta_gates(lp, ba[..., :hv], ba[..., hv:])
    with jax.named_scope("conv"):
        mixed = jax.nn.silu(view.conv(li, mixed, lp["conv"]))
        q = _l2(mixed[..., :kw].reshape(bsz, t, hk, dk)) * dk ** -0.5
        k = _l2(mixed[..., kw:2 * kw].reshape(bsz, t, hk, dk))
        v = mixed[..., 2 * kw:].reshape(bsz, t, hv, dv)
    o = view.delta(li, q, k, v, g, beta)
    with jax.named_scope("gate_norm"):
        y = rms_norm(o, lp["gn"], cfg.rms_norm_eps) * jax.nn.silu(
            z.reshape(bsz, t, hv, dv))
    with jax.named_scope("out"):
        return y.reshape(bsz, t, -1) @ lp["out"]


def gated_shared_expert(f, lp):
    """``sigmoid(f . w_sg) * Shared(f)``."""
    return jax.nn.sigmoid(f @ lp["sg"]) * swiglu(
        f, lp["s1"][0], lp["s3"][0], lp["s2"][0])


def qwen3next_block(cfg: Qwen3NextConfig, i: int, lp, h, positions, view):
    """Layer ``i`` on ``h`` ``[B, T, hidden]`` at ``positions`` ``[B, T]``:
    ``(h', counts)`` with ``counts`` the pairs each held expert received."""
    eps = cfg.rms_norm_eps
    with jax.named_scope("qwen3next/norm"):
        x = zc_norm(h, lp["n1"], eps)
    if cfg.is_full(i):
        with jax.named_scope("qwen3next/attn"):
            h = h + _full_attention(cfg, lp, x, positions, view,
                                    cfg.full_layers.index(i))
    else:
        with jax.named_scope("qwen3next/gdn"):
            h = h + _delta_mixer(cfg, lp, x, view,
                                 cfg.linear_layers.index(i))
    with jax.named_scope("qwen3next/norm"):
        f = zc_norm(h, lp["n2"], eps)
    lo = cfg.experts_held[0] if cfg.experts_held else 0
    ffn, counts = _moe.moe_feed_forward(
        f.reshape(-1, f.shape[-1]), lp["gate"], None, lp["w1"], lp["w3"],
        lp["w2"], top_k=cfg.num_experts_per_tok,
        norm_topk=cfg.norm_topk_prob, expert_lo=lo, scope="qwen3next",
        route="softmax")
    with jax.named_scope("qwen3next/shared_expert"):
        ffn = ffn.reshape(h.shape) + gated_shared_expert(f, lp)
    return h + ffn, counts


def qwen3next_hidden(cfg: Qwen3NextConfig, params, tokens, positions, view):
    """Final-norm hidden states ``[B, T, hidden]`` and the layers' ``counts``
    (a list, one ``[n]`` a layer). ``tokens`` index the held rows of the
    embedding."""
    h = params["tok"][tokens]
    all_counts = []
    for i, lp in enumerate(params["layers"]):
        h, counts = qwen3next_block(cfg, i, lp, h, positions, view)
        all_counts.append(counts)
    with jax.named_scope("qwen3next/norm"):
        return zc_norm(h, params["fnw"], cfg.rms_norm_eps), all_counts


def qwen3next_logits(cfg: Qwen3NextConfig, params, tokens):
    """Logits ``[B, T, held vocabulary]`` of whole sequences (no cache)."""
    positions = jnp.arange(tokens.shape[1], dtype=jnp.int32)[None]
    h, _ = qwen3next_hidden(cfg, params, tokens, positions, FullSequence())
    return h @ params["head"]


# -- the Layer graph -------------------------------------------------------------

class ZeroCentredRMSNorm(Layer):
    """``x / sqrt(mean(x^2) + eps) * (1 + weight)``; the weight starts at 0."""

    def __init__(self, size: int, epsilon: float):
        super().__init__()
        self.epsilon = float(epsilon)
        self.weight = self.create_parameter(
            [size], default_initializer=I.Constant(0.0))

    def forward(self, x):
        eps = self.epsilon
        return apply("zero_centred_rms_norm",
                     lambda a, w: zc_norm(a, w, eps), x, self.weight)


class GatedAttention(Layer):
    """The projections of the full-attention layers: a query projection
    twice as wide as the heads (a head's columns: query, then output gate)."""

    def __init__(self, c: Qwen3NextConfig):
        super().__init__()
        width = c.num_attention_heads * c.head_dim
        kv = c.num_key_value_heads * c.head_dim
        self.q_proj = _proj(c.hidden_size, 2 * width)
        self.k_proj = _proj(c.hidden_size, kv)
        self.v_proj = _proj(c.hidden_size, kv)
        self.o_proj = _proj(width, c.hidden_size)
        self.q_norm = ZeroCentredRMSNorm(c.head_dim, c.rms_norm_eps)
        self.k_norm = ZeroCentredRMSNorm(c.head_dim, c.rms_norm_eps)

    def param_tree(self, leaf):
        return {"qw": leaf(self.q_proj.weight), "kw": leaf(self.k_proj.weight),
                "vw": leaf(self.v_proj.weight), "ow": leaf(self.o_proj.weight),
                "qn": leaf(self.q_norm.weight), "kn": leaf(self.k_norm.weight)}


class GatedDeltaNet(Layer):
    """The parameters of a Gated-DeltaNet mixer. ``A_log`` and ``dt_bias``
    start in the public initialisation's ranges (``exp(A_log)`` up to 16,
    ``softplus(dt_bias)`` between 0.001 and 0.1)."""

    def __init__(self, c: Qwen3NextConfig):
        super().__init__()
        hv = c.linear_num_value_heads
        self.in_proj_qkvz = _proj(c.hidden_size,
                                  c.conv_width + c.value_width)
        self.in_proj_ba = _proj(c.hidden_size, 2 * hv)
        self.conv1d = Layer()       # depthwise kernel, [channels, K]
        self.conv1d.weight = self.create_parameter(
            [c.conv_width, c.linear_conv_kernel_dim],
            default_initializer=I.Normal(0.0, 0.3))
        self.A_log = self.create_parameter(
            [hv], default_initializer=I.Uniform(0.0, 2.77))
        self.dt_bias = self.create_parameter(
            [hv], default_initializer=I.Uniform(-6.9, -2.25))
        self.norm = RMSNorm(c.linear_value_head_dim, c.rms_norm_eps)
        self.out_proj = _proj(c.value_width, c.hidden_size)

    def param_tree(self, leaf):
        return {"qkvz": leaf(self.in_proj_qkvz.weight),
                "ba": leaf(self.in_proj_ba.weight),
                "conv": leaf(self.conv1d.weight), "alog": leaf(self.A_log),
                "dtb": leaf(self.dt_bias), "gn": leaf(self.norm.weight),
                "out": leaf(self.out_proj.weight)}


class Qwen3NextDecoderLayer(Layer):
    def __init__(self, c: Qwen3NextConfig, i: int):
        super().__init__()
        self.input_layernorm = ZeroCentredRMSNorm(c.hidden_size,
                                                  c.rms_norm_eps)
        if c.is_full(i):
            self.self_attn = GatedAttention(c)
        else:
            self.linear_attn = GatedDeltaNet(c)
        self.post_attention_layernorm = ZeroCentredRMSNorm(c.hidden_size,
                                                           c.rms_norm_eps)
        self.mlp = MoEFeedForward(
            c.hidden_size, c.moe_intermediate_size, c.num_experts,
            c.num_experts_per_tok, c.norm_topk_prob, held=c.experts_held,
            shared=(c.shared_expert_intermediate_size
                    // c.moe_intermediate_size),
            scope="qwen3next", route="softmax", shared_gate=True)

    def param_tree(self, raw: bool):
        """This layer's leaves under the short keys :func:`qwen3next_block`
        reads: raw arrays (``raw``) or the Parameters themselves."""
        leaf = functools.partial(_leaf, raw=raw)
        ff = self.mlp
        out = {"n1": leaf(self.input_layernorm.weight),
               "n2": leaf(self.post_attention_layernorm.weight),
               "gate": leaf(ff.gate.weight),
               "w1": leaf(ff.experts.w1), "w3": leaf(ff.experts.w3),
               "w2": leaf(ff.experts.w2),
               "s1": leaf(ff.shared_experts.w1),
               "s3": leaf(ff.shared_experts.w3),
               "s2": leaf(ff.shared_experts.w2),
               "sg": leaf(ff.shared_expert_gate.weight)}
        out.update((self.self_attn if hasattr(self, "self_attn")
                    else self.linear_attn).param_tree(leaf))
        return out


class Qwen3NextModel(Layer):
    def __init__(self, config: Qwen3NextConfig):
        super().__init__()
        self.embed_tokens = Embedding(
            config.vocab_held, config.hidden_size,
            weight_attr=ParamAttr(initializer=I.Normal(0.0, 0.02)))
        self.layers = LayerList([Qwen3NextDecoderLayer(config, i)
                                 for i in range(config.num_hidden_layers)])
        self.norm = ZeroCentredRMSNorm(config.hidden_size,
                                       config.rms_norm_eps)


class Qwen3NextForCausalLM(Layer):
    """``forward`` runs whole sequences with no cache; the serving engine
    reads :meth:`param_tree` and runs the same block through its caches. The
    output head is a matrix of its own, over the held vocabulary."""

    def __init__(self, config: Qwen3NextConfig):
        super().__init__()
        self.config = config
        self.model = Qwen3NextModel(config)
        self.lm_head = _proj(config.hidden_size, config.vocab_held)

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
        return apply("qwen3next_forward",
                     lambda params, ids: qwen3next_logits(cfg, params, ids),
                     self.param_tree(raw=False), input_ids)
