"""Trinity (``model_type`` ``afmoe``, arcee-ai's Trinity-Mini family):
sliding-window and full attention layers mixed, sparse experts beside a
shared expert after the leading dense layers, an untied head.

The block is written ONCE, as a function of a parameter pytree and a *cache
view* (:func:`trinity_block`), as ``models/lfm2.py`` does. A view answers the
one question whose answer depends on where the sequence's past lives:

- ``view.attend(i, q, k, v, scale, window)``: causal attention of ``q`` for
  layer ``i`` over the keys and values so far, ``k``/``v`` included;
  ``window`` is the layer's ``sliding_window`` (a query at row ``t`` reads
  the rows ``t - window < j <= t``) or None in a full layer.

:class:`FullSequence` is the view with no past (whole sequences from
position 0); the paged views (``serving/llm/paged/trinity.py``) keep a window
layer's rows and a full layer's rows in page groups of their own. The
equations, per layer on the residual stream ``h`` (``rms`` with a learned
weight, no biases anywhere; (a) marks what ``config.json`` does not say and
the public modelling code of the model type does, see
``benchmark/configs/trinity-mini.json``):

    h0 = sqrt(hidden) * E[token]                           (mup_enabled; a)
    x = rms(h; n1)
    q, k, v = x Wq, x Wk, x Wv;  q, k = rms(q; qn), rms(k; kn) per head  (a)
    sliding layer: q, k = rope(q), rope(k);  full layer: no positions    (a)
    o = softmax(q k^T / sqrt(D), causal [and j > t - window];
                head j reads KV head j // g) v
    h = h + rms((sigmoid(x Wgate) * o) Wo; n2)                           (a)
    f = rms(h; n3)
    dense (i < num_dense_layers):  ffn = (silu(f W1) * (f W3)) W2
    experts:  s = sigmoid(f Wr);  chosen = top k of s + expert_bias      (a)
              w_e = route_scale * s_e / (sum of the chosen s + 1e-20)
              ffn = Shared(f) + sum over the chosen e of w_e Expert_e(f)
    h = h + rms(ffn; n4)                                                 (a)
    logits = rms(h; final) W_head

**A chip's share.** ``experts_held = (lo, n)``: this holder's experts of
each layer; routing is over all ``num_experts`` and the layer's result is
``Shared(f)`` + the held experts' part (what the absent ones would add is
left out, and that partial result goes on to the next layer). ``vocab_rows =
(lo, n)``: the rows of the embedding and the columns of the head held here;
token ids are then indices INTO the slice, and logits and sampling are over
the slice.

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
from .lfm2 import (SwiGLU, _leaf, _proj, grouped_causal_attention, rms_norm,
                   rope, swiglu)

SLIDING, FULL = "sliding_attention", "full_attention"


@dataclass(frozen=True)
class TrinityConfig:
    """Every key of the published ``config.json`` (hashable: it keys the
    compiled programs), then what the model type means beyond its keys,
    with the public modelling code's defaults, then the share held here."""
    vocab_size: int = 200192
    hidden_size: int = 2048
    intermediate_size: int = 6144
    moe_intermediate_size: int = 1024
    num_hidden_layers: int = 32
    layer_types: Tuple[str, ...] = ()
    global_attn_every_n_layers: int = 4
    sliding_window: int = 2048
    num_attention_heads: int = 32
    num_key_value_heads: int = 4
    head_dim: int = 128
    num_dense_layers: int = 2
    num_experts: int = 128
    num_experts_per_tok: int = 8
    num_shared_experts: int = 1
    num_expert_groups: int = 1
    num_limited_groups: int = 1
    n_group: int = 1
    topk_group: int = 1
    score_func: str = "sigmoid"
    route_norm: bool = True
    route_scale: float = 2.826
    load_balance_coeff: float = 0.001   # training only: not read
    use_grouped_mm: bool = True         # an implementation switch: not read
    mup_enabled: bool = True
    hidden_act: str = "silu"
    rms_norm_eps: float = 1e-5
    rope_theta: float = 10000.0
    rope_scaling: Optional[str] = None
    max_position_embeddings: int = 131072
    tie_word_embeddings: bool = False
    model_type: str = "afmoe"
    # -- not in the published config ----------------------------------------
    route_eps: float = 1e-20
    # -- the share held here -------------------------------------------------
    experts_held: Optional[Tuple[int, int]] = None
    vocab_rows: Optional[Tuple[int, int]] = None

    def __post_init__(self):
        object.__setattr__(self, "layer_types", tuple(self.layer_types))
        for name in ("experts_held", "vocab_rows"):
            if getattr(self, name) is not None:
                object.__setattr__(self, name, tuple(getattr(self, name)))
        if len(self.layer_types) != self.num_hidden_layers:
            raise ValueError(
                f"{len(self.layer_types)} layer_types for "
                f"{self.num_hidden_layers} layers")
        bad = set(self.layer_types) - {SLIDING, FULL}
        if bad:
            raise ValueError(f"unknown layer types {sorted(bad)}")
        for name, wanted in (
                ("num_expert_groups", 1), ("num_limited_groups", 1),
                ("n_group", 1), ("topk_group", 1), ("rope_scaling", None),
                ("score_func", "sigmoid"), ("hidden_act", "silu"),
                ("tie_word_embeddings", False)):
            if getattr(self, name) != wanted:
                raise NotImplementedError(
                    f"{name}={getattr(self, name)!r} is not implemented "
                    f"(this family runs {name}={wanted!r})")
        if self.num_attention_heads % self.num_key_value_heads:
            raise ValueError("query heads must be a multiple of KV heads")
        if self.sliding_window < 1:
            raise ValueError(f"sliding_window {self.sliding_window} < 1")
        lo, n = self.vocab_rows or (0, self.vocab_size)
        if not (0 <= lo and n >= 1 and lo + n <= self.vocab_size):
            raise ValueError(
                f"vocab_rows {self.vocab_rows} outside 0..{self.vocab_size}")

    @property
    def vocab_held(self) -> int:
        """Rows of the embedding (columns of the head) held here."""
        return self.vocab_rows[1] if self.vocab_rows else self.vocab_size

    @property
    def window_layers(self) -> Tuple[int, ...]:
        return tuple(i for i, t in enumerate(self.layer_types)
                     if t == SLIDING)

    @property
    def full_layers(self) -> Tuple[int, ...]:
        return tuple(i for i, t in enumerate(self.layer_types) if t == FULL)

    def window_of(self, i: int) -> Optional[int]:
        """Layer ``i``'s window, None where it attends to everything."""
        return self.sliding_window if self.layer_types[i] == SLIDING else None

    @property
    def num_expert_layers(self) -> int:
        return max(self.num_hidden_layers - self.num_dense_layers, 0)

    @property
    def embed_scale(self) -> float:
        return self.hidden_size ** 0.5 if self.mup_enabled else 1.0


# -- the arithmetic (raw arrays; shared by forward, chunk and decode) ---------

class FullSequence:
    """The view with no past: whole sequences from position 0. Records each
    layer's ``(k, v)``, what a cache would have to keep."""

    def __init__(self):
        self.kv = []

    def attend(self, i, q, k, v, scale, window):
        self.kv.append((k, v))
        return grouped_causal_attention(q, k, v, scale, window)


def _attention(cfg: TrinityConfig, i: int, lp, x, positions, view):
    bsz, t, _ = x.shape
    d = cfg.head_dim
    q = (x @ lp["qw"]).reshape(bsz, t, cfg.num_attention_heads, d)
    k = (x @ lp["kw"]).reshape(bsz, t, cfg.num_key_value_heads, d)
    v = (x @ lp["vw"]).reshape(bsz, t, cfg.num_key_value_heads, d)
    q = rms_norm(q, lp["qn"], cfg.rms_norm_eps)
    k = rms_norm(k, lp["kn"], cfg.rms_norm_eps)
    window = cfg.window_of(i)
    if window is not None:      # a full layer has no positions
        q = rope(q, positions, cfg.rope_theta)
        k = rope(k, positions, cfg.rope_theta)
    out = view.attend(i, q, k, v, d ** -0.5, window)
    return (jax.nn.sigmoid(x @ lp["gw"]) * out.reshape(bsz, t, -1)) @ lp["ow"]


def trinity_block(cfg: TrinityConfig, i: int, lp, h, positions, view):
    """Layer ``i`` on ``h`` ``[B, T, hidden]`` at ``positions`` ``[B, T]``:
    ``(h', counts)`` with ``counts`` the pairs each held expert received
    (None in a dense layer)."""
    eps = cfg.rms_norm_eps
    kind = "window" if cfg.layer_types[i] == SLIDING else "full"
    with jax.named_scope(f"trinity/attn_{kind}"):
        op = _attention(cfg, i, lp, rms_norm(h, lp["n1"], eps), positions,
                        view)
        h = h + rms_norm(op, lp["n2"], eps)
    f = rms_norm(h, lp["n3"], eps)
    if i < cfg.num_dense_layers:
        with jax.named_scope("trinity/ffn"):
            ffn = swiglu(f, lp["w1"], lp["w3"], lp["w2"])
        return h + rms_norm(ffn, lp["n4"], eps), None
    lo = cfg.experts_held[0] if cfg.experts_held else 0
    ffn, counts = _moe.moe_feed_forward(
        f.reshape(-1, f.shape[-1]), lp["gate"], lp["bias"], lp["w1"],
        lp["w3"], lp["w2"], top_k=cfg.num_experts_per_tok,
        norm_topk=cfg.route_norm, scale=cfg.route_scale, expert_lo=lo,
        eps=cfg.route_eps, scope="trinity")
    ffn = ffn.reshape(h.shape)
    if cfg.num_shared_experts:
        with jax.named_scope("trinity/shared_expert"):
            ffn = ffn + swiglu(f, lp["s1"][0], lp["s3"][0], lp["s2"][0])
    return h + rms_norm(ffn, lp["n4"], eps), counts


def trinity_hidden(cfg: TrinityConfig, params, tokens, positions, view):
    """Final-norm hidden states ``[B, T, hidden]`` and the expert layers'
    ``counts`` (a list, one ``[n]`` per expert layer). ``tokens`` index the
    held rows of the embedding."""
    h = cfg.embed_scale * params["tok"][tokens]
    all_counts = []
    for i, lp in enumerate(params["layers"]):
        h, counts = trinity_block(cfg, i, lp, h, positions, view)
        if counts is not None:
            all_counts.append(counts)
    return rms_norm(h, params["fnw"], cfg.rms_norm_eps), all_counts


def trinity_logits(cfg: TrinityConfig, params, tokens):
    """Logits ``[B, T, held vocabulary]`` of whole sequences (no cache)."""
    positions = jnp.arange(tokens.shape[1], dtype=jnp.int32)[None]
    h, _ = trinity_hidden(cfg, params, tokens, positions, FullSequence())
    return h @ params["head"]


# -- the Layer graph -------------------------------------------------------------

class GatedAttention(Layer):
    def __init__(self, c: TrinityConfig):
        super().__init__()
        width = c.num_attention_heads * c.head_dim
        kv = c.num_key_value_heads * c.head_dim
        self.q_proj = _proj(c.hidden_size, width)
        self.k_proj = _proj(c.hidden_size, kv)
        self.v_proj = _proj(c.hidden_size, kv)
        self.gate_proj = _proj(c.hidden_size, width)
        self.o_proj = _proj(width, c.hidden_size)
        self.q_norm = RMSNorm(c.head_dim, c.rms_norm_eps)
        self.k_norm = RMSNorm(c.head_dim, c.rms_norm_eps)


class TrinityDecoderLayer(Layer):
    def __init__(self, c: TrinityConfig, i: int):
        super().__init__()
        self.input_layernorm = RMSNorm(c.hidden_size, c.rms_norm_eps)
        self.self_attn = GatedAttention(c)
        self.post_attention_layernorm = RMSNorm(c.hidden_size, c.rms_norm_eps)
        self.pre_mlp_layernorm = RMSNorm(c.hidden_size, c.rms_norm_eps)
        if i < c.num_dense_layers:
            self.mlp = SwiGLU(c.hidden_size, c.intermediate_size)
        else:
            self.mlp = MoEFeedForward(
                c.hidden_size, c.moe_intermediate_size, c.num_experts,
                c.num_experts_per_tok, c.route_norm, c.route_scale,
                held=c.experts_held, shared=c.num_shared_experts,
                eps=c.route_eps, scope="trinity")
        self.post_mlp_layernorm = RMSNorm(c.hidden_size, c.rms_norm_eps)

    def param_tree(self, raw: bool):
        """This layer's leaves under the short keys :func:`trinity_block`
        reads: raw arrays (``raw``) or the Parameters themselves."""
        leaf = functools.partial(_leaf, raw=raw)
        a, ff = self.self_attn, self.mlp
        out = {"n1": leaf(self.input_layernorm.weight),
               "n2": leaf(self.post_attention_layernorm.weight),
               "n3": leaf(self.pre_mlp_layernorm.weight),
               "n4": leaf(self.post_mlp_layernorm.weight),
               "qw": leaf(a.q_proj.weight), "kw": leaf(a.k_proj.weight),
               "vw": leaf(a.v_proj.weight), "gw": leaf(a.gate_proj.weight),
               "ow": leaf(a.o_proj.weight), "qn": leaf(a.q_norm.weight),
               "kn": leaf(a.k_norm.weight)}
        if isinstance(ff, SwiGLU):
            out.update({"w1": leaf(ff.w1.weight), "w3": leaf(ff.w3.weight),
                        "w2": leaf(ff.w2.weight)})
            return out
        out.update({"gate": leaf(ff.gate.weight),
                    "bias": leaf(ff.expert_bias),
                    "w1": leaf(ff.experts.w1), "w3": leaf(ff.experts.w3),
                    "w2": leaf(ff.experts.w2)})
        if hasattr(ff, "shared_experts"):
            sh = ff.shared_experts
            out.update({"s1": leaf(sh.w1), "s3": leaf(sh.w3),
                        "s2": leaf(sh.w2)})
        return out


class TrinityModel(Layer):
    def __init__(self, config: TrinityConfig):
        super().__init__()
        self.embed_tokens = Embedding(
            config.vocab_held, config.hidden_size,
            weight_attr=ParamAttr(initializer=I.Normal(0.0, 0.02)))
        self.layers = LayerList([TrinityDecoderLayer(config, i)
                                 for i in range(config.num_hidden_layers)])
        self.norm = RMSNorm(config.hidden_size, config.rms_norm_eps)


class TrinityForCausalLM(Layer):
    """``forward`` runs whole sequences with no cache; the serving engine
    reads :meth:`param_tree` and runs the same block through its caches.
    The output head is a matrix of its own, over the held vocabulary."""

    def __init__(self, config: TrinityConfig):
        super().__init__()
        self.config = config
        self.model = TrinityModel(config)
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
        return apply("trinity_forward",
                     lambda params, ids: trinity_logits(cfg, params, ids),
                     self.param_tree(raw=False), input_ids)
