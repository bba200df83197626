"""Moonlight (``model_type`` ``deepseek_v3``, moonshotai's Moonlight-16B-A3B
family): multi-head LATENT attention, a leading dense layer, then sparse
experts beside shared experts, an untied head.

The block is written ONCE, as a function of a parameter pytree and a *cache
view* (:func:`moonlight_block`), as ``models/lfm2.py`` and
``models/trinity.py`` do. What a token leaves behind for later queries is
ONE row a layer for all the heads, ``[c_t | r_t]``: a latent of
``kv_lora_rank`` numbers (after its norm) and one rotary key part of
``qk_rope_head_dim`` (after its positions). A view answers the one question
whose answer depends on where those rows live, and in which ORDER the
products are best made there:

- ``view.attend(i, qn, qr, c, r, w_ukv, scale)``: causal attention of layer
  ``i``'s queries (``qn`` ``[B, T, H, nope]``, ``qr`` ``[B, T, H, rope]``,
  rotated) over the rows so far, ``c`` ``[B, T, rank]`` and ``r`` ``[B, T,
  rope]`` included; ``w_ukv`` ``[rank, H * (nope + v)]`` holds, a head,
  ``[W_UK | W_UV]``. Returns ``[B, T, H, v]``.

:class:`FullSequence` is the view with no past (whole sequences from position
0) and EXPANDS: per-head keys ``c W_UK`` and values ``c W_UV`` for every
row, as a 16-head model attends. The paged views
(``serving/llm/paged/moonlight.py``) keep the rows in pages; the decode view
ABSORBS the up-projections into the query and the result and attends over
the latent rows themselves. The equations, per layer on the residual stream
``h`` (``rms`` with a learned weight, no biases anywhere; (a) marks what
``config.json`` does not say and the public modelling code of the model type
does, see ``benchmark/configs/moonlight-16b-a3b.json``):

    h0 = E[token]
    x = rms(h; n1)                                          (two pre-norms; a)
    q = x Wq, per head [qn (nope) | qr (rope)];  qr = rope(qr)
    [c' | r'] = x Wdkv;  c = rms(c'; kvn) (a);  r = rope(r')  one for all heads
    expanded:  kn = c W_UK,  v = c W_UV                          per head
               s = (qn . kn + qr . r) / sqrt(nope + rope)                (a)
               o = softmax(s, causal) v
    absorbed:  q~ = qn W_UK^T;  s = (q~ . c + qr . r) / sqrt(nope + rope)
               o = (softmax(s, causal) c) W_UV         the same numbers
    h = h + concat(o) Wo
    f = rms(h; n2)
    dense (i < first_k_dense_replace):  ffn = (silu(f Wg) * (f Wu)) Wd
    experts:  s = sigmoid(f Wr);  chosen = top k of s + e_score_correction_bias
              w_e = routed_scaling_factor * s_e / (sum of the chosen s + 1e-20)
              ffn = Shared(f) + sum over the chosen e of w_e Expert_e(f)
    h = h + ffn
    logits = rms(h; final) W_head

Rotary positions: the published weights rotate the pairs ``(2i, 2i + 1)`` of
the 64 rotary columns; the public code permutes those columns to halves and
rotates halves, which on ``qr`` and ``r`` alike gives the same dot products.
This module rotates halves (``models.lfm2.rope``): a checkpoint's rotary
columns of ``Wq`` and ``Wdkv`` are permuted so when it is loaded.

**A chip's share.** ``experts_held = (lo, n)`` and ``vocab_rows = (lo, n)``
as ``models/trinity.py`` has them: routing is over all ``n_routed_experts``
and the layer's result is ``Shared(f)`` + the held experts' part (what the
absent ones would add is left out, and that partial result goes on to the
next layer); token ids index the held rows of the embedding and logits are
over the held columns of the head.

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
from .lfm2 import SwiGLU, _leaf, _proj, rms_norm, rope, swiglu

_NEG = -1e30


@dataclass(frozen=True)
class MoonlightConfig:
    """Every key of the published ``config.json`` (hashable: it keys the
    compiled programs), then what the model type means beyond its keys,
    then the share held here."""
    vocab_size: int = 163840
    hidden_size: int = 2048
    intermediate_size: int = 11264
    moe_intermediate_size: int = 1408
    num_hidden_layers: int = 27
    num_attention_heads: int = 16
    num_key_value_heads: int = 16
    kv_lora_rank: int = 512
    q_lora_rank: Optional[int] = None
    qk_nope_head_dim: int = 128
    qk_rope_head_dim: int = 64
    v_head_dim: int = 128
    first_k_dense_replace: int = 1
    moe_layer_freq: int = 1
    n_routed_experts: int = 64
    n_shared_experts: int = 2
    num_experts_per_tok: int = 6
    n_group: int = 1
    topk_group: int = 1
    topk_method: str = "noaux_tc"
    scoring_func: str = "sigmoid"
    norm_topk_prob: bool = True
    routed_scaling_factor: float = 2.446
    seq_aux: bool = True                # training only: not read
    ep_size: int = 1                    # an implementation switch: not read
    num_nextn_predict_layers: int = 0
    attention_bias: bool = False
    hidden_act: str = "silu"
    rms_norm_eps: float = 1e-5
    rope_theta: float = 50000.0
    rope_scaling: Optional[str] = None
    max_position_embeddings: int = 8192
    tie_word_embeddings: bool = False
    model_type: str = "deepseek_v3"
    # -- not in the published config ----------------------------------------
    route_eps: float = 1e-20
    # -- the share held here -------------------------------------------------
    experts_held: Optional[Tuple[int, int]] = None
    vocab_rows: Optional[Tuple[int, int]] = None

    def __post_init__(self):
        for name in ("experts_held", "vocab_rows"):
            if getattr(self, name) is not None:
                object.__setattr__(self, name, tuple(getattr(self, name)))
        for name, wanted in (
                ("q_lora_rank", None), ("n_group", 1), ("topk_group", 1),
                ("rope_scaling", None), ("scoring_func", "sigmoid"),
                ("topk_method", "noaux_tc"), ("moe_layer_freq", 1),
                ("num_nextn_predict_layers", 0), ("attention_bias", False),
                ("hidden_act", "silu"), ("tie_word_embeddings", False)):
            if getattr(self, name) != wanted:
                raise NotImplementedError(
                    f"{name}={getattr(self, name)!r} is not implemented "
                    f"(this family runs {name}={wanted!r})")
        if self.num_key_value_heads != self.num_attention_heads:
            raise ValueError(
                "latent attention expands a key and a value for every "
                "query head: num_key_value_heads must equal "
                "num_attention_heads")
        lo, n = self.vocab_rows or (0, self.vocab_size)
        if not (0 <= lo and n >= 1 and lo + n <= self.vocab_size):
            raise ValueError(
                f"vocab_rows {self.vocab_rows} outside 0..{self.vocab_size}")

    @property
    def vocab_held(self) -> int:
        """Rows of the embedding (columns of the head) held here."""
        return self.vocab_rows[1] if self.vocab_rows else self.vocab_size

    @property
    def num_expert_layers(self) -> int:
        return max(self.num_hidden_layers - self.first_k_dense_replace, 0)

    @property
    def qk_head_dim(self) -> int:
        return self.qk_nope_head_dim + self.qk_rope_head_dim

    @property
    def softmax_scale(self) -> float:
        """Over the whole query head, rotary part included (no rope scaling,
        so no further factor)."""
        return self.qk_head_dim ** -0.5

    @property
    def latent_row(self) -> int:
        """Numbers a token leaves behind a layer: ``[c | r]``."""
        return self.kv_lora_rank + self.qk_rope_head_dim


# -- the arithmetic (raw arrays; shared by forward, chunk and decode) ---------

def split_ukv(cfg: MoonlightConfig, w_ukv):
    """``(W_UK [rank, H, nope], W_UV [rank, H, v])`` of ``w_ukv`` ``[rank,
    H * (nope + v)]``."""
    w = w_ukv.reshape(cfg.kv_lora_rank, cfg.num_attention_heads,
                      cfg.qk_nope_head_dim + cfg.v_head_dim)
    return w[..., :cfg.qk_nope_head_dim], w[..., cfg.qk_nope_head_dim:]


def expanded_causal_attention(cfg, qn, qr, c, r, w_ukv, scale):
    """The expanded order within whole sequences: per-head keys and values
    of every row, a whole score row under the causal mask."""
    wuk, wuv = split_ukv(cfg, w_ukv)
    kn = jnp.einsum("bkc,chd->bkhd", c, wuk)
    v = jnp.einsum("bkc,chd->bkhd", c, wuv)
    scores = (jnp.einsum("bqhd,bkhd->bhqk", qn, kn)
              + jnp.einsum("bqhd,bkd->bhqk", qr, r)) * scale
    t = qn.shape[1]
    seen = jnp.arange(t)[None, :] <= jnp.arange(t)[:, None]
    probs = jax.nn.softmax(jnp.where(seen, scores, _NEG), axis=-1)
    return jnp.einsum("bhqk,bkhd->bqhd", probs, v)


class FullSequence:
    """The view with no past: whole sequences from position 0, the expanded
    order. Records each layer's ``(c, r)``, what a cache would have to
    keep."""

    def __init__(self, cfg: MoonlightConfig):
        self.cfg, self.rows = cfg, []

    def attend(self, i, qn, qr, c, r, w_ukv, scale):
        self.rows.append((c, r))
        with jax.named_scope("expand"):
            return expanded_causal_attention(self.cfg, qn, qr, c, r, w_ukv,
                                             scale)


def _attention(cfg: MoonlightConfig, i: int, lp, x, positions, view):
    bsz, t, _ = x.shape
    heads, rank = cfg.num_attention_heads, cfg.kv_lora_rank
    with jax.named_scope("q"):
        q = (x @ lp["qw"]).reshape(bsz, t, heads, cfg.qk_head_dim)
        qn = q[..., :cfg.qk_nope_head_dim]
        qr = rope(q[..., cfg.qk_nope_head_dim:], positions, cfg.rope_theta)
    with jax.named_scope("latent"):
        down = x @ lp["dkv"]
        c = rms_norm(down[..., :rank], lp["kvn"], cfg.rms_norm_eps)
        r = rope(down[..., None, rank:], positions, cfg.rope_theta)[..., 0, :]
    out = view.attend(i, qn, qr, c, r, lp["ukv"], cfg.softmax_scale)
    with jax.named_scope("out"):
        return out.reshape(bsz, t, -1) @ lp["ow"]


def moonlight_block(cfg: MoonlightConfig, i: int, lp, h, positions, view):
    """Layer ``i`` on ``h`` ``[B, T, hidden]`` at ``positions`` ``[B, T]``:
    ``(h', counts)`` with ``counts`` the pairs each held expert received
    (None in a dense layer)."""
    eps = cfg.rms_norm_eps
    with jax.named_scope("moonlight/norm"):
        x = rms_norm(h, lp["n1"], eps)
    with jax.named_scope("moonlight/mla"):
        h = h + _attention(cfg, i, lp, x, positions, view)
    with jax.named_scope("moonlight/norm"):
        f = rms_norm(h, lp["n2"], eps)
    if i < cfg.first_k_dense_replace:
        with jax.named_scope("moonlight/ffn"):
            return h + swiglu(f, lp["w1"], lp["w3"], lp["w2"]), None
    lo = cfg.experts_held[0] if cfg.experts_held else 0
    ffn, counts = _moe.moe_feed_forward(
        f.reshape(-1, f.shape[-1]), lp["gate"], lp["bias"], lp["w1"],
        lp["w3"], lp["w2"], top_k=cfg.num_experts_per_tok,
        norm_topk=cfg.norm_topk_prob, scale=cfg.routed_scaling_factor,
        expert_lo=lo, eps=cfg.route_eps, scope="moonlight")
    ffn = ffn.reshape(h.shape)
    if cfg.n_shared_experts:
        with jax.named_scope("moonlight/shared_expert"):
            ffn = ffn + swiglu(f, lp["s1"][0], lp["s3"][0], lp["s2"][0])
    return h + ffn, counts


def moonlight_hidden(cfg: MoonlightConfig, params, tokens, positions, view):
    """Final-norm hidden states ``[B, T, hidden]`` and the expert layers'
    ``counts`` (a list, one ``[n]`` per expert layer). ``tokens`` index the
    held rows of the embedding."""
    h = params["tok"][tokens]
    all_counts = []
    for i, lp in enumerate(params["layers"]):
        h, counts = moonlight_block(cfg, i, lp, h, positions, view)
        if counts is not None:
            all_counts.append(counts)
    with jax.named_scope("moonlight/norm"):
        return rms_norm(h, params["fnw"], cfg.rms_norm_eps), all_counts


def moonlight_logits(cfg: MoonlightConfig, params, tokens):
    """Logits ``[B, T, held vocabulary]`` of whole sequences (no cache)."""
    positions = jnp.arange(tokens.shape[1], dtype=jnp.int32)[None]
    h, _ = moonlight_hidden(cfg, params, tokens, positions,
                            FullSequence(cfg))
    return h @ params["head"]


# -- the Layer graph -------------------------------------------------------------

class LatentAttention(Layer):
    """The projections of multi-head latent attention with the query
    projected directly (``q_lora_rank`` null)."""

    def __init__(self, c: MoonlightConfig):
        super().__init__()
        heads = c.num_attention_heads
        self.q_proj = _proj(c.hidden_size, heads * c.qk_head_dim)
        self.kv_a_proj_with_mqa = _proj(c.hidden_size, c.latent_row)
        self.kv_a_layernorm = RMSNorm(c.kv_lora_rank, c.rms_norm_eps)
        self.kv_b_proj = _proj(
            c.kv_lora_rank, heads * (c.qk_nope_head_dim + c.v_head_dim))
        self.o_proj = _proj(heads * c.v_head_dim, c.hidden_size)


class MoonlightDecoderLayer(Layer):
    def __init__(self, c: MoonlightConfig, i: int):
        super().__init__()
        self.input_layernorm = RMSNorm(c.hidden_size, c.rms_norm_eps)
        self.self_attn = LatentAttention(c)
        self.post_attention_layernorm = RMSNorm(c.hidden_size, c.rms_norm_eps)
        if i < c.first_k_dense_replace:
            self.mlp = SwiGLU(c.hidden_size, c.intermediate_size)
        else:
            self.mlp = MoEFeedForward(
                c.hidden_size, c.moe_intermediate_size, c.n_routed_experts,
                c.num_experts_per_tok, c.norm_topk_prob,
                c.routed_scaling_factor, held=c.experts_held,
                shared=c.n_shared_experts, eps=c.route_eps,
                scope="moonlight")

    def param_tree(self, raw: bool):
        """This layer's leaves under the short keys :func:`moonlight_block`
        reads: raw arrays (``raw``) or the Parameters themselves."""
        leaf = functools.partial(_leaf, raw=raw)
        a, ff = self.self_attn, self.mlp
        out = {"n1": leaf(self.input_layernorm.weight),
               "n2": leaf(self.post_attention_layernorm.weight),
               "qw": leaf(a.q_proj.weight),
               "dkv": leaf(a.kv_a_proj_with_mqa.weight),
               "kvn": leaf(a.kv_a_layernorm.weight),
               "ukv": leaf(a.kv_b_proj.weight), "ow": leaf(a.o_proj.weight)}
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


class MoonlightModel(Layer):
    def __init__(self, config: MoonlightConfig):
        super().__init__()
        self.embed_tokens = Embedding(
            config.vocab_held, config.hidden_size,
            weight_attr=ParamAttr(initializer=I.Normal(0.0, 0.02)))
        self.layers = LayerList([MoonlightDecoderLayer(config, i)
                                 for i in range(config.num_hidden_layers)])
        self.norm = RMSNorm(config.hidden_size, config.rms_norm_eps)


class MoonlightForCausalLM(Layer):
    """``forward`` runs whole sequences with no cache (the expanded order);
    the serving engine reads :meth:`param_tree` and runs the same block
    through its caches. The output head is a matrix of its own, over the held
    vocabulary."""

    def __init__(self, config: MoonlightConfig):
        super().__init__()
        self.config = config
        self.model = MoonlightModel(config)
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
        return apply("moonlight_forward",
                     lambda params, ids: moonlight_logits(cfg, params, ids),
                     self.param_tree(raw=False), input_ids)
