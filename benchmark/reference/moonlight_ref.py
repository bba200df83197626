"""Plain reference of the Moonlight (``model_type`` ``deepseek_v3``)
architecture: ``jax.numpy``, float32, matrix multiplications at precision
``highest``, no kernel, no cache, no chunks, no absorption, no batching. The
attention is multi-head LATENT attention in its EXPANDED order: every row's
latent is multiplied up into a key and a value for each of the heads, and a
whole score row stands under the causal mask; the expert layer loops over the
experts held. Rows are only *computed* in blocks (``lax.map`` over blocks of
query rows, of feed-forward rows) so that a 7,168-token row fits; and the
weights are asked for a layer at a time (``layer_weights(i)``). It imports
nothing of the program.

Follows the published configuration of moonshotai/Moonlight-16B-A3B layer by
layer (the equations are in ISSUE 38 and in ``PERF.md`` section 4). From the
``config.json``: every size, ``first_k_dense_replace``, ``moe_layer_freq`` 1,
``q_lora_rank`` null (the query is projected directly), ``rope_theta`` with no
scaling, ``scoring_func`` sigmoid, ``topk_method`` noaux_tc with ``n_group``
and ``topk_group`` 1 (the group-limited choice is then the plain one),
``norm_topk_prob``, ``routed_scaling_factor``, ``n_shared_experts``, the
untied head. ASSUMED, each stated in the configuration file under
``assumed`` with its source (the public modelling code of the model type and
the DeepSeek-V2 report, arXiv:2405.04434, neither of which could be read
here: there is no network):

- two RMSNorms a layer, each BEFORE its sublayer; nothing norms a result;
- an RMSNorm with a learned weight over the 512 of the latent
  (``kv_a_layernorm``), before it is cached and before it is multiplied up;
- ONE rotary key part of 64 for all the heads, rotated before it is cached;
- the softmax scale is ``1 / sqrt(qk_nope_head_dim + qk_rope_head_dim)``;
- ``e_score_correction_bias`` takes part in the CHOICE of the experts only;
- the renormalisation's epsilon is 1e-20;
- the shared experts are ONE SwiGLU of width ``n_shared_experts x
  moe_intermediate_size``, unweighted;
- the token embedding is not scaled.

Departures from the public implementation: the rotary columns. The published
weights rotate the pairs ``(2i, 2i + 1)`` of the 64 rotary columns; the
public code permutes those columns to halves and rotates halves. This
reference (and the program) rotates halves of the columns AS THEY STAND: with
seeded weights the two differ by a fixed permutation of the rotary columns of
``q_w`` and ``dkv_w``, which no dot product ``qr . r`` sees. None other known;
what the description itself may have wrong cannot be checked here.

**A chip's share.** An expert stack may hold a share ``[lo, lo + n)`` of the
experts (``arch.expert_lo``, the stack's length): routing is over all of them
and the absent experts' part of the sum is left out; the shared experts are
computed whole. The embedding and the head may be a slice of the vocabulary:
token ids are then indices into the slice.

**Near-ties and what they reach**: as ``trinity_ref.py`` tells it. The
forward carries, beside each position's smallest routing margin, its ``risk``
in [0, 1]: 1 from the expert layer on in which its own margin is under
``tau`` and one of the two experts at the edge is held here, and in every
attention layer at least the attention-weighted sum of the risks of the rows
a head reads, root mean square over the heads. It reads this reference alone.

``mode`` lowers the precision of every matrix multiplication, for the
controls that must come out as not correct: ``highest`` (the reference),
``high`` (three bf16 passes) and ``bfloat16`` (operands rounded to bf16).
"""
from __future__ import annotations

import functools
from typing import NamedTuple

import jax
import jax.numpy as jnp

MODES = ("highest", "high", "bfloat16")

#: query rows of a block of attention, rows of a block of feed-forward
Q_ROWS, FFN_ROWS = 512, 2048

#: what the configuration file must say under ``assumed`` (the tests hold the
#: file to this list)
ASSUMED = ("two pre-norms a layer", "kv_a_layernorm", "one rotary key part",
           "softmax scale", "rotary columns", "e_score_correction_bias",
           "route_eps", "shared experts", "embedding", "no biases",
           "weights layout")


def _lower(x, mode):
    if mode == "bfloat16":
        return x.astype(jnp.bfloat16).astype(jnp.float32)
    return x


def _ein(eq, a, b, mode):
    prec = jax.lax.Precision.HIGH if mode == "high" \
        else jax.lax.Precision.HIGHEST
    return jnp.einsum(eq, _lower(a, mode), _lower(b, mode), precision=prec)


class Arch(NamedTuple):
    """What the forward pass reads from a configuration (hashable, so a
    jitted function can take it as a static argument)."""
    num_layers: int
    n_head: int
    nope: int
    rope: int
    v_dim: int
    rank: int
    eps: float
    rope_theta: float
    num_dense: int
    top_k: int
    route_norm: bool
    route_scale: float
    route_eps: float
    expert_lo: int = 0


def arch_of(cfg: dict) -> Arch:
    """Of a configuration file: ``share.experts_held`` says where the held
    experts start among the router's outputs."""
    return Arch(cfg["num_hidden_layers"], cfg["num_attention_heads"],
                cfg["qk_nope_head_dim"],
                cfg["qk_rope_head_dim"], cfg["v_head_dim"],
                cfg["kv_lora_rank"], cfg["rms_norm_eps"],
                float(cfg["rope_theta"]), cfg["first_k_dense_replace"],
                cfg["num_experts_per_tok"], bool(cfg["norm_topk_prob"]),
                float(cfg["routed_scaling_factor"]),
                float(cfg["assumed"]["route_eps"]),
                int(cfg["share"]["experts_held"][0]))


def rms(x, w, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps) * w


def _row_blocks(fn, rows: int, *xs):
    """``fn`` over blocks of ``rows`` rows of ``xs`` (all ``[S, ...]``; a
    whole ``S`` that is no multiple of ``rows`` runs as one block)."""
    s = xs[0].shape[0]
    if s <= rows or s % rows:
        return fn(*xs)
    out = jax.lax.map(lambda b: fn(*b), tuple(
        x.reshape((s // rows, rows) + x.shape[1:]) for x in xs))
    return jax.tree_util.tree_map(
        lambda y: y.reshape((s,) + y.shape[2:]), out)


def _rope(x, theta):
    """Rotate-half RoPE over the last axis; ``x`` is ``[S, heads, D]`` at
    positions 0..S-1."""
    s, _, d = x.shape
    inv = theta ** (-jnp.arange(0, d, 2, dtype=jnp.float32) / d)
    ang = jnp.arange(s, dtype=jnp.float32)[:, None] * inv[None]
    cos = jnp.concatenate([jnp.cos(ang), jnp.cos(ang)], -1)[:, None]
    sin = jnp.concatenate([jnp.sin(ang), jnp.sin(ang)], -1)[:, None]
    rot = jnp.concatenate([-x[..., d // 2:], x[..., :d // 2]], -1)
    return x * cos + rot * sin


def attention(w, x, arch, mode, risk):
    """Multi-head latent attention on ``x`` ``[S, h]`` (the normed stream),
    EXPANDED: a key ``[kn | r]`` and a value for every row and head.
    ``(result, reached [S])``: ``reached`` is the attention-weighted sum of
    ``risk`` over the rows a head reads, root mean square over the heads."""
    s, hq = x.shape[0], arch.n_head
    q = _ein("sh,hk->sk", x, w["q_w"], mode).reshape(
        s, hq, arch.nope + arch.rope)
    qn, qr = q[..., :arch.nope], _rope(q[..., arch.nope:], arch.rope_theta)
    down = _ein("sh,hk->sk", x, w["dkv_w"], mode)
    c = rms(down[:, :arch.rank], w["kv_norm"], arch.eps)
    r = _rope(down[:, None, arch.rank:], arch.rope_theta)[:, 0]    # [S, rope]
    up = _ein("sc,ck->sk", c, w["ukv_w"], mode).reshape(
        s, hq, arch.nope + arch.v_dim)
    kn, v = up[..., :arch.nope], up[..., arch.nope:]
    col = jnp.arange(s)[None]
    scale = (arch.nope + arch.rope) ** -0.5

    def rows(qnb, qrb, at):
        scores = (_ein("qhd,rhd->hqr", qnb, kn, mode)
                  + _ein("qhd,rd->hqr", qrb, r, mode)) * scale
        probs = jax.nn.softmax(
            jnp.where(col <= at[:, None], scores, -jnp.inf), axis=-1)
        # a head that reads a row at risk alone moves its 1/H of the result
        # by all of it: the heads' root mean square, not their mean
        reach = jnp.einsum("hqr,r->qh", probs, risk)
        return (_ein("hqr,rhd->qhd", probs, v, mode),
                jnp.sqrt(jnp.mean(reach * reach, axis=1)))

    ctx, reached = _row_blocks(rows, Q_ROWS, qn, qr, jnp.arange(s))
    return _ein("sk,kh->sh", ctx.reshape(s, hq * arch.v_dim), w["o_w"],
                mode), reached


def swiglu(f, w1, w3, w2, mode):
    a = _ein("sh,hf->sf", f, w1, mode)
    return _ein("sf,fh->sh", jax.nn.silu(a) * _ein("sh,hf->sf", f, w3, mode),
                w2, mode)


def route(w, f, arch, mode):
    """``(weights [S, E], margin [S], touches [S])``: the weight of every
    expert at every position (zero where not chosen), the gap between the
    last chosen and the first rejected of ``s + expert_bias``, and whether
    one of those two experts is HELD here."""
    s = jax.nn.sigmoid(_ein("sh,he->se", f, w["router"], mode))
    biased = s + w["expert_bias"]
    order = jnp.argsort(-biased, axis=-1)
    edge = order[:, arch.top_k - 1:arch.top_k + 1]              # [S, 2]
    kth, nxt = (jnp.take_along_axis(biased, edge[:, i:i + 1], -1)[:, 0]
                for i in (0, 1))
    held = (edge >= arch.expert_lo) & (
        edge < arch.expert_lo + w["w1"].shape[0])
    wts = jnp.where(biased >= kth[:, None], s, 0.0)
    if arch.route_norm:
        wts = wts / (jnp.sum(wts, axis=-1, keepdims=True) + arch.route_eps)
    return wts * arch.route_scale, kth - nxt, jnp.any(held, axis=-1)


def feed_forward(w, f, arch, mode):
    """The expert sublayer on ``f`` ``[S, h]``: the shared experts (one
    SwiGLU), and the held experts one after another, each on every row and
    weighted by the routing (zero where it was not chosen). ``(ffn, margin,
    touches)``."""
    wts, margin, touches = route(w, f, arch, mode)
    out = swiglu(f, w["s1"][0], w["s3"][0], w["s2"][0], mode) \
        if "s1" in w else jnp.zeros_like(f)
    for e in range(w["w1"].shape[0]):
        out = out + wts[:, arch.expert_lo + e, None] * swiglu(
            f, w["w1"][e], w["w3"][e], w["w2"][e], mode)
    return out, margin, touches


@functools.partial(jax.jit, static_argnums=(2, 3))
def block(w, h, arch, mode, risk, tau):
    """One layer: ``(h', routing margin [S], risk [S])`` (the margin
    infinite in a dense layer). ``w`` holds the layer's leaves under their
    short names; a layer with a ``router`` is an expert layer."""
    op, reached = attention(w, rms(h, w["n1"], arch.eps), arch, mode, risk)
    h = h + op

    def ffn(hb):
        f = rms(hb, w["n2"], arch.eps)
        if "router" in w:
            out, margin, touches = feed_forward(w, f, arch, mode)
        else:
            out = swiglu(f, w["w1"], w["w3"], w["w2"], mode)
            margin = jnp.full(hb.shape[:1], jnp.inf)
            touches = jnp.zeros(hb.shape[:1], bool)
        return hb + out, margin, touches

    h, margin, touches = _row_blocks(ffn, FFN_ROWS, h)
    risk = jnp.maximum(jnp.maximum(risk, jnp.minimum(reached, 1.0)),
                       ((margin < tau) & touches).astype(risk.dtype))
    return h, margin, risk


def hidden_states(top, layer_weights, arch, tokens, mode="highest", tau=0.0):
    """Final-norm hidden states ``[S, h]`` of one token row ``[S]``, each
    position's smallest routing margin over the expert layers ``[S]``, and
    its ``risk`` ``[S]`` under ``tau`` (the module's docstring). ``top``
    holds ``embed`` and ``final_norm``; ``layer_weights(i)`` gives layer
    ``i``'s leaves."""
    h = top["embed"][tokens]
    margin = jnp.full(tokens.shape, jnp.inf)
    risk = jnp.zeros(tokens.shape, jnp.float32)
    tau = jnp.asarray(tau, jnp.float32)
    for i in range(arch.num_layers):
        h, m, risk = block(layer_weights(i), h, arch, mode, risk, tau)
        margin = jnp.minimum(margin, m)
    return rms(h, top["final_norm"], arch.eps), margin, risk


def logits_of(top, hidden, mode="highest"):
    return _ein("sh,hv->sv", hidden, top["head"], mode)
