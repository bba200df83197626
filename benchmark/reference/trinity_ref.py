"""Plain reference of the Trinity (``model_type`` ``afmoe``) architecture:
``jax.numpy``, float32, matrix multiplications at precision ``highest``, no
kernel, no cache, no chunks, no page groups, no batching: the window of a
sliding layer is a mask on a whole score row, and the expert layer loops over
the experts held. Rows are only *computed* in blocks (``lax.map`` over blocks
of query rows, of feed-forward rows) so that a 17,408-token row fits; and the
weights are asked for a layer at a time (``layer_weights(i)``). It imports
nothing of the program.

Follows the published configuration of arcee-ai/Trinity-Mini layer by layer
(the equations are in ISSUE 32 and in ``PERF.md`` section 4). From the
``config.json``: every size, ``layer_types``, ``sliding_window``,
``num_dense_layers``, ``rope_theta`` with no scaling, ``score_func``
sigmoid, ``route_norm``, ``route_scale``, ``num_shared_experts``,
``mup_enabled``, the untied head. ASSUMED, each stated in the configuration
file under ``assumed`` with its source (the public modelling code of the
model type, which could not be read here: there is no network):

- the embedding is scaled by ``sqrt(hidden_size)`` (``mup_enabled``);
- four RMSNorms a layer, one before and one after each sublayer, the later
  one on the sublayer's RESULT before it is added;
- an RMSNorm with a learned weight over each q and k head;
- rotate-half rotary positions in ``sliding_attention`` layers only; a
  ``full_attention`` layer has no positions;
- a sliding layer's query at row ``t`` reads rows ``t - window < j <= t``;
- the output gate ``sigmoid(x Wgate)`` over all ``Hq * D`` channels;
- ``expert_bias`` takes part in the CHOICE of the experts only;
- the renormalisation's epsilon is 1e-20 (``route_eps``);
- the shared expert is unweighted.

Departures: none known from that description; what the description itself
may have wrong cannot be checked here.

**A chip's share.** An expert stack may hold a share ``[lo, lo + n)`` of the
experts (``arch.expert_lo``, the stack's length): routing is over all of them
and the absent experts' part of the sum is left out; the shared expert is
computed whole. The embedding and the head may be a slice of the vocabulary:
token ids are then indices into the slice.

**Near-ties and what they reach.** Top-k routing is discontinuous: where the
last chosen and the first rejected of ``s + expert_bias`` lie within rounding
a program may choose another expert than this reference, and with few experts
held and the expert layer's result normed that moves the position's hidden
state by a large part of itself. Every later position that ATTENDS to the row
inherits some of it. The forward therefore carries, beside the smallest
routing margin of each position, a ``risk`` in [0, 1] a position: 1 from the
expert layer on in which its own margin is under ``tau`` and one of the two
experts at the edge is held here (a flip between two absent experts changes
nothing this holder computes), and in every
attention layer at least the attention-weighted sum of the risks of the rows a
head reads, as they stand when the layer is reached, gathered over the heads
by their root mean square (one head that reads a row at risk alone moves its
share of the result by all of it). It
says how much of a position's state may rest on a choice that rounding
decides; it reads this reference alone.

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
SLIDING = "sliding_attention"

#: query rows of a block of attention, rows of a block of feed-forward
Q_ROWS, FFN_ROWS = 512, 2048


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
    layer_types: tuple
    n_head: int
    n_kv_head: int
    head_dim: int
    window: int
    eps: float
    rope_theta: float
    embed_scale: float
    num_dense: int
    top_k: int
    route_norm: bool
    route_scale: float
    route_eps: float
    expert_lo: int = 0


def arch_of(cfg: dict) -> Arch:
    """Of a configuration file: ``share.experts_held`` says where the held
    experts start among the router's outputs."""
    return Arch(tuple(cfg["layer_types"][:cfg["num_hidden_layers"]]),
                cfg["num_attention_heads"], cfg["num_key_value_heads"],
                cfg["head_dim"], cfg["sliding_window"], cfg["rms_norm_eps"],
                float(cfg["rope_theta"]),
                cfg["hidden_size"] ** 0.5 if cfg["mup_enabled"] else 1.0,
                cfg["num_dense_layers"], cfg["num_experts_per_tok"],
                bool(cfg["route_norm"]), float(cfg["route_scale"]),
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
    """Rotate-half RoPE over the whole head; ``x`` is ``[S, heads, D]`` at
    positions 0..S-1."""
    s, _, d = x.shape
    inv = theta ** (-jnp.arange(0, d, 2, dtype=jnp.float32) / d)
    ang = jnp.arange(s, dtype=jnp.float32)[:, None] * inv[None]
    cos = jnp.concatenate([jnp.cos(ang), jnp.cos(ang)], -1)[:, None]
    sin = jnp.concatenate([jnp.sin(ang), jnp.sin(ang)], -1)[:, None]
    rot = jnp.concatenate([-x[..., d // 2:], x[..., :d // 2]], -1)
    return x * cos + rot * sin


def attention(w, x, sliding: bool, arch, mode, risk):
    """The gated attention sublayer on ``x`` ``[S, h]`` (the normed stream):
    a sliding layer rotates q and k and masks the rows behind its window.
    ``(result, reached [S])``: ``reached`` is the attention-weighted sum of
    ``risk`` over the rows a head reads, root mean square over the heads."""
    s = x.shape[0]
    hq, hkv, d = arch.n_head, arch.n_kv_head, arch.head_dim
    q = _ein("sh,hk->sk", x, w["q_w"], mode).reshape(s, hq, d)
    k = _ein("sh,hk->sk", x, w["k_w"], mode).reshape(s, hkv, d)
    v = _ein("sh,hk->sk", x, w["v_w"], mode).reshape(s, hkv, d)
    q, k = rms(q, w["q_norm"], arch.eps), rms(k, w["k_norm"], arch.eps)
    if sliding:
        q, k = _rope(q, arch.rope_theta), _rope(k, arch.rope_theta)
    qg = q.reshape(s, hkv, hq // hkv, d)       # query head j reads j // g
    col = jnp.arange(s)[None]

    def rows(qb, at):
        scores = _ein("qkgd,rkd->kgqr", qb, k, mode) * d ** -0.5
        seen = col <= at[:, None]
        if sliding:
            seen &= col > at[:, None] - arch.window
        probs = jax.nn.softmax(jnp.where(seen, scores, -jnp.inf), axis=-1)
        # what a head reads of the rows at risk; a head that reads one such
        # row alone moves its 1/Hq of the result by all of it, so the heads
        # are gathered by their root mean square, not their mean
        reach = jnp.einsum("kgqr,r->qkg", probs, risk)
        return (_ein("kgqr,rkd->qkgd", probs, v, mode),
                jnp.sqrt(jnp.mean(reach * reach, axis=(1, 2))))

    ctx, reached = _row_blocks(rows, Q_ROWS, qg, jnp.arange(s))
    gate = jax.nn.sigmoid(_ein("sh,hk->sk", x, w["gate_w"], mode))
    return _ein("sk,kh->sh", gate * ctx.reshape(s, hq * d), w["o_w"],
                mode), reached


def swiglu(f, w1, w3, w2, mode):
    a = _ein("sh,hf->sf", f, w1, mode)
    return _ein("sf,fh->sh", jax.nn.silu(a) * _ein("sh,hf->sf", f, w3, mode),
                w2, mode)


def route(w, f, arch, mode):
    """``(weights [S, E], margin [S], touches [S])``: the weight of every
    expert at every position (zero where not chosen), the gap between the
    last chosen and the first rejected of ``s + expert_bias``, and whether
    one of those two experts is HELD here (were they to change places, only
    then would this holder's part of the layer change: the other chosen
    weights move by the gap over their sum)."""
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
    """The expert sublayer on ``f`` ``[S, h]``: the shared expert, and the
    held experts one after another, each on every row and weighted by the
    routing (zero where it was not chosen). ``(ffn, margin, touches)``."""
    wts, margin, touches = route(w, f, arch, mode)
    out = swiglu(f, w["s1"][0], w["s3"][0], w["s2"][0], mode) \
        if "s1" in w else jnp.zeros_like(f)
    for e in range(w["w1"].shape[0]):
        out = out + wts[:, arch.expert_lo + e, None] * swiglu(
            f, w["w1"][e], w["w3"][e], w["w2"][e], mode)
    return out, margin, touches


@functools.partial(jax.jit, static_argnums=(1, 3, 4))
def block(w, sliding, h, arch, mode, risk, tau):
    """One layer: ``(h', routing margin [S], risk [S])`` (the margin
    infinite in a dense layer). ``w`` holds the layer's leaves under their
    short names; a layer with a ``router`` is an expert layer."""
    op, reached = attention(w, rms(h, w["n1"], arch.eps), sliding, arch, mode,
                            risk)
    h = h + rms(op, w["n2"], arch.eps)

    def ffn(hb):
        f = rms(hb, w["n3"], arch.eps)
        if "router" in w:
            out, margin, touches = feed_forward(w, f, arch, mode)
        else:
            out = swiglu(f, w["w1"], w["w3"], w["w2"], mode)
            margin = jnp.full(hb.shape[:1], jnp.inf)
            touches = jnp.zeros(hb.shape[:1], bool)
        return hb + rms(out, w["n4"], arch.eps), margin, touches

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
    h = arch.embed_scale * top["embed"][tokens]
    margin = jnp.full(tokens.shape, jnp.inf)
    risk = jnp.zeros(tokens.shape, jnp.float32)
    tau = jnp.asarray(tau, jnp.float32)
    for i, kind in enumerate(arch.layer_types):
        h, m, risk = block(layer_weights(i), kind == SLIDING, h, arch, mode,
                           risk, tau)
        margin = jnp.minimum(margin, m)
    return rms(h, top["final_norm"], arch.eps), margin, risk


def logits_of(top, hidden, mode="highest"):
    return _ein("sh,hv->sv", hidden, top["head"], mode)
