"""Plain reference of the Qwen3-Next (``model_type`` ``qwen3_next``)
architecture: ``jax.numpy``, float32, matrix multiplications at precision
``highest``, no kernel, no cache, no chunks, no batching. The linear layers
run the gated delta rule as the TOKEN-BY-TOKEN RECURRENCE (a ``lax.scan`` over
tokens, one ``[128, 128]`` state a value head; no chunked form, no triangular
solve); the full layers take one softmax over a whole masked row; the expert
layer runs every held expert on every row. Rows are only *computed* in blocks
(``lax.map`` over blocks of query rows, of projection and feed-forward rows)
so that a 33,792-token row fits; and the weights are asked for a layer at a
time (``layer_weights(i)``). It imports nothing of the program.

Follows the published configuration of Qwen/Qwen3-Next-80B-A3B-Instruct layer
by layer. From the ``config.json``: every size, ``full_attention_interval``
(layer ``i`` is full iff ``(i + 1) % 4 == 0``), ``decoder_sparse_step`` 1 and
``mlp_only_layers`` [] (every layer has experts), ``norm_topk_prob``,
``partial_rotary_factor``, ``rope_theta`` with no scaling, ``rms_norm_eps``,
the untied head. ASSUMED, each stated in the configuration file under
``assumed`` with its source (the public modelling code of the model type and
the Gated Delta Networks paper, arXiv:2412.06464, neither of which could be
read here: there is no network):

    h0 = E[token];  h = h + Mixer(N1(h));  h = h + MoE(N2(h))
    logits = Nf(h) W_head;   N(x) = x / sqrt(mean(x^2) + eps) * (1 + w)
    full:    [q | gate] = x Wq a head (halves of 256), k = x Wk, v = x Wv
             q, k = N_q(q), N_k(k) a head; rotate-half RoPE over the FIRST 64
             columns; causal softmax at 1 / sqrt(256), head j on KV head j // 8
             o = (attn * sigmoid(gate)) Wo
    linear:  [q | k | v | z] = x W_qkvz;  [b | a] = x W_ba
             [q | k | v] = silu(causal depthwise conv, kernel 4)
             beta = sigmoid(b);  g = -exp(A_log) * softplus(a + dt_bias)
             q, k = x / sqrt(sum(x^2) + 1e-6) a head;  q = q / sqrt(128)
             per value head h (key head h // 2), S [128, 128] from 0:
                 S <- exp(g_t) S;  r_t = v_t - S^T k_t
                 S <- S + beta_t k_t r_t^T;  o_t = S^T q_t
             y = (w * o / sqrt(mean(o^2) + eps)) * silu(z);  Mixer = y W_out
    experts: p = softmax(x W_r) over all 512; the top 10 of p
             w_e = p_e / sum of the chosen p
             MoE = sum_e w_e SwiGLU_e(x) + sigmoid(x . w_sg) * SwiGLU_shared(x)

Departures from the public implementation: the columns of ``in_proj_qkvz``
and ``in_proj_ba``, which the published weights interleave by key head and
this reference (and the program) keep concatenated ``[q | k | v | z]`` and
``[b | a]``: with seeded weights a fixed permutation of columns that no
product sees. The published next-token-prediction head has no key in the
``config.json`` and is left out. None other known; what the description
itself may have wrong cannot be checked here.

**A chip's share.** An expert stack may hold a share ``[lo, lo + n)`` of the
experts (``arch.expert_lo``, the stack's length): routing is over all of them
and the absent experts' part of the sum is left out; the gated shared expert
is computed whole. The embedding and the head may be a slice of the
vocabulary: token ids are then indices into the slice.

**Near-ties and what they reach.** Top-10 of 512 is discontinuous: where the
last chosen and the first rejected router LOGIT lie within rounding of each
other, program and reference may choose differently, and if one of the two
experts at that edge is held here the row's result differs by one expert's
weighted output. The forward carries, beside each position's smallest such
margin (in logits: the softmax is monotone), its ``risk`` in [0, 1]: 1 from
the layer on in which its own margin is under ``tau`` and one of the two
experts at the edge is held here; in a full layer at least the
attention-weighted sum of the risks of the rows a head reads, root mean
square over the heads (``trinity_ref.py``); and in a LINEAR layer at least
what the state carries of it: a value head's state takes ``beta_t risk_t`` of
a token and keeps ``alpha`` of what it had (``R_t = alpha_t R_(t-1) + beta_t
risk_t``, at most 1), root mean square over the value heads: a flipped row
reaches every later token through the state, for as long as the head
remembers. It reads this reference alone.

``mode`` lowers the precision of every matrix multiplication (the
recurrence's products with the state included), for the controls that must
come out as not correct: ``highest`` (the reference), ``high`` (three bf16
passes) and ``bfloat16`` (operands rounded to bf16).
"""
from __future__ import annotations

import functools
from typing import NamedTuple

import jax
import jax.numpy as jnp

MODES = ("highest", "high", "bfloat16")

#: query rows of a block of attention, rows of a block of projections and of
#: feed-forward (33,792 = 66 x 512 = 33 x 1,024)
Q_ROWS, FFN_ROWS = 512, 1024
L2_EPS = 1e-6

#: what the configuration file must say under ``assumed`` (the tests hold the
#: file to this list)
ASSUMED = ("residual stream", "zero-centred norms", "layer pattern",
           "query and output gate", "q and k norms", "partial rotary",
           "gated delta rule", "gate norm", "projection columns", "routing",
           "shared expert", "next-token head", "weights layout")


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
    interval: int
    n_head: int
    n_kv: int
    head_dim: int
    rotary_dim: int
    rope_theta: float
    eps: float
    key_heads: int
    value_heads: int
    key_dim: int
    value_dim: int
    conv_kernel: int
    top_k: int
    route_norm: bool
    expert_lo: int = 0


def arch_of(cfg: dict) -> Arch:
    """Of a configuration file: ``share.experts_held`` says where the held
    experts start among the router's outputs."""
    return Arch(cfg["num_hidden_layers"], cfg["full_attention_interval"],
                cfg["num_attention_heads"], cfg["num_key_value_heads"],
                cfg["head_dim"],
                int(cfg["head_dim"] * cfg["partial_rotary_factor"]),
                float(cfg["rope_theta"]), cfg["rms_norm_eps"],
                cfg["linear_num_key_heads"], cfg["linear_num_value_heads"],
                cfg["linear_key_head_dim"], cfg["linear_value_head_dim"],
                cfg["linear_conv_kernel_dim"], cfg["num_experts_per_tok"],
                bool(cfg["norm_topk_prob"]),
                int(cfg["share"]["experts_held"][0]))


def rms(x, w, eps):
    """The zero-centred norm: the weight multiplies as ``1 + w``."""
    return x * jax.lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True)
                             + eps) * (1.0 + w)


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


def _rope(x, theta, rotary: int):
    """Rotate-half RoPE over the first ``rotary`` columns of the last axis;
    ``x`` is ``[S, heads, D]`` at positions 0..S-1."""
    s = x.shape[0]
    part, rest = x[..., :rotary], x[..., rotary:]
    inv = theta ** (-jnp.arange(0, rotary, 2, dtype=jnp.float32) / rotary)
    ang = jnp.arange(s, dtype=jnp.float32)[:, None] * inv[None]
    cos = jnp.concatenate([jnp.cos(ang), jnp.cos(ang)], -1)[:, None]
    sin = jnp.concatenate([jnp.sin(ang), jnp.sin(ang)], -1)[:, None]
    rot = jnp.concatenate([-part[..., rotary // 2:],
                           part[..., :rotary // 2]], -1)
    return jnp.concatenate([part * cos + rot * sin, rest], -1)


def attention(w, x, arch, mode, risk):
    """Gated full attention on ``x`` ``[S, h]`` (the normed stream).
    ``(result, reached [S])``: ``reached`` is the attention-weighted sum of
    ``risk`` over the rows a head reads, root mean square over the heads."""
    s, hq, hkv, d = x.shape[0], arch.n_head, arch.n_kv, arch.head_dim
    both = _ein("sh,hk->sk", x, w["q_w"], mode).reshape(s, hq, 2 * d)
    q, gate = both[..., :d], both[..., d:]
    k = _ein("sh,hk->sk", x, w["k_w"], mode).reshape(s, hkv, d)
    v = _ein("sh,hk->sk", x, w["v_w"], mode).reshape(s, hkv, d)
    q = _rope(rms(q, w["q_norm"], arch.eps), arch.rope_theta, arch.rotary_dim)
    k = _rope(rms(k, w["k_norm"], arch.eps), arch.rope_theta, arch.rotary_dim)
    q = q.reshape(s, hkv, hq // hkv, d)
    col = jnp.arange(s)[None]

    def rows(qb, at):
        scores = _ein("qkgd,rkd->kgqr", qb, k, mode) * d ** -0.5
        probs = jax.nn.softmax(
            jnp.where(col <= at[:, None], scores, -jnp.inf), axis=-1)
        # a head that reads a row at risk alone moves its 1/H of the result
        # by all of it: the heads' root mean square, not their mean
        reach = jnp.einsum("kgqr,r->qkg", probs, risk).reshape(-1, hq)
        return (_ein("kgqr,rkd->qkgd", probs, v, mode),
                jnp.sqrt(jnp.mean(reach * reach, axis=1)))

    ctx, reached = _row_blocks(rows, Q_ROWS, q, jnp.arange(s))
    ctx = ctx.reshape(s, hq * d) * jax.nn.sigmoid(gate.reshape(s, hq * d))
    return _ein("sk,kh->sh", ctx, w["o_w"], mode), reached


def _l2(x):
    return x * jax.lax.rsqrt(jnp.sum(x * x, axis=-1, keepdims=True) + L2_EPS)


def delta_layer(w, x, arch, mode, risk):
    """The Gated-DeltaNet mixer on ``x`` ``[S, h]`` (the normed stream), the
    recurrence a token a step. ``(result, reached [S])``: ``reached`` is what
    the states carry of ``risk`` at each token, root mean square over the
    value heads."""
    s = x.shape[0]
    hk, hv, dk, dv = (arch.key_heads, arch.value_heads, arch.key_dim,
                      arch.value_dim)
    kw, vw = hk * dk, hv * dv

    def project(xb):
        return (_ein("sh,hk->sk", xb, w["qkvz_w"], mode),
                _ein("sh,hk->sk", xb, w["ba_w"], mode))

    proj, ba = _row_blocks(project, FFN_ROWS, x)
    mixed, z = proj[:, :2 * kw + vw], proj[:, 2 * kw + vw:]
    taps = arch.conv_kernel
    padded = jnp.pad(mixed, ((taps - 1, 0), (0, 0)))
    mixed = jax.nn.silu(sum(w["conv_w"][:, j] * padded[j:j + s]
                            for j in range(taps)))
    q = _l2(mixed[:, :kw].reshape(s, hk, dk)) * dk ** -0.5
    k = _l2(mixed[:, kw:2 * kw].reshape(s, hk, dk))
    v = mixed[:, 2 * kw:].reshape(s, hv, dv)
    beta = jax.nn.sigmoid(ba[:, :hv])
    alpha = jnp.exp(-jnp.exp(w["a_log"])
                    * jax.nn.softplus(ba[:, hv:] + w["dt_bias"]))

    def token(carry, xs):
        state, held = carry                  # [hv, dk, dv], [hv]
        qt, kt, vt, at, bt, rt = xs
        qt, kt = (jnp.repeat(y, hv // hk, axis=0) for y in (qt, kt))
        state = at[:, None, None] * state
        r = vt - _ein("hkv,hk->hv", state, kt, mode)
        state = state + bt[:, None, None] * kt[:, :, None] * r[:, None, :]
        held = jnp.minimum(at * held + bt * rt, 1.0)
        return (state, held), (_ein("hkv,hk->hv", state, qt, mode),
                               jnp.sqrt(jnp.mean(held * held)))

    _, (o, reached) = jax.lax.scan(
        token, (jnp.zeros((hv, dk, dv), jnp.float32), jnp.zeros((hv,))),
        (q, k, v, alpha, beta, risk))
    y = w["g_norm"] * o * jax.lax.rsqrt(
        jnp.mean(o * o, axis=-1, keepdims=True) + arch.eps)
    y = (y * jax.nn.silu(z.reshape(s, hv, dv))).reshape(s, vw)
    return _row_blocks(lambda yb: _ein("sk,kh->sh", yb, w["out_w"], mode),
                       FFN_ROWS, y), reached


def swiglu(f, w1, w3, w2, mode):
    a = _ein("sh,hf->sf", f, w1, mode)
    return _ein("sf,fh->sh", jax.nn.silu(a) * _ein("sh,hf->sf", f, w3, mode),
                w2, mode)


def route(w, f, arch, mode):
    """``(weights [S, E], margin [S], touches [S])``: the weight of every
    expert at every position (zero where not chosen), the gap between the
    last chosen and the first rejected router LOGIT, and whether one of those
    two experts is HELD here."""
    logits = _ein("sh,he->se", f, w["router"], mode)
    p = jax.nn.softmax(logits, axis=-1)
    order = jnp.argsort(-logits, axis=-1)
    edge = order[:, arch.top_k - 1:arch.top_k + 1]              # [S, 2]
    kth, nxt = (jnp.take_along_axis(logits, edge[:, i:i + 1], -1)[:, 0]
                for i in (0, 1))
    held = (edge >= arch.expert_lo) & (
        edge < arch.expert_lo + w["w1"].shape[0])
    wts = jnp.where(logits >= kth[:, None], p, 0.0)
    if arch.route_norm:
        wts = wts / jnp.sum(wts, axis=-1, keepdims=True)
    return wts, kth - nxt, jnp.any(held, axis=-1)


def feed_forward(w, f, arch, mode):
    """The expert sublayer on ``f`` ``[S, h]``: the gated shared expert, and
    the held experts one after another, each on every row and weighted by
    the routing (zero where it was not chosen). ``(ffn, margin, touches)``."""
    wts, margin, touches = route(w, f, arch, mode)
    gate = jax.nn.sigmoid(_ein("sh,hk->sk", f, w["sg"], mode))
    out = gate * swiglu(f, w["s1"][0], w["s3"][0], w["s2"][0], mode)
    n = w["w1"].shape[0]

    def expert(acc, xs):
        w1, w3, w2, share = xs
        return acc + share[:, None] * swiglu(f, w1, w3, w2, mode), None

    out, _ = jax.lax.scan(expert, out, (
        w["w1"], w["w3"], w["w2"],
        wts[:, arch.expert_lo:arch.expert_lo + n].T))
    return out, margin, touches


@functools.partial(jax.jit, static_argnums=(2, 3))
def block(w, h, arch, mode, risk, tau):
    """One layer: ``(h', routing margin [S], risk [S])``. ``w`` holds the
    layer's leaves under their short names; a layer with a ``q_w`` is a
    full-attention layer."""
    x = _row_blocks(lambda hb: rms(hb, w["n1"], arch.eps), FFN_ROWS, h)
    mixer = attention if "q_w" in w else delta_layer
    op, reached = mixer(w, x, arch, mode, risk)
    h = h + op

    def ffn(hb):
        out, margin, touches = feed_forward(
            w, rms(hb, w["n2"], arch.eps), arch, mode)
        return hb + out, margin, touches

    h, margin, touches = _row_blocks(ffn, FFN_ROWS, h)
    risk = jnp.maximum(jnp.maximum(risk, jnp.minimum(reached, 1.0)),
                       ((margin < tau) & touches).astype(risk.dtype))
    return h, margin, risk


def hidden_states(top, layer_weights, arch, tokens, mode="highest", tau=0.0):
    """Final-norm hidden states ``[S, h]`` of one token row ``[S]``, each
    position's smallest routing margin over the layers ``[S]``, and its
    ``risk`` ``[S]`` under ``tau`` (the module's docstring). ``top`` holds
    ``embed`` and ``final_norm``; ``layer_weights(i)`` gives layer ``i``'s
    leaves."""
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
