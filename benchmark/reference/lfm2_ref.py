"""Plain reference of the LFM2-MoE architecture: ``jax.numpy``, float32,
matrix multiplications at precision ``highest``, no kernel, no cache, no
batching; every expert is computed densely and masked by the routing.

Follows the published configuration of LiquidAI/LFM2-8B-A1B layer by layer
(the equations are in ISSUE 26 and in ``PERF.md`` section 4): pre-norm
blocks with RMSNorm; a gated short convolution (depthwise, causal, length
``conv_L_cache``) or grouped-query attention with per-head q/k RMSNorm and
rotate-half RoPE as the operator; a SwiGLU feed-forward in the leading dense
layers and, after them, experts chosen by sigmoid scores plus a selection
bias, weighted by the scores alone. The output head is tied to the token
embedding (an assumption the configuration file states). It imports nothing
of the program.

Weights are a flat dict (``benchmark/lfm2_weights.py``); matrices are
``[in, out]``, expert stacks ``[experts, in, out]``. An expert stack may hold
a share ``[lo, lo + n)`` of the experts (``arch.expert_lo``): routing is over
all of them and the absent experts' part of the sum is left out.

``mode`` lowers the precision of every matrix multiplication, for the
controls that must come out as not correct: ``highest`` (the reference),
``high`` (three bf16 passes) and ``bfloat16`` (operands rounded to bf16).
"""
from __future__ import annotations

from typing import NamedTuple

import jax
import jax.numpy as jnp

MODES = ("highest", "high", "bfloat16")


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
    eps: float
    rope_theta: float
    conv_l: int
    num_dense: int
    num_experts: int
    top_k: int
    norm_topk: bool
    routed_scale: float
    expert_lo: int = 0


def arch_of(cfg: dict, expert_lo: int = 0) -> Arch:
    return Arch(tuple(cfg["layer_types"][:cfg["num_hidden_layers"]]),
                cfg["num_attention_heads"], cfg["num_key_value_heads"],
                cfg["norm_eps"], float(cfg["rope_theta"]),
                cfg["conv_L_cache"], cfg["num_dense_layers"],
                cfg["num_experts"], cfg["num_experts_per_tok"],
                bool(cfg["norm_topk_prob"]),
                float(cfg["routed_scaling_factor"]), expert_lo)


def rms(x, w, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps) * w


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


def short_conv(w, p, u, arch, mode):
    s = u.shape[0]
    b, c, x = jnp.split(_ein("sh,hk->sk", u, w[p + "conv_in"], mode), 3, -1)
    z = b * x
    zp = jnp.concatenate([jnp.zeros((arch.conv_l - 1, z.shape[1]), z.dtype),
                          z])
    conv = sum(w[p + "conv_k"][:, j] * zp[j:j + s]
               for j in range(arch.conv_l))
    return _ein("sh,hk->sk", c * conv, w[p + "conv_out"], mode)


def attention(w, p, u, arch, mode):
    s = u.shape[0]
    hq, hkv = arch.n_head, arch.n_kv_head
    q = _ein("sh,hk->sk", u, w[p + "q_w"], mode).reshape(s, hq, -1)
    k = _ein("sh,hk->sk", u, w[p + "k_w"], mode).reshape(s, hkv, -1)
    v = _ein("sh,hk->sk", u, w[p + "v_w"], mode).reshape(s, hkv, -1)
    d = q.shape[-1]
    q = _rope(rms(q, w[p + "q_norm"], arch.eps), arch.rope_theta)
    k = _rope(rms(k, w[p + "k_norm"], arch.eps), arch.rope_theta)
    k = jnp.repeat(k, hq // hkv, axis=1)       # query head j reads j // g
    v = jnp.repeat(v, hq // hkv, axis=1)
    scores = _ein("qnd,knd->nqk", q, k, mode) * (d ** -0.5)
    causal = jnp.tril(jnp.ones((s, s), bool))
    probs = jax.nn.softmax(jnp.where(causal, scores, -jnp.inf), axis=-1)
    ctx = _ein("nqk,knd->qnd", probs, v, mode).reshape(s, hq * d)
    return _ein("sh,hk->sk", ctx, w[p + "o_w"], mode)


def swiglu(f, w1, w3, w2, mode):
    a = _ein("sh,hf->sf", f, w1, mode)
    return _ein("sf,fh->sh", jax.nn.silu(a) * _ein("sh,hf->sf", f, w3, mode),
                w2, mode)


def route(w, p, f, arch, mode):
    """``(weights [S, E], margin [S])``: the weight of every expert at every
    position (zero where not chosen), and the gap between the last chosen
    and the first rejected of ``s + expert_bias``."""
    s = jax.nn.sigmoid(_ein("sh,he->se", f, w[p + "gate"], mode))
    ranked = jnp.sort(s + w[p + "expert_bias"], axis=-1)[:, ::-1]
    kth, nxt = ranked[:, arch.top_k - 1], ranked[:, arch.top_k]
    chosen = (s + w[p + "expert_bias"]) >= kth[:, None]
    wts = jnp.where(chosen, s, 0.0)
    if arch.norm_topk:
        wts = wts / (jnp.sum(wts, axis=-1, keepdims=True) + 1e-6)
    return wts * arch.routed_scale, kth - nxt


def experts(w, p, f, wts, arch, mode):
    """Every expert held, on every position, masked by the routing."""
    n = w[p + "w1"].shape[0]
    a = _ein("sh,ehf->esf", f, w[p + "w1"], mode)
    b = _ein("sh,ehf->esf", f, w[p + "w3"], mode)
    y = _ein("esf,efh->esh", jax.nn.silu(a) * b, w[p + "w2"], mode)
    held = wts[:, arch.expert_lo:arch.expert_lo + n]          # [S, n]
    return jnp.einsum("esh,se->sh", y, held,
                      precision=jax.lax.Precision.HIGHEST)


def block(w, i, h, arch, mode="highest"):
    """One layer: ``(h', routing margin [S])`` (infinite in a dense layer)."""
    p = f"l{i}."
    u = rms(h, w[p + "op_norm"], arch.eps)
    op = short_conv if arch.layer_types[i] == "conv" else attention
    h = h + op(w, p, u, arch, mode)
    f = rms(h, w[p + "ffn_norm"], arch.eps)
    if i < arch.num_dense:
        return (h + swiglu(f, w[p + "w1"], w[p + "w3"], w[p + "w2"], mode),
                jnp.full(h.shape[:1], jnp.inf))
    wts, margin = route(w, p, f, arch, mode)
    return h + experts(w, p, f, wts, arch, mode), margin


def hidden_states(w, arch, tokens, mode="highest"):
    """Final-norm hidden states ``[S, h]`` of one token row ``[S]``, and each
    position's smallest routing margin over the expert layers ``[S]``."""
    h = w["embed"][tokens]
    margin = jnp.full(tokens.shape, jnp.inf)
    for i in range(len(arch.layer_types)):
        h, m = block(w, i, h, arch, mode)
        margin = jnp.minimum(margin, m)
    return rms(h, w["final_norm"], arch.eps), margin


def logits_of(w, hidden, mode="highest"):
    return _ein("sh,vh->sv", hidden, w["embed"], mode)
