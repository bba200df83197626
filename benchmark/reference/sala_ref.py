"""Plain reference of the MiniCPM-SALA architecture: ``jax.numpy``, float32,
matrix multiplications at precision ``highest``, no kernel, no cache, no
batching and no chunking of the mathematics: the linear layers run the
token-by-token recurrence (``lax.scan``), the sparse layers select and attend
per query row. Rows are only *computed* in blocks (``lax.map`` over blocks of
query rows, of feed-forward rows) so that a 33,792-token row fits beside the
weights; and the weights are asked for a layer at a time (``layer_weights(i)``)
so that one layer's 1.1 GB is alive at once, not the model's 11.3 GB.

Follows the published configuration of openbmb/MiniCPM-SALA layer by layer
(the equations are in ISSUE 30 and in ``PERF.md`` section 4): pre-norm blocks
with RMSNorm and the MiniCPM family's scalings (``scale_emb`` on the
embedding, ``scale_depth / sqrt(published depth)`` on both residual branches,
``hidden / dim_model_base`` under the head); as the mixer either InfLLM-V2
block-sparse attention (MiniCPM4 report, arXiv:2506.07900: NoPE, per-head q/k
RMSNorm, a sigmoid output gate) or Lightning Attention-2 (arXiv:2401.04658:
per-head q/k RMSNorm, rotate-half RoPE, a per-head decay, an output RMSNorm
and a sigmoid gate); a SwiGLU feed-forward; an untied head. It imports
nothing of the program.

Departures from the public implementation, each stated in the configuration
file under ``assumed``: the sparse sizes (kernel 32, stride 16, block 64,
top-k 64, 1 initial block, window 2,048, dense below 8,192) are MiniCPM4's
published ``sparse_config``, which this model's ``config.json`` does not
repeat; the public kernels normalise the first stage's softmax through a
second, coarser compression of the keys, where this follows the report's
definition (one softmax over the visible compressed keys); the gates are
sigmoids over all 4,096 channels; the linear layers' decay is
``exp(-2^(-8 (h + 1) / H))`` in every layer; the output norm is per head with
one learned weight of the head's size.

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
SPARSE = "minicpm4"

#: query rows of a block of sparse attention, rows of a block of feed-forward
Q_ROWS, FFN_ROWS = 128, 1024


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
    mixer_types: tuple
    n_head: int
    n_kv_head: int
    head_dim: int
    lin_heads: int
    lin_head_dim: int
    eps: float
    rope_theta: float
    scale_emb: float
    residual_scale: float
    logit_divisor: float
    kernel: int
    stride: int
    block: int
    topk: int
    init_blocks: int
    window: int
    dense_len: int


def arch_of(cfg: dict, topk: int = None) -> Arch:
    a = cfg["assumed"]
    return Arch(
        tuple(cfg["mixer_types"][:cfg["num_hidden_layers"]]),
        cfg["num_attention_heads"], cfg["num_key_value_heads"],
        cfg["head_dim"], cfg["lightning_nh"], cfg["lightning_head_dim"],
        float(cfg["rms_norm_eps"]), float(cfg["rope_theta"]),
        float(cfg["scale_emb"]),
        float(cfg["scale_depth"]) / float(a["residual_depth"]) ** 0.5,
        cfg["hidden_size"] / cfg["dim_model_base"],
        a["sparse_kernel_size"], a["sparse_kernel_stride"],
        a["sparse_block_size"], a["sparse_topk"] if topk is None else topk,
        a["sparse_init_blocks"], a["sparse_window_size"],
        a["sparse_dense_len"])


def rms(x, w, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps) * w


def _row_blocks(fn, rows: int, *xs):
    """``fn`` over blocks of ``rows`` leading rows of each of ``xs``, one
    block at a time, the results laid end to end."""
    s = xs[0].shape[0]
    rows = min(rows, s)
    if s % rows:
        raise ValueError(f"{s} rows are no multiple of the block of {rows}")
    cut = [x.reshape((s // rows, rows) + x.shape[1:]) for x in xs]
    out = jax.lax.map(lambda block: fn(*block), tuple(cut))
    return jax.tree.map(lambda y: y.reshape((s,) + y.shape[2:]), out)


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


# -- the sparse layer ------------------------------------------------------------

def _select(arch, qrow, ckeys, n, mode):
    """One query row: ``qrow`` ``[Hkv, G, D]``, ``ckeys`` ``[J, Hkv, D]``,
    context ``n`` -> (blocks read, bool ``[Hkv, NB]``; margin between the
    last block chosen by score and the first rejected, over the KV heads)."""
    ks, st, bs = arch.kernel, arch.stride, arch.block
    j_n = ckeys.shape[0]
    nb = j_n * st // bs
    j = jnp.arange(j_n)
    visible = st * j + ks <= n
    logits = _ein("kgd,jkd->kgj", qrow, ckeys, mode) * arch.head_dim ** -0.5
    p = jax.nn.softmax(jnp.where(visible, logits, -jnp.inf), axis=-1)
    s = jnp.where(visible, jnp.sum(jnp.where(visible, p, 0.0), axis=1),
                  -jnp.inf)                                      # [Hkv, J]
    b = jnp.arange(nb)
    # the kernels that overlap block b: those with a row in [bs*b, bs*b + bs)
    first = (bs * b - ks) // st + 1
    over = first[:, None] + jnp.arange((bs + ks) // st - 1)[None]  # [NB, 5]
    inside = (over >= 0) & (over < j_n)
    score = jnp.max(jnp.where(inside, s[:, jnp.clip(over, 0, j_n - 1)],
                              -jnp.inf), axis=-1)                # [Hkv, NB]
    live = bs * b < n
    forced = live & ((b < arch.init_blocks)
                     | (bs * (b + 1) > n - arch.window))
    ranked = jnp.where(forced, jnp.inf, jnp.where(live, score, -jnp.inf))
    order = jnp.argsort(-ranked, axis=-1, stable=True)           # best first
    take = min(arch.topk, nb)
    chosen = jnp.zeros(ranked.shape, bool).at[
        jnp.arange(ranked.shape[0])[:, None], order[:, :take]].set(True)
    sparse = n > arch.dense_len
    blocks = jnp.where(sparse, chosen & live, live[None])
    # the margin: among the blocks chosen by score, the last taken against
    # the first left. Neighbouring blocks share the kernel that overlaps
    # both, so two scores may be ONE number: such a pair moves together
    # under rounding and the lower index wins on both sides, so the gaps
    # that count are then those to the next values above and below.
    free = jnp.sort(jnp.where(forced | ~live, -jnp.inf, score),
                    axis=-1)[:, ::-1]
    kf = take - jnp.sum(forced)

    def gap(i):     # free[i] - free[i + 1], infinite off either end
        hi = jnp.take(free, jnp.clip(i, 0, nb - 1), axis=-1)
        lo = jnp.take(free, jnp.clip(i + 1, 0, nb - 1), axis=-1)
        inside = (i >= 0) & (i + 1 < nb) & jnp.isfinite(lo)
        return jnp.where(inside, hi - lo, jnp.inf)

    at, above, below = gap(kf - 1), gap(kf - 2), gap(kf)
    contested = sparse & (kf >= 1)
    margin = jnp.min(jnp.where(
        contested, jnp.where(at > 0, at, jnp.minimum(above, below)),
        jnp.inf))
    return blocks, margin


def sparse_attention(w, x, arch, mode):
    """``(attention output [S, Hq D], selection margin [S])``."""
    s = x.shape[0]
    hq, hkv, d = arch.n_head, arch.n_kv_head, arch.head_dim
    q = _ein("sh,hk->sk", x, w["q_w"], mode).reshape(s, hq, d)
    k = _ein("sh,hk->sk", x, w["k_w"], mode).reshape(s, hkv, d)
    v = _ein("sh,hk->sk", x, w["v_w"], mode).reshape(s, hkv, d)
    q = rms(q, w["q_norm"], arch.eps).reshape(s, hkv, hq // hkv, d)
    k = rms(k, w["k_norm"], arch.eps)
    ks, st, bs = arch.kernel, arch.stride, arch.block
    nb = -(-s // bs)
    j_n = nb * bs // st
    kp = jnp.concatenate([k, jnp.zeros((j_n * st + ks - s, hkv, d))])
    ckeys = jax.vmap(lambda j: jnp.mean(jax.lax.dynamic_slice_in_dim(
        kp, st * j, ks), axis=0))(jnp.arange(j_n))
    rows = jnp.arange(nb * bs)
    kpad = jnp.concatenate([k, jnp.zeros((nb * bs - s, hkv, d))])
    vpad = jnp.concatenate([v, jnp.zeros((nb * bs - s, hkv, d))])

    def block_of_queries(qb, tb):
        blocks, margin = jax.vmap(
            lambda qrow, t: _select(arch, qrow, ckeys, t + 1, mode))(qb, tb)
        mask = jnp.repeat(blocks, bs, axis=-1) \
            & (rows[None] <= tb[:, None])[:, None]               # [Q,Hkv,R]
        scores = _ein("qkgd,rkd->qkgr", qb, kpad, mode) * d ** -0.5
        probs = jax.nn.softmax(
            jnp.where(mask[:, :, None], scores, -jnp.inf), axis=-1)
        return (_ein("qkgr,rkd->qkgd", probs, vpad, mode).reshape(-1, hq * d),
                margin)

    return _row_blocks(block_of_queries, Q_ROWS, q, jnp.arange(s))


def sparse_layer(w, x, arch, mode):
    ctx, margin = sparse_attention(w, x, arch, mode)
    gate = jax.nn.sigmoid(_ein("sh,hk->sk", x, w["gate_w"], mode))
    return _ein("sk,kh->sh", gate * ctx, w["o_w"], mode), margin


# -- the linear layer ------------------------------------------------------------

def decay_rates(heads: int):
    return jnp.asarray([2.0 ** (-8.0 * (h + 1) / heads)
                        for h in range(heads)], jnp.float32)


def linear_layer(w, x, arch, mode):
    s = x.shape[0]
    h, d = arch.lin_heads, arch.lin_head_dim
    q = _ein("sh,hk->sk", x, w["q_w"], mode).reshape(s, h, d)
    k = _ein("sh,hk->sk", x, w["k_w"], mode).reshape(s, h, d)
    v = _ein("sh,hk->sk", x, w["v_w"], mode).reshape(s, h, d)
    q = _rope(rms(q, w["q_norm"], arch.eps), arch.rope_theta)
    k = _rope(rms(k, w["k_norm"], arch.eps), arch.rope_theta)
    lam = jnp.exp(-decay_rates(h))[:, None, None]

    def token(state, qkv):
        qt, kt, vt = qkv
        state = lam * state + kt[:, :, None] * vt[:, None, :]
        return state, jnp.sum(state * qt[:, :, None], axis=1) * d ** -0.5

    _, y = jax.lax.scan(token, jnp.zeros((h, d, d), jnp.float32), (q, k, v))
    y = rms(y, w["o_norm"], arch.eps).reshape(s, h * d)
    gate = jax.nn.sigmoid(_ein("sh,hk->sk", x, w["z_w"], mode))
    return _ein("sk,kh->sh", gate * y, w["o_w"], mode)


# -- the model -------------------------------------------------------------------

def swiglu(w, f, mode):
    a = _ein("sh,hf->sf", f, w["w1"], mode)
    return _ein("sf,fh->sh", jax.nn.silu(a) * _ein("sh,hf->sf", f, w["w3"],
                                                   mode), w["w2"], mode)


@functools.partial(jax.jit, static_argnums=(1, 3, 4))
def block(w, kind, h, arch, mode="highest"):
    """One layer: ``(h', selection margin [S])`` (infinite in a linear
    layer). ``w`` holds the layer's leaves under their short names."""
    x = rms(h, w["n1"], arch.eps)
    if kind == SPARSE:
        op, margin = sparse_layer(w, x, arch, mode)
    else:
        op, margin = linear_layer(w, x, arch, mode), \
            jnp.full(h.shape[:1], jnp.inf)
    h = h + arch.residual_scale * op
    return _row_blocks(
        lambda hb: hb + arch.residual_scale * swiglu(
            w, rms(hb, w["n2"], arch.eps), mode), FFN_ROWS, h), margin


def hidden_states(top, layer_weights, arch, tokens, mode="highest"):
    """Final-norm hidden states ``[S, h]`` of one token row ``[S]``, divided
    for the head, and each position's smallest selection margin over the
    sparse layers ``[S]``. ``top`` holds ``embed`` and ``final_norm``;
    ``layer_weights(i)`` gives layer ``i``'s leaves."""
    h = arch.scale_emb * top["embed"][tokens]
    margin = jnp.full(tokens.shape, jnp.inf)
    for i, kind in enumerate(arch.mixer_types):
        h, m = block(layer_weights(i), kind, h, arch, mode)
        margin = jnp.minimum(margin, m)
    return rms(h, top["final_norm"], arch.eps) / arch.logit_divisor, margin


def logits_of(top, hidden, mode="highest"):
    return _ein("sh,hv->sv", hidden, top["head"], mode)
