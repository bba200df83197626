"""Seeded Qwen3-Next weights, made on the device one layer at a time.

Both sides of the comparison call this with the same seed: the driver loads
each layer into the program's model as it is made (no second copy of the
7.9 GB is ever alive), and the reference makes a layer again when it reaches
it, after the program's state is freed. Leaves are float32.
:func:`clear_programs` drops the makers' compiled programs once the weights
exist (``sala_weights.py`` says why).

Only the chip's SHARE is made: the ``num_experts`` experts held of each layer
(the router keeps its ``share.num_experts_published`` outputs) and the
``vocab_size`` rows held of the embedding and columns of the head.

Names (``benchmark/reference/qwen3next_ref.py`` reads them): ``embed`` [V,
h], ``head`` [h, V], ``final_norm`` [h], and per layer ``n1 n2`` [h],
``router`` [h, E], ``w1 w3`` [n, h, f] ``w2`` [n, f, h] (the held experts),
``s1 s3`` [1, h, fs] ``s2`` [1, fs, h] (the shared expert), ``sg`` [h, 1]
(its gate); a full-attention layer has ``q_w`` [h, H x 2D] (a head's columns
``[query | gate]``), ``k_w v_w`` [h, Hkv x D], ``o_w`` [H x D, h], ``q_norm
k_norm`` [D]; a Gated-DeltaNet layer ``qkvz_w`` [h, 2 Hk Dk + 2 Hv Dv] (``[q |
k | v | z]``), ``ba_w`` [h, 2 Hv] (``[b | a]``), ``conv_w`` [2 Hk Dk + Hv Dv,
K], ``a_log dt_bias`` [Hv], ``g_norm`` [Dv], ``out_w`` [Hv Dv, h]; matrices
are ``[in, out]``.

Spreads. Every sublayer reads an RMS-normed stream, so a projection of spread
``1/sqrt(fan_in)`` keeps every product of order one; the embedding has spread
one (the model does not scale it), and the stream grows by about one a
sublayer, as a pre-norm model's does. The ZERO-CENTRED norm weights are seeded
near 0 (spread 0.1: the norm multiplies by ``1 + w``) and the gate norm's
plain weight near 1, so that a norm taken the other way shows. ``q_norm``
carries the attention's sharpness: a query head is normed AFTER its
projection, so a gain on ``q_w`` would vanish; its zero-centred weight lies
near ``Q_GAIN - 1``, a score ``q . k / sqrt(256)`` is then about ``N(0,
Q_GAIN)``, a quarter of its variance from the 64 rotated columns, and
attention is sharp, so the next token depends on the context (see
``weights.py``) and on the rotary part. The decays keep the public
initialisation's spread: ``A_log`` the log of U(0, 16) a value head and
``dt_bias`` near 1, so ``alpha = exp(-A softplus(a + 1))`` lies between
``exp(-30)`` and 1, a few heads of a layer remembering tens of tokens and
most a handful; the short convolution's taps have spread 0.5 (four taps: a
result of spread one). Router rows of spread ``1/sqrt(h)`` give logits ``N(0,
1)``: the top 10 of a softmax over 512 such carry weights of 0.05-0.25 after
renormalisation. With 64 of 512 held a token sends 1.25 of its 10 pairs to a
held expert, each weighted about a tenth: one flipped choice moves a row of
the stream by a hundredth of itself, so the routed experts need no gain of
their own (``moonlight_weights.py`` has one of 0.6 for weights of 0.4). The
head is NOT tied: its COLUMNS come in pairs ``PAIR_SHARE`` apart, so the two
best logits of a position are a pair, and a greedy token tells which of them
the program's arithmetic put first. ``PAIR_SHARE`` is a third of the other
families' 3e-4: a sampled run compares about 3,400 served tokens here, a
third of Moonlight's 11,000, and the share of them that a rounding puts the
other way round is inversely proportional to the pairs' distance, so the
COUNT a sound engine and the ``high`` control are told apart by is the size of
theirs (``benchmark/limits/serve-qwen3next-longdoc.json``). A head of spread
``1/sqrt(h)`` gives logits of spread one.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np

EMBED_STD = 1.0
PAIR_SHARE = 1e-4
Q_GAIN = 3.0
CONV_STD = 0.5
A_MAX = 16.0

#: zero-centred norm weights, seeded near 0
_ZERO_CENTRED = ("n1", "n2", "k_norm", "final_norm")


def is_full(cfg: dict, i: int) -> bool:
    return (i + 1) % cfg["full_attention_interval"] == 0


def layer_shapes(cfg: dict, i: int) -> dict:
    h = cfg["hidden_size"]
    n, f = cfg["num_experts"], cfg["moe_intermediate_size"]
    e, fs = cfg["share"]["num_experts_published"], cfg[
        "shared_expert_intermediate_size"]
    out = {"n1": (h,), "n2": (h,), "router": (h, e), "w1": (n, h, f),
           "w3": (n, h, f), "w2": (n, f, h), "s1": (1, h, fs),
           "s3": (1, h, fs), "s2": (1, fs, h), "sg": (h, 1)}
    if is_full(cfg, i):
        heads, kv, d = (cfg["num_attention_heads"],
                        cfg["num_key_value_heads"], cfg["head_dim"])
        out.update(q_w=(h, heads * 2 * d), k_w=(h, kv * d), v_w=(h, kv * d),
                   o_w=(heads * d, h), q_norm=(d,), k_norm=(d,))
        return out
    hv = cfg["linear_num_value_heads"]
    kw = cfg["linear_num_key_heads"] * cfg["linear_key_head_dim"]
    vw = hv * cfg["linear_value_head_dim"]
    out.update(qkvz_w=(h, 2 * kw + 2 * vw), ba_w=(h, 2 * hv),
               conv_w=(2 * kw + vw, cfg["linear_conv_kernel_dim"]),
               a_log=(hv,), dt_bias=(hv,),
               g_norm=(cfg["linear_value_head_dim"],), out_w=(vw, h))
    return out


def _scaled(leaf: str, x):
    if leaf in _ZERO_CENTRED:
        return 0.1 * x
    if leaf == "q_norm":
        return (Q_GAIN - 1.0) + 0.1 * x
    if leaf in ("g_norm", "dt_bias"):
        return 1.0 + 0.1 * x
    if leaf == "a_log":         # log of U(0, 16): the normal's CDF is uniform
        return jnp.log(A_MAX * jnp.clip(jax.scipy.stats.norm.cdf(x), 1e-6))
    if leaf == "conv_w":
        return CONV_STD * x
    return x * (x.shape[-2] ** -0.5)        # a projection: 1/sqrt(fan_in)


def _key(seed: int):
    s = int(seed) & ((1 << 64) - 1)
    return np.array([s >> 32, s & 0xFFFFFFFF], np.uint32)


@functools.partial(jax.jit, static_argnums=(2,))
def _make_layer(seed_words, index, shapes):
    key = jax.random.fold_in(
        jax.random.wrap_key_data(seed_words, impl="threefry2x32"), 1 + index)
    return {leaf: _scaled(leaf, jax.random.normal(
        jax.random.fold_in(key, j), shape, jnp.float32))
        for j, (leaf, shape) in enumerate(shapes)}


@functools.partial(jax.jit, static_argnums=(1, 2))
def _make_top(seed_words, vocab, h):
    key = jax.random.wrap_key_data(seed_words, impl="threefry2x32")
    half = jax.random.normal(jax.random.fold_in(key, 1),
                             (h, (vocab + 1) // 2), jnp.float32)
    x = jax.random.normal(jax.random.fold_in(key, 2), (h, vocab), jnp.float32)
    return {"embed": EMBED_STD * jax.random.normal(
                jax.random.fold_in(key, 4), (vocab, h), jnp.float32),
            "head": h ** -0.5 * (jnp.repeat(half, 2, axis=1)[:, :vocab]
                                 + PAIR_SHARE * x),
            "final_norm": _scaled("final_norm", jax.random.normal(
                jax.random.fold_in(key, 3), (h,), jnp.float32))}


def make_top(cfg: dict, seed: int) -> dict:
    return _make_top(_key(seed), cfg["vocab_size"], cfg["hidden_size"])


def make_layer(cfg: dict, seed: int, i: int) -> dict:
    """Layer ``i``'s leaves under their short names. Layers of one kind
    share one compiled program (the index is an argument)."""
    shapes = tuple(sorted(layer_shapes(cfg, i).items()))
    return _make_layer(_key(seed), i, shapes)


def clear_programs():
    """Unload the makers' compiled programs (and the region the device
    reserves for their temporaries)."""
    _make_layer.clear_cache()
    _make_top.clear_cache()
