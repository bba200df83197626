"""Seeded Trinity weights, made on the device one layer at a time.

Both sides of the comparison call this with the same seed: the driver loads
each layer into the program's model as it is made (no second copy of the 5 GB
is ever alive), and the reference makes a layer again when it reaches it,
after the program's state is freed. Leaves are float32.
:func:`clear_programs` drops the makers' compiled programs once the weights
exist (``sala_weights.py`` says why).

Only the chip's SHARE is made: the ``num_experts`` experts held of each
expert layer (the router keeps its ``share.num_experts_published`` outputs)
and the ``vocab_size`` rows held of the embedding and columns of the head.

Names (``benchmark/reference/trinity_ref.py`` reads them): ``embed`` [V, h],
``head`` [h, V], ``final_norm`` [h], and per layer ``n1 n2 n3 n4`` [h],
``q_w gate_w`` [h, Hq D], ``k_w v_w`` [h, Hkv D], ``o_w`` [Hq D, h], ``q_norm
k_norm`` [D], then ``w1 w3`` [h, f] ``w2`` [f, h] (dense) or ``router`` [h, E]
``expert_bias`` [E], ``w1 w3`` [n, h, f] ``w2`` [n, f, h] (the held experts)
and ``s1 s3`` [1, h, f] ``s2`` [1, f, h] (the shared expert); matrices are
``[in, out]``.

Spreads. Every sublayer reads an RMS-normed stream and its result is normed
again before it is added, so a projection of spread ``1/sqrt(fan_in)`` keeps
every product of order one. Norm weights lie away from one and
``expert_bias`` away from zero, so that a part left out shows. The q/k head
norms carry a gain of ``QK_NORM_GAIN``: softmax scores are then about ``N(0,
3)`` and attention is sharp, so the next token depends on the context (see
``weights.py``). Router rows of spread ``1/sqrt(h)`` give scores
``sigmoid(N(0, 1))``; ``expert_bias`` is the published model's load-balancing
term, so its spread here (``EXPERT_BIAS_STD`` 0.02, about twice the spacing
of 128 scores round the eighth) leaves the loads balanced
(``lfm2_weights.py`` tells what 0.1 did). The norm on the feed-forward's
RESULT (``n4``) carries a gain of ``FFN_NORM_GAIN`` 0.1: with about one held
expert a token and the result normed, one flipped routing choice (a 16k prompt
makes 131k choices, and rounding decides a few dozen of them) would otherwise
move that position's state by a third of itself, and every later token that
attends to the row with it: at a gain of one, six runs of seven read gaps of
0.1-0.3 at tokens whose own routing was nowhere near a tie (PERF.md section
6, PR 32). At 0.1 the expert layer still shows when it is wrong (the shared
expert left out moves logits by tenths) and a flip reaches its neighbours a
tenth as far. The head is NOT tied: its COLUMNS
come in pairs ``PAIR_SHARE`` apart, so the two best logits of a position are
a pair, and a greedy token tells which of them the program's arithmetic put
first. A head of spread ``1/sqrt(h)`` gives logits of spread one.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np

EMBED_STD = 0.02
PAIR_SHARE = 3e-4
QK_NORM_GAIN = 1.8
EXPERT_BIAS_STD = 0.02
FFN_NORM_GAIN = 0.1


def layer_shapes(cfg: dict, i: int) -> dict:
    h, d = cfg["hidden_size"], cfg["head_dim"]
    wq, wkv = cfg["num_attention_heads"] * d, cfg["num_key_value_heads"] * d
    out = {"n1": (h,), "n2": (h,), "n3": (h,), "n4": (h,), "q_w": (h, wq),
           "k_w": (h, wkv), "v_w": (h, wkv), "gate_w": (h, wq),
           "o_w": (wq, h), "q_norm": (d,), "k_norm": (d,)}
    if i < cfg["num_dense_layers"]:
        f = cfg["intermediate_size"]
        out.update(w1=(h, f), w3=(h, f), w2=(f, h))
        return out
    n, f = cfg["num_experts"], cfg["moe_intermediate_size"]
    e = cfg["share"]["num_experts_published"]
    out.update(router=(h, e), expert_bias=(e,), w1=(n, h, f), w3=(n, h, f),
               w2=(n, f, h))
    if cfg["num_shared_experts"]:
        fs = f * cfg["num_shared_experts"]
        out.update(s1=(1, h, fs), s3=(1, h, fs), s2=(1, fs, h))
    return out


def _scaled(leaf: str, x):
    if leaf in ("n1", "n2", "n3", "final_norm"):
        return 1.0 + 0.1 * x
    if leaf == "n4":
        return FFN_NORM_GAIN * (1.0 + 0.1 * x)
    if leaf in ("q_norm", "k_norm"):
        return QK_NORM_GAIN * (1.0 + 0.1 * x)
    if leaf == "expert_bias":
        return EXPERT_BIAS_STD * x
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
