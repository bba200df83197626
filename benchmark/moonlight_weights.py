"""Seeded Moonlight weights, made on the device one layer at a time.

Both sides of the comparison call this with the same seed: the driver loads
each layer into the program's model as it is made (no second copy of the
3.9 GB is ever alive), and the reference makes a layer again when it reaches
it, after the program's state is freed. Leaves are float32.
:func:`clear_programs` drops the makers' compiled programs once the weights
exist (``sala_weights.py`` says why).

Only the chip's SHARE is made: the ``n_routed_experts`` experts held of each
expert layer (the router keeps its ``share.num_experts_published`` outputs)
and the ``vocab_size`` rows held of the embedding and columns of the head.

Names (``benchmark/reference/moonlight_ref.py`` reads them): ``embed`` [V, h],
``head`` [h, V], ``final_norm`` [h], and per layer ``n1 n2`` [h], ``q_w`` [h,
H (nope + rope)], ``dkv_w`` [h, rank + rope], ``kv_norm`` [rank], ``ukv_w``
[rank, H (nope + v)] (a head: ``[W_UK | W_UV]``), ``o_w`` [H v, h], then ``w1
w3`` [h, f] ``w2`` [f, h] (the dense layer) or ``router`` [h, E]
``expert_bias`` [E], ``w1 w3`` [n, h, f] ``w2`` [n, f, h] (the held experts)
and ``s1 s3`` [1, h, fs] ``s2`` [1, fs, h] (the shared experts, ONE SwiGLU of
``fs = n_shared_experts x f``); matrices are ``[in, out]``.

Spreads. Every sublayer reads an RMS-normed stream, so a projection of spread
``1/sqrt(fan_in)`` keeps every product of order one; the embedding has spread
one (the model does not scale it), and the stream grows by about one a
sublayer, as a pre-norm model's does (nothing here norms a RESULT). Norm
weights lie away from one and ``expert_bias`` away from zero, so that a part
left out shows. ``q_w`` carries a gain of ``Q_GAIN``: a score ``(qn . kn + qr
. r) / sqrt(192)`` is then about ``N(0, 3)``, a third of its variance from
the rotary part, and attention is sharp, so the next token depends on the
context (see ``weights.py``) and on BOTH parts of the score. Router rows of
spread ``1/sqrt(h)`` give scores ``sigmoid(N(0, 1))``; ``expert_bias`` is the
published model's load-balancing term, so its spread here
(``EXPERT_BIAS_STD`` 0.02) leaves the loads balanced (``lfm2_weights.py``
tells what 0.1 did). The routed experts' DOWN-projections carry a gain of
``EXPERT_OUT_GAIN``: top-6 of 64 is discontinuous, a 6k prompt makes 49k
choices and rounding decides a few of them; a flipped choice swaps one
expert's weighted result for another's in that row, and every later token
that attends to the row inherits some of it. ``trinity_weights.py`` bought
room with a gain of 0.1 on the norm of the feed-forward's result; this model
has no such norm, so the gain goes into the seeded ``w2`` of the routed
experts, set so that one flipped choice moves a row of the stream by about a
tenth of itself: at a gain of one a held expert's weighted result has a
spread of 0.24-0.25 where it is chosen, 0.17 and 0.15 of the stream's 1.4
and 1.65 in the first two expert layers (a CPU reading at the published
widths, 256 tokens); at 0.6, 0.10 and 0.09. With 8 of 64 held a flip mostly
swaps a held expert for an absent one, so it adds or removes one such
result. The head is NOT
tied: its COLUMNS come in pairs ``PAIR_SHARE`` apart, so the two best logits
of a position are a pair, and a greedy token tells which of them the
program's arithmetic put first. A head of spread ``1/sqrt(h)`` gives logits
of spread one.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np

EMBED_STD = 1.0
PAIR_SHARE = 3e-4
Q_GAIN = 3.0
EXPERT_BIAS_STD = 0.02
EXPERT_OUT_GAIN = 0.6


def layer_shapes(cfg: dict, i: int) -> dict:
    h, heads = cfg["hidden_size"], cfg["num_attention_heads"]
    nope, rope = cfg["qk_nope_head_dim"], cfg["qk_rope_head_dim"]
    rank, v = cfg["kv_lora_rank"], cfg["v_head_dim"]
    out = {"n1": (h,), "n2": (h,), "q_w": (h, heads * (nope + rope)),
           "dkv_w": (h, rank + rope), "kv_norm": (rank,),
           "ukv_w": (rank, heads * (nope + v)), "o_w": (heads * v, h)}
    if i < cfg["first_k_dense_replace"]:
        f = cfg["intermediate_size"]
        out.update(w1=(h, f), w3=(h, f), w2=(f, h))
        return out
    n, f = cfg["n_routed_experts"], cfg["moe_intermediate_size"]
    e = cfg["share"]["num_experts_published"]
    out.update(router=(h, e), expert_bias=(e,), w1=(n, h, f), w3=(n, h, f),
               w2=(n, f, h))
    if cfg["n_shared_experts"]:
        fs = f * cfg["n_shared_experts"]
        out.update(s1=(1, h, fs), s3=(1, h, fs), s2=(1, fs, h))
    return out


def _scaled(leaf: str, x):
    if leaf in ("n1", "n2", "kv_norm", "final_norm"):
        return 1.0 + 0.1 * x
    if leaf == "expert_bias":
        return EXPERT_BIAS_STD * x
    gain = {"q_w": Q_GAIN}.get(leaf, 1.0)
    if leaf == "w2" and x.ndim == 3:        # the routed experts' own
        gain = EXPERT_OUT_GAIN
    return gain * x * (x.shape[-2] ** -0.5)     # a projection: 1/sqrt(fan_in)


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
