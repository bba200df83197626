"""Seeded LFM2-MoE weights, made on the device one layer at a time.

Both sides of the comparison call this with the same seed: the driver loads
each layer into the program's model as it is made (so no second copy of the
9.8 GB is ever alive), and the reference makes them again after the
program's state is freed. Leaves are float32.

Names (``benchmark/reference/lfm2_ref.py`` reads them): ``embed`` [V, h],
``final_norm`` [h], and per layer ``l<i>.`` + ``op_norm ffn_norm`` [h], then
``conv_in`` [h, 3h] ``conv_k`` [h, L] ``conv_out`` [h, h] or ``q_w`` [h, h]
``k_w v_w`` [h, kv] ``o_w`` [h, h] ``q_norm k_norm`` [D], then ``w1 w3``
[h, f] ``w2`` [f, h] (dense) or ``gate`` [h, E] ``expert_bias`` [E] ``w1 w3``
[E, h, f] ``w2`` [E, f, h]; matrices are ``[in, out]``.

Spreads. Every operator and feed-forward reads an RMS-normed stream, so a
projection of spread ``1/sqrt(fan_in)`` gives unit outputs and the residual
stream grows like the square root of the depth. Norm weights lie away from
one and ``expert_bias`` away from zero, so that a part left out shows. The
q/k head norms carry a gain of ``QK_NORM_GAIN``: scores are then about
``N(0, 3)`` and attention is sharp, so the next token depends on the
context (see ``weights.py``). Router rows of spread ``1/sqrt(h)`` give scores
``sigmoid(N(0, 1))``, so the four chosen differ from token to token.
``expert_bias`` is the published model's load-balancing term, so its spread
here leaves the loads balanced: at ``EXPERT_BIAS_STD`` 0.02 (about the
spacing of the scores round the fourth) it decides one choice in three and
a tick of 32 tokens reads 31.4 of 32 experts a layer whatever the seed. At
0.1 the same few experts won in every token (the fullest took 15-21 of 32
tokens, 26-28 experts were read, by seed) and the tick's time followed the
seed (PERF.md, PR 26).
Token-embedding rows come in pairs ``PAIR_SHARE`` apart, as in
``weights.py``: the two best logits of a position are then a pair, and a
greedy token tells which of them the program's arithmetic put first.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np

EMBED_STD = 0.02
PAIR_SHARE = 3e-4
QK_NORM_GAIN = 1.8
CONV_KERNEL_STD = 0.5
EXPERT_BIAS_STD = 0.02


def layer_shapes(cfg: dict, i: int) -> dict:
    h = cfg["hidden_size"]
    d = h // cfg["num_attention_heads"]
    kv = cfg["num_key_value_heads"] * d
    out = {"op_norm": (h,), "ffn_norm": (h,)}
    if cfg["layer_types"][i] == "conv":
        out.update(conv_in=(h, 3 * h), conv_k=(h, cfg["conv_L_cache"]),
                   conv_out=(h, h))
    else:
        out.update(q_w=(h, h), k_w=(h, kv), v_w=(h, kv), o_w=(h, h),
                   q_norm=(d,), k_norm=(d,))
    if i < cfg["num_dense_layers"]:
        f = cfg["intermediate_size"]
        out.update(w1=(h, f), w3=(h, f), w2=(f, h))
    else:
        e, f = cfg["num_experts"], cfg["moe_intermediate_size"]
        out.update(gate=(h, e), expert_bias=(e,), w1=(e, h, f), w3=(e, h, f),
                   w2=(e, f, h))
    return out


def _scaled(leaf: str, x):
    if leaf in ("op_norm", "ffn_norm", "final_norm"):
        return 1.0 + 0.1 * x
    if leaf in ("q_norm", "k_norm"):
        return QK_NORM_GAIN * (1.0 + 0.1 * x)
    if leaf == "conv_k":
        return CONV_KERNEL_STD * x
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
                             ((vocab + 1) // 2, h), jnp.float32)
    x = jax.random.normal(jax.random.fold_in(key, 2), (vocab, h), jnp.float32)
    return {"embed": EMBED_STD * (jnp.repeat(half, 2, axis=0)[:vocab]
                                  + PAIR_SHARE * x),
            "final_norm": _scaled("final_norm", jax.random.normal(
                jax.random.fold_in(key, 3), (h,), jnp.float32))}


def make_top(cfg: dict, seed: int) -> dict:
    return _make_top(_key(seed), cfg["vocab_size"], cfg["hidden_size"])


def make_layer(cfg: dict, seed: int, i: int) -> dict:
    """Layer ``i``'s leaves under their short names (no ``l<i>.``). Layers
    of one kind share one compiled program (the index is an argument)."""
    shapes = tuple(sorted(layer_shapes(cfg, i).items()))
    return _make_layer(_key(seed), i, shapes)


def make_lfm2_weights(cfg: dict, seed: int) -> dict:
    """The whole flat dict, for the reference."""
    out = dict(make_top(cfg, seed))
    for i in range(cfg["num_hidden_layers"]):
        out.update({f"l{i}.{k}": v
                    for k, v in make_layer(cfg, seed, i).items()})
    return out
