"""Seeded GPT-2-architecture weights, made on the device in one jitted call.

Both sides of the comparison call this with the same seed: the driver loads
the result into the program's model, and the reference calls it again after
the program's state is freed. Leaves are float32, the type the engine serves
and the trainer keeps its master weights in.

Names: ``wte`` [V, h], ``wpe`` [P, h], ``lnf_w``/``lnf_b`` [h], and per layer
``h<i>.`` + ``ln1_w ln1_b q_w q_b k_w k_b v_w v_b o_w o_b ln2_w ln2_b fc_w
fc_b proj_w proj_b``; matrices are ``[in, out]``.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np

STD = 0.02
#: Token-embedding rows come in pairs that differ by this share of a row's
#: spread. The output head is tied to the embedding, so at every position the
#: two best logits are a pair a few 1e-4 apart, and a greedy token tells
#: which of them the program's arithmetic put first: served tokens then carry
#: the program's precision, which with unpaired random rows they hardly do
#: (two logits within 1e-5 of each other occur once in tens of thousands of
#: positions). Shapes and work are unchanged.
PAIR_SHARE = 3e-4
#: Queries and keys have entries of spread QK_GAIN / sqrt(h) and values of
#: V_GAIN / sqrt(h) (0.12 and 0.08 at h=768, against GPT-2's 0.02), and the
#: attention output projection is doubled. With GPT-2's own initial spread
#: attention is nearly uniform, every position's output is nearly the same
#: vector, and greedy decoding falls into one repeated token whatever the
#: prompt: served tokens would then test almost nothing. With sharp
#: attention the next token depends on the context (40 distinct tokens of 40
#: at GPT-2-small width), so a fault in the cache, the positions or the
#: attention changes what is served.
QK_GAIN = 3.3
V_GAIN = 2.2


def leaf_shapes(cfg: dict, positions: int) -> dict:
    h, f = cfg["n_embd"], cfg["n_inner"]
    shapes = {"wte": (cfg["vocab_size"], h), "wpe": (positions, h),
              "lnf_w": (h,), "lnf_b": (h,)}
    for i in range(cfg["n_layer"]):
        p = f"h{i}."
        shapes.update({
            p + "ln1_w": (h,), p + "ln1_b": (h,),
            p + "q_w": (h, h), p + "q_b": (h,), p + "k_w": (h, h),
            p + "k_b": (h,), p + "v_w": (h, h), p + "v_b": (h,),
            p + "o_w": (h, h), p + "o_b": (h,),
            p + "ln2_w": (h,), p + "ln2_b": (h,),
            p + "fc_w": (h, f), p + "fc_b": (f,),
            p + "proj_w": (f, h), p + "proj_b": (h,)})
    return shapes


def _scaled(leaf, x, h, layers, sharp):
    """A leaf from standard-normal draws ``x``."""
    if leaf in ("ln1_w", "ln2_w", "lnf_w"):
        return 1.0 + 0.1 * x      # norms away from the identity, biases away
    if leaf.endswith("_b"):       # from zero: a part left out then shows
        return STD * x
    if sharp and leaf in ("q_w", "k_w"):
        return (QK_GAIN / h ** 0.5) * x
    if sharp and leaf == "v_w":
        return (V_GAIN / h ** 0.5) * x
    if leaf in ("o_w", "proj_w"):     # GPT-2's residual scaling
        return ((2.0 if sharp and leaf == "o_w" else 1.0) * STD
                / (2.0 * layers) ** 0.5) * x
    return STD * x


@functools.partial(jax.jit, static_argnums=(1, 2, 3, 4, 5, 6))
def _make(seed_words, vocab, positions, h, f, layers, sharp):
    cfg = {"vocab_size": vocab, "n_embd": h, "n_inner": f, "n_layer": 1}
    shapes = leaf_shapes(cfg, positions)
    key = jax.random.wrap_key_data(seed_words, impl="threefry2x32")
    out = {}
    # one draw per kind of leaf, all layers at once: a few dozen operations
    # to trace and compile in place of one per leaf
    for i, (name, shape) in enumerate(shapes.items()):
        k = jax.random.fold_in(key, i)
        leaf = name.split(".")[-1]
        if name == "wte":
            x = jax.random.normal(k, shape, jnp.float32)
            half = jax.random.normal(jax.random.fold_in(k, 1),
                                     ((vocab + 1) // 2, h), jnp.float32)
            out[name] = STD * (jnp.repeat(half, 2, axis=0)[:vocab]
                               + PAIR_SHARE * x)
        elif name.startswith("h0."):
            x = jax.random.normal(k, (layers,) + shape, jnp.float32)
            for layer in range(layers):
                out[f"h{layer}.{leaf}"] = _scaled(leaf, x[layer], h, layers,
                                                  sharp)
        else:
            out[name] = _scaled(leaf, jax.random.normal(k, shape, jnp.float32),
                                h, layers, sharp)
    return out


def make_gpt_weights(cfg: dict, seed: int, positions: int,
                     sharp_attention: bool = True) -> dict:
    """``{name: float32 array}`` for the configuration, from the seed.
    Served models get sharp attention (see QK_GAIN); a training run starts
    from GPT-2's own spread (``sharp_attention=False``)."""
    s = int(seed) & ((1 << 64) - 1)
    words = np.array([s >> 32, s & 0xFFFFFFFF], np.uint32)
    return _make(words, cfg["vocab_size"], positions, cfg["n_embd"],
                 cfg["n_inner"], cfg["n_layer"], bool(sharp_attention))
