"""Seeded MiniCPM-SALA weights, made on the device one layer at a time.

Both sides of the comparison call this with the same seed: the driver loads
each layer into the program's model as it is made (no second copy of the
11.3 GB is ever alive), and the reference makes a layer again when it
reaches it, after the program's state is freed. Leaves are float32.
:func:`clear_programs` drops the makers' compiled programs once the weights
exist: the device keeps a region reserved for the temporaries of every
loaded program (``weights.py``'s maker keeps 1.61 GB, PERF.md 0c), which
this cell cannot spare.

Names (``benchmark/reference/sala_ref.py`` reads them): ``embed`` [V, h],
``head`` [h, V], ``final_norm`` [h], and per layer ``n1 n2`` [h], ``w1 w3``
[h, f], ``w2`` [f, h], then for a sparse layer ``q_w gate_w`` [h, Hq D],
``k_w v_w`` [h, Hkv D], ``o_w`` [Hq D, h], ``q_norm k_norm`` [D], and for a
linear layer ``q_w k_w v_w z_w`` [h, H D], ``o_w`` [H D, h], ``q_norm
k_norm o_norm`` [D]; matrices are ``[in, out]``.

Spreads. Every mixer and feed-forward reads an RMS-normed stream, so a
projection of spread ``1/sqrt(fan_in)`` gives unit outputs. Norm weights lie
away from one, so that a part left out shows. The q/k head norms carry a
gain of ``QK_NORM_GAIN``: softmax scores are then about ``N(0, 3)`` and
attention is sharp, so the next token depends on the context (see
``weights.py``). The decays are the configuration's assumed ones (the
program and the reference compute them from the head count; no weight). The
head is NOT tied: its COLUMNS come in pairs ``PAIR_SHARE`` apart (as the
embedding's rows do in ``weights.py``), so the two best logits of a position
are a pair, and a greedy token tells which of them the program's arithmetic
put first. ``HEAD_STD`` 0.25 gives logits of spread one behind the family's
``1 / (hidden / dim_model_base)``.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np

EMBED_STD = 0.02
HEAD_STD = 0.25
PAIR_SHARE = 3e-4
QK_NORM_GAIN = 1.8

SPARSE = "minicpm4"


def layer_shapes(cfg: dict, i: int) -> dict:
    h, f = cfg["hidden_size"], cfg["intermediate_size"]
    out = {"n1": (h,), "n2": (h,), "w1": (h, f), "w3": (h, f), "w2": (f, h)}
    if cfg["mixer_types"][i] == SPARSE:
        d = cfg["head_dim"]
        wq, wkv = cfg["num_attention_heads"] * d, \
            cfg["num_key_value_heads"] * d
        out.update(q_w=(h, wq), k_w=(h, wkv), v_w=(h, wkv), gate_w=(h, wq),
                   o_w=(wq, h), q_norm=(d,), k_norm=(d,))
    else:
        d = cfg["lightning_head_dim"]
        w = cfg["lightning_nh"] * d
        out.update(q_w=(h, w), k_w=(h, w), v_w=(h, w), z_w=(h, w),
                   o_w=(w, h), q_norm=(d,), k_norm=(d,), o_norm=(d,))
    return out


def _scaled(leaf: str, x):
    if leaf in ("n1", "n2", "final_norm", "o_norm"):
        return 1.0 + 0.1 * x
    if leaf in ("q_norm", "k_norm"):
        return QK_NORM_GAIN * (1.0 + 0.1 * x)
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
            "head": HEAD_STD * (jnp.repeat(half, 2, axis=1)[:, :vocab]
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
