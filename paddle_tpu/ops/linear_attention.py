"""Decayed linear attention in its chunked form (forward only).

Per head, with decay rate ``s > 0`` (``lam = exp(-s)``), a ``[D, D]`` state
and one token a step (Lightning Attention-2, arXiv:2401.04658):

    S_t = lam * S_{t-1} + k_t v_t^T          y_t = S_t^T q_t

so ``y_t = sum_{j <= t} lam^(t-j) (q_t . k_j) v_j``. The chunked form cuts the
rows into sub-chunks of ``SUB`` tokens. Inside a sub-chunk the sum over ``j``
is a masked ``[SUB, SUB]`` product (``q k^T`` times ``lam^(i-j)`` under the
causal mask, then times ``v``); what came before the sub-chunk arrives
through the state (``lam^(i+1) * S^T q_i``), and the state moves on by the
whole sub-chunk at once. It is the same sum in another order: exact against
the recurrence to float32 rounding.

No power of ``lam`` is ever divided by. ``lam^SUB`` underflows for the
fastest heads (``s = 0.84`` gives ``exp(-108)``), so a form that scales
``k_j`` by ``lam^-j`` overflows where this one multiplies by
``exp(-s * (i - j))`` of non-negative differences only.

``lens`` says how many of a row's ``T`` tokens are real (right padding): the
outputs of the padding are junk and the state returned is the state after
token ``lens - 1``, so a prompt's last, shorter chunk leaves what the next
decode step must find. The one-token case (``T = 1``) is that decode step's
update.

reference: none. The reference codebase has no linear-attention operator;
the parity target is the token-by-token recurrence above
(``benchmark/reference/sala_ref.py:linear_layer``).
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

__all__ = ["decayed_linear_attention", "SUB"]

#: rows of a sub-chunk: one MXU tile, and ``[SUB, SUB]`` decay masks
SUB = 128


def _sub_chunk(state, q, k, v, rate, n):
    """One sub-chunk of at most ``SUB`` rows. ``state`` ``[B, H, D, Dv]``,
    ``q k`` ``[B, C, H, D]``, ``v`` ``[B, C, H, Dv]``, ``rate`` ``[H]``,
    ``n`` ``[B]`` (real rows, ``0 <= n <= C``): ``(y [B, C, H, Dv],
    state')``."""
    c = q.shape[1]
    i = jnp.arange(c, dtype=jnp.float32)
    real = jnp.arange(c)[None] < n[:, None]                        # [B, C]
    # rows of the padding add nothing to the state or to later rows
    k = jnp.where(real[:, :, None, None], k, 0.0)
    diff = i[:, None] - i[None]                                    # i - j
    within = jnp.where(diff >= 0, jnp.exp(
        -rate[:, None, None] * jnp.maximum(diff, 0.0)), 0.0)       # [H, C, C]
    scores = jnp.einsum("bihd,bjhd->bhij", q, k) * within[None]
    y = jnp.einsum("bhij,bjhe->bihe", scores, v)
    carried = jnp.exp(-rate[None] * (i[:, None] + 1.0))            # [C, H]
    y = y + jnp.einsum("bihd,bhde->bihe", q, state) * carried[None, :, :,
                                                              None]
    # the state after row n - 1: row j has decayed n - 1 - j times
    nf = n.astype(jnp.float32)
    left = nf[:, None] - 1.0 - i[None]                             # [B, C]
    to_end = jnp.where(left[..., None] >= 0, jnp.exp(
        -rate[None, None] * jnp.maximum(left, 0.0)[..., None]), 0.0)
    state = (state * jnp.exp(-rate[None] * nf[:, None])[..., None, None]
             + jnp.einsum("bjhd,bjhe->bhde", k * to_end[..., None], v))
    return y, state


def decayed_linear_attention(q, k, v, decay, state, lens):
    """``q k`` ``[B, T, H, D]``, ``v`` ``[B, T, H, Dv]``, ``decay`` ``[H]``
    (the rates ``s_h``, ``lam_h = exp(-s_h)``), ``state`` ``[B, H, D, Dv]``
    (float32), ``lens`` ``[B]`` int32 -> ``(y [B, T, H, Dv], state')``.
    ``T`` is at most ``SUB`` or a multiple of it. Any scale of the scores
    is the caller's (on ``q``)."""
    t = q.shape[1]
    lens = jnp.clip(lens.astype(jnp.int32), 0, t)
    rate = decay.astype(jnp.float32)
    if t <= SUB:
        return _sub_chunk(state, q, k, v, rate, lens)
    if t % SUB:
        raise ValueError(f"{t} rows are neither at most {SUB} nor a "
                         f"multiple of it")

    def cut(x):     # [B, T, ...] -> [T / SUB, B, SUB, ...]
        return jnp.moveaxis(
            x.reshape((x.shape[0], t // SUB, SUB) + x.shape[2:]), 1, 0)

    def step(carry, xs):
        qc, kc, vc, c = xs
        y, carry = _sub_chunk(carry, qc, kc, vc, rate,
                              jnp.clip(lens - c * SUB, 0, SUB))
        return carry, y

    state, ys = jax.lax.scan(
        step, state, (cut(q), cut(k), cut(v), jnp.arange(t // SUB)))
    return jnp.moveaxis(ys, 0, 1).reshape(v.shape), state
