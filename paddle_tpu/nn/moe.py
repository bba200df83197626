"""RMSNorm and the sparse-expert feed-forward layer (top-k, sigmoid or
softmax scores, no dropped tokens). The arithmetic is in ``ops/moe.py``; this
is the ``Layer`` that owns the parameters and is told which experts it
holds."""
from __future__ import annotations

from typing import Optional, Tuple

import jax
import jax.numpy as jnp

from ..ops.dispatch import apply
from ..ops import moe as _moe
from . import initializer as I
from .layer_base import Layer


def rms_norm(x, w, eps: float):
    """``x * rsqrt(mean(x^2, -1) + eps) * w`` in float32 (raw arrays)."""
    x32 = x.astype(jnp.float32)
    y = x32 * jax.lax.rsqrt(jnp.mean(x32 * x32, axis=-1, keepdims=True) + eps)
    return (y * w).astype(x.dtype)


def swiglu(f, w1, w3, w2):
    """The gated feed-forward ``(silu(f W1) * (f W3)) W2`` (raw arrays)."""
    return (jax.nn.silu(f @ w1) * (f @ w3)) @ w2


class RMSNorm(Layer):
    """Root-mean-square norm over the last axis, no bias."""

    def __init__(self, size: int, epsilon: float = 1e-5):
        super().__init__()
        self.epsilon = float(epsilon)
        self.weight = self.create_parameter(
            [size], default_initializer=I.Constant(1.0))

    def forward(self, x):
        eps = self.epsilon
        return apply("rms_norm", lambda a, w: rms_norm(a, w, eps), x,
                     self.weight)


class ExpertStack(Layer):
    """The SwiGLU experts held here, stacked: ``w1``/``w3`` ``[n, h, f]``
    (gate and up), ``w2`` ``[n, f, h]`` (down). A checkpoint's
    ``experts.<e>.w1.weight`` is row ``e - expert_lo`` of ``w1``."""

    def __init__(self, num_held: int, hidden: int, width: int):
        super().__init__()
        init = I.Normal(0.0, 0.02)
        self.w1 = self.create_parameter([num_held, hidden, width],
                                        default_initializer=init)
        self.w3 = self.create_parameter([num_held, hidden, width],
                                        default_initializer=init)
        self.w2 = self.create_parameter([num_held, width, hidden],
                                        default_initializer=init)


class MoEFeedForward(Layer):
    """``sum over the top_k chosen e of w_e * SwiGLU_e(x)``.

    ``route="sigmoid"``: scores are ``sigmoid(x @ gate)``; the chosen set is
    the ``top_k`` of score + ``expert_bias``; the weights are the scores
    alone, normalised over the chosen (``norm_topk``, with ``eps``) and
    scaled. ``route="softmax"``: the ``top_k`` of ``softmax(x @ gate)`` over
    ALL the experts, normalised over the chosen and scaled; there is no
    ``expert_bias`` and no epsilon. ``held = (lo, n)`` says which of the
    ``num_experts`` live here (default: all): either routing is over all of
    them and the result is the held experts' part of the sum (a call of
    more than 128 tokens then sizes its row buffer for the held share:
    ``ops/moe.py:window_sizes``). ``shared``
    experts (a count; one SwiGLU of ``shared * width``) are computed for
    every token by EVERY holder, unweighted or, with ``shared_gate``, times
    ``sigmoid(x @ shared_expert_gate)`` (one number a token): the result is
    then ``Shared(x)`` + the held experts' part, and whoever adds the holders'
    parts up counts the shared one once."""

    def __init__(self, hidden: int, width: int, num_experts: int,
                 top_k: int, norm_topk: bool = True, scale: float = 1.0,
                 held: Optional[Tuple[int, int]] = None, shared: int = 0,
                 eps: float = 1e-6, scope: str = "lfm2",
                 route: str = "sigmoid", shared_gate: bool = False):
        super().__init__()
        if route not in _moe.ROUTES:
            raise ValueError(
                f"route must be one of {_moe.ROUTES}, got {route!r}")
        if shared_gate and not shared:
            raise ValueError("shared_gate gates a shared expert: shared is 0")
        self.top_k, self.norm_topk, self.scale = top_k, norm_topk, scale
        self.eps, self.scope, self.route = eps, scope, route
        self.expert_lo, num_held = held or (0, num_experts)
        if not (0 <= self.expert_lo and num_held >= 1
                and self.expert_lo + num_held <= num_experts):
            raise ValueError(f"held experts {held} outside 0..{num_experts}")
        if top_k > num_experts:
            raise ValueError(f"top_k {top_k} of {num_experts} experts")
        self.gate = Layer()
        self.gate.weight = self.create_parameter(
            [hidden, num_experts], default_initializer=I.Normal(0.0, 0.02))
        if route == "sigmoid":      # the selection bias is that routing's
            self.expert_bias = self.create_parameter(
                [num_experts], default_initializer=I.Constant(0.0))
        self.experts = ExpertStack(num_held, hidden, width)
        if shared:      # one stack row: the same leaves as an expert's
            self.shared_experts = ExpertStack(1, hidden, shared * width)
        if shared_gate:
            self.shared_expert_gate = Layer()
            self.shared_expert_gate.weight = self.create_parameter(
                [hidden, 1], default_initializer=I.Normal(0.0, 0.02))

    def forward(self, x):
        kw = dict(top_k=self.top_k, norm_topk=self.norm_topk,
                  scale=self.scale, expert_lo=self.expert_lo, eps=self.eps,
                  scope=self.scope, route=self.route)
        biased = self.route == "sigmoid"

        def _ffn(a, gate, *rest):
            bias, rest = (rest[0], rest[1:]) if biased else (None, rest)
            (w1, w3, w2), shared = rest[:3], rest[3:]
            out, _counts = _moe.moe_feed_forward(
                a.reshape(-1, a.shape[-1]), gate, bias, w1, w3, w2, **kw)
            out = out.reshape(a.shape)
            if not shared:
                return out
            both = swiglu(a, *(w[0] for w in shared[:3]))
            if len(shared) == 4:        # the gated shared expert
                both = jax.nn.sigmoid(a @ shared[3]) * both
            return out + both
        e = self.experts
        sh = getattr(self, "shared_experts", None)
        sg = getattr(self, "shared_expert_gate", None)
        return apply("moe_feed_forward", _ffn, x, self.gate.weight,
                     *((self.expert_bias,) if biased else ()),
                     e.w1, e.w3, e.w2,
                     *((sh.w1, sh.w3, sh.w2) if sh is not None else ()),
                     *((sg.weight,) if sg is not None else ()))
