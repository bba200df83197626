"""Fleet utilities: recompute (activation checkpointing) + gradient merge.

reference:
- recompute: python/paddle/distributed/fleet/utils/recompute.py:63
  RecomputeFunction — a PyLayer that drops activations in forward and
  re-runs the block under the SAVED RNG state in backward (:54
  swith_rng_state). TPU design: ``jax.checkpoint`` (remat) expresses the
  same trade inside the compiled graph; RNG determinism holds because
  dropout keys are explicit functional inputs (functionalize.py routes
  every draw through the trace key), so the re-run sees identical keys by
  construction.
- gradient merge: python/paddle/fluid/optimizer.py:5949
  GradientMergeOptimizer — accumulate k micro-batch gradients, step once.
"""
from __future__ import annotations

from typing import Callable, List

import numpy as np
import jax
import jax.numpy as jnp

from ...core.tensor import Tensor
from ...core import autograd_engine as _ag
from ...ops.dispatch import apply
from ...ops.pallas_attention import RESIDUAL_NAMES

# one object for every call: JAX keys its tracing caches by a policy's identity
_KEEP_FLASH_RESIDUALS = jax.checkpoint_policies.save_only_these_names(
    *RESIDUAL_NAMES)


def recompute(function: Callable, *args, **kwargs):
    """reference: fleet/utils/recompute.py:63. Under a trace (to_static /
    hapi fused step — the perf path) the block is wrapped in jax.checkpoint
    so XLA rematerializes instead of stashing activations. In eager mode the
    tape already retains exactly the op-level residuals jax.vjp chose;
    the call is then a transparent passthrough.

    The checkpoint keeps a block's inputs and the flash forward's ``out`` and
    ``lse`` (``pallas_attention.RESIDUAL_NAMES``): only the kernel can make
    them again, so dropping them launches it twice a layer. At 48 heads x
    4,096 rows x 64 they are 26 MB a layer (25.2 bf16 + 0.8 float32), 51 MB
    as the TPU's tiles hold a minor axis of 64 in 128 lanes. A block with no
    flash call inside holds no such name and keeps its inputs alone."""
    use_reentrant = kwargs.pop("use_reentrant", True)
    preserve_rng_state = kwargs.pop("preserve_rng_state", True)
    del use_reentrant, preserve_rng_state

    leaves = [a for a in jax.tree_util.tree_leaves(
        args, is_leaf=lambda x: isinstance(x, Tensor))
        if isinstance(a, Tensor)]
    traced = any(isinstance(l._data, jax.core.Tracer) for l in leaves)
    if not traced:
        return function(*args, **kwargs)

    # one op through the funnel whose impl re-runs `function` under
    # jax.checkpoint; Tensors rebuilt inside so nested framework ops trace
    def impl(*raws):
        def inner(*rs):
            ts = [Tensor(r) for r in rs]
            out = function(*_rebuild(args, ts), **kwargs)
            out_leaves = jax.tree_util.tree_leaves(
                out, is_leaf=lambda x: isinstance(x, Tensor))
            return tuple(o._data if isinstance(o, Tensor) else o
                         for o in out_leaves)
        return jax.checkpoint(inner, policy=_KEEP_FLASH_RESIDUALS)(*raws)

    out_struct = function(*args, **kwargs)  # trace once for the structure
    out_leaves, td = jax.tree_util.tree_flatten(
        out_struct, is_leaf=lambda x: isinstance(x, Tensor))
    res = apply("recompute", impl, *leaves)
    res_list = list(res) if isinstance(res, (list, tuple)) else [res]
    return jax.tree_util.tree_unflatten(td, res_list)


def _rebuild(args, tensors):
    it = iter(tensors)
    return jax.tree_util.tree_map(
        lambda x: next(it) if isinstance(x, Tensor) else x, args,
        is_leaf=lambda x: isinstance(x, Tensor))


class GradientMergeOptimizer:
    """reference: fluid/optimizer.py:5949 — accumulate k steps of gradients,
    apply once (micro-batch accumulation without touching user loops)."""

    def __init__(self, inner_optimizer, k_steps=1, avg=True):
        self._inner = inner_optimizer
        self._k = int(k_steps)
        self._avg = bool(avg)
        self._acc = {}
        self._count = 0

    def __getattr__(self, name):
        return getattr(object.__getattribute__(self, "_inner"), name)

    def step(self):
        inner = self._inner
        self._count += 1
        for p in inner._parameter_list:
            if p._grad is None:
                continue
            if id(p) in self._acc:
                self._acc[id(p)] = self._acc[id(p)] + p._grad
            else:
                self._acc[id(p)] = p._grad
        if self._count < self._k:
            for p in inner._parameter_list:
                p._grad = None
            return
        for p in inner._parameter_list:
            g = self._acc.pop(id(p), None)
            if g is None:
                continue
            p._grad = g / self._k if self._avg else g
        inner.step()
        self._count = 0
        self._acc = {}

    def clear_grad(self, set_to_zero=False):
        self._inner.clear_grad(set_to_zero)

    clear_gradients = clear_grad

    def minimize(self, loss, **kw):
        loss.backward()
        self.step()


class LocalSGDOptimizer:
    """reference: distributed/fleet/meta_optimizers/localsgd_optimizer.py:25
    — run k local optimizer steps between parameter averages instead of
    all-reducing gradients every step.

    TPU framing: inside one process, GSPMD's per-step gradient allreduce
    rides ICI and overlaps with compute, so LocalSGD buys nothing there.
    The win is at the multi-host/DCN boundary — each process trains locally
    for ``k_steps`` and parameters are averaged across processes
    periodically. Pair with ``DataParallel`` and do NOT call
    ``apply_collective_grads`` (the whole point is to skip it); this
    wrapper performs the periodic cross-process parameter average.
    """

    def __init__(self, inner_optimizer, k_steps=1, begin_step=1):
        self._inner = inner_optimizer
        self._k = max(1, int(k_steps))
        self._begin = max(1, int(begin_step))
        self._t = 0

    def __getattr__(self, name):
        return getattr(object.__getattribute__(self, "_inner"), name)

    def _average_params(self):
        from .. import collective as C
        for p in self._inner._parameter_list:
            C.all_reduce(p, op=C.ReduceOp.AVG)

    def step(self):
        self._inner.step()
        self._t += 1
        if self._t >= self._begin and (self._t - self._begin) % self._k == 0:
            self._average_params()

    def clear_grad(self, set_to_zero=False):
        self._inner.clear_grad(set_to_zero)

    clear_gradients = clear_grad

    def minimize(self, loss, **kw):
        loss.backward()
        self.step()
