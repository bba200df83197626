"""paddle.amp: automatic mixed precision.

Reference: python/paddle/amp/auto_cast.py:20, grad_scaler.py:20 →
fluid/dygraph/amp/auto_cast.py:91 amp_guard + loss_scaler.py:27 AmpScaler,
C++ white/black lists imperative/amp_auto_cast.h:31, and the AMP ops
check_finite_and_unscale / update_loss_scaling (operators/amp/).

TPU design: the preferred low dtype is bfloat16 (MXU native, same exponent
range as fp32 ⇒ loss scaling is a no-op kept for API parity); float16 is
supported with real dynamic loss scaling for parity with ported scripts. The
autocast hook lives in the op-dispatch funnel (ops/dispatch.py), exactly
where the reference tracer casts inputs (imperative/tracer.cc:162).
"""
from __future__ import annotations

import contextlib
from typing import Dict, List, Optional

import numpy as np
import jax.numpy as jnp

from ..core.tensor import Tensor
from ..core import dtypes as _dt
from ..core import monitor as _monitor
from ..ops.dispatch import register_amp_handler, apply_raw

# reference: imperative/amp_auto_cast.cc default lists
WHITE_LIST = {
    "conv1d", "conv2d", "conv3d", "conv1d_transpose", "conv2d_transpose",
    "conv3d_transpose", "matmul_v2", "bmm", "mm", "mv", "linear", "mul",
    "einsum", "addmm",
}
BLACK_LIST = {
    "exp", "square", "log", "log2", "log10", "log1p", "reduce_mean",
    "reduce_sum", "logsumexp", "mean", "softmax_with_cross_entropy",
    "sigmoid_cross_entropy_with_logits", "bce_loss", "nll_loss",
    "cross_entropy", "p_norm", "dist", "squared_l2_norm", "cumsum",
    "mse_loss", "l1_loss", "kldiv_loss", "softmax", "log_softmax",
}
# Normalization ops compute their statistics in f32 internally
# (nn/functional/norm.py _stat_dtype), so under bf16 they are dtype-NEUTRAL:
# bf16 activations flow straight through without the f32 up/down-cast
# ping-pong that doubles conv→bn HBM traffic (the reference keeps bn fp32
# because fp16 statistics overflow — fp16 keeps that behavior here).
NORM_OPS = {"layer_norm", "batch_norm", "instance_norm", "group_norm",
            "norm"}
# So does the hard-label softmax cross entropy (nn/functional/loss.py): its
# sums and its loss are f32 from the logits as given, and those logits are
# what it keeps for backward. Cast up before it, the cast's f32 [N, V]
# result would be kept in their place.
F32_INSIDE_OPS = NORM_OPS | {"softmax_cross_entropy_rows"}

_STATE = {"enabled": False, "dtype": None, "level": "O1",
          "custom_white": set(), "custom_black": set()}


def _amp_hook(op_name: str, tensors: List[Tensor]) -> List[Tensor]:
    if not _STATE["enabled"]:
        return tensors
    low = _STATE["dtype"]
    white = (WHITE_LIST | _STATE["custom_white"]) - _STATE["custom_black"]
    black = BLACK_LIST | _STATE["custom_black"]
    if np.dtype(low) == np.dtype("float16"):
        black = black | F32_INSIDE_OPS
    elif op_name in F32_INSIDE_OPS and op_name not in _STATE["custom_black"]:
        return tensors  # bf16-neutral: f32 stats happen inside the op
    if _STATE["level"] == "O2":
        cast_low = op_name not in black
    else:
        cast_low = op_name in white
    out = []
    for t in tensors:
        if _dt.is_floating(t.dtype):
            if cast_low and t.dtype != low and t.dtype != np.dtype("float64"):
                out.append(_cast_keep_graph(t, low))
                continue
            if (not cast_low and op_name in black
                    and t.dtype == np.dtype(low)):
                out.append(_cast_keep_graph(t, np.float32))
                continue
        out.append(t)
    return out


def _cast_keep_graph(t: Tensor, dtype):
    # cast through the dispatch funnel so grads flow (cast has a vjp)
    d = np.dtype(dtype)
    from ..ops.dispatch import apply
    prev = _STATE["enabled"]
    _STATE["enabled"] = False  # avoid recursive autocast of the cast op
    try:
        return apply("amp_cast", lambda x: x.astype(d), t)
    finally:
        _STATE["enabled"] = prev


register_amp_handler(_amp_hook)


@contextlib.contextmanager
def auto_cast(enable=True, custom_white_list=None, custom_black_list=None,
              level="O1", dtype="bfloat16"):
    """reference: amp/auto_cast.py:20 (dtype default here is bf16 — the TPU
    native low precision; pass 'float16' for parity experiments)."""
    prev = dict(_STATE)
    _STATE["enabled"] = bool(enable)
    _STATE["dtype"] = _dt.convert_dtype(dtype)
    _STATE["level"] = level
    _STATE["custom_white"] = set(custom_white_list or ())
    _STATE["custom_black"] = set(custom_black_list or ())
    try:
        yield
    finally:
        _STATE.update(prev)


amp_guard = auto_cast


def enable_operator_amp(level="O1", dtype="bfloat16", custom_white_list=None,
                        custom_black_list=None):
    """Globally enable per-op auto-cast without a context manager — the
    fleet-strategy path (reference: the AMP meta-optimizer makes the whole
    program mixed-precision rather than a scoped region)."""
    _STATE["enabled"] = True
    _STATE["dtype"] = _dt.convert_dtype(dtype)
    _STATE["level"] = level
    _STATE["custom_white"] = set(custom_white_list or ())
    _STATE["custom_black"] = set(custom_black_list or ())


def disable_operator_amp():
    _STATE["enabled"] = False


def is_auto_cast_enabled():
    return _STATE["enabled"]


def get_amp_dtype():
    return _STATE["dtype"]


def decorate(models, optimizers=None, level="O1", dtype="bfloat16",
             master_weight=None, save_dtype=None):
    """reference: amp/auto_cast.py decorate (O2 casts model params to the low
    dtype; optimizers keep fp32 master weights via multi_precision)."""
    low = _dt.convert_dtype(dtype)
    single = not isinstance(models, (list, tuple))
    model_list = [models] if single else list(models)
    if level == "O2":
        for m in model_list:
            m._cast_to(low)
            m._casted_by_pure_fp16 = True
    if optimizers is None:
        return models if single else model_list
    return (models if single else model_list), optimizers


import functools
import jax


@jax.jit
def _fused_unscale(grads, scale):
    """check_finite_and_unscale as one XLA program (reference:
    operators/amp/check_finite_and_unscale_op). ``scale`` is traced so
    dynamic loss-scale changes don't recompile."""
    inv = 1.0 / scale
    out = tuple(g * inv.astype(g.dtype) for g in grads)
    finite = jnp.stack([jnp.all(jnp.isfinite(g)) for g in out])
    return out, ~jnp.all(finite)


class GradScaler:
    """Dynamic loss scaling (reference: amp/grad_scaler.py:20 →
    fluid/dygraph/amp/loss_scaler.py:27 AmpScaler; kernels
    check_finite_and_unscale + update_loss_scaling as one fused check here).

    With bf16 (TPU default) scaling is mathematically unnecessary; the class
    still tracks found_inf so ported fp16 scripts behave identically."""

    def __init__(self, enable=True, init_loss_scaling=2.0 ** 15,
                 incr_ratio=2.0, decr_ratio=0.5, incr_every_n_steps=2000,
                 decr_every_n_nan_or_inf=1, use_dynamic_loss_scaling=True):
        self._enable = enable
        self._scale = float(init_loss_scaling) if enable else 1.0
        self._incr_ratio = incr_ratio
        self._decr_ratio = decr_ratio
        self._incr_every_n = incr_every_n_steps
        self._decr_every_n = decr_every_n_nan_or_inf
        self._dynamic = use_dynamic_loss_scaling
        self._good_steps = 0
        self._bad_steps = 0
        self._found_inf = False
        # per-optimizer UNSCALED state (reference: grad_scaler.py caches an
        # OptState per optimizer) so multi-optimizer recipes can't
        # double-unscale or step with still-scaled grads
        self._unscaled_ids = set()

    def scale(self, var):
        if not self._enable:
            return var
        return var * self._scale

    def unscale_(self, optimizer):
        if not self._enable:
            return
        if id(optimizer) in self._unscaled_ids:
            raise RuntimeError(
                "unscale_() has already been called on this optimizer since "
                "the last update()")
        # one fused program: unscale every grad and reduce a single
        # found_inf flag — a single host sync instead of O(n_params)
        # device round-trips (reference fuses this the same way in the
        # check_finite_and_unscale kernel, operators/amp/)
        grads = [p._grad for p in optimizer._parameter_list
                 if p._grad is not None]
        if grads:
            new_grads, found = _fused_unscale(
                tuple(grads), jnp.asarray(self._scale, jnp.float32))
            it = iter(new_grads)
            for p in optimizer._parameter_list:
                if p._grad is not None:
                    p._grad = next(it)
            if bool(found):
                self._found_inf = True
                _monitor.stat_add("amp.found_inf_steps", 1)
        self._unscaled_ids.add(id(optimizer))

    def step(self, optimizer):
        if not self._enable:
            optimizer.step()
            return
        if id(optimizer) not in self._unscaled_ids:
            self.unscale_(optimizer)
        if not self._found_inf:
            optimizer.step()

    def update(self):
        self._unscaled_ids.clear()
        if not (self._enable and self._dynamic):
            self._found_inf = False
            return
        if self._found_inf:
            self._bad_steps += 1
            self._good_steps = 0
            if self._bad_steps >= self._decr_every_n:
                self._scale = max(self._scale * self._decr_ratio, 1.0)
                self._bad_steps = 0
        else:
            self._good_steps += 1
            self._bad_steps = 0
            if self._good_steps >= self._incr_every_n:
                self._scale *= self._incr_ratio
                self._good_steps = 0
        self._found_inf = False
        _monitor.stat_set("amp.loss_scale", self._scale)

    def minimize(self, optimizer, scaled_loss):
        scaled_loss.backward()
        self.step(optimizer)
        self.update()

    def is_enable(self):
        return self._enable

    def is_use_dynamic_loss_scaling(self):
        return self._dynamic

    def get_init_loss_scaling(self):
        return self._scale

    def set_init_loss_scaling(self, v):
        self._scale = float(v)

    def state_dict(self):
        # emits both this repo's historical keys (good_steps/bad_steps) and
        # the reference AmpScaler's (incr_count/decr_count, grad_scaler.py),
        # so checkpoints round-trip with ported scripts in either direction
        return {"scale": self._scale, "incr_ratio": self._incr_ratio,
                "decr_ratio": self._decr_ratio,
                "incr_every_n_steps": self._incr_every_n,
                "decr_every_n_nan_or_inf": self._decr_every_n,
                "good_steps": self._good_steps, "bad_steps": self._bad_steps,
                "incr_count": self._good_steps,
                "decr_count": self._bad_steps,
                "use_dynamic_loss_scaling": self._dynamic,
                "found_inf": self._found_inf}

    def load_state_dict(self, state):
        self._scale = state.get("scale", self._scale)
        self._incr_ratio = state.get("incr_ratio", self._incr_ratio)
        self._decr_ratio = state.get("decr_ratio", self._decr_ratio)
        self._incr_every_n = state.get("incr_every_n_steps",
                                       self._incr_every_n)
        self._decr_every_n = state.get("decr_every_n_nan_or_inf",
                                       self._decr_every_n)
        self._good_steps = int(state.get(
            "good_steps", state.get("incr_count", self._good_steps)))
        self._bad_steps = int(state.get(
            "bad_steps", state.get("decr_count", self._bad_steps)))
        self._dynamic = bool(state.get("use_dynamic_loss_scaling",
                                       self._dynamic))
        self._found_inf = bool(state.get("found_inf", False))


AmpScaler = GradScaler
