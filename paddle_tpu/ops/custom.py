"""Custom-op extension path: register user ops (jnp compositions, Pallas
TPU kernels, or host C/C++ callbacks) into the framework op surface.

Reference: paddle/fluid/framework/custom_operator.cc:511
RegisterOperatorWithMetaInfo (dynamic registration of ops loaded from user
.so files) + python/paddle/utils/cpp_extension/ (setuptools JIT build).

TPU design (SURVEY §7 decision 3): a custom op is any traceable function —
the dispatch funnel gives it autograd (vjp), AMP visibility, nan-checks
and profiling for free, so "registration" is just binding it into the ops
namespace. Three tiers:
- :func:`register_op` — pure jnp/lax composition (covers ~everything).
- :func:`register_pallas_op` — hand-written Pallas TPU kernel for the rare
  op XLA schedules badly; runs in interpret mode off-TPU so tests stay
  hardware-independent.
- :func:`register_cpp_op` — host-side C/C++ function (built from source
  with the system toolchain, bound via ctypes) wrapped in
  ``jax.pure_callback`` — the ctypes analog of PD_BUILD_OP for host-side
  pre/post-processing.
"""
from __future__ import annotations

import ctypes
import os
import subprocess
from typing import Callable, Optional, Sequence

import numpy as np
import jax
import jax.numpy as jnp

from .dispatch import apply, OP_REGISTRY
from ..core.pallas_mode import resolve_interpret
from ..core.tensor import Tensor


def register_op(name: str, fn: Callable, module=None):
    """Bind ``fn(*raw_arrays, **attrs)`` as op ``name`` on the ops
    namespace: ``paddle.ops.<name>(tensors...)`` with autograd via the
    dispatch funnel (reference: custom_operator.cc RegisterOperator)."""
    import sys
    mod = module or sys.modules["paddle_tpu.ops"]
    if hasattr(mod, name):
        raise ValueError(f"op {name!r} already registered")

    def api(*args, **attrs):
        return apply(name, fn, *args, **attrs)
    api.__name__ = name
    api.__doc__ = fn.__doc__
    setattr(mod, name, api)
    return api


def register_pallas_op(name: str, kernel_call: Callable, module=None):
    """Register an op whose implementation is a pallas_call wrapper.
    ``kernel_call(*raws, interpret=...)`` must accept ``interpret`` so the
    op runs everywhere (interpret=True off-TPU)."""
    def fn(*raws, **attrs):
        return kernel_call(*raws, interpret=resolve_interpret(name),
                           **attrs)
    fn.__doc__ = kernel_call.__doc__
    return register_op(name, fn, module=module)


def register_cpp_op(name: str, source: str, fn_name: Optional[str] = None,
                    build_dir: Optional[str] = None, module=None):
    """Compile a C/C++ source (exporting
    ``void <fn_name>(const float* in, float* out, long n)`` with C
    linkage) and register it as an elementwise-shaped host op via
    jax.pure_callback (reference: utils/cpp_extension/cpp_extension.py
    setuptools JIT build + PD_BUILD_OP)."""
    fn_name = fn_name or name
    build_dir = build_dir or os.path.join(
        os.path.expanduser("~/.cache/paddle_tpu"), "cpp_ops")
    os.makedirs(build_dir, exist_ok=True)
    src_path = os.path.join(build_dir, f"{name}.cpp")
    so_path = os.path.join(build_dir, f"lib{name}.so")
    with open(src_path, "w") as f:
        f.write(source)
    subprocess.run(["g++", "-O2", "-shared", "-fPIC", "-o", so_path,
                    src_path], check=True, capture_output=True)
    lib = ctypes.CDLL(so_path)
    cfn = getattr(lib, fn_name)
    cfn.argtypes = [ctypes.POINTER(ctypes.c_float),
                    ctypes.POINTER(ctypes.c_float), ctypes.c_long]

    def host(x):
        x = np.ascontiguousarray(x, np.float32)
        out = np.empty_like(x)
        cfn(x.ctypes.data_as(ctypes.POINTER(ctypes.c_float)),
            out.ctypes.data_as(ctypes.POINTER(ctypes.c_float)), x.size)
        return out

    def fn(a):
        return jax.pure_callback(
            host, jax.ShapeDtypeStruct(a.shape, jnp.float32), a,
            vmap_method="sequential")
    return register_op(name, fn, module=module)


# -- the shipped Pallas kernel: greedy NMS ------------------------------------
#
# Why this op (VERDICT r3 task 10 / profiler finding): greedy NMS is an
# inherently sequential scan over score-sorted candidates; the XLA lowering
# of lax.scan launches one tiny fused loop body per candidate with the
# [k,k] IoU matrix re-read from HBM each step. The Pallas kernel keeps the
# IoU matrix and the kept-mask resident in VMEM across the whole loop —
# one kernel launch, zero HBM traffic in the loop body.

def _nms_kernel(iou_ref, valid_ref, thr_ref, kept_ref, *, unroll=1):
    # Mosaic-friendly formulation: everything 2-D, the kept-mask carried
    # through the fori_loop in vector registers (no per-element VMEM
    # stores), dynamic column selection via a masked reduction.
    k = iou_ref.shape[0]
    iou = iou_ref[:]                                          # [k, k]
    vvec = valid_ref[:]                                       # [k, 1]
    thr = thr_ref[0, 0]
    row_ids = jax.lax.broadcasted_iota(jnp.int32, (k, 1), 0)
    col_ids = jax.lax.broadcasted_iota(jnp.int32, (1, k), 1)

    def body(i, kept):                                        # kept [k, 1]
        row = jnp.sum(iou * (col_ids == i).astype(iou.dtype),
                      axis=1, keepdims=True)                  # iou[:, i]
        sup = jnp.any((kept == 1) & (row > thr) & (row_ids < i))
        valid_i = jnp.any((row_ids == i) & (vvec != 0))
        keep_i = jnp.logical_and(valid_i, jnp.logical_not(sup))
        return jnp.where(row_ids == i, keep_i.astype(jnp.int32), kept)

    kept_ref[:] = jax.lax.fori_loop(0, k, body,
                                    jnp.zeros((k, 1), jnp.int32),
                                    unroll=unroll)


def _nms_unroll(k: int) -> int:
    """Loop-unroll factor from the autotuner's winner cache (key
    ``nms|{platform}|k{k}``); 1 — the historical behavior — when no
    winner is known. The sequential scan's body is tiny, so unrolling
    amortizes per-iteration scalar overhead."""
    try:
        from ..tuner import get_nms_config
        cfg = get_nms_config(k)
        u = int(cfg["unroll"]) if cfg else 1
    except Exception:
        return 1
    # a bad factor would change trip arithmetic; only accept exact
    # divisors of the candidate count
    return u if u >= 1 and k % u == 0 else 1


def pallas_greedy_nms(iou, valid, thr, interpret=None, unroll=None):
    """Greedy NMS over score-sorted candidates as ONE Pallas kernel.

    iou [k,k] f32 (symmetric, sorted by score desc), valid [k] int32,
    thr [1] f32 → kept mask [k] int32. Matches the lax.scan reference in
    detection._greedy_nms_mask (equivalence-tested); the IoU matrix and
    the mask stay VMEM/register resident across the whole loop.
    ``unroll=None`` defers the loop-unroll factor to the tuner cache.
    """
    import functools

    from jax.experimental import pallas as pl

    k = iou.shape[0]
    if unroll is None:
        unroll = _nms_unroll(k)
    out = pl.pallas_call(
        functools.partial(_nms_kernel, unroll=int(unroll)),
        out_shape=jax.ShapeDtypeStruct((k, 1), jnp.int32),
        interpret=resolve_interpret("greedy_nms", interpret),
        name="greedy_nms",
    )(iou.astype(jnp.float32), valid.reshape(k, 1).astype(jnp.int32),
      thr.reshape(1, 1).astype(jnp.float32))
    return out.reshape(k)
