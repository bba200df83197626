"""Compile-or-interpret: the one decision every ``pallas_call`` site shares.

A Pallas TPU kernel is compiled by Mosaic when jax runs on a TPU and run
by the Pallas interpreter everywhere else (same numerics, so CPU tests
cover the kernel's math). Every ``pallas_call`` in the package asks
:func:`resolve_interpret` under a stable kernel name; the answer is
recorded so a caller — ``chip_smoke.py``, a test — can assert that no
kernel on its path was interpreted. A kernel whose dots follow its input's
dtype (the flash family), or take another than its input's (the latent walk
of ``paged_attn``: bfloat16 parts of float32 operands), records that operand
dtype beside it, so the same callers can assert which width the MXU was fed.
"""
from __future__ import annotations

import threading
from typing import Dict, Optional, Set, Tuple

import jax
import numpy as np

_LOCK = threading.Lock()
_CHOSEN: Dict[str, bool] = {}
_OPERANDS: Dict[str, Set[str]] = {}


def resolve_interpret(kernel: str, requested: Optional[bool] = None,
                      operand_dtype=None) -> bool:
    """``interpret=`` for the ``pallas_call`` named ``kernel``: the
    caller's explicit ``requested`` value, else True exactly when the
    default backend is not a TPU. Records the answer (trace time), and
    ``operand_dtype`` — the dtype the kernel's dots take their operands
    in — when the caller names one."""
    interpret = (jax.default_backend() != "tpu" if requested is None
                 else requested)
    with _LOCK:
        _CHOSEN[kernel] = _CHOSEN.get(kernel, False) or interpret
    if operand_dtype is not None:
        record_operand_dtype(kernel, operand_dtype)
    return interpret


def record_operand_dtype(kernel: str, operand_dtype) -> None:
    """Record (trace time) that a trace of ``kernel`` feeds its dots
    ``operand_dtype``: for a call site that knows the dtype only in one of
    its branches (the latent walk of ``paged_attn``)."""
    with _LOCK:
        _OPERANDS.setdefault(kernel, set()).add(np.dtype(operand_dtype).name)


def chosen_modes() -> Dict[str, bool]:
    """``{kernel name: interpreted}`` for every kernel traced so far in
    this process; True if any trace of that kernel was interpreted."""
    with _LOCK:
        return dict(_CHOSEN)


def chosen_operand_dtypes() -> Dict[str, Tuple[str, ...]]:
    """``{kernel name: sorted dtype names}`` of the MXU operand dtypes
    every trace of that kernel so far in this process was built with
    (kernels that name none are absent)."""
    with _LOCK:
        return {k: tuple(sorted(v)) for k, v in _OPERANDS.items()}
