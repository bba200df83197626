"""Compile-or-interpret: the one decision every ``pallas_call`` site shares.

A Pallas TPU kernel is compiled by Mosaic when jax runs on a TPU and run
by the Pallas interpreter everywhere else (same numerics, so CPU tests
cover the kernel's math). Every ``pallas_call`` in the package asks
:func:`resolve_interpret` under a stable kernel name; the answer is
recorded so a caller — ``chip_smoke.py``, a test — can assert that no
kernel on its path was interpreted.
"""
from __future__ import annotations

import threading
from typing import Dict, Optional

import jax

_LOCK = threading.Lock()
_CHOSEN: Dict[str, bool] = {}


def resolve_interpret(kernel: str, requested: Optional[bool] = None) -> bool:
    """``interpret=`` for the ``pallas_call`` named ``kernel``: the
    caller's explicit ``requested`` value, else True exactly when the
    default backend is not a TPU. Records the answer (trace time)."""
    interpret = (jax.default_backend() != "tpu" if requested is None
                 else requested)
    with _LOCK:
        _CHOSEN[kernel] = _CHOSEN.get(kernel, False) or interpret
    return interpret


def chosen_modes() -> Dict[str, bool]:
    """``{kernel name: interpreted}`` for every kernel traced so far in
    this process; True if any trace of that kernel was interpreted."""
    with _LOCK:
        return dict(_CHOSEN)
