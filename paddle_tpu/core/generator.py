"""Random generator: global + per-name RNG state.

TPU-native equivalent of the reference Generator
(reference: paddle/fluid/framework/generator.cc, python/paddle/fluid/generator.py,
`paddle.seed`). On TPU randomness is functional: a Generator owns a JAX PRNG key
and hands out split subkeys; compiled code threads keys explicitly.

Also hosts the RNG state-tracker used for parallel dropout determinism
(reference: fleet/meta_parallel/parallel_layers/random.py RNGStatesTracker).
"""
from __future__ import annotations

import contextlib
import threading

import numpy as np
import jax


class Generator:
    def __init__(self, seed: int = 0):
        self._seed = int(seed)
        # the key is built on first use: PRNGKey initialises the XLA
        # backend, and a process that only imports the package (launcher,
        # spawn/DataLoader parent) must not take the chip from its children
        self._key = None
        self._lock = threading.Lock()

    def manual_seed(self, seed: int):
        self._seed = int(seed)
        self._key = None
        return self

    seed = manual_seed

    def initial_seed(self) -> int:
        return self._seed

    def _current_key(self):
        if self._key is None:
            self._key = jax.random.PRNGKey(self._seed)
        return self._key

    def split(self, n: int = 1):
        """Return n fresh subkeys, advancing the state."""
        with self._lock:
            keys = jax.random.split(self._current_key(), n + 1)
            self._key = keys[0]
            return keys[1] if n == 1 else keys[1:]

    def get_state(self):
        with self._lock:
            return np.asarray(self._current_key())

    def set_state(self, state):
        self._key = jax.numpy.asarray(state, dtype=jax.numpy.uint32)


_DEFAULT = Generator(0)
_NUMPY_SEEDED = [False]

# While a functionalization trace is active (paddle_tpu/jit/functionalize.py),
# key draws are rerouted through the trace's key argument so compiled programs
# get fresh randomness per call instead of a baked-in constant key.
_TRACE_HOOK = [None]


def default_generator() -> Generator:
    return _DEFAULT


def seed(value: int) -> Generator:
    """paddle.seed parity (reference: framework/generator.cc seeds all device
    generators; here one functional key feeds all devices)."""
    _DEFAULT.manual_seed(value)
    np.random.seed(value & 0xFFFFFFFF)
    _NUMPY_SEEDED[0] = True
    return _DEFAULT


def next_key():
    if _TRACE_HOOK[0] is not None:
        return _TRACE_HOOK[0]()
    return _DEFAULT.split(1)


def get_rng_state():
    return _DEFAULT.get_state()


def set_rng_state(state):
    _DEFAULT.set_state(state)


class RNGStatesTracker:
    """Named RNG states so e.g. tensor-parallel dropout can be identical inside
    a TP group but different across DP ranks
    (reference: fleet/meta_parallel/parallel_layers/random.py:30)."""

    def __init__(self):
        self._states = {}

    def add(self, name: str, seed_value: int):
        if name in self._states:
            raise ValueError(f"RNG state {name} already exists")
        self._states[name] = Generator(seed_value)

    def reset(self):
        self._states = {}

    @contextlib.contextmanager
    def rng_state(self, name: str):
        if name not in self._states:
            raise KeyError(f"RNG state {name} not registered")
        global _DEFAULT
        prev = _DEFAULT
        _DEFAULT = self._states[name]
        try:
            yield
        finally:
            _DEFAULT = prev


_TRACKER = RNGStatesTracker()


def get_rng_state_tracker() -> RNGStatesTracker:
    return _TRACKER
