"""ExecutableCache: compiled-callable cache keyed on (model, shapes, dtype).

The compile-once-reuse layer under the serving engine, the standalone
:class:`~paddle_tpu.inference.Predictor`, the LLM scheduler and the
static decoder — ONE process-wide in-memory cache (``default_cache()``),
so two components over the same program reuse each other's executables.
An entry is whatever ``compile_fn`` returns — a ``jax.jit`` wrapper or an
AOT ``Compiled`` — so each distinct input signature costs exactly one XLA
compile and every later hit is a cheap executable launch. LRU-bounded
with hit/miss/evict counters published to the default StatRegistry
(``serving.executable_cache.*`` on ``/metricsz``; zero misses after
warmup is the steady state).

Persistence (fleet-wide, survives restarts) is two independent tiers:

* JAX's own persistent compilation cache: every ``jit`` in the process,
  not just serving, skips XLA backend compiles that any earlier process
  already paid for. It lives where ``JAX_COMPILATION_CACHE_DIR`` says —
  JAX reads that variable itself and no code here overrides it. Entry
  points (``chip_smoke.py``, ``bench.py``, ``python -m
  paddle_tpu.serving``) call :func:`place_jax_compilation_cache` so that,
  with the variable unset, the cache sits at one fixed git-ignored path
  in the checkout (:data:`IN_CHECKOUT_JAX_CACHE`) — the directory is part
  of the cache key, so it must never move.
* :class:`PersistentExecutableStore` under
  ``$PADDLE_TPU_COMPILE_CACHE/executables`` (or
  :func:`enable_persistent_compilation`): whole serialized AOT
  executables keyed by the cache's own process-stable signature tokens,
  loaded by ``get_or_compile(..., persist_key=...)`` without issuing a
  compile request at all.
"""
from __future__ import annotations

import hashlib
import os
import pickle
import threading
import time
import warnings
from collections import OrderedDict
from typing import Any, Callable, Dict, Optional, Sequence, Tuple

from ..core import monitor as _mon
from ..observability import tracer as _tracer

#: signature element: ((dim, ...), dtype-string) per input array
SigT = Tuple[Tuple[Tuple[int, ...], str], ...]

_DEFAULT_CAPACITY_ENV = "PADDLE_TPU_EXEC_CACHE_SIZE"
_PERSIST_ENV = "PADDLE_TPU_COMPILE_CACHE"

#: /metricsz namespace for the shared cache's counters
_STAT_PREFIX = "serving.executable_cache."

#: bump when the on-disk executable entry format changes
_STORE_VERSION = 2


def signature_of(arrays: Sequence[Any]) -> SigT:
    """Shape/dtype signature of a list of arrays (numpy or jax)."""
    return tuple((tuple(int(d) for d in a.shape), str(a.dtype))
                 for a in arrays)


# -- persistent compilation (fleet-wide, survives restarts) -------------------

#: where JAX's persistent compilation cache goes when
#: ``JAX_COMPILATION_CACHE_DIR`` is unset: <checkout>/.jax_cache
#: (git-ignored; never ~/.cache, a tempfile name, a pid or a timestamp)
IN_CHECKOUT_JAX_CACHE = os.path.join(
    os.path.dirname(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__)))), ".jax_cache")


def place_jax_compilation_cache() -> str:
    """Called once by an entry point before its first compile; returns the
    directory JAX's persistent compilation cache uses.

    With ``JAX_COMPILATION_CACHE_DIR`` set the directory is left to JAX;
    otherwise it becomes :data:`IN_CHECKOUT_JAX_CACHE`. Either way the
    min-compile-time/min-entry-size floors are dropped so every
    executable qualifies."""
    import jax
    placed = os.environ.get("JAX_COMPILATION_CACHE_DIR", "").strip()
    if not placed:
        placed = IN_CHECKOUT_JAX_CACHE
        jax.config.update("jax_compilation_cache_dir", placed)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", -1)
    return placed


_PERSIST_ROOT: Optional[str] = None
_PERSIST_LOCK = threading.Lock()
_PERSIST_RESOLVED = False


def enable_persistent_compilation(path: Optional[str] = None) -> str:
    """Anchor the :class:`PersistentExecutableStore` at
    ``<root>/executables`` and return the root: ``path`` or
    ``$PADDLE_TPU_COMPILE_CACHE``. Idempotent; the first caller's root
    wins. JAX's own compilation cache is not touched (see the module
    docstring)."""
    global _PERSIST_ROOT, _PERSIST_RESOLVED
    with _PERSIST_LOCK:
        if _PERSIST_ROOT is not None:
            return _PERSIST_ROOT
        root = path or os.environ.get(_PERSIST_ENV, "").strip()
        if not root:
            raise ValueError(
                "enable_persistent_compilation: pass a path or set "
                f"{_PERSIST_ENV}")
        _PERSIST_ROOT = os.path.expanduser(root)
        _PERSIST_RESOLVED = True
        return _PERSIST_ROOT


def persistent_root() -> Optional[str]:
    """The active persistence root, auto-enabling from the environment on
    first call; None when persistence is off (no env var, no explicit
    :func:`enable_persistent_compilation`)."""
    global _PERSIST_RESOLVED
    with _PERSIST_LOCK:
        if _PERSIST_ROOT is not None or _PERSIST_RESOLVED:
            return _PERSIST_ROOT
        _PERSIST_RESOLVED = True
        if not os.environ.get(_PERSIST_ENV, "").strip():
            return None
    return enable_persistent_compilation()


def _reset_persistence_for_tests():
    global _PERSIST_ROOT, _PERSIST_RESOLVED, _STORE
    with _PERSIST_LOCK:
        _PERSIST_ROOT = None
        _PERSIST_RESOLVED = False
    with _STORE_LOCK:
        _STORE = None


class PersistentExecutableStore:
    """Whole serialized executables on disk, keyed by process-stable
    cache-key strings.

    Entries are ``pickle((payload, in_tree, out_tree, device_ids))`` —
    the first three from ``jax.experimental.serialize_executable``, the
    last the ids of the devices the executable was compiled for (it is
    loaded onto exactly those, not onto every local device) — under a
    sha256 filename of
    (key, jax version, backend platform, store version) — a jax upgrade
    or platform change simply misses instead of loading an incompatible
    executable. All failure modes (corrupt file, version skew, unpickla-
    ble payload, unwritable dir) degrade to miss-with-warning: a bad
    store can never take down serving, the entry is recompiled and
    rewritten.
    """

    def __init__(self, directory: str):
        self.directory = directory

    def _path(self, key: str) -> str:
        import jax
        try:
            platform = jax.devices()[0].platform
        except Exception:
            platform = "unknown"
        tag = f"{_STORE_VERSION}|{jax.__version__}|{platform}|{key}"
        h = hashlib.sha256(tag.encode()).hexdigest()
        return os.path.join(self.directory, f"{h}.jaxexec")

    def load(self, key: str):
        """The deserialized executable for ``key``, or None."""
        import jax
        from jax.experimental import serialize_executable as _se
        path = self._path(key)
        try:
            with open(path, "rb") as f:
                payload, in_tree, out_tree, device_ids = pickle.loads(
                    f.read())
            by_id = {d.id: d for d in jax.devices()}
            exe = _se.deserialize_and_load(
                payload, in_tree, out_tree,
                execution_devices=[by_id[i] for i in device_ids])
        except FileNotFoundError:
            _mon.stat_add(_STAT_PREFIX + "disk_misses", 1)
            return None
        except Exception as e:
            _mon.stat_add(_STAT_PREFIX + "disk_errors", 1)
            warnings.warn(
                f"persistent executable cache: dropping unreadable entry "
                f"{os.path.basename(path)} ({type(e).__name__}: {e}); "
                f"recompiling")
            try:
                os.unlink(path)
            except OSError:
                pass
            return None
        _mon.stat_add(_STAT_PREFIX + "disk_hits", 1)
        return exe

    def save(self, key: str, compiled: Any) -> bool:
        """Serialize ``compiled`` if it supports AOT serialization
        (``jax.stages.Compiled``); atomically write. False (with at most
        a warning) on anything else — callers treat persistence as an
        optimization, never state."""
        from jax.experimental import serialize_executable as _se
        try:
            device_ids = [d.id for d in
                          compiled.runtime_executable().local_devices()]
            blob = pickle.dumps(_se.serialize(compiled) + (device_ids,))
        except Exception:
            return False            # lazy jit wrapper etc. — memory-only
        path = self._path(key)
        tmp = f"{path}.tmp.{os.getpid()}"
        try:
            os.makedirs(self.directory, exist_ok=True)
            with open(tmp, "wb") as f:
                f.write(blob)
            os.replace(tmp, path)
        except OSError as e:
            warnings.warn(f"persistent executable cache: could not write "
                          f"{path}: {e}")
            try:
                os.unlink(tmp)
            except OSError:
                pass
            return False
        _mon.stat_add(_STAT_PREFIX + "disk_writes", 1)
        return True


_STORE: Optional[PersistentExecutableStore] = None
_STORE_LOCK = threading.Lock()


def persistent_store() -> Optional[PersistentExecutableStore]:
    """Process-wide executable store under the persistence root, or None
    when persistence is off."""
    global _STORE
    root = persistent_root()
    if root is None:
        return None
    with _STORE_LOCK:
        if _STORE is None or not _STORE.directory.startswith(root):
            _STORE = PersistentExecutableStore(
                os.path.join(root, "executables"))
        return _STORE


class ExecutableCache:
    """LRU cache of compiled executables with observable counters."""

    def __init__(self, capacity: int = 128):
        if capacity < 1:
            raise ValueError("capacity must be >= 1")
        self._capacity = capacity
        self._lock = threading.Lock()
        self._entries: "OrderedDict[Any, Any]" = OrderedDict()
        self.hits = 0
        self.misses = 0
        self.evictions = 0

    def __len__(self) -> int:
        with self._lock:
            return len(self._entries)

    def get_or_compile(self, key: Any, compile_fn: Callable[[], Any], *,
                       persist_key: Optional[str] = None) -> Any:
        """Return the cached executable for ``key``, compiling on miss.

        ``compile_fn`` runs outside the lock (XLA compiles can take
        seconds); concurrent misses on the same key race benignly — the
        first finisher's entry wins and the duplicate is dropped.

        ``persist_key`` opts this entry into the on-disk executable tier
        (no-op when persistence is off). It MUST be process-stable —
        derived from artifact paths/signatures, never from ``id()`` — or
        a restarted process could load someone else's executable.
        Entries whose compiled object is not AOT-serializable silently
        stay memory-only.
        """
        with self._lock:
            entry = self._entries.get(key)
            if entry is not None:
                self._entries.move_to_end(key)
                self.hits += 1
                _mon.stat_add(_STAT_PREFIX + "hits", 1)
                return entry
            self.misses += 1
        _mon.stat_add(_STAT_PREFIX + "misses", 1)
        store = persistent_store() if persist_key else None
        compiled = store.load(persist_key) if store is not None else None
        from_disk = compiled is not None
        if compiled is None:
            # compile hook: stamp every miss with its build duration (for
            # jit entries this is trace+lower; XLA compile itself may
            # still be deferred to first execution) — recompile pressure
            # shows up as `jit.compile_ms` and on the span timeline.
            t0 = time.perf_counter()
            with _tracer.span("jit/compile",
                              {"cache_key": repr(key)[:200]}):
                compiled = compile_fn()
            _mon.stat_observe("jit.compile_ms",
                              (time.perf_counter() - t0) * 1e3)
            _mon.stat_add("jit.cache_misses", 1)
        with self._lock:
            winner = self._entries.setdefault(key, compiled)
            self._entries.move_to_end(key)
            while len(self._entries) > self._capacity:
                self._entries.popitem(last=False)
                self.evictions += 1
                _mon.stat_add(_STAT_PREFIX + "evictions", 1)
            _mon.stat_set(_STAT_PREFIX + "size", len(self._entries))
        if store is not None and not from_disk and winner is compiled:
            store.save(persist_key, winner)
        return winner

    def contains(self, key: Any) -> bool:
        with self._lock:
            return key in self._entries

    def clear(self):
        with self._lock:
            self._entries.clear()

    def stats(self) -> Dict[str, int]:
        with self._lock:
            return {"size": len(self._entries), "capacity": self._capacity,
                    "hits": self.hits, "misses": self.misses,
                    "evictions": self.evictions}


_DEFAULT: Optional[ExecutableCache] = None
_DEFAULT_LOCK = threading.Lock()


def default_cache() -> ExecutableCache:
    """Process-wide cache (Predictors share it so two predictors over the
    same artifact reuse each other's executables). Capacity comes from
    ``PADDLE_TPU_EXEC_CACHE_SIZE`` (default 128)."""
    global _DEFAULT
    with _DEFAULT_LOCK:
        if _DEFAULT is None:
            cap = int(os.environ.get(_DEFAULT_CAPACITY_ENV, "128") or "128")
            _DEFAULT = ExecutableCache(capacity=cap)
        return _DEFAULT


def _reset_default_cache_for_tests():
    global _DEFAULT
    with _DEFAULT_LOCK:
        _DEFAULT = None
