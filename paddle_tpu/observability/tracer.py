"""Structured span tracer: low-overhead host-side timeline spans.

Design constraints (ISSUE 8, cf. arxiv 2301.13062 — fusion/copy/transfer
pathologies are only findable with per-step cost *and timeline* data):

* **Disabled cost ~= one list index.** ``span()`` is called on every train
  step, every decode tick and every serving request, so the off path must
  allocate nothing: a module-level ``_ENABLED = [False]`` gate (mirroring
  ``profiler._ACTIVE``) short-circuits to one shared immutable no-op
  context manager. Hot paths must go through ``span()`` — constructing
  ``Span`` directly bypasses the gate (policed by PTA005's span-fastpath
  sub-check).
* **Lock-free recording.** Finished spans land in a bounded
  ``deque(maxlen=...)`` — CPython deque append/iteration are GIL-atomic,
  so worker threads, the train loop and a signal-triggered flight dump can
  share the ring without a lock (same discipline as the sentinel's halt
  path; see PTA006 notes in tools/analyze).
* **Timeline alignment.** When a ``paddle_tpu.profiler`` trace is active,
  each span also enters a ``jax.profiler.TraceAnnotation`` so host spans
  line up with XLA's device timeline in the same Perfetto view.

Timestamps are ``time.perf_counter_ns`` (monotonic); ``clock_origin_ns``
is recorded so exporters can map onto wall time.
"""
from __future__ import annotations

import itertools
import os
import threading
import time
from collections import deque
from typing import Dict, List, Optional

from .. import profiler as _profiler

#: module-level gate, mirroring ``profiler._ACTIVE``: a one-element list so
#: the hot-path check is a single LOAD + index with no attribute lookup on
#: a rebindable global.
_ENABLED = [False]

#: default ring capacity (finished spans retained). ~200 bytes/span.
DEFAULT_CAPACITY = int(os.environ.get("PADDLE_TPU_TRACE_CAPACITY", "8192"))

_tls = threading.local()

#: span ids, unique in the process; ``next()`` on a count is GIL-atomic.
#: 0 is "no span" (the ``parent`` of a span opened at depth 0).
_SPAN_IDS = itertools.count(1)


def _stack() -> list:
    s = getattr(_tls, "stack", None)
    if s is None:
        s = _tls.stack = []
    return s


class _NoopSpan:
    """Shared do-nothing context manager returned while tracing is off.

    One module-level instance; ``__enter__``/``__exit__`` do no work, so an
    instrumented call site costs one function call + one index when
    tracing is disabled."""

    __slots__ = ()

    def __enter__(self):
        return self

    def __exit__(self, exc_type, exc, tb):
        return False

    def set_attr(self, key, value):  # API parity with Span
        return self


NOOP_SPAN = _NoopSpan()


class Span:
    """One timed region. Create via ``span()`` / ``SpanTracer.span()`` —
    never directly in hot paths (the constructor runs even when tracing is
    disabled, defeating the fast path)."""

    __slots__ = ("name", "attrs", "t0_ns", "t1_ns", "tid", "thread_name",
                 "depth", "id", "parent", "_tracer", "_ann")

    def __init__(self, tracer: "SpanTracer", name: str,
                 attrs: Optional[Dict] = None):
        self._tracer = tracer
        self.name = name
        self.attrs = attrs
        self.t0_ns = 0
        self.t1_ns = 0
        self.tid = 0
        self.thread_name = ""
        self.depth = 0
        self.id = 0
        self.parent = 0
        self._ann = None

    def set_attr(self, key, value):
        if self.attrs is None:
            self.attrs = {}
        self.attrs[key] = value
        return self

    def __enter__(self):
        t = threading.current_thread()
        self.tid = t.ident or 0
        self.thread_name = t.name
        stack = _stack()
        self.depth = len(stack)
        # the enclosing span on this thread: a span's self time is its
        # duration less what the spans that name it as parent cover
        self.id = next(_SPAN_IDS)
        self.parent = stack[-1].id if stack else 0
        stack.append(self)
        if _profiler._ACTIVE[0]:
            try:
                import jax
                self._ann = jax.profiler.TraceAnnotation(self.name)
                self._ann.__enter__()
            except Exception:
                self._ann = None
        self.t0_ns = time.perf_counter_ns()
        return self

    def __exit__(self, exc_type, exc, tb):
        self.t1_ns = time.perf_counter_ns()
        if self._ann is not None:
            try:
                self._ann.__exit__(exc_type, exc, tb)
            except Exception:
                pass
            self._ann = None
        stack = _stack()
        if stack and stack[-1] is self:
            stack.pop()
        else:  # unbalanced exit (e.g. generator abandoned mid-span)
            try:
                stack.remove(self)
            except ValueError:
                pass
        if exc_type is not None:
            self.set_attr("error", exc_type.__name__)
        self._tracer._record(self)
        return False


class SpanTracer:
    """Span factory + bounded ring of finished spans.

    The module-level default tracer (``enable()``/``span()``) is what all
    built-in instrumentation uses; standalone tracers exist for tests."""

    def __init__(self, capacity: int = DEFAULT_CAPACITY):
        self.capacity = capacity
        self._ring = deque(maxlen=capacity)  # GIL-atomic append
        self._dropped = 0
        self.pid = os.getpid()
        # perf_counter->wall mapping, refreshed on enable()
        self.clock_origin_ns = time.perf_counter_ns()
        self.wall_origin_s = time.time()

    # -- recording ----------------------------------------------------------
    def span_always(self, name: str, attrs: Optional[Dict] = None) -> Span:
        """Unconditionally-recording span (tests, cold paths). Hot paths
        must use the module-level ``span()`` — it is the only entry point
        with the zero-alloc disabled fast path (PTA005 polices this)."""
        return Span(self, name, attrs)

    def _record(self, s: Span):
        ring = self._ring
        if len(ring) == ring.maxlen:
            self._dropped += 1
        ring.append({
            "name": s.name,
            "ts_ns": s.t0_ns,
            "dur_ns": s.t1_ns - s.t0_ns,
            "tid": s.tid,
            "thread": s.thread_name,
            "depth": s.depth,
            "id": s.id,
            "parent": s.parent,
            "attrs": s.attrs,
        })

    # -- readout ------------------------------------------------------------
    def drain(self) -> List[Dict]:
        """Snapshot and clear the ring (export consumes spans once)."""
        out = []
        ring = self._ring
        while True:
            try:
                out.append(ring.popleft())
            except IndexError:
                return out

    def spans(self) -> List[Dict]:
        """Non-destructive snapshot of recorded spans, oldest first."""
        return list(self._ring)

    @property
    def dropped(self) -> int:
        return self._dropped

    def clear(self):
        self._ring.clear()
        self._dropped = 0


_TRACER = SpanTracer()


def default_tracer() -> SpanTracer:
    return _TRACER


def is_enabled() -> bool:
    return _ENABLED[0]


def enable(capacity: Optional[int] = None):
    """Turn span recording on (idempotent). ``capacity`` resizes the ring."""
    if capacity is not None and capacity != _TRACER.capacity:
        _TRACER.capacity = capacity
        _TRACER._ring = deque(_TRACER._ring, maxlen=capacity)
    _TRACER.clock_origin_ns = time.perf_counter_ns()
    _TRACER.wall_origin_s = time.time()
    _ENABLED[0] = True


def disable():
    _ENABLED[0] = False


def span(name: str, attrs: Optional[Dict] = None):
    """The instrumentation entry point: ``with span("train/step"): ...``.

    Returns the shared no-op when tracing is disabled — zero allocation on
    the hot path. Pass attributes as a dict (``span("x", {"k": v})``) only
    where the dict itself is cheap relative to the region timed."""
    if not _ENABLED[0]:
        return NOOP_SPAN
    return Span(_TRACER, name, attrs)


# re-exported by paddle_tpu.observability; env opt-in lives here so the
# import side effect is one getenv.
if os.environ.get("PADDLE_TPU_TRACE", "").lower() in ("1", "true", "on"):
    enable()
