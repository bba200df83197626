"""paddle.profiler: tracing and profiling.

TPU-native equivalent of the reference profiler stack
(reference: paddle/fluid/platform/profiler.cc:59 RecordEvent RAII,
device_tracer.cc CUPTI timeline, python/paddle/fluid/profiler.py:314
``profiler`` context, start_profiler :190 / stop_profiler :257, and the
newer paddle.profiler.Profiler API). Here the device timeline comes from
XLA's own tracing via ``jax.profiler`` (viewable in TensorBoard /
Perfetto), host annotations map to ``jax.profiler.TraceAnnotation``, and
the op-dispatch funnel emits one annotation per op while a profile is
active (the reference pushes RecordEvent in Tracer::TraceOp,
imperative/tracer.cc:137).

Usage::

    with paddle.profiler.Profiler(log_dir="/tmp/prof") as prof:
        for batch in loader:
            train_step(batch)
            prof.step()
    # then: tensorboard --logdir /tmp/prof  (or xprof)
"""
from __future__ import annotations

import contextlib
import glob
import os
import time
from typing import Optional

import jax

# gate consulted by the op-dispatch funnel; a module-level list so the
# check is one indexing op on the eager hot path
_ACTIVE = [False]


def is_profiling() -> bool:
    return _ACTIVE[0]


class RecordEvent:
    """Host-side named annotation (reference: platform/profiler.cc:59
    RecordEvent; python: paddle.profiler.RecordEvent). Usable as a context
    manager or begin()/end() pair; shows up on the trace timeline."""

    def __init__(self, name: str, event_type=None):
        self.name = name
        self._ann = None
        self.begin_ns: Optional[int] = None

    def begin(self):
        self._ann = jax.profiler.TraceAnnotation(self.name)
        self._ann.__enter__()
        self.begin_ns = time.perf_counter_ns()

    def end(self):
        if self._ann is not None:
            self._ann.__exit__(None, None, None)
            self._ann = None

    def __enter__(self):
        self.begin()
        return self

    def __exit__(self, *exc):
        self.end()
        return False


#: what the XLA CPU client's threads hold beside operations: its own frames
_NOT_AN_OPERATION = ("Threadpool", "end:", "ThunkExecutor")


def _device_lines(log_dir: str) -> list:
    """The device lines of the newest capture under ``log_dir``, each
    ``[[name, start_ns, dur_ns], ...]``: a chip's ``XLA Ops`` line, or off
    the chip the XLA CPU client's threads, which stand in for it."""
    paths = sorted(glob.glob(os.path.join(
        log_dir, "plugins", "profile", "*", "*.xplane.pb")))
    if not paths:
        return []
    from jax.profiler import ProfileData
    lines = []
    for plane in ProfileData.from_file(paths[-1]).planes:
        chip = plane.name.startswith("/device:TPU:")
        for line in plane.lines:
            if line.name == "XLA Ops" if chip \
                    else line.name.startswith("tf_XLAPjRtCpuClient"):
                lines.append([
                    [ev.name, int(ev.start_ns), int(ev.duration_ns)]
                    for ev in line.events if ev.duration_ns > 0
                    and not ev.name.startswith(_NOT_AN_OPERATION)])
    return lines


class Profiler:
    """reference: paddle.profiler.Profiler (new API) /
    fluid/profiler.py:314 ``profiler`` context. Captures an XLA trace into
    ``log_dir``; ``step()`` emits per-step markers
    (jax.profiler.StepTraceAnnotation) that TensorBoard's profile tab
    groups by training step."""

    def __init__(self, targets=None, scheduler=None, on_trace_ready=None,
                 log_dir: str = "./profiler_log", timer_only: bool = False):
        self.log_dir = log_dir
        self.timer_only = timer_only
        self._on_trace_ready = on_trace_ready
        self._running = False
        self._step_no = 0
        self._step_ann = None
        self._step_times = []
        self._last_step_t = None

    def start(self):
        if self._running:
            return
        if not self.timer_only:
            os.makedirs(self.log_dir, exist_ok=True)
            jax.profiler.start_trace(self.log_dir)
        _ACTIVE[0] = True
        self._running = True
        self._last_step_t = time.perf_counter()
        self._begin_step_annotation()

    def stop(self):
        if not self._running:
            return
        self._end_step_annotation()
        _ACTIVE[0] = False
        if not self.timer_only:
            jax.profiler.stop_trace()
        self._running = False
        if self._on_trace_ready is not None:
            self._on_trace_ready(self)

    def _begin_step_annotation(self):
        if not self.timer_only:
            self._step_ann = jax.profiler.StepTraceAnnotation(
                "train", step_num=self._step_no)
            self._step_ann.__enter__()

    def _end_step_annotation(self):
        if self._step_ann is not None:
            self._step_ann.__exit__(None, None, None)
            self._step_ann = None

    def step(self, num_samples: Optional[int] = None):
        """Mark a training-step boundary (reference: Profiler.step)."""
        now = time.perf_counter()
        if self._last_step_t is not None:
            self._step_times.append(now - self._last_step_t)
        self._last_step_t = now
        self._end_step_annotation()
        self._step_no += 1
        if self._running:
            self._begin_step_annotation()

    def step_info(self, unit=None) -> str:
        if not self._step_times:
            return "no steps recorded"
        import numpy as np
        ts = np.asarray(self._step_times)
        return (f"steps={len(ts)} avg={ts.mean() * 1e3:.3f}ms "
                f"min={ts.min() * 1e3:.3f}ms max={ts.max() * 1e3:.3f}ms")

    def summary(self, sorted_by=None, op_detail=True, thread_sep=False,
                time_unit="ms"):
        """The host-side step timing, then the captured device time by the
        program's own scopes (``observability.opscope``): exclusive time of
        every ``(program, scope, phase)``, largest first. The per-operation
        table stays in the captured trace (TensorBoard / xprof)."""
        print(self.step_info())
        seconds = self.device_time_by_scope()
        if seconds:
            from ..observability import opscope
            print(opscope.format_table(seconds, unit=time_unit))

    def device_time_by_scope(self) -> dict:
        """``{(program, scope, phase): exclusive seconds}`` of this
        profiler's newest capture, summed over the device lines; empty
        while it runs, for ``timer_only`` and where nothing was captured.
        Lowers every program noted so far once more (``opscope.table``)."""
        if self._running or self.timer_only:
            return {}
        from ..observability import opscope
        total = {}
        for line in _device_lines(self.log_dir):
            for key, s in opscope.by_scope(line).items():
                total[key] = total.get(key, 0.0) + s
        return total

    def export(self, path=None, format=None):
        return self.log_dir

    def __enter__(self):
        self.start()
        return self

    def __exit__(self, *exc):
        self.stop()
        return False


# -- fluid-style module functions (reference: fluid/profiler.py) -------------

_FLUID_PROF: Optional[Profiler] = None


def start_profiler(state: str = "All", tracer_option: str = "Default",
                   log_dir: str = "./profiler_log"):
    """reference: fluid/profiler.py:190."""
    global _FLUID_PROF
    if _FLUID_PROF is None:
        _FLUID_PROF = Profiler(log_dir=log_dir)
        _FLUID_PROF.start()


def stop_profiler(sorted_key=None, profile_path: Optional[str] = None):
    """reference: fluid/profiler.py:257."""
    global _FLUID_PROF
    if _FLUID_PROF is not None:
        _FLUID_PROF.stop()
        _FLUID_PROF = None


@contextlib.contextmanager
def profiler(state: str = "All", sorted_key=None, profile_path=None,
             tracer_option: str = "Default", log_dir: str = "./profiler_log"):
    """reference: fluid/profiler.py:314 (context-manager form)."""
    start_profiler(state, tracer_option, log_dir)
    try:
        yield
    finally:
        stop_profiler(sorted_key, profile_path)


@contextlib.contextmanager
def cuda_profiler(*a, **k):
    """CUDA-era no-op kept for ported scripts (reference:
    fluid/profiler.py cuda_profiler)."""
    yield


def reset_profiler():
    pass
