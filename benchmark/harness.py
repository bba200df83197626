"""What every driver shares: the compile counter, the profiler window, the
device's description and memory, percentiles."""
from __future__ import annotations

import contextlib
import os
import shutil
import time

import numpy as np

from . import trace as _trace


class CompileCounter:
    """Counts the programs JAX is asked to compile (cache hits included:
    a hit inside the window still means a shape that set-up did not warm).
    After chip_smoke.CompileCounter."""

    def __init__(self):
        from jax import monitoring
        self.requests = 0
        monitoring.register_event_listener(self._on_event)

    def _on_event(self, name, **_kw):
        if name == "/jax/compilation_cache/compile_requests_use_cache":
            self.requests += 1


def place_compile_cache(root: str) -> str:
    """JAX's persistent cache at a fixed path inside the checkout, unless
    the environment already names one."""
    import jax
    placed = os.environ.get("JAX_COMPILATION_CACHE_DIR", "").strip()
    if not placed:
        placed = os.path.join(root, ".jax_cache")
        jax.config.update("jax_compilation_cache_dir", placed)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", -1)
    return placed


def device_description() -> dict:
    import jax
    devs = jax.devices()
    return {"platform": devs[0].platform, "kind": devs[0].device_kind,
            "count": len(devs)}


class MemoryPeak:
    """The program's peak on the fullest chip, from samples the driver
    takes while the program's state is alive.

    A sample is ``bytes_in_use + bytes_reserved`` of ``memory_stats()``:
    live buffers plus the region the runtime reserves for the loaded
    programs' temporaries. (``peak_bytes_in_use`` alone leaves the
    temporaries out: the train step's 6 GB of logits never show in it. It
    also keeps set-up's and the reference's own peaks, which are not the
    program's.) 0 where the backend reports nothing, as the CPU does.
    """

    def __init__(self):
        self.peak = 0

    def sample(self) -> int:
        import jax
        for d in jax.devices():
            stats = d.memory_stats() or {}
            self.peak = max(self.peak, int(stats.get("bytes_in_use", 0)
                                           + stats.get("bytes_reserved", 0)))
        return self.peak


class Phases:
    """Prints what each phase of set-up took, so that a cold run's split
    (compile, weights, warm-up) is on the lines above the result."""

    def __init__(self, start: float):
        self.last = start

    def done(self, what: str):
        now = time.perf_counter()
        print(f"set-up: {what} {now - self.last:.2f} s", flush=True)
        self.last = now


def percentile(values, q: float) -> float:
    return float(np.percentile(np.asarray(values, np.float64), q))


class TraceWindow:
    """A profiler trace of a short stretch of the measured window.

    ``start()`` and ``stop()`` are called on one thread. Python-level
    tracing is off (it slows the host and bloats the file); the program's
    own spans and the benchmark's reach the trace as annotations because
    the program's profiler flag is raised for the stretch.
    """

    def __init__(self, out_dir: str, rehearsal: bool = False):
        self.rehearsal = rehearsal
        self.log_dir = os.path.join(out_dir, "trace")
        self.t_start = self.t_stop = None
        self._stack = None

    def start(self):
        import jax
        import paddle_tpu as paddle
        from paddle_tpu.observability import tracer as program_tracer
        shutil.rmtree(self.log_dir, ignore_errors=True)
        os.makedirs(self.log_dir, exist_ok=True)
        options = jax.profiler.ProfileOptions()
        options.python_tracer_level = 0
        jax.profiler.start_trace(self.log_dir, profiler_options=options)
        self._stack = contextlib.ExitStack()
        # raises the flag that makes the program's spans annotate the trace
        flag = paddle.profiler.Profiler(timer_only=True)
        flag.start()
        self._stack.callback(flag.stop)
        program_tracer.enable()
        self._stack.callback(program_tracer.disable)
        self._stack.enter_context(
            jax.profiler.TraceAnnotation(_trace.WINDOW_SPAN))
        self.t_start = time.perf_counter()

    def stop(self):
        import jax
        self.t_stop = time.perf_counter()
        self._stack.close()
        jax.profiler.stop_trace()

    def reduced(self) -> dict:
        flat = _trace.flatten(_trace.find_xplane(self.log_dir),
                              rehearsal=self.rehearsal)
        shutil.rmtree(self.log_dir, ignore_errors=True)
        return _trace.reduce(flat)


def span(name: str):
    """A benchmark span: a profiler annotation, free when no trace runs."""
    import jax
    return jax.profiler.TraceAnnotation(name)
