"""paddle_tpu.observability — unified runtime telemetry.

One measurement substrate spanning training, serving and LLM decode
(docs/observability.md):

* :mod:`.tracer` — structured span tracer (``with span("train/step")``)
  with a zero-alloc disabled fast path; Chrome/Perfetto export via
  :mod:`.export` / ``tools/trace_export.py``.
* :mod:`.metrics` — Prometheus text exposition of a ``StatRegistry``
  (served at ``/metricsz`` by ``paddle_tpu.serving.http``).
* :mod:`.stepmeter` — per-step MFU/FLOPs accounting from XLA cost
  analysis + measured wall time (``train.mfu``, ``serving.llm.mfu``).
* :mod:`.opscope` — device time by the program's own ``jax.named_scope``s:
  every hot program is noted when it is traced, and a device event is
  mapped back to the scope its instruction came from.
* :mod:`.flight` — crash flight recorder (last-N events/spans/stats as
  JSONL on sentinel halt, unhandled loop exceptions, SIGTERM drain).

``enable()`` turns the whole substrate on (span recording + armed flight
recorder); instrumented call sites cost ~one indexed load when disabled.
"""
from __future__ import annotations

from typing import Optional

from . import (export, flight, metrics, opscope, stepmeter,  # noqa: F401
               tracer)
from .export import export_chrome_trace, load_chrome_trace  # noqa: F401
from .flight import (FlightRecorder, default_recorder,  # noqa: F401
                     record_event)
from .metrics import render_prometheus  # noqa: F401
from .stepmeter import StepMeter, compiled_flops  # noqa: F401
from .tracer import (SpanTracer, default_tracer, is_enabled,  # noqa: F401
                     span)


def enable(capacity: Optional[int] = None):
    """Enable span recording and arm the flight recorder."""
    tracer.enable(capacity)
    flight.arm()


def disable():
    """Stop span recording and disarm crash-path dumps (the recorded ring
    and flight events are kept until ``tracer.default_tracer().clear()``)."""
    tracer.disable()
    flight.disarm()
