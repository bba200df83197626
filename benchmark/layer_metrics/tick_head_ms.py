"""Exclusive device time of the logits product and sampling in one decode
tick (ms): events of ``jit__step`` under ``decode/head`` and
``decode/sample``. Serves ``tick_head_ms.closed``."""
from benchmark import scope_time


def read(run):
    return scope_time.ms_per_span(run, scope_time.TICK_SPAN, "jit__step",
                                  scopes=("decode/head", "decode/sample"))
