"""Exclusive device time of the LayerNorms in one decode tick (ms): events of
``jit__step`` under ``gpt/norm``. Serves ``tick_norm_ms.closed``."""
from benchmark import scope_time


def read(run):
    return scope_time.ms_per_span(run, scope_time.TICK_SPAN, "jit__step",
                                  scopes=("gpt/norm",))
