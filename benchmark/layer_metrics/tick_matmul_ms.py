"""Exclusive device time of the weight products in one decode tick (ms):
events of ``jit__step`` under ``gpt/qkv``, ``gpt/proj`` and ``gpt/mlp``, the
prefetches of their weights included. Serves ``tick_matmul_ms.closed``."""
from benchmark import scope_time


def read(run):
    return scope_time.ms_per_span(run, scope_time.TICK_SPAN, "jit__step",
                                  scopes=("gpt/qkv", "gpt/proj", "gpt/mlp"))
