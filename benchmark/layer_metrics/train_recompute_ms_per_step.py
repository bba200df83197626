"""Exclusive device time one train step spends computing the forward pass a
second time (ms): events under the checkpoint's ``rematted_computation``."""
from benchmark import scope_time


def read(run):
    return scope_time.ms_per_span(run, scope_time.STEP_SPAN, phase="recompute")
