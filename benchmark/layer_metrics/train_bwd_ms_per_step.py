"""Exclusive device time of one train step's backward pass (ms), recompute
left out: events whose ``op_name`` autodiff marked ``transpose``."""
from benchmark import scope_time


def read(run):
    return scope_time.ms_per_span(run, scope_time.STEP_SPAN, phase="backward")
