"""Exclusive device time of one train step's forward pass (ms), the flash
kernel's first run included: events whose ``op_name`` autodiff marked
``jvp`` and not ``transpose``."""
from benchmark import scope_time


def read(run):
    return scope_time.ms_per_span(run, scope_time.STEP_SPAN, phase="forward")
