"""Exclusive device time of clip, decay and the AdamW update in one train
step (ms): events under the scope ``train/optimizer``."""
from benchmark import scope_time


def read(run):
    return scope_time.ms_per_span(run, scope_time.STEP_SPAN, phase="optimizer")
