"""Exclusive device time of the logits product and the loss in one train
step, forward and backward (ms): events under the scope ``gpt/loss_head``."""
from benchmark import scope_time


def read(run):
    return scope_time.ms_per_span(run, scope_time.STEP_SPAN,
                                  scopes=("gpt/loss_head",))
