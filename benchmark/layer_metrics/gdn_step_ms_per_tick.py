"""Device time of the gated delta rule's one-token step (scope ``gdn_step`` of
the decode step, every linear layer) in one decode tick (ms)."""
from benchmark import costs_qwen3next


def read(run):
    return costs_qwen3next.ms_per_tick(run, "gdn_step")
