"""Device time of the expert product (kernels ``moe_experts_up`` and
``moe_experts_down``, 6 expert layers) in one decode tick (ms)."""
from benchmark import costs_lfm2


def read(run):
    m = costs_lfm2.decode_moe(run)
    return None if m is None else m[0] / m[1] * 1e3
