"""Least time the traced ticks' expert work could take over the expert
kernels' device time (%), where a chip holds a share of each layer's
experts: the weights of the held experts that received a pair, once a tick
each, and the pairs routed to them; memory-bound."""
from benchmark import costs_trinity


def read(run):
    if "share" not in ((run.get("cell") or {}).get("config_data") or {}):
        return None
    return costs_trinity.held_experts_roofline(run)
