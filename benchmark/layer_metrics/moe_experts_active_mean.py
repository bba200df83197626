"""Distinct experts an expert layer reads in a decode tick, of the layer's
32: the engine's ``moe_experts_active`` over ticks and expert layers."""
from benchmark import costs_lfm2


def read(run):
    ticks = costs_lfm2.window_ticks(run)
    active = run.get("counters", {}).get("moe_experts_active")
    if not ticks or not active:
        return None
    return active / ticks / costs_lfm2.expert_layers(
        run["cell"]["config_data"])
