"""Least time the traced ticks' expert work could take over the expert
kernels' device time (%): the weights of the experts that received a token,
once a tick each, and 6 x h x f operations a routed pair. The counters
cover the whole window and the trace a few seconds of it, so the window's
mean a tick is taken times the ticks traced; activations are left out, so
this is a lower bound."""
from benchmark import costs, costs_lfm2


def read(run):
    m = costs_lfm2.decode_moe(run)
    ticks = costs_lfm2.window_ticks(run)
    c = run.get("counters", {})
    if m is None or not ticks or not c.get("moe_experts_active"):
        return None
    seconds, traced = m
    cost = costs_lfm2.moe_tick_cost(
        run["cell"]["config_data"],
        c["moe_experts_active"] / ticks * traced,
        c.get("moe_pairs_routed", 0) / ticks * traced)
    return costs.share_pct(costs.least_seconds(cost, run["peaks"])[0],
                           seconds, "moe_experts")
