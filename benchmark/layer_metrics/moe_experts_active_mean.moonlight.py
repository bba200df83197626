"""``moe_experts_active_mean`` under the Moonlight configuration's keys: the
HELD experts an expert layer reads in a decode tick, of the 8 held."""
from benchmark import costs_lfm2, costs_moonlight


def read(run):
    ticks = costs_lfm2.window_ticks(run)
    active = run.get("counters", {}).get("moe_experts_active")
    if not ticks or not active or not costs_moonlight.is_latent(run):
        return None
    cfg = run["cell"]["config_data"]
    return active / ticks / (cfg["num_hidden_layers"]
                             - cfg["first_k_dense_replace"])
