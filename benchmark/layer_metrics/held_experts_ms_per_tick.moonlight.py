"""``held_experts_ms_per_tick`` under the Moonlight configuration's keys."""
from benchmark import costs_lfm2, costs_moonlight


def read(run):
    if not costs_moonlight.is_latent(run):
        return None
    m = costs_lfm2.decode_moe(costs_moonlight.as_lfm2(run))
    return None if m is None else m[0] / m[1] * 1e3
