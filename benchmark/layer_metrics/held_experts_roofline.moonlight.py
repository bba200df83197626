"""``held_experts_roofline`` under the Moonlight configuration's keys
(``n_routed_experts`` held of ``share.num_experts_published``)."""
from benchmark import costs_moonlight


def read(run):
    if not costs_moonlight.is_latent(run):
        return None
    return costs_moonlight.held_experts_roofline(run)
