"""Operations and bytes the Trinity cell's kernels need, from shapes and the
engine's counters alone.

As in ``costs.py`` these are what the algorithm requires, so a share
computed from them is a lower bound. ``paged_attn`` runs in every layer: a
full attention layer walks the pages up to the position
(``paged_attn.pages_live``, a page a sequence and tick, whatever the layer),
a window layer the pages from its window's first row on
(``window_attn.pages_walked``, counted over the window layers). A page is 64
rows of a key and a value of every KV head. The expert kernels of a decode
tick read the weights of the HELD experts that got a pair, once a tick each
(``moe_experts_active`` counts held experts only), and compute the pairs
routed to them, the held share of ``moe_pairs_routed``.
"""
from __future__ import annotations

from . import costs, costs_lfm2

FULL, SLIDING = "full_attention", "sliding_attention"


def layers_of(cfg: dict, kind: str) -> int:
    return cfg["layer_types"][:cfg["num_hidden_layers"]].count(kind)


def walked_pages(cfg: dict, counters: dict) -> float:
    """(page, layer) pairs the walks of both kinds of layer read, from the
    engine's counters over some stretch."""
    return (counters.get("paged_attn.pages_live", 0) * layers_of(cfg, FULL)
            + counters.get("window_attn.pages_walked", 0))


def walk_cost(cfg: dict, page_size: int, pages: float,
              queries: float) -> dict:
    """``paged_attn`` over ``pages`` (page, layer) pairs for ``queries``
    (token, layer) pairs: ``costs.paged_attn_cost`` with every row a key and
    a value of the KV heads, and the query heads' operations."""
    rows = float(pages) * page_size
    d, hq, hkv = (cfg["head_dim"], cfg["num_attention_heads"],
                  cfg["num_key_value_heads"])
    itemsize = 4
    return {"flops": 4.0 * rows * hq * d,
            "bytes": (2.0 * rows * hkv + 2.0 * queries * hq) * d * itemsize}


def held_tick_cost(cfg: dict, experts_active: float, pairs: float) -> dict:
    """``costs_lfm2.moe_tick_cost`` with the pairs that reach a held expert:
    the held share of all the pairs routed."""
    held = cfg["num_experts"] / cfg["share"]["num_experts_published"]
    return costs_lfm2.moe_tick_cost(cfg, experts_active, pairs * held)


def held_experts_roofline(run):
    """Least time of the traced ticks' expert work over the expert kernels'
    device time (%), as ``layer_metrics/moe_experts_roofline.py`` takes it:
    the window's mean a tick times the ticks traced."""
    m = costs_lfm2.decode_moe(run)
    ticks = costs_lfm2.window_ticks(run)
    c = run.get("counters", {})
    if m is None or not ticks or not c.get("moe_experts_active"):
        return None
    seconds, traced = m
    cost = held_tick_cost(run["cell"]["config_data"],
                          c["moe_experts_active"] / ticks * traced,
                          c.get("moe_pairs_routed", 0) / ticks * traced)
    return costs.share_pct(costs.least_seconds(cost, run["peaks"])[0],
                           seconds, "moe_experts (held)")
