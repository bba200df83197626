"""Least time the selected walk of ``paged_attn`` could take over its device
time in the traced stretch (%): the rows of the pages the selections named
during the stretch (the engine's counters read at its two ends), K and V of
one KV head a page; memory-bound."""
from benchmark import costs, costs_sala
from benchmark.readers import paged


def read(run):
    c = run.get("trace_counters") or {}
    pages = c.get("sparse_attn.pages_selected", 0)
    p = paged(run)
    if p is None or not pages:
        return None
    cfg = run["cell"]["config_data"]
    page_size = run["cell"]["traffic_data"]["engine"]["page_size"]
    queries = c.get("tokens_generated", 0) * costs_sala.sparse_layers(cfg)
    cost = costs_sala.selected_walk_cost(cfg, page_size, pages, queries)
    return costs.share_pct(costs.least_seconds(cost, run["peaks"])[0], p[0],
                           "paged_attn (selected walk)")
