"""Least time ``paged_attn`` could take over its device time in the traced
stretch (%), in a cell whose layers walk different pages: the rows of the
pages the full layers' walks and the window layers' bounded walks read
during the stretch (the engine's counters read at its two ends);
memory-bound."""
from benchmark import costs, costs_trinity
from benchmark.readers import paged


def read(run):
    c = run.get("trace_counters") or {}
    cfg = (run.get("cell") or {}).get("config_data") or {}
    if "share" not in cfg or not c.get("window_attn.pages_walked"):
        return None
    p = paged(run)
    if p is None:
        return None
    pages = costs_trinity.walked_pages(cfg, c)
    page_size = run["cell"]["traffic_data"]["engine"]["page_size"]
    queries = c.get("tokens_generated", 0) * cfg["num_hidden_layers"]
    cost = costs_trinity.walk_cost(cfg, page_size, pages, queries)
    return costs.share_pct(costs.least_seconds(cost, run["peaks"])[0], p[0],
                           "paged_attn (window and full walks)")
