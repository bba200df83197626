"""Least time ``paged_attn`` could take over its device time (%): the rows
the decode tokens emitted during the trace had to read (token j of a request
attends prompt + j rows), in every layer, at HBM speed. Serves
``paged_attn_roofline.closed``."""
from benchmark import costs
from benchmark.readers import paged


def read(run):
    p = paged(run)
    if p is None:
        return None
    cfg = run["cell"]["config_data"]
    lo, hi = run["trace_span"]
    rows = queries = 0
    for r in run["all_records"]:
        for j, t in enumerate(r["times"]):
            if j and lo <= t <= hi:
                rows += len(r["prompt"]) + j
                queries += 1
    cost = costs.paged_attn_cost(
        rows * cfg["n_layer"], cfg["n_head"], cfg["n_embd"] // cfg["n_head"],
        itemsize=4, queries=queries * cfg["n_layer"])
    return costs.share_pct(costs.least_seconds(cost, run["peaks"])[0], p[0],
                           "paged_attn")
