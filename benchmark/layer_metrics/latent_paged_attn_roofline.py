"""Least time ``paged_attn`` could take over its device time in the traced
stretch (%), in a cell whose cache keeps latent rows: the rows its walks
read during the stretch (``latent_attn.rows_live``, the engine's counter read
at the stretch's two ends) at the bytes a row holds (``kv_row_bytes``,
padding included), the queries and results, and ``heads x (576 + 512) x 2``
operations a row counted at one pass a product; the larger of the two
bounds."""
from benchmark import costs, costs_moonlight
from benchmark.readers import paged


def read(run):
    c = run.get("trace_counters") or {}
    gauges = run.get("gauges") or {}
    if not costs_moonlight.is_latent(run) \
            or not c.get("latent_attn.rows_live") \
            or not gauges.get("kv_row_bytes"):
        return None
    p = paged(run)
    if p is None:
        return None
    cfg = run["cell"]["config_data"]
    queries = c.get("tokens_generated", 0) * cfg["num_hidden_layers"]
    cost = costs_moonlight.latent_walk_cost(
        cfg, gauges["kv_row_bytes"], c["latent_attn.rows_live"], queries)
    return costs.share_pct(costs.least_seconds(cost, run["peaks"])[0], p[0],
                           "paged_attn (latent rows)")
