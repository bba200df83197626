"""Least time grouped-query ``paged_attn`` could take over its device time
(%): the rows the decode tokens emitted during the trace had to read (token
j of a request attends prompt + j rows), in every ATTENTION layer, K and V
over the KV heads, operations over the query heads."""
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
    layers = cfg["layer_types"][:cfg["num_hidden_layers"]].count(
        "full_attention")
    hq, hkv = cfg["num_attention_heads"], cfg["num_key_value_heads"]
    d = cfg["hidden_size"] // hq
    flops = 4.0 * rows * layers * hq * d
    nbytes = (2.0 * rows * hkv + 2.0 * queries * hq) * layers * d * 4
    return costs.share_pct(
        costs.least_seconds({"flops": flops, "bytes": nbytes},
                            run["peaks"])[0], p[0], "paged_attn")
