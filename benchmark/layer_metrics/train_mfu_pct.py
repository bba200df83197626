"""Model FLOP/s over the chip's peak (%): 6N plus causal attention per
token, no recompute, at the median step (so that starting the profiler does
not count)."""
from benchmark import costs
from benchmark.readers import train_step_ms_p50


def read(run):
    step_ms = train_step_ms_p50(run)
    if step_ms is None:
        return None
    tr, cfg = run["cell"]["traffic_data"], run["cell"]["config_data"]
    per_token = costs.gpt_train_flops_per_token(cfg, tr["seq_len"])
    tok_s = run["tokens_per_step"] * 1e3 / step_ms
    return costs.share_pct(per_token * tok_s,
                           run["peaks"]["flops_per_s"] * run["device"]["count"],
                           "train_mfu_pct")
