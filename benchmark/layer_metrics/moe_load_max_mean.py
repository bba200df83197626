"""The fullest expert's tokens in a decode tick (the largest over the
expert layers), mean over the window's ticks; the mean load is
``top_k x batch / experts``."""
from benchmark import costs_lfm2


def read(run):
    ticks = costs_lfm2.window_ticks(run)
    load = run.get("counters", {}).get("moe_load_max")
    return load / ticks if ticks and load else None
