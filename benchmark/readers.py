"""What several per-layer metric files share: how to find spans, kernels
and the engine's histograms in a run (what the driver measured, the engine's
counters, the reduced trace). Each metric's own arithmetic is in its file
under ``layer_metrics/``."""
from __future__ import annotations

import statistics

from . import costs, trace as _trace

FLASH_KERNELS = ("flash_fwd", "flash_bwd_dq", "flash_bwd_dkv")
STEP_SPAN = "bench/step"
TICK_SPAN = "serving.llm/decode_tick"


def flat_trace(run):
    tr = run.get("trace")
    return tr["flat"] if tr else None


def spans_in_window(run, name):
    """How many host spans of that name lie wholly inside the traced window."""
    flat = flat_trace(run)
    lo, hi = _trace.window_of(flat)
    return sum(1 for n, s, d in flat["host"]
               if n == name and s >= lo and s + d <= hi)


def hist_p50(run, name):
    h = run.get("hist", {}).get(name)
    return h["p50"] if h and h["count"] else None


def train_step_ms_p50(run):
    if not run.get("steps"):
        return None
    return statistics.median((b - a) * 1e3 for a, b in run["steps"])


def flash(run):
    """(device seconds, least seconds, steps) of the flash kernels traced."""
    flat = flat_trace(run)
    if flat is None:
        return None
    tr, cfg = run["cell"]["traffic_data"], run["cell"]["config_data"]
    busy = least = 0.0
    for kernel in FLASH_KERNELS:
        seconds, calls = _trace.kernel_seconds(flat, kernel)
        cost = costs.flash_call_cost(
            kernel, tr["batch"], cfg["n_head"], tr["seq_len"],
            cfg["n_embd"] // cfg["n_head"], itemsize=2)
        busy += seconds
        least += calls * costs.least_seconds(cost, run["peaks"])[0]
    steps = spans_in_window(run, STEP_SPAN)
    return (busy, least, steps) if busy and steps else None


def paged(run):
    """(device seconds, ticks) of paged_attn in the traced window."""
    flat = flat_trace(run)
    if flat is None:
        return None
    seconds, _calls = _trace.kernel_seconds(flat, "paged_attn")
    ticks = spans_in_window(run, TICK_SPAN)
    return (seconds, ticks) if seconds and ticks else None
