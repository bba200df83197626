"""Due to admitted, median (ms): time to first token less the engine's
median prefill."""
import statistics

from benchmark.readers import hist_p50


def read(run):
    prefill = hist_p50(run, "prefill_ms")
    if prefill is None or not run.get("ttft_ms"):
        return None
    return statistics.median(max(t - prefill, 0.0) for t in run["ttft_ms"])
