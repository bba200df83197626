"""Median wall time of one prefill, from the engine (ms)."""
from benchmark.readers import hist_p50


def read(run):
    return hist_p50(run, "prefill_ms")
