"""Median wall time of a decode tick, from the engine (ms). Serves
``decode_tick_ms_p50.closed`` and ``.open``."""
from benchmark.readers import hist_p50


def read(run):
    return hist_p50(run, "decode_tick_ms")
