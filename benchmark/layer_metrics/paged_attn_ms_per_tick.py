"""Device time of ``paged_attn`` in one decode tick (ms). Serves
``paged_attn_ms_per_tick.closed`` and ``.open``."""
from benchmark.readers import paged


def read(run):
    p = paged(run)
    return None if p is None else p[0] * 1e3 / p[1]
