"""Host work of one decode tick (ms): the capacity pass before it, the
dispatch of the step, and the statistics and per-slot emit loop after the
fetch, over the window's ticks. Serves ``tick_host_ms_mean.closed`` and
``.open``."""
from benchmark.worker_phases import phase_seconds, ticks

HOST = ("tick_capacity", "tick_dispatch", "tick_emit")


def read(run):
    w, n = phase_seconds(run), ticks(run)
    if w is None or not n:
        return None
    return 1e3 * sum(w.get(k, 0.0) for k in HOST) / n
