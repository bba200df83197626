"""Share of its loop time the worker ran Python and dispatched (%): the
loop less the waiting for a request and less the two blocking fetches, the
tick's and the admission's. Serves ``worker_host_pct.closed`` and
``.open``."""
from benchmark.worker_phases import share_of_loop_pct

WAITING = ("idle_wait", "tick_fetch", "first_token_fetch")


def read(run):
    return share_of_loop_pct(
        run, lambda w: w["loop"] - sum(w.get(k, 0.0) for k in WAITING))
