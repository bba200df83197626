"""Share of its loop time the worker waited for a request to exist (%):
``worker.idle_wait_s`` over ``worker.loop_s``. Serves
``worker_idle_wait_pct.open``: eight closed-loop clients on eight slots
leave the worker nothing to wait for, so the decode cell has no such split."""
from benchmark.worker_phases import share_of_loop_pct


def read(run):
    return share_of_loop_pct(run, lambda w: w.get("idle_wait", 0.0))
