"""Enqueued to the start of the admission that commits, mean over the
window's admissions (ms): the engine's own ``queue_wait_s``, where
``queue_wait_ms_p50`` infers the wait from outside."""
from benchmark.worker_phases import admissions


def read(run):
    wait = (run.get("counters") or {}).get("queue_wait_s")
    n = admissions(run)
    return None if wait is None or not n else 1e3 * wait / n
