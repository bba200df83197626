"""Host page mapping of one admission (ms): ``worker.admit_pages_s`` (slot
allocation, prefix adoption and ``kv.ensure_pages``, one small dispatch a
page) over the window's admissions. The ledger's value comes from a traced
run, whose admissions the profiler's stop slows (PERF.md section 7, row 9)."""
from benchmark.worker_phases import admissions, phase_seconds


def read(run):
    w, n = phase_seconds(run), admissions(run)
    if w is None or not n:
        return None
    return 1e3 * w.get("admit_pages", 0.0) / n
