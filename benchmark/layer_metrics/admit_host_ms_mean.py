"""The host's part of one admission (ms): ``worker.admit_s`` less the wait
for the first token, over the window's admissions. The ledger's value
comes from a traced run, whose admissions the profiler's stop slows (PERF.md
section 7, row 9)."""
from benchmark.worker_phases import admissions, phase_seconds


def read(run):
    w, n = phase_seconds(run), admissions(run)
    if w is None or not n:
        return None
    return 1e3 * (w.get("admit", 0.0) - w.get("first_token_fetch", 0.0)) / n
