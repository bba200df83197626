"""How late the load generator submitted, 99th percentile (ms)."""
from benchmark.harness import percentile


def read(run):
    return percentile(run["gen_late_ms"], 99) if run.get("gen_late_ms") \
        else None
