"""Time from due to first token, 90th percentile (ms)."""
from benchmark.harness import percentile


def read(run):
    return percentile(run["ttft_ms"], 90) if run.get("ttft_ms") else None
