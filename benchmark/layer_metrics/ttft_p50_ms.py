"""Time from the moment a request was due to its first token, median over
the requests due inside the window (ms)."""
from benchmark.harness import percentile


def read(run):
    return percentile(run["ttft_ms"], 50) if run.get("ttft_ms") else None
