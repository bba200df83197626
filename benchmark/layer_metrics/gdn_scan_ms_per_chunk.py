"""Device time of the gated delta rule's chunked scan (scope ``gdn_scan`` of
the chunk program, every linear layer) in one ``prefill_chunk`` span (ms)."""
from benchmark import costs_qwen3next


def read(run):
    return costs_qwen3next.ms_per_chunk(run, "gdn_scan")
