"""Device time of the Gated-DeltaNet mixers whole (scope ``qwen3next/gdn`` of
the chunk program: projections, convolution, the scan, the gate norm, the
output projection) in one ``prefill_chunk`` span (ms)."""
from benchmark import costs_qwen3next


def read(run):
    # a program without the scope (another family's, an older one) has
    # nothing to read
    return costs_qwen3next.ms_per_chunk(run, "qwen3next/gdn")
