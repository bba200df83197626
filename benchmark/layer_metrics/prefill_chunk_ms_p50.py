"""Median time of one prefill chunk, from the engine's histogram
``prefill_chunk_ms`` (ms): from the chunk's dispatch to the end of the fetch
that follows it (the tick's, the first token's, or the chunk's own where
nothing decodes)."""
from benchmark.readers import hist_p50


def read(run):
    return hist_p50(run, "prefill_chunk_ms")
