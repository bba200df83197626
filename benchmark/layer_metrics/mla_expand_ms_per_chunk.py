"""Device time of the expanded order's own products (scope
``moonlight/mla/expand`` of the chunk program: the prefix's pages gathered
and multiplied by ``W_UKV`` a tile at a time, and the chunk's own keys and
values) in one ``prefill_chunk`` span (ms)."""
from benchmark import costs_moonlight, scope_time


def read(run):
    return scope_time.ms_per_span(run, costs_moonlight.CHUNK_SPAN,
                                  "jit__chunk",
                                  scopes=("moonlight/mla/expand",)) or None
