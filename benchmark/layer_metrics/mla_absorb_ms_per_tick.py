"""Device time of the absorbed order's own products (scope
``moonlight/mla/absorb`` of the decode step: ``q~ = qn W_UK^T`` before the
walk and ``o~ W_UV`` after it) in one decode tick (ms)."""
from benchmark import scope_time


def read(run):
    # a program without the scope (another family's, an older one) has
    # nothing to read
    return scope_time.ms_per_span(run, scope_time.TICK_SPAN, "jit__step",
                                  scopes=("moonlight/mla/absorb",)) or None
