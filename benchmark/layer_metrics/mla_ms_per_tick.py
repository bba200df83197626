"""Device time of latent attention whole (the scopes under
``moonlight/mla`` of the decode step: query, latent row and its write,
absorption, the walk, the output projection) in one decode tick (ms)."""
from benchmark import scope_time


def read(run):
    # a program without the scope (another family's, an older one) has
    # nothing to read
    return scope_time.ms_per_span(run, scope_time.TICK_SPAN, "jit__step",
                                  scopes=("moonlight/mla",)) or None
