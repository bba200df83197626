"""Device time of the three flash kernels in one train step (ms)."""
from benchmark.readers import flash


def read(run):
    f = flash(run)
    return None if f is None else f[0] * 1e3 / f[2]
