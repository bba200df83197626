"""Least time the flash kernels could take over their device time (%)."""
from benchmark import costs
from benchmark.readers import flash


def read(run):
    f = flash(run)
    return None if f is None else costs.share_pct(f[1], f[0], "flash kernels")
