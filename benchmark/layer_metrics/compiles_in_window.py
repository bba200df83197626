"""Programs JAX was asked to compile inside the window (count). Serves
``compiles_in_window.train``, ``.closed`` and ``.open``."""


def read(run):
    return run.get("compiles_in_window")
