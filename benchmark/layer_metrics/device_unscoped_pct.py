"""Share of the traced window's exclusive device time that the program's
scopes fail to name (%): the twin of ``idle_unattributed_pct``. Serves
``device_unscoped_pct.train``, ``.closed`` and ``.open``."""
from benchmark import scope_time


def read(run):
    return scope_time.unscoped_pct(run)
