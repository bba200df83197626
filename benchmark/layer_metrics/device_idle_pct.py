"""Share of the traced window in which no operation ran on the chip (%).
Serves ``device_idle_pct.train``, ``.closed`` and ``.open``."""


def read(run):
    tr = run.get("trace")
    return None if not tr else 100.0 * (1.0 - tr["busy_s"] / tr["window_s"])
