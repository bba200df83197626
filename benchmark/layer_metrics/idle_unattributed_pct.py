"""Share of the device's idle time that falls while no span of the program
or the benchmark is open (%): what the spans fail to name. Every gap is
counted, not only the ten largest buckets the result line prints. Serves
``idle_unattributed_pct.train``, ``.closed`` and ``.open``."""
from benchmark import trace


def read(run):
    tr = run.get("trace")
    if not tr or tr["window_s"] <= tr["busy_s"]:
        return None
    flat = tr["flat"]
    gaps = dict(trace.idle_gaps(flat, n=len(flat["host"]) + 2))
    return 100.0 * gaps.get(trace.NO_SPAN, 0.0) \
        / (tr["window_s"] - tr["busy_s"])
