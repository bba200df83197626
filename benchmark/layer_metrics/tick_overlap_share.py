"""Share of the window's decode ticks whose step was dispatched while the
step before it was still unfetched: the engine's ``ticks_overlapped`` over
the ticks (one ``decode_tick_ms`` sample each). Near 1 where the batch stays
full; lower where admissions, an empty batch or control calls settle the
step in flight. Nothing to read from a program that does not count it, nor
from an engine whose tick stays serial (chunked prefill, speculation).
Serves ``tick_overlap_share.closed``, ``.moe`` and ``.open``."""
from benchmark.worker_phases import ticks


def read(run):
    overlapped = (run.get("counters") or {}).get("ticks_overlapped")
    n = ticks(run)
    if overlapped is None or not n:
        return None
    return overlapped / n
