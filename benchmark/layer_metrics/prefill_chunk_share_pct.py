"""Share of its loop time the worker spent in the ``prefill_chunk`` phase
(%): building and dispatching chunks and, where nothing decodes, waiting for
them (``worker.prefill_chunk_s`` over ``worker.loop_s``)."""
from benchmark.worker_phases import phase_seconds, share_of_loop_pct


def read(run):
    w = phase_seconds(run)
    if w is None or "prefill_chunk" not in w:
        return None
    return share_of_loop_pct(run, lambda w: w["prefill_chunk"])
