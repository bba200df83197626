"""Least time the traced chunks' gated delta rule could take over scope
``gdn_scan``'s device time (%): ``7 x 128 x 128`` operations a (token, layer,
value head) at one pass a product, the bytes of ``q k v g beta o`` and a
state in and out a (chunk, layer); counted from the recurrence and
``gdn.chunk_rows`` alone (``benchmark/costs_qwen3next.py``)."""
from benchmark import costs_qwen3next


def read(run):
    return costs_qwen3next.scan_roofline(run)
