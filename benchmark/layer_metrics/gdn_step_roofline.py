"""Least time the traced ticks' gated delta steps could take over scope
``gdn_step``'s device time (%): every stepped (slot, layer) reads its state
once and writes it once (``gdn.step_rows``); memory-bound
(``benchmark/costs_qwen3next.py``)."""
from benchmark import costs_qwen3next


def read(run):
    return costs_qwen3next.step_roofline(run)
