"""Operations and bytes the LFM2-MoE cell's kernels need, from shapes and
the engine's counters alone, and where their device time is in a trace.

As in ``costs.py`` these are what the algorithm requires: an expert's three
matrices are read once in a tick in which it received a token, however many
tiles the kernel gave it, and activations are left out of the bytes, so a
share computed from them is a lower bound.
"""
from __future__ import annotations

from . import readers, trace as _trace

MOE_KERNEL = "moe_experts"


def expert_bytes(cfg: dict, itemsize: int = 4) -> int:
    """One expert's gate, up and down matrices."""
    return 3 * cfg["hidden_size"] * cfg["moe_intermediate_size"] * itemsize


def expert_layers(cfg: dict) -> int:
    return cfg["num_hidden_layers"] - cfg["num_dense_layers"]


def moe_tick_cost(cfg: dict, experts_active: float, pairs: float) -> dict:
    """The expert product of decode ticks in which ``experts_active``
    (expert, layer, tick) triples received a token and ``pairs``
    token-expert pairs were routed: each such expert's weights once, and
    three ``h x f`` products of 2 operations an element a pair."""
    return {"bytes": float(experts_active) * expert_bytes(cfg),
            "flops": 6.0 * cfg["hidden_size"] * cfg["moe_intermediate_size"]
            * float(pairs)}


def decode_rows(cfg: dict, slots: int) -> int:
    """Rows of the decode tick's expert row buffer (``ops/moe.py``: one tile
    of ``slots`` rows, rounded up to 8, for every expert)."""
    return cfg["num_experts"] * (-(-slots // 8) * 8)


def decode_moe(run):
    """(device seconds, ticks) of the expert kernels of the decode ticks in
    the traced window. The prefill programs run the same kernels over more
    rows; an operation belongs to a decode tick when its result has the
    tick's row count (the trace names an operation by its instruction's
    text, ``%moe_experts_up.3 = f32[1024,1792]{...} custom-call(...)``)."""
    flat = readers.flat_trace(run)
    if flat is None:
        return None
    cfg = run["cell"]["config_data"]
    rows = decode_rows(cfg, run["cell"]["traffic_data"]["engine"]["num_slots"])
    lo, hi = _trace.window_of(flat)
    seconds = sum(d for name, s, d in flat["device"][0]
                  if MOE_KERNEL in name and f"[{rows}," in name
                  and lo <= s < hi) / 1e9
    ticks = readers.spans_in_window(run, readers.TICK_SPAN)
    return (seconds, ticks) if seconds and ticks else None


def window_ticks(run) -> int:
    return run.get("hist", {}).get("decode_tick_ms", {}).get("count", 0)
