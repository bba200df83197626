"""Operations and bytes the Qwen3-Next cell's mechanism needs, from the
RECURRENCE and the engine's counters alone, and where its device time is in a
trace.

As in ``costs.py`` these are what the algorithm requires, never what an
implementation happens to run: the gated delta rule costs, a row and value
head, ``7 x Dk x Dv`` operations (the decay of the state ``Dk Dv``, ``S^T k``
and ``S^T q`` ``2 Dk Dv`` each, the rank-one write ``2 Dk Dv``), counted at
ONE pass a product, and the bytes of its ``q k v g beta o``; a chunk's scan
and a decode step also read the state once and write it once, a (chunk,
layer) or (slot, layer) pair. Nothing here reads the operator's chunk size or
knows whether it is a kernel, so a share computed from these reads the same
work whatever implements it, is a lower bound, and cannot pass 100%.
``gdn.chunk_rows`` counts the (token, linear layer) pairs the chunks scanned,
``gdn.step_rows`` the (slot, linear layer) pairs the ticks stepped.

The expert kernels are the held-experts path of ``costs_trinity.py``, whose
keys this configuration shares (``num_experts`` held of
``share.num_experts_published``).
"""
from __future__ import annotations

from . import costs, readers, scope_time

CHUNK_SPAN = "serving.llm/prefill_chunk"
CHUNK_PROGRAM, STEP_PROGRAM = "jit__chunk", "jit__step"


def is_qwen3next(run) -> bool:
    return "linear_num_value_heads" in (
        (run.get("cell") or {}).get("config_data") or {})


def linear_layers(cfg: dict) -> int:
    n, every = cfg["num_hidden_layers"], cfg["full_attention_interval"]
    return n - n // every


def state_bytes(cfg: dict, itemsize: int = 4) -> int:
    """One value-head-stacked state of one (slot, layer)."""
    return (cfg["linear_num_value_heads"] * cfg["linear_key_head_dim"]
            * cfg["linear_value_head_dim"] * itemsize)


def row_flops(cfg: dict) -> float:
    """The recurrence of one (token, layer) pair, every value head."""
    return 7.0 * cfg["linear_num_value_heads"] * cfg["linear_key_head_dim"] \
        * cfg["linear_value_head_dim"]


def row_bytes(cfg: dict, itemsize: int = 4) -> float:
    """``q k v g beta o`` of one (token, layer) pair."""
    hk, hv = cfg["linear_num_key_heads"], cfg["linear_num_value_heads"]
    return float(itemsize) * (2 * hk * cfg["linear_key_head_dim"]
                              + 2 * hv * cfg["linear_value_head_dim"]
                              + 2 * hv)


def scan_cost(cfg: dict, rows: float, chunk_layers: float) -> dict:
    """The chunk programs' scans over ``rows`` (token, layer) pairs in
    ``chunk_layers`` (chunk, layer) pairs: each of those carries a state in
    and out."""
    return {"flops": rows * row_flops(cfg),
            "bytes": rows * row_bytes(cfg)
            + 2.0 * chunk_layers * state_bytes(cfg)}


def step_cost(cfg: dict, rows: float) -> dict:
    """The decode steps over ``rows`` (slot, layer) pairs: each reads its
    state and writes it."""
    return {"flops": rows * row_flops(cfg),
            "bytes": rows * (row_bytes(cfg) + 2.0 * state_bytes(cfg))}


def scope_seconds(run, program: str, scope: str):
    """Device seconds of the traced window inside ``scope`` of ``program``;
    None without a trace or where the program carries no such scope."""
    found = scope_time.seconds(run)
    if found is None:
        return None
    return sum(s for (prog, sc, _), s in found.items()
               if prog == program and scope_time.within(sc, scope)) or None


def scan_roofline(run):
    """Least time of the traced chunks' scans over scope ``gdn_scan``'s
    device time (%)."""
    c = run.get("trace_counters") or {}
    seconds = scope_seconds(run, CHUNK_PROGRAM, "gdn_scan")
    if not is_qwen3next(run) or not seconds or not c.get("gdn.chunk_rows"):
        return None
    cfg = run["cell"]["config_data"]
    cost = scan_cost(cfg, c["gdn.chunk_rows"],
                     c.get("prefill_chunks", 0) * linear_layers(cfg))
    return costs.share_pct(costs.least_seconds(cost, run["peaks"])[0],
                           seconds, "gdn_scan")


def step_roofline(run):
    """Least time of the traced ticks' steps over scope ``gdn_step``'s device
    time (%); memory-bound."""
    c = run.get("trace_counters") or {}
    seconds = scope_seconds(run, STEP_PROGRAM, "gdn_step")
    if not is_qwen3next(run) or not seconds or not c.get("gdn.step_rows"):
        return None
    cost = step_cost(run["cell"]["config_data"], c["gdn.step_rows"])
    return costs.share_pct(costs.least_seconds(cost, run["peaks"])[0],
                           seconds, "gdn_step")


def ms_per_chunk(run, scope: str):
    if not is_qwen3next(run):
        return None
    return scope_time.ms_per_span(run, CHUNK_SPAN, CHUNK_PROGRAM,
                                  scopes=(scope,)) or None


def ms_per_tick(run, scope: str):
    if not is_qwen3next(run):
        return None
    return scope_time.ms_per_span(run, readers.TICK_SPAN, STEP_PROGRAM,
                                  scopes=(scope,)) or None
