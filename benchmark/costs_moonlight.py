"""Operations and bytes the Moonlight cell's kernels need, from shapes and
the engine's counters alone.

As in ``costs.py`` these are what the algorithm requires, so a share computed
from them is a lower bound. ``paged_attn`` runs in every layer over LATENT
rows: a walk reads each cached row of its sequence once, AS HELD (a token and
layer keep ``kv_row_bytes``: the 576 numbers and the padding behind them), and
a row serves both products of all the query heads: ``heads x (576 + 512) x
2`` operations (one dot with the whole row for the score, the weighted sum of
its first 512 columns for the result). ``latent_attn.rows_live`` counts the
rows the walks read, a sequence, tick and layer. The operations are counted
at ONE pass a product (the kernel makes several at precision ``highest``), so
a compute-bound least time is a lower bound too.

The expert kernels are the held-experts path of ``costs_trinity.py`` under
this configuration's keys (``n_routed_experts``, ``first_k_dense_replace``).
"""
from __future__ import annotations

from . import costs, costs_lfm2

CHUNK_SPAN = "serving.llm/prefill_chunk"


def latent_widths(cfg: dict) -> tuple:
    """``(value, rotary)`` columns of a cached row."""
    return cfg["kv_lora_rank"], cfg["qk_rope_head_dim"]


def latent_walk_cost(cfg: dict, row_bytes: float, rows: float,
                     queries: float) -> dict:
    """``paged_attn`` over ``rows`` (row, layer) pairs of ``row_bytes`` each
    for ``queries`` (token, layer) pairs."""
    value, rotary = latent_widths(cfg)
    heads, itemsize = cfg["num_attention_heads"], 4
    return {"flops": 2.0 * rows * heads * (2 * value + rotary),
            "bytes": float(rows) * row_bytes
            + float(queries) * heads * (2 * value + rotary) * itemsize}


def as_lfm2(run) -> dict:
    """The run with the configuration's expert keys under the names
    ``costs_lfm2`` reads (``num_experts`` HELD, ``num_dense_layers``)."""
    cell = run["cell"]
    cfg = dict(cell["config_data"])
    cfg.update(num_experts=cfg["n_routed_experts"],
               num_dense_layers=cfg["first_k_dense_replace"])
    return dict(run, cell=dict(cell, config_data=cfg))


def held_tick_cost(cfg: dict, experts_active: float, pairs: float) -> dict:
    """``costs_lfm2.moe_tick_cost`` with the pairs that reach a held expert:
    the held share of all the pairs routed."""
    held = cfg["n_routed_experts"] / cfg["share"]["num_experts_published"]
    return costs_lfm2.moe_tick_cost(cfg, experts_active, pairs * held)


def held_experts_roofline(run):
    """Least time of the traced ticks' expert work over the expert kernels'
    device time (%): the window's mean a tick times the ticks traced."""
    m = costs_lfm2.decode_moe(as_lfm2(run))
    ticks = costs_lfm2.window_ticks(run)
    c = run.get("counters", {})
    if m is None or not ticks or not c.get("moe_experts_active"):
        return None
    seconds, traced = m
    cost = held_tick_cost(run["cell"]["config_data"],
                          c["moe_experts_active"] / ticks * traced,
                          c.get("moe_pairs_routed", 0) / ticks * traced)
    return costs.share_pct(costs.least_seconds(cost, run["peaks"])[0],
                           seconds, "moe_experts (held)")


def is_latent(run) -> bool:
    return "kv_lora_rank" in ((run.get("cell") or {}).get("config_data")
                              or {})
