"""Operations and bytes the MiniCPM-SALA cell's kernels need, from shapes and
the engine's counters alone.

As in ``costs.py`` these are what the algorithm requires, so a share
computed from them is a lower bound: the selected walk of ``paged_attn`` is
charged the rows of the pages the selections name (K and V of one KV head a
page, from the engine's ``sparse_attn.pages_selected``, which counts (page,
KV head, sparse layer) triples a tick) and its queries in and results out;
the compressed keys are read by the selection, an XLA computation beside the
kernel, and are not the kernel's bytes.
"""
from __future__ import annotations


def selected_walk_cost(cfg: dict, page_size: int, pages: float,
                       queries: float, itemsize: int = 4) -> dict:
    """``paged_attn`` over ``pages`` selected (page, KV head, layer) triples
    for ``queries`` (token, sparse layer) pairs: every row of a page is a
    key and a value of ``head_dim``; QK^T and PV are ``2 * D`` operations a
    row and query head each, ``G`` query heads to a KV head."""
    d = cfg["head_dim"]
    groups = cfg["num_attention_heads"] // cfg["num_key_value_heads"]
    rows = float(pages) * page_size
    return {"bytes": (2.0 * rows * d
                      + 2.0 * queries * cfg["num_attention_heads"] * d)
            * itemsize,
            "flops": 4.0 * rows * groups * d}


def sparse_layers(cfg: dict) -> int:
    return cfg["mixer_types"][:cfg["num_hidden_layers"]].count("minicpm4")
