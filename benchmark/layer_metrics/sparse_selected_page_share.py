"""Share of the live pages that the sparse layers' selections read in the
window's decode ticks: the engine's ``sparse_attn.pages_selected`` over
``sparse_attn.pages_live`` (1.0 would mean the walk ignores the
selection)."""


def read(run):
    c = run.get("counters") or {}
    live = c.get("sparse_attn.pages_live", 0)
    return c.get("sparse_attn.pages_selected", 0) / live if live else None
