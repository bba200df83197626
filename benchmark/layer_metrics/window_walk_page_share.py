"""Share of the live pages that the window layers' walks read in the
window's decode ticks: the engine's ``window_attn.pages_walked`` over
``window_attn.pages_live`` (1.0 would mean the walk ignores the window)."""


def read(run):
    c = run.get("counters") or {}
    live = c.get("window_attn.pages_live", 0)
    return c.get("window_attn.pages_walked", 0) / live if live else None
