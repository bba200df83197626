"""Share of the pages one group would hold for the same sequences that the
window layers' page group holds mapped, sampled every decode tick of the
window: the engine's ``kv_pages.window_held`` over
``kv_pages.window_unbounded`` (1.0 would mean the allocator ignores the
window)."""


def read(run):
    c = run.get("counters") or {}
    unbounded = c.get("kv_pages.window_unbounded", 0)
    return c.get("kv_pages.window_held", 0) / unbounded if unbounded else None
