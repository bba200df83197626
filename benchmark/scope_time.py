"""Device time by the program's own scopes, for the per-layer readers.

The program notes every hot program when it traces it and can map a device
event back to the ``jax.named_scope`` its instruction came from
(``paddle_tpu.observability.opscope``). Here that join is made once a run,
over chip 0's operations inside the traced window, and kept on the run:
exclusive seconds by ``(program, scope, phase)``. A program from before
``opscope`` has nothing to read and every reader of these gives None.
"""
from __future__ import annotations

from . import readers, trace as _trace

STEP_SPAN = readers.STEP_SPAN
TICK_SPAN = readers.TICK_SPAN
UNSCOPED = "_unscoped_"


def seconds(run):
    """``{(program, scope, phase): exclusive seconds}`` of the traced
    window; None without a trace, before ``opscope``, or where the join
    finds no event in any noted program."""
    flat = readers.flat_trace(run)
    if flat is None or not flat["device"]:
        return None
    if "scope_seconds" not in run:
        run["scope_seconds"] = None
        try:
            from paddle_tpu.observability import opscope
        except ImportError:
            return None
        lo, hi = _trace.window_of(flat)
        found = opscope.by_scope(
            [e for e in flat["device"][0] if lo <= e[1] < hi])
        if any(scope != UNSCOPED for _, scope, _ in found):
            run["scope_seconds"] = found
    return run["scope_seconds"]


def within(scope: str, name: str) -> bool:
    """Whether the scope path ``gpt/attn/paged_attn`` lies inside ``name``
    (``gpt/attn``)."""
    return "/" + name + "/" in "/" + scope + "/"


def ms_per_span(run, span, program=None, scopes=None, phase=None):
    """Milliseconds a host span ``span`` of the events that ran in
    ``program`` (any, if None), inside one of ``scopes`` and in ``phase``."""
    found = seconds(run)
    if found is None:
        return None
    spans = readers.spans_in_window(run, span)
    if not spans:
        return None
    kept = sum(s for (prog, scope, ph), s in found.items()
               if program in (None, prog) and phase in (None, ph)
               and (scopes is None or any(within(scope, n) for n in scopes)))
    return kept * 1e3 / spans


def unscoped_pct(run):
    """Share of the window's exclusive device time that no scope names (%):
    events found in no noted program, or in two, and instructions that stand
    outside every scope."""
    found = seconds(run)
    if found is None:
        return None
    return 100.0 * sum(s for (_, scope, _), s in found.items()
                       if scope == UNSCOPED) / sum(found.values())
