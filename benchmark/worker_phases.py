"""What the per-layer metrics of the LLM worker's phases share: the window
deltas of the engine's ``worker.<phase>_s`` counters (seconds the worker
thread spent in each phase of its loop, counted by the program whether or
not a trace runs), and the window's ticks and admissions.

The open loop reads the counters after its drain, a few seconds past the
window, so a share is taken over ``loop`` (the worker's own elapsed time
between the two reads), never over the window's length."""
from __future__ import annotations

PREFIX, SUFFIX = "worker.", "_s"


def phase_seconds(run):
    """``{phase: seconds}`` between the window's two reads of the counters,
    or None where the program counts no phases (a train run; a program from
    before the counters)."""
    counters = run.get("counters") or {}
    if not counters.get(PREFIX + "loop" + SUFFIX):
        return None
    return {k[len(PREFIX):-len(SUFFIX)]: v for k, v in counters.items()
            if k.startswith(PREFIX) and k.endswith(SUFFIX)}


def share_of_loop_pct(run, seconds_of):
    """100 x ``seconds_of(phases)`` over the worker's loop time."""
    w = phase_seconds(run)
    return None if w is None else 100.0 * seconds_of(w) / w["loop"]


def ticks(run) -> int:
    return (run.get("hist") or {}).get("decode_tick_ms", {}).get("count", 0)


def admissions(run) -> int:
    return (run.get("counters") or {}).get("prefills", 0)
