"""Empirical trial runner: time candidates, keep the median, prune early.

The contract mirrors TVM's measure loop at micro scale: every candidate
is compiled once (excluded from timing), then timed ``trials`` times with
a blocking fetch after each run; the score is the median, which is robust
to the one-off stalls a shared chip shows. Early pruning: after the first
timed run, a candidate already slower than ``prune_factor`` x the best
median so far is abandoned — on a 30-candidate space this cuts wall time
roughly in half without changing the winner.
"""
from __future__ import annotations

import statistics
import time
from typing import Any, Callable, Dict, List, Optional, Tuple

import jax


def _wait(out: Any) -> None:
    """Block until every device value in ``out`` (one array or any
    pytree of them: the backward lane returns ``(dq, dk, dv)``) is
    ready, so that the wait is real."""
    jax.block_until_ready(out)  # noqa: PTA002 -- tuner trial barrier: timing requires completion


def time_once(run: Callable[[], Any]) -> float:
    """One timed execution of ``run``, to the end of its device work."""
    t0 = time.perf_counter()
    _wait(run())
    return time.perf_counter() - t0


def measure(run: Callable[[], Any], trials: int = 5,
            best_so_far: Optional[float] = None,
            prune_factor: float = 2.0) -> Optional[float]:
    """Median-of-``trials`` seconds for ``run`` (after one untimed
    warmup that also absorbs the compile). Returns None when the
    candidate fails to build/run, or when early pruning fires."""
    try:
        _wait(run())
        first = time_once(run)
    except Exception:
        return None
    if best_so_far is not None and first > best_so_far * prune_factor:
        return None                       # early pruning
    times = [first]
    for _ in range(max(0, trials - 1)):
        times.append(time_once(run))
    return statistics.median(times)


def search(candidates: List[Any],
           make_runner: Callable[[Any], Callable[[], Any]],
           trials: int = 5, prune_factor: float = 2.0
           ) -> Tuple[Optional[Any], Optional[float], Dict[str, float]]:
    """Time every candidate; returns (winner, winner_seconds, results).

    ``make_runner(candidate)`` returns the zero-arg callable to time (it
    may raise for unbuildable candidates — that candidate just scores
    None). ``results`` maps repr(candidate) -> median seconds for the
    candidates that completed, for reports and tests.
    """
    best: Optional[Any] = None
    best_t: Optional[float] = None
    results: Dict[str, float] = {}
    for cand in candidates:
        try:
            run = make_runner(cand)
        except Exception:
            continue
        t = measure(run, trials=trials, best_so_far=best_t,
                    prune_factor=prune_factor)
        if t is None:
            continue
        results[repr(cand)] = t
        if best_t is None or t < best_t:
            best, best_t = cand, t
    return best, best_t, results
