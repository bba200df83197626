"""paddle-tpu-analyze: rule-based static analysis for the jit-era codebase.

The reference enforces correctness natively at the C++ layer
(PADDLE_ENFORCE / platform/errors.h); a pure-Python JAX port has no such
guardrail, so tracer leaks, hidden host syncs and API-surface drift only
surface at runtime.  This package is the static gate: a small `ast`-based
framework (stdlib only — it must run before anything heavy imports) with

- per-rule enable/disable (``--rule`` / ``--skip``),
- inline ``# noqa: PTA###`` suppressions,
- a checked-in baseline (tools/analyze/baseline.json) so pre-existing
  findings don't block CI while newly introduced ones do,
- ``--json`` machine output and gate-style exit codes
  (0 clean, 1 new findings, 2 internal error).

Rules (see docs/static_analysis.md):

========  ==============================================================
PTA001    tracer-safety: host-forcing ops inside jit-reachable functions
PTA002    host sync in hot-path directories (ops/, optimizer/, amp/, ...)
PTA003    silent except in resilience-critical paths
PTA004    op registry <-> tools/op_catalog.txt consistency
PTA005    API hygiene: mutable default args, missing future annotations
========  ==============================================================

Run: ``python -m tools.analyze [--json] [--baseline FILE] [--rule NAME]
[paths...]``
"""
from .core import (  # noqa: F401
    Finding, Project, SourceFile,
    load_baseline, split_findings, baseline_payload, write_baseline,
    run_rules, filter_noqa,
)

__version__ = "1.0"
