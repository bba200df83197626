"""Trace-level audit runner: the dynamic half of ``tools.analyze``.

The AST tier reads source text; this tier runs the *programs*. It imports
the repo's registered auditable entrypoints (``paddle_tpu.core.audit`` —
hapi train step, static Executor step, serving predict, LLM
prefill/decode), captures each one's jaxpr and lowered HLO under
``JAX_PLATFORMS=cpu``, and records per-entrypoint stats that the trace
rules (PTA009 fusion/transfer audit, PTA010 retrace sentinel) turn into
findings anchored at the registration site.

The audit compiles real code, so it only runs when a trace rule is
selected explicitly (``--only PTA009,PTA010``) and its result is memoized
per process — both rules read one report. ``PTA_TRACE_ENTRYPOINTS``
(comma-separated names) restricts which entrypoints run, for CI shards
and focused debugging.
"""
from __future__ import annotations

import hashlib
import os
import sys
import traceback
import warnings
from dataclasses import dataclass, field
from typing import Any, Dict, List, Optional, Tuple

from . import passes


@dataclass
class EntrypointStats:
    """Everything the audit learned about one entrypoint."""
    name: str
    tags: Tuple[str, ...] = ()
    path: str = ""   # registration site (repo-relative)
    line: int = 0
    error: str = ""  # build/trace failure — other fields are then partial
    trace_count: int = -1           # jit traces across the two variants
    fingerprints: List[str] = field(default_factory=list)
    fingerprint_stable: bool = True
    transfers: List[str] = field(default_factory=list)
    large_consts: List[Dict[str, Any]] = field(default_factory=list)
    donation: Optional[Dict[str, Any]] = None  # set when check applies
    hlo: Dict[str, int] = field(default_factory=dict)
    # collective-schedule audit (PTA012): ordered per-rank schedule,
    # total wire bytes per step, and any invariant violations
    collectives: List[Dict[str, Any]] = field(default_factory=list)
    collective_bytes: int = 0
    collective_issues: List[Dict[str, Any]] = field(default_factory=list)
    # fusion-miss audit (PTA014): region count, HBM bytes crossing
    # unfused elementwise/dot/norm boundaries, ranked worst offenders
    fusion_regions: int = 0
    unfused_boundary_bytes: int = 0
    top_fusion_misses: List[Dict[str, Any]] = field(default_factory=list)

    def payload(self) -> Dict[str, Any]:
        return {
            "tags": list(self.tags), "path": self.path, "line": self.line,
            "error": self.error, "trace_count": self.trace_count,
            "fingerprints": self.fingerprints,
            "fingerprint_stable": self.fingerprint_stable,
            "transfers": self.transfers,
            "large_consts": self.large_consts,
            "donation": self.donation, "hlo": self.hlo,
            "collectives": self.collectives,
            "collective_bytes": self.collective_bytes,
            "collective_issues": self.collective_issues,
            "fusion_regions": self.fusion_regions,
            "unfused_boundary_bytes": self.unfused_boundary_bytes,
            "top_fusion_misses": self.top_fusion_misses,
        }


@dataclass
class TraceReport:
    platform: str
    entrypoint_stats: Dict[str, EntrypointStats]
    error: str = ""  # registry-level failure (jax/paddle_tpu unimportable)

    def stats_payload(self) -> Dict[str, Any]:
        return {
            "version": 1,
            "platform": self.platform,
            "error": self.error,
            "entrypoints": {n: s.payload()
                            for n, s in sorted(
                                self.entrypoint_stats.items())},
        }


_LAST: Optional[TraceReport] = None

#: entrypoint scope installed by the driver (--changed-only): None = all,
#: [] = none. Wins over PTA_TRACE_ENTRYPOINTS; an explicit run_audit
#: names argument wins over both.
_SCOPE: Optional[List[str]] = None


def set_audit_scope(names: Optional[List[str]]) -> None:
    """Restrict which entrypoints the memoized audit runs (the
    --changed-only seam). Invalidates any memoized report so the scope
    takes effect even after a prior full run."""
    global _SCOPE, _LAST
    _SCOPE = names
    _LAST = None


def last_report() -> Optional[TraceReport]:
    return _LAST


def get_report() -> TraceReport:
    """Run the audit once per process; PTA009 and PTA010 share it."""
    global _LAST
    if _LAST is None:
        _LAST = run_audit()
    return _LAST


def _reset_for_tests() -> None:
    global _LAST
    _LAST = None


def _repo_root() -> str:
    return os.path.dirname(os.path.dirname(os.path.dirname(
        os.path.dirname(os.path.abspath(__file__)))))


def run_audit(names: Optional[List[str]] = None) -> TraceReport:
    """Build + trace every registered entrypoint. Never raises: failures
    are recorded per-entrypoint (or report-level for import failures) so
    one broken entrypoint doesn't hide the rest."""
    # must win the race with the first jax import: tracing on an
    # accelerator would make the audit a TPU job
    os.environ.setdefault("JAX_PLATFORMS", "cpu")
    root = _repo_root()
    if root not in sys.path:
        sys.path.insert(0, root)
    try:
        import jax
        try:
            # jax may have been imported before the setdefault above (the
            # env var is read at import); the config knob still wins if no
            # backend is live yet
            jax.config.update("jax_platforms", "cpu")
        except Exception:
            pass  # backend already initialized — platform field records it
        from paddle_tpu.core import audit as _audit
        eps = _audit.load_default_entrypoints()
        platform = jax.default_backend()
    except Exception:
        return TraceReport(platform="unavailable", entrypoint_stats={},
                           error=traceback.format_exc(limit=3))

    if names is None:
        names = _SCOPE
    if names is None:
        env = os.environ.get("PTA_TRACE_ENTRYPOINTS", "")
        names = [n.strip() for n in env.split(",") if n.strip()] or None
    stats: Dict[str, EntrypointStats] = {}
    for name, ep in sorted(eps.items()):
        if names is not None and name not in names:
            continue
        stats[name] = audit_entrypoint(name, ep)
    return TraceReport(platform=platform, entrypoint_stats=stats)


def audit_spec(name: str, spec, tags: Tuple[str, ...] = (),
               path: str = "", line: int = 0) -> EntrypointStats:
    """Audit one already-built AuditSpec (the test seam: fixtures hand in
    synthetic specs without touching the registry)."""
    import jax

    st = EntrypointStats(name=name, tags=tuple(tags), path=path, line=line)
    try:
        # -- static program analysis (jaxpr level) -------------------------
        mj_kwargs = {}
        if "static_argnums" in spec.jit_kwargs:
            mj_kwargs["static_argnums"] = spec.jit_kwargs["static_argnums"]
        closed = jax.make_jaxpr(spec.fn, **mj_kwargs)(*spec.make_args(0))
        st.transfers = passes.scan_transfers(closed)
        st.large_consts = passes.scan_large_consts(closed)
        st.collectives, st.collective_issues = \
            passes.collective_schedule(closed)
        st.collective_bytes = sum(e["bytes"] for e in st.collectives)
        if "train" in st.tags and "donate_argnums" not in spec.jit_kwargs:
            st.donation = passes.donation_opportunities(closed)

        # -- retrace sentinel (PTA010) --------------------------------------
        counter = {"n": 0}

        def _counting(*a):
            counter["n"] += 1
            return spec.fn(*a)

        jitted = jax.jit(_counting, **spec.jit_kwargs)
        with warnings.catch_warnings():
            # CPU ignores donate_argnums with a warning; irrelevant here
            warnings.simplefilter("ignore")
            jitted(*spec.make_args(0))
            jitted(*spec.make_args(1))
            # record BEFORE the lowers below: .lower() traces again
            st.trace_count = counter["n"]

            # executable fingerprint per variant — same program must lower
            # to byte-identical StableHLO when only array values change
            # (.lower() re-traces on every call regardless of the cache)
            fresh = jax.jit(spec.fn, **spec.jit_kwargs)
            for variant in (0, 1):
                text = fresh.lower(*spec.make_args(variant)).as_text()
                st.fingerprints.append(
                    hashlib.sha1(text.encode()).hexdigest()[:16])
            st.fingerprint_stable = (st.fingerprints[0]
                                     == st.fingerprints[1])

            # -- post-XLA census (fusion/copy stats + fusion misses) --------
            compiled = fresh.lower(*spec.make_args(0)).compile()
            hlo_text = compiled.as_text()
            st.hlo = passes.parse_hlo_stats(hlo_text)
            fus = passes.fusion_miss_report(hlo_text)
            st.fusion_regions = fus["fusion_regions"]
            st.unfused_boundary_bytes = fus["unfused_boundary_bytes"]
            st.top_fusion_misses = fus["top_fusion_misses"]
    except Exception:
        st.error = traceback.format_exc(limit=3)
    return st


def _resolve_module(root: str, dotted: str) -> Optional[str]:
    """Root-relative path of a dotted module under ``root``, or None."""
    base = dotted.replace(".", "/")
    for cand in (base + ".py", base + "/__init__.py"):
        if os.path.isfile(os.path.join(root, cand)):
            return cand
    return None


def _resolve_reexport(root: str, init_relpath: str, name: str,
                      depth: int = 0) -> List[str]:
    """Resolve a name re-exported by a package ``__init__.py`` to the
    submodule(s) that define it, chasing chained re-exports a few hops.
    Keeps --changed-only scoping precise without traversing the whole
    hub: ``from paddle_tpu.nn import Linear`` maps to nn/layers.py, not
    to everything nn's __init__ imports."""
    import ast

    if depth > 4:
        return []
    try:
        with open(os.path.join(root, init_relpath), "rb") as f:
            tree = ast.parse(f.read().decode("utf-8", errors="replace"))
    except (OSError, SyntaxError):
        return []
    pkg_parts = init_relpath.replace(os.sep, "/").split("/")[:-1]
    for node in ast.walk(tree):
        if not isinstance(node, ast.ImportFrom):
            continue
        mod = _absolute_module(pkg_parts, node)
        if not mod:
            continue
        for alias in node.names:
            if (alias.asname or alias.name) != name or alias.name == "*":
                continue
            p = _resolve_module(root, f"{mod}.{alias.name}")
            if p:
                return [p]
            p = _resolve_module(root, mod)
            if p and p.endswith("__init__.py"):
                return [p] + _resolve_reexport(root, p, alias.name,
                                               depth + 1)
            if p:
                return [p]
    return []


def _absolute_module(pkg_parts: List[str], node) -> str:
    """Absolute dotted module of an ImportFrom, resolving relative
    levels against the importing file's package."""
    if node.level:
        # `from ..ops import x` in pkg/a/b.py: level 1 anchors at pkg/a,
        # each extra level walks one package up
        base_parts = pkg_parts[:len(pkg_parts) - (node.level - 1)]
        prefix = ".".join(base_parts)
        return f"{prefix}.{node.module}" if node.module else prefix
    return node.module or ""


def _file_imports(root: str, relpath: str) -> List[str]:
    """Root-relative paths this file statically imports (module- and
    function-level), restricted to modules that live under ``root``.
    Names pulled from package ``__init__.py`` hubs resolve through
    :func:`_resolve_reexport` to their defining submodules."""
    import ast

    try:
        with open(os.path.join(root, relpath), "rb") as f:
            tree = ast.parse(f.read().decode("utf-8", errors="replace"))
    except (OSError, SyntaxError):
        return []
    pkg_parts = relpath.replace(os.sep, "/").split("/")[:-1]
    out: List[str] = []
    for node in ast.walk(tree):
        if isinstance(node, ast.Import):
            for alias in node.names:
                p = _resolve_module(root, alias.name)
                if p:
                    out.append(p)
        elif isinstance(node, ast.ImportFrom):
            mod = _absolute_module(pkg_parts, node)
            for alias in node.names:
                p = _resolve_module(root, f"{mod}.{alias.name}") \
                    if mod else None
                if p:
                    out.append(p)
                    continue
                p = _resolve_module(root, mod) if mod else None
                if not p:
                    continue
                out.append(p)
                if p.endswith("__init__.py") and alias.name != "*":
                    out.extend(_resolve_reexport(root, p, alias.name))
    return [p for p in out if p]


#: files that belong to every closure but whose own imports are NOT
#: followed: the audit registry's load_default_entrypoints() imports all
#: registration modules, so traversing through it would make every
#: entrypoint's closure total and defeat the --changed-only scoping
_CLOSURE_BARRIERS = ("paddle_tpu/core/audit.py",)


def _is_barrier(relpath: str) -> bool:
    """Files whose imports are not traversed: the audit registry and
    package ``__init__.py`` hubs. Hubs stay closure *members* (editing
    one re-traces its importers) but names pulled through them resolve
    per-name via :func:`_resolve_reexport` instead of dragging in every
    submodule the hub touches."""
    return (relpath in _CLOSURE_BARRIERS
            or relpath.endswith("__init__.py"))


def _import_closure(root: str, relpath: str,
                    cache: Dict[str, set]) -> set:
    """Transitive static import closure of one file (memoized BFS)."""
    if relpath in cache:
        return cache[relpath]
    closure = {relpath}
    cache[relpath] = closure  # placed before BFS: cycles terminate
    frontier = [relpath]
    while frontier:
        cur = frontier.pop()
        if _is_barrier(cur) and cur != relpath:
            continue
        for dep in _file_imports(root, cur):
            if dep not in closure:
                closure.add(dep)
                frontier.append(dep)
    return closure


def scope_entrypoints(root: str, changed_relpaths) -> List[str]:
    """Registered entrypoint names whose static import closure touches
    any changed file — the --changed-only trace scope. An entrypoint's
    closure starts at its registration file (``ep.path``); an empty
    result means no entrypoint is affected and the trace tier can skip
    compiling entirely."""
    os.environ.setdefault("JAX_PLATFORMS", "cpu")
    if root not in sys.path:
        sys.path.insert(0, root)
    from paddle_tpu.core import audit as _audit
    eps = _audit.load_default_entrypoints()
    changed = {p.replace(os.sep, "/") for p in changed_relpaths}
    cache: Dict[str, set] = {}
    out = []
    for name, ep in sorted(eps.items()):
        if ep.path and _import_closure(root, ep.path, cache) & changed:
            out.append(name)
    return out


def audit_entrypoint(name: str, ep) -> EntrypointStats:
    try:
        spec = ep.build()
    except Exception:
        st = EntrypointStats(name=name, tags=tuple(ep.tags), path=ep.path,
                             line=ep.line)
        st.error = traceback.format_exc(limit=3)
        return st
    return audit_spec(name, spec, tags=ep.tags, path=ep.path, line=ep.line)
