"""PTA013: Pallas kernel-safety lint.

The Pallas surface (ops/pallas_attention.py fwd+bwd, ops/paged_attention
.py, the ring lanes in distributed/fleet/sequence_parallel.py) carries
safety invariants that nothing enforced until now — they lived in code
review convention. This rule walks every ``pl.pallas_call`` /
``pl.BlockSpec`` site and flags:

- **unguarded grid division** (error): a grid dimension computed as
  ``length // block`` where ``block`` is a dynamic name with neither a
  divisibility guard (``if length % block: raise``) nor provenance from
  a ``*sanitize*`` helper (the ``_sanitize_block`` /
  ``_sanitize_ring_blocks`` / ``_sanitize_block_h`` idiom). A
  non-dividing block makes the grid floor-divide and silently drop the
  tail rows/keys.
- **VMEM-busting block shapes** (error): constant BlockSpec shapes whose
  combined footprint (``paddle_tpu/tuner/space.py:blockspec_vmem_bytes``)
  exceeds ``VMEM_BUDGET``; plus — in :meth:`finalize` — every committed
  ``default_winners.json`` entry checked against the family VMEM model
  (``flash_vmem_bytes`` / ``paged_attn_vmem_bytes``), so a stale
  hand-edited winner fails lint instead of OOMing Mosaic on a TPU.
- **low-precision accumulator** (error): reduction accumulators or VMEM
  scratch (``pl.when``-initialized ``acc``/``m``/``l`` style) declared
  below f32 — ``jnp.zeros(..., jnp.bfloat16)`` in a kernel body or
  ``pltpu.VMEM(shape, jnp.float16)`` scratch. Online-softmax statistics
  accumulated in bf16 lose the exactness contract; integer masks are
  fine.
- **no interpret lane** (warning): a ``pl.pallas_call`` without an
  ``interpret=`` keyword — the kernel is unreachable off-TPU, so CPU
  tier-1 can never cover its math (ops/custom.py register_pallas_op
  convention requires the lane).

The VMEM cost models are imported from ``paddle_tpu/tuner/space.py`` via
``importlib`` file loading (the module is pure stdlib; importing the
*package* would pull jax, and the AST tier must stay stdlib-only).
"""
from __future__ import annotations

import ast
import importlib.util
import os
from typing import Dict, List, Optional, Tuple

from .base import Rule
from ..core import Finding, Project, SourceFile, dotted_name, walk_own_body

WINNERS_PATH = "paddle_tpu/tuner/default_winners.json"
SPACE_PATH = "paddle_tpu/tuner/space.py"

#: float dtypes below f32 — illegal for kernel accumulators/scratch.
#: Integer dtypes (NMS index masks) and f32/f64 never match.
_LOW_PRECISION = {"bfloat16", "float16", "half"}

_ITEMSIZE = {"bfloat16": 2, "float16": 2, "float32": 4, "float64": 8}

#: allocation calls whose result is a fresh array an accumulator is
#: typically initialized from
_ACC_ALLOCATORS = {"zeros", "ones", "full", "empty",
                   "zeros_like", "ones_like", "full_like", "empty_like"}

_SPACE_CACHE: Dict[str, object] = {}


def _load_space(root: str):
    """Load paddle_tpu/tuner/space.py as a standalone module (NOT through
    the package, whose __init__ imports jax — the AST tier must run
    without jax installed)."""
    path = os.path.join(root, SPACE_PATH)
    mod = _SPACE_CACHE.get(path)
    if mod is None:
        spec = importlib.util.spec_from_file_location("_pta013_space", path)
        mod = importlib.util.module_from_spec(spec)
        spec.loader.exec_module(mod)
        _SPACE_CACHE[path] = mod
    return mod


def _low_precision_dtype(node: Optional[ast.AST]) -> Optional[str]:
    """'bfloat16'/'float16' when the expression names a sub-f32 float
    dtype (``jnp.bfloat16``, ``"float16"``), else None."""
    if node is None:
        return None
    if isinstance(node, ast.Constant) and isinstance(node.value, str):
        name = node.value
    else:
        name = dotted_name(node).rsplit(".", 1)[-1]
    if name in _LOW_PRECISION or name.startswith("float8"):
        return name
    return None


def _const_shape(node: ast.AST) -> Optional[Tuple[int, ...]]:
    """The tuple of ints when ``node`` is an all-constant shape tuple."""
    if not isinstance(node, (ast.Tuple, ast.List)):
        return None
    dims = []
    for e in node.elts:
        if isinstance(e, ast.Constant) and isinstance(e.value, int):
            dims.append(e.value)
        else:
            return None
    return tuple(dims)


def _call_name(node: ast.Call) -> str:
    """Last attribute segment of the callee: pl.pallas_call -> pallas_call."""
    return dotted_name(node.func).rsplit(".", 1)[-1]


def parse_winner_key(key: str) -> Optional[Dict[str, object]]:
    """Decode a default_winners.json key into its model parameters.

    ``flash_fwd|tpu|bfloat16|d64|q4096|k4096|c1`` ->
    ``{"family": "flash_fwd", "dtype": "bfloat16", "d": 64, ...}``.
    Returns None for families without a VMEM model (nms, compress).
    """
    parts = key.split("|")
    family = parts[0]
    if not (family.startswith("flash") or family.startswith("ring_flash")
            or family == "paged_attn"):
        return None
    out: Dict[str, object] = {"family": family, "dtype": parts[2]}
    for p in parts[3:]:
        if len(p) > 1 and p[0] in "dqkhpc" and p[1:].isdigit():
            out[p[0]] = int(p[1:])
    return out


def iter_winner_footprints(root: str):
    """Yield ``(key, family, vmem_bytes, budget)`` for every committed
    winner that has a VMEM model. Shared by the rule's finalize and the
    tier-1 fail-fast test (tests/test_pallas_lint.py)."""
    import json
    space = _load_space(root)
    with open(os.path.join(root, WINNERS_PATH)) as f:
        entries = json.load(f).get("entries", {})
    for key, entry in sorted(entries.items()):
        params = parse_winner_key(key)
        if params is None:
            continue
        cfg = entry.get("config", {})
        itemsize = _ITEMSIZE.get(str(params["dtype"]), 4)
        family = str(params["family"])
        if family == "paged_attn":
            bytes_ = space.paged_attn_vmem_bytes(
                int(cfg.get("block_h", 1)), int(params.get("p", 16)),
                int(params.get("d", 64)), itemsize)
        else:
            bytes_ = space.flash_vmem_bytes(
                int(cfg.get("block_q", 16)), int(cfg.get("block_k", 16)),
                int(params.get("k", params.get("q", 16))),
                int(params.get("d", 64)), itemsize,
                bwd=family.endswith("bwd"), q_len=params.get("q"))
        yield key, family, bytes_, space.VMEM_BUDGET


class PallasSafetyRule(Rule):
    code = "PTA013"
    name = "pallas-kernel-safety"
    description = ("Pallas kernel-safety lint: unguarded grid divisions "
                   "(no divisibility check or sanitize-helper "
                   "provenance), VMEM-budget-busting BlockSpec shapes "
                   "and committed tuner winners, sub-f32 kernel "
                   "accumulators/scratch, pallas_call without an "
                   "interpret= lane")
    severity = "error"

    def visit_file(self, sf: SourceFile, project: Project) -> List[Finding]:
        if "pallas" not in sf.text:
            return []
        findings: List[Finding] = []
        space = None
        for node in ast.walk(sf.tree):
            if isinstance(node, (ast.FunctionDef, ast.AsyncFunctionDef)):
                findings.extend(self._check_function(sf, node))
            elif isinstance(node, ast.Call):
                name = _call_name(node)
                if name == "pallas_call":
                    findings.extend(self._check_interpret_lane(sf, node))
                    if space is None:
                        space = _load_space(project.root)
                    findings.extend(
                        self._check_blockspec_vmem(sf, node, space))
                elif name == "VMEM":
                    findings.extend(self._check_vmem_scratch(sf, node))
        return findings

    def finalize(self, project: Project) -> List[Finding]:
        """Committed tuner winners must fit the family VMEM model — a
        stale hand-edited entry should fail lint in CI, not OOM Mosaic
        on the first TPU run."""
        if not os.path.isfile(os.path.join(project.root, WINNERS_PATH)):
            return []
        winners_sf = project.read_rootfile(WINNERS_PATH)
        findings: List[Finding] = []
        for key, family, bytes_, budget in iter_winner_footprints(
                project.root):
            if bytes_ <= budget:
                continue
            line = next((i for i, ln in enumerate(
                winners_sf.lines, 1) if key in ln), 1)
            findings.append(Finding(
                self.code, WINNERS_PATH, line, 0,
                f"committed winner `{key}` needs {bytes_} VMEM bytes "
                f"({bytes_ / (1 << 20):.1f} MiB) by the `{family}` cost "
                f"model — over the {budget} byte budget; this entry "
                f"would OOM Mosaic on real hardware, re-tune it",
                anchor=f"pallas:winner:{key}", severity="error"))
        return findings

    # -- (a) unguarded grid division -----------------------------------------

    def _check_function(self, sf: SourceFile,
                        fn: ast.AST) -> List[Finding]:
        findings: List[Finding] = []
        calls = [n for n in walk_own_body(fn) if isinstance(n, ast.Call)]
        grid_tuples = []
        for call in calls:
            if _call_name(call) not in ("pallas_call",
                                        "PrefetchScalarGridSpec"):
                continue
            for kw in call.keywords:
                if kw.arg == "grid" and isinstance(kw.value,
                                                   (ast.Tuple, ast.List)):
                    grid_tuples.append(kw.value)
        if grid_tuples:
            guarded = self._guarded_divisors(fn)
            sanitized = self._sanitized_names(fn)
            for tup in grid_tuples:
                for elt in tup.elts:
                    findings.extend(self._check_grid_elt(
                        sf, elt, guarded, sanitized))
        findings.extend(self._check_kernel_accumulators(sf, fn))
        return findings

    def _guarded_divisors(self, fn: ast.AST) -> set:
        """Names that appear as the right operand of a `%` inside an
        `if` test whose body raises — the explicit divisibility guard
        (`if s_pad % bq or kv_pad % bk: raise ValueError(...)`)."""
        guarded = set()
        for node in walk_own_body(fn):
            if not isinstance(node, ast.If):
                continue
            if not any(isinstance(b, ast.Raise) for b in node.body):
                continue
            for sub in ast.walk(node.test):
                if (isinstance(sub, ast.BinOp)
                        and isinstance(sub.op, ast.Mod)
                        and isinstance(sub.right, ast.Name)):
                    guarded.add(sub.right.id)
        return guarded

    def _sanitized_names(self, fn: ast.AST) -> set:
        """Names bound (anywhere in the function) from a call to a
        ``*sanitize*`` helper — the sanctioned provenance
        (`block_h = _sanitize_block_h(block_h, num_heads)`)."""
        names = set()
        for node in walk_own_body(fn):
            if not isinstance(node, ast.Assign):
                continue
            val = node.value
            is_sanitize = (isinstance(val, ast.Call)
                           and "sanitize" in dotted_name(val.func).lower())
            if not is_sanitize and isinstance(val, (ast.Tuple, ast.List)):
                continue
            if not is_sanitize:
                continue
            for tgt in node.targets:
                if isinstance(tgt, ast.Name):
                    names.add(tgt.id)
                elif isinstance(tgt, (ast.Tuple, ast.List)):
                    names.update(e.id for e in tgt.elts
                                 if isinstance(e, ast.Name))
        return names

    def _check_grid_elt(self, sf: SourceFile, elt: ast.AST,
                        guarded: set, sanitized: set) -> List[Finding]:
        findings: List[Finding] = []
        for sub in ast.walk(elt):
            if not (isinstance(sub, ast.BinOp)
                    and isinstance(sub.op, ast.FloorDiv)):
                continue
            div = sub.right
            if not isinstance(div, ast.Name):
                continue  # constant or attribute divisors: shape-static
            if div.id in guarded or div.id in sanitized:
                continue
            findings.append(sf.finding(
                self.code, sub,
                f"grid dimension floor-divides by dynamic block "
                f"`{div.id}` with no divisibility guard — a "
                f"non-dividing block silently drops the tail "
                f"rows/keys; add `if length % {div.id}: raise` or "
                f"bind it through a `_sanitize_*` helper "
                f"(ops/pallas_attention.py idiom)"))
        return findings

    # -- (b) VMEM footprint ---------------------------------------------------

    def _check_blockspec_vmem(self, sf: SourceFile, call: ast.Call,
                              space) -> List[Finding]:
        """Sum the constant-shape BlockSpec blocks of one pallas_call; a
        footprint over budget is a finding even though dynamic shapes are
        skipped — the constant blocks alone are a lower bound."""
        shapes = []
        for sub in ast.walk(call):
            if not (isinstance(sub, ast.Call)
                    and _call_name(sub) == "BlockSpec" and sub.args):
                continue
            shape = _const_shape(sub.args[0])
            if shape:
                shapes.append(shape)
        if not shapes:
            return []
        bytes_ = space.blockspec_vmem_bytes(shapes)
        if bytes_ <= space.VMEM_BUDGET:
            return []
        return [sf.finding(
            self.code, call,
            f"pallas_call BlockSpecs pin {bytes_} bytes "
            f"({bytes_ / (1 << 20):.1f} MiB) of VMEM at f32 — over the "
            f"{space.VMEM_BUDGET} byte budget "
            f"(paddle_tpu/tuner/space.py); shrink the blocks or tile "
            f"the long axis through the grid",
            anchor=f"pallas:vmem:{sf.line_text(call.lineno)}")]

    # -- (c) low-precision accumulators/scratch -------------------------------

    def _check_kernel_accumulators(self, sf: SourceFile,
                                   fn: ast.AST) -> List[Finding]:
        args = getattr(fn, "args", None)
        if args is None or not any(a.arg.endswith("_ref")
                                   for a in args.posonlyargs + args.args):
            return []
        findings: List[Finding] = []
        for node in walk_own_body(fn):
            if not isinstance(node, ast.Call):
                continue
            name = _call_name(node)
            if name not in _ACC_ALLOCATORS:
                continue
            dtype_node = next((kw.value for kw in node.keywords
                               if kw.arg == "dtype"), None)
            if dtype_node is None and node.args:
                # positional dtype: zeros(shape, dtype) / full(shape,
                # fill, dtype); *_like(x, dtype) also lands at args[1]
                idx = 2 if name == "full" else 1
                if len(node.args) > idx:
                    dtype_node = node.args[idx]
            low = _low_precision_dtype(dtype_node)
            if low:
                findings.append(sf.finding(
                    self.code, node,
                    f"kernel accumulator allocated as {low} via "
                    f"`{name}` — online-softmax/reduction statistics "
                    f"must accumulate in f32 (declare f32 and cast on "
                    f"the final store, ops/pallas_attention.py idiom)"))
        return findings

    def _check_vmem_scratch(self, sf: SourceFile,
                            call: ast.Call) -> List[Finding]:
        dtype_node = None
        if len(call.args) > 1:
            dtype_node = call.args[1]
        else:
            dtype_node = next((kw.value for kw in call.keywords
                               if kw.arg == "dtype"), None)
        low = _low_precision_dtype(dtype_node)
        if not low:
            return []
        return [sf.finding(
            self.code, call,
            f"VMEM scratch declared {low} — scratch accumulators carry "
            f"running statistics across grid steps and must stay f32 "
            f"(the output cast happens once, on the final store)")]

    # -- (d) interpret lane ---------------------------------------------------

    def _check_interpret_lane(self, sf: SourceFile,
                              call: ast.Call) -> List[Finding]:
        if any(kw.arg == "interpret" for kw in call.keywords):
            return []
        return [sf.finding(
            self.code, call,
            "pallas_call without an `interpret=` keyword — the kernel "
            "is unreachable off-TPU, so CPU tier-1 can never cover its "
            "math; thread an interpret flag through "
            "(ops/custom.py convention)",
            severity="warning")]


RULE = PallasSafetyRule()
