"""Device time by the program's own scopes.

A device event in a profiler trace is named by the compiled instruction it
ran (on the chip the instruction's whole text, ``%fusion.12 = f32[8,2048]...``;
on the CPU its bare name). The same instruction, with the ``op_name`` that
holds every ``jax.named_scope`` and autodiff's ``jvp(...)`` /
``transpose(...)`` wrappers, stands in the text of the program's own
compiled executable. So the program can say which of its parts an event
belongs to, with nothing but the events' names:

* :func:`note` is called from INSIDE a traced body (``jit_program``'s
  wrapper, the hapi train step), so it runs once a trace and never on a
  call. It keeps the jitted callable and its arguments as
  ``jax.ShapeDtypeStruct``s, no array.
* :func:`table` lowers and compiles every noted program again (JAX answers
  with the executable it holds) and parses ``compiled.as_text()``; where
  that executable came out of a persistent cache another tree filled, with
  other scopes in its text, the program is compiled anew (``_rows_of``).
  Nothing is lowered before somebody asks; lowering runs a program's Python
  body once more where JAX no longer holds its trace (after
  ``jax.clear_caches()``), and a program is lowered with no shardings (one
  device).
* :func:`by_scope` takes one device line ``[[name, start_ns, dur_ns], ...]``
  and returns exclusive seconds by ``(program, scope, phase)``.

Scopes are named ``family/part`` (``gpt/mlp``, ``train/optimizer``); an
event's scope is the path of those its ``op_name`` holds, innermost last.
"""
from __future__ import annotations

import contextlib
import re
import threading
import time
import warnings

import jax

UNSCOPED = "_unscoped_"
NO_PROGRAM = "-"

_LOCK = threading.Lock()
_NOTED = {}     # (program, signature) -> (jitted, structs, context), None
                # once read
_ROWS = {}      # (program, signature) -> {instruction: (shape, op_name)}
_INDEX = {}     # instruction -> {(shape, program, scope, phase)}
_SPENT = [0.0]  # seconds table() has worked in this process


def note(program: str, jitted, args, context=None) -> None:
    """Remember that ``jitted`` was traced for ``args`` (tracers, or
    arrays) under the name ``program`` (the compiled module's,
    ``jit__step``). Idempotent by program and signature. ``context()``
    gives a context manager to lower under, where the trace depends on
    state of the caller's that JAX does not see (the train step's
    ``amp.auto_cast``)."""
    leaves, treedef = jax.tree_util.tree_flatten(args)
    key = (program, treedef,
           tuple((tuple(a.shape), str(a.dtype)) for a in leaves))
    if key in _NOTED:
        return
    structs = treedef.unflatten([
        jax.ShapeDtypeStruct(a.shape, a.dtype, weak_type=getattr(
            getattr(a, "aval", None), "weak_type", False)) for a in leaves])
    with _LOCK:
        _NOTED.setdefault(key, (jitted, structs, context))


def noted() -> list:
    """The names of the programs noted so far, one a signature."""
    return [key[0] for key in list(_NOTED)]


def table_seconds() -> float:
    """What :func:`table` has cost this process so far."""
    return _SPENT[0]


_INSTR = re.compile(r"^\s*(ROOT )?%?([\w.\-]+) = \(?([a-z0-9]+\[[0-9,]*\])?")
_OP_NAME = re.compile(r'op_name="([^"]*)"')
_CALLS = re.compile(r"calls=%?([\w.\-]+)")
_OPERAND = re.compile(r"%([\w.\-]+)")
_COMPUTATION = re.compile(r"^(?:ENTRY )?%?([\w.\-]+) \(.*\{\s*$")


def parse_hlo(text: str) -> dict:
    """``{instruction: (result shape, op_name)}`` of a compiled module's
    text. An instruction that carries no ``op_name`` of its own takes its
    computation's root's (a fusion), or else its first user's (the
    compiler's own prefetches, ``copy-start`` / ``slice-done``: their time
    is the wait for what the user reads)."""
    rows, calls, roots, users, computation = {}, {}, {}, {}, None
    for line in text.splitlines():
        m = _COMPUTATION.match(line)
        if m:
            computation = m.group(1)
            continue
        m = _INSTR.match(line)
        if not m:
            continue
        root, name, shape = m.groups()
        op = _OP_NAME.search(line)
        op = op.group(1) if op else ""
        rows[name] = (shape, op)
        if root:
            roots[computation] = op
        for operand in _OPERAND.findall(line, m.end()):
            users.setdefault(operand, name)
        if not op:
            called = _CALLS.search(line)
            if called:
                calls[name] = called.group(1)
    for name, (shape, op) in rows.items():
        if op:
            continue
        op, at = roots.get(calls.get(name), ""), name
        for _ in range(4):              # start -> done -> the fusion
            if op or at not in users:
                break
            at = users[at]
            op = rows[at][1] if at in rows else ""
        rows[name] = (shape, op)
    return rows


_WRAPPER = re.compile(r"^(\w+)\((.*)\)$")
#: path components JAX writes itself: no scope of the program's
_JAX_OWN = re.compile(
    r"^(while|body|cond|closed_call|core_call|checkpoint|remat\d*|"
    r"rematted_computation|custom_jvp_call|custom_vjp_call\w*|"
    r"custom_lin|branch_\d+_fun|shard_map|pjit)$")


def _split(path: str) -> list:
    """``path`` cut at the slashes that stand outside brackets."""
    parts, depth, last = [], 0, 0
    for i, ch in enumerate(path):
        if ch == "(":
            depth += 1
        elif ch == ")":
            depth -= 1
        elif ch == "/" and depth == 0:
            parts.append(path[last:i])
            last = i + 1
    parts.append(path[last:])
    return [p for p in parts if p]


def _walk(path: str, names: list, marks: set) -> bool:
    """Collect the scope components of ``path`` into ``names`` and JAX's
    own markers into ``marks``; says whether the last component was a
    name (at the top level that one is the primitive)."""
    named = False
    for part in _split(path):
        m = _WRAPPER.match(part)
        named = False
        if m:
            marks.add(m.group(1))
            if m.group(1) not in ("jit", "pjit"):    # theirs is a function's
                _walk(m.group(2), names, marks)      # name, not a scope
        elif _JAX_OWN.match(part):
            marks.add(part)
        else:
            names.append(part)
            named = True
            # through a nested ``jit`` JAX writes the stack it stands in
            # once more (``a/b/jit(f)/a/b/jit(f)/...``): kept once
            for k in range(1, len(names) // 2 + 1):
                if names[-k:] == names[-2 * k:-k]:
                    del names[-k:]
                    break
    return named


_PHASE_SCOPES = ("train/forward", "train/optimizer")


def scope_of(op_name: str) -> tuple:
    """``(scope, phase)`` of an instruction's ``op_name``: the scopes it
    stands in, outermost first and innermost last (``gpt/attn/paged_attn``),
    without the two that only say the phase."""
    names, marks = [], set()
    # a fused instruction may carry several, "a;b": the first is its root's
    if _walk(op_name.split(";")[0], names, marks):
        names.pop()                                  # the primitive
    path = "/" + "/".join(names) + "/"
    if "/train/optimizer/" in path:
        phase = "optimizer"
    elif "transpose" in marks:
        phase = "recompute" if "rematted_computation" in marks else "backward"
    elif "jvp" in marks or "/train/forward/" in path:
        phase = "forward"
    else:
        phase = "-"
    inner = path
    for own in _PHASE_SCOPES:
        inner = inner.replace("/" + own + "/", "/")
    scope = inner.strip("/") or next(
        (own for own in _PHASE_SCOPES if "/" + own + "/" in path), UNSCOPED)
    return scope, phase


def _index(program: str, rows: dict) -> None:
    for name, (shape, op_name) in rows.items():
        _INDEX.setdefault(name, set()).add(
            (shape, program) + scope_of(op_name))


def _scope_words(paths) -> set:
    """The names that ``op_name``s or name stacks hold, a scope's two halves
    apart (``gpt``, ``mlp``; an ``op_name``'s primitive too)."""
    words = set()
    for path in set(paths):
        names = []
        _walk(path, names, set())
        words.update(names)
    return words


def _name_stacks(jaxpr, out: set) -> set:
    """The name stack of every equation of ``jaxpr`` and of what it calls."""
    for eqn in jaxpr.eqns:
        out.add(str(eqn.source_info.name_stack))
        for value in eqn.params.values():
            for sub in value if isinstance(value, (tuple, list)) else (value,):
                sub = getattr(sub, "jaxpr", sub)
                if hasattr(sub, "eqns"):
                    _name_stacks(sub, out)
    return out


_KEY_HOLDS_METADATA = "jax_compilation_cache_include_metadata_in_key"


def _rows_of(jitted, structs) -> dict:
    """``parse_hlo`` of the executable ``jitted`` runs for ``structs``. JAX
    keys its persistent cache by a program's code WITHOUT the metadata, so
    the cache may have answered with what another tree compiled from the
    same code under other scopes, or none. The compiler names the
    instructions of one code alike whatever the metadata, so where a scope
    of the trace is missing from the text the program is compiled once
    more with the metadata in the key: that text is this tree's."""
    traced = jitted.trace(*structs)
    rows = parse_hlo(traced.lower().compile().as_text())
    wanted = _scope_words(_name_stacks(traced.jaxpr.jaxpr, set()))
    if not wanted <= _scope_words(op for _, op in rows.values()):
        was = getattr(jax.config, _KEY_HOLDS_METADATA)
        jax.config.update(_KEY_HOLDS_METADATA, True)
        try:
            # an option, though it says what the configuration says, keeps
            # JAX from handing back the executable it holds in memory
            rows = parse_hlo(traced.lower().compile(compiler_options={
                "exec_time_optimization_effort":
                    jax.config.jax_exec_time_optimization_effort}).as_text())
        finally:
            jax.config.update(_KEY_HOLDS_METADATA, was)
    return rows


def _read_noted() -> None:
    """Lower, compile and parse what has been noted and not yet read."""
    with _LOCK:
        pending = [(k, v) for k, v in _NOTED.items() if v is not None]
    began = time.perf_counter()
    for key, (jitted, structs, context) in pending:
        try:
            with context() if context else contextlib.nullcontext():
                rows = _rows_of(jitted, structs)
        except Exception as e:  # noqa: BLE001 -- its events stay unscoped
            warnings.warn(f"opscope: {key[0]} cannot be lowered again: "
                          f"{type(e).__name__}: {e}")
            rows = {}
        with _LOCK:
            _ROWS[key] = rows
            _NOTED[key] = None      # drops the callable, and what it holds
            _index(key[0], rows)
    _SPENT[0] += time.perf_counter() - began


def table() -> dict:
    """``{(program, instruction, result shape): op_name}`` of every program
    noted so far. The work is done once a program, when first asked."""
    _read_noted()
    return {(key[0], name, shape): op_name for key, rows in _ROWS.items()
            for name, (shape, op_name) in rows.items()}


def exclusive(events) -> list:
    """``[(name, start_ns, own_ns), ...]``: each event's duration less the
    events nested inside it on the line (a ``while`` holds its body)."""
    out, stack = [], []     # stack of [end, index into out]
    for name, start, dur in sorted(events, key=lambda e: (e[1], -e[2])):
        while stack and stack[-1][0] <= start:
            stack.pop()
        if stack and start + dur <= stack[-1][0]:
            out[stack[-1][1]][2] -= dur
        out.append([name, start, dur])
        stack.append([start + dur, len(out) - 1])
    return out


_EVENT = re.compile(r"^%?([\w.\-]+)(?: = \(?([a-z0-9]+\[[0-9,]*\]))?")


def _found(name: str):
    """``(program, scope, phase)`` of an event's name (an instruction's
    text, or its bare name where the trace gives no more), or None where no
    noted program holds the instruction, or two do."""
    m = _EVENT.match(name)
    if not m:
        return None
    instruction, shape = m.groups()
    hits = {h[1:] for h in _INDEX.get(instruction, ())
            if shape is None or h[0] == shape}
    return hits.pop() if len(hits) == 1 else None


def by_scope(events) -> dict:
    """Exclusive seconds of one device line by ``(program, scope, phase)``.
    Reads what has been noted first, so the first call pays for
    :func:`table`."""
    _read_noted()
    total, seen = {}, {}
    for name, _start, own in exclusive(events):
        if name not in seen:
            seen[name] = _found(name) or (NO_PROGRAM, UNSCOPED, "-")
        total[seen[name]] = total.get(seen[name], 0) + own
    return {k: v / 1e9 for k, v in total.items()}


def format_table(seconds: dict, unit: str = "ms") -> str:
    """What :func:`by_scope` gave as text, largest first."""
    scale = {"s": 1.0, "ms": 1e3, "us": 1e6}[unit]
    whole = sum(seconds.values()) or 1.0
    wide = max([24] + [len(scope) for _, scope, _ in seconds])
    lines = [f"{'program':<18} {'scope':<{wide}} {'phase':<10} "
             f"{unit:>10} {'share':>7}"]
    for (program, scope, phase), s in sorted(seconds.items(),
                                             key=lambda kv: -kv[1]):
        lines.append(f"{program:<18} {scope:<{wide}} {phase:<10} "
                     f"{s * scale:>10.3f} {100 * s / whole:>6.1f}%")
    return "\n".join(lines)
