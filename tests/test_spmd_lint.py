"""Tier-1 tests for the SPMD collective-correctness tier (PTA011 source
lint + PTA012 collective-schedule audit) and the driver satellites that
shipped with it (--changed-only, exit-2 SARIF salvage, docs↔rules
consistency, the collective_bytes audit gate).

Layers:

- seeded-fixture acceptance: every PTA011 finding class fires on
  ``tests/fixtures/spmd_seeded.py`` and each is killable by noqa and by
  a baseline entry;
- pure collective-schedule passes against tiny shard_map programs
  (broken ring, healthy ring, scan trip counts, divergent cond,
  mismatched all_to_all pair, the no-collective negative space);
- PTA012 rule behaviour over synthetic reports (the test seam the
  PTA009/PTA010 tests use);
- the acceptance negatives: PTA011 over the real repo is clean, and the
  check_audit_regression gate fails on seeded collective_bytes
  inflation but tolerates drift within slack.
"""
import dataclasses
import json
import os
import re
import subprocess
import sys

import pytest

os.environ.setdefault("JAX_PLATFORMS", "cpu")

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if REPO not in sys.path:
    sys.path.insert(0, REPO)

import jax                                              # noqa: E402
import jax.numpy as jnp                                 # noqa: E402
import numpy as np                                      # noqa: E402
from jax import lax                                     # noqa: E402
from jax.sharding import Mesh, PartitionSpec as P       # noqa: E402

from paddle_tpu.core.audit import AuditSpec             # noqa: E402
from tools.analyze import trace as trace_mod            # noqa: E402
from tools.analyze.trace import (EntrypointStats,       # noqa: E402
                                 TraceReport, audit_spec, passes)
from tools.analyze.core import (Project, filter_noqa,   # noqa: E402
                                baseline_payload, run_rules,
                                split_findings)
from tools.analyze.rules import rules_by_code           # noqa: E402

PTA011 = rules_by_code()["PTA011"]
PTA012 = rules_by_code()["PTA012"]

FIXTURE = os.path.join("tests", "fixtures", "spmd_seeded.py")


def _driver(args):
    return subprocess.run([sys.executable, "-m", "tools.analyze"] + args,
                          cwd=REPO, capture_output=True, text=True)


def _mesh(n, axis):
    return Mesh(np.array(jax.devices()[:n]), (axis,))


# -- PTA011 seeded-fixture acceptance ----------------------------------------

def test_spmd_fixture_fires_every_pta011_class_and_nothing_else():
    proc = _driver(["--baseline", "none", "--rule", "PTA011", "--json",
                    FIXTURE])
    assert proc.returncode == 1, proc.stdout
    found = json.loads(proc.stdout)["findings"]
    assert all(f["rule"] == "PTA011" for f in found)
    assert all(f["severity"] == "error" for f in found)
    blob = " | ".join(f["message"] for f in found)
    # (a) rank-gated: one via the direct lax call, one via the
    # env-derived rank variable gating a collective wrapper
    assert blob.count("reachable only under rank-dependent") == 2
    assert "`jax.process_index()`" in blob
    assert "env `PADDLE_TRAINER_ID`" in blob
    # (b) swallowed collective
    assert "whose `except Exception`" in blob
    assert "re-raise so the whole cohort fails together" in blob
    # (c) axis hygiene: 'pd' is the seeded typo; the ring fixture's 'r'
    # axis is declared by make_ring_mesh and must NOT fire
    assert "names axis 'pd'" in blob
    assert "names axis 'r'" not in blob
    # (d) per-host loop trip count
    assert "loop whose trip count derives from a per-host value" in blob
    assert len(found) == 5, [f["message"] for f in found]
    # the clean_* functions stay clean: uniform psum with jnp.where
    # masking and a rank-gated print are both sanctioned idioms
    lines = {f["line"] for f in found}
    src = open(os.path.join(REPO, FIXTURE)).read().splitlines()
    for i, text in enumerate(src, 1):
        if "clean_" in text and "def " in text:
            assert not any(i <= ln <= i + 5 for ln in lines)


def test_pta011_killable_by_noqa(tmp_path):
    src = open(os.path.join(REPO, FIXTURE)).read()
    patched = []
    for line in src.splitlines():
        if ("lax.psum" in line or "all_reduce(x)" in line
                or "lax.all_gather" in line or "lax.ppermute" in line):
            line += "  # noqa: PTA011 -- seeded fixture, deliberately divergent"
        patched.append(line)
    p = tmp_path / "spmd_noqa.py"
    p.write_text("\n".join(patched) + "\n")
    proc = _driver(["--baseline", "none", "--rule", "PTA011", "--json",
                    str(p)])
    assert proc.returncode == 0, proc.stdout
    payload = json.loads(proc.stdout)
    assert payload["findings"] == []
    assert payload["counts"]["suppressed"] == 5


def test_pta011_killable_by_baseline(tmp_path):
    bl = tmp_path / "baseline.json"
    wrote = _driver(["--baseline", str(bl), "--write-baseline",
                     "--rule", "PTA011", FIXTURE])
    assert wrote.returncode == 0, wrote.stdout
    proc = _driver(["--baseline", str(bl), "--rule", "PTA011", "--json",
                    FIXTURE])
    assert proc.returncode == 0, proc.stdout
    payload = json.loads(proc.stdout)
    assert payload["counts"]["new"] == 0
    assert payload["counts"]["baselined"] == 5


def test_pta011_clean_on_fleet_code():
    # the real fleet code uses the uniform-schedule idioms (jnp.where
    # masking, lax.switch) — the rule must not invent findings there.
    # (test_analyze_perf covers the full repo with the default tier.)
    proc = _driver(["--baseline", "none", "--rule", "PTA011", "--json",
                    "paddle_tpu/distributed"])
    assert proc.returncode == 0, proc.stdout
    assert json.loads(proc.stdout)["findings"] == []


# -- collective-schedule pass (jaxpr level) -----------------------------------

def _schedule_of(fn, *args, n=4, axis="r", in_specs=P("r"),
                 out_specs=P("r")):
    wrapped = jax.shard_map(fn, mesh=_mesh(n, axis), in_specs=in_specs,
                            out_specs=out_specs, check_vma=False)
    return passes.collective_schedule(jax.make_jaxpr(wrapped)(*args))


def test_broken_ring_permutation_flagged():
    from tests.fixtures.spmd_seeded import broken_ring_body
    sched, issues = _schedule_of(broken_ring_body, jnp.zeros((8, 4)))
    assert [e["primitive"] for e in sched] == ["ppermute"]
    assert sched[0]["perm_kind"] == "partial"
    assert len(issues) == 1 and issues[0]["kind"] == "broken-permutation"
    assert issues[0]["axis_size"] == 4
    assert issues[0]["covered_ranks"] == [0, 1, 2]   # rank 3 orphaned


def test_healthy_ring_and_open_chain_pass():
    def ring(x):
        return lax.ppermute(x, "r", perm=[(i, (i + 1) % 4)
                                          for i in range(4)])

    def chain(x):  # the pipeline's open shift: covers every rank
        return lax.ppermute(x, "r", perm=[(i, i + 1) for i in range(3)])

    for fn, kind in ((ring, "ring"), (chain, "shift")):
        sched, issues = _schedule_of(fn, jnp.zeros((8, 4)))
        assert issues == []
        assert sched[0]["perm_kind"] == kind


def test_classify_perm_edge_cases():
    cp = passes._classify_perm
    assert cp([(0, 1), (1, 0)], 2) == "ring"
    assert cp([(0, 1), (0, 2)], 4) == "invalid"      # duplicate source
    assert cp([(0, 5)], 4) == "invalid"              # out of range
    assert cp([(0, 1), (1, 0), (2, 3), (3, 2)], 4) == "multi-cycle"
    assert cp([(0, 1)], None) == "unknown"
    assert cp([], 4) == "empty"


def test_scan_multiplies_trip_count_into_wire_bytes():
    def body(x):
        def step(c, _):
            return lax.psum(c, "r"), None
        out, _ = lax.scan(step, x, None, length=5)
        return out

    sched, issues = _schedule_of(body, jnp.zeros((8, 4), jnp.float32))
    assert issues == []
    (entry,) = sched
    assert entry["primitive"] == "psum"
    assert entry["trip_count"] == 5
    assert entry["bytes"] == 5 * 2 * 4 * 4   # trips × local [2,4] f32


def test_rank_divergent_cond_branches_flagged():
    def body(x):
        return lax.cond(jnp.sum(x) > 0,
                        lambda v: lax.psum(v, "r"),
                        lambda v: v * 2.0, x)

    sched, issues = _schedule_of(body, jnp.zeros((8, 4), jnp.float32))
    assert any(i["kind"] == "rank-divergent-cond" for i in issues)


def test_uniform_cond_branches_pass():
    def body(x):
        return lax.cond(jnp.sum(x) > 0,
                        lambda v: lax.psum(v, "r"),
                        lambda v: lax.psum(v * 2.0, "r"), x)

    _, issues = _schedule_of(body, jnp.zeros((8, 4), jnp.float32))
    assert issues == []


def test_mismatched_all_to_all_pair_flagged():
    def body(x):
        y = lax.all_to_all(x, "r", 0, 1, tiled=True)
        return lax.all_to_all(y, "r", 0, 1, tiled=True)  # must be 1,0

    _, issues = _schedule_of(body, jnp.zeros((64, 8), jnp.float32))
    assert any(i["kind"] == "alltoall-pairing" for i in issues)

    def ok(x):   # dispatch then the transposed return trip
        y = lax.all_to_all(x, "r", 0, 1, tiled=True)
        return lax.all_to_all(y, "r", 1, 0, tiled=True)

    _, issues = _schedule_of(ok, jnp.zeros((64, 8), jnp.float32))
    assert issues == []


def test_no_collective_entrypoint_negative_space():
    # single-device entrypoints must yield an empty schedule and zero
    # issues — no rank-invariance false positive on collective-free code
    def step(x):
        return jnp.tanh(x) * 2.0 + 1.0

    spec = AuditSpec(fn=step,
                     make_args=lambda v: (jnp.full((4, 4), float(v)),))
    st = audit_spec("no_collectives", spec)
    assert st.error == ""
    assert st.collectives == []
    assert st.collective_bytes == 0
    assert st.collective_issues == []


# -- PTA012 rule over reports -------------------------------------------------

def _report_with(**overrides):
    st = EntrypointStats(name="ep", tags=("train",),
                         path=FIXTURE, line=76)
    for k, v in overrides.items():
        setattr(st, k, v)
    return TraceReport(platform="cpu", entrypoint_stats={"ep": st})


def _pta012_findings(report, monkeypatch):
    monkeypatch.setattr(trace_mod, "_LAST", report)
    return PTA012.finalize(None)


def test_pta012_flags_broken_permutation_as_error(monkeypatch):
    fs = _pta012_findings(_report_with(collective_issues=[{
        "kind": "broken-permutation", "axis": "r", "axis_size": 4,
        "perm": [[0, 1], [1, 2], [2, 0]], "classification": "partial",
        "covered_ranks": [0, 1, 2]}]), monkeypatch)
    assert len(fs) == 1
    assert fs[0].severity == "error"
    assert "partial permutation" in fs[0].message
    assert fs[0].anchor == "trace:ep:broken-perm:r"
    assert (fs[0].path, fs[0].line) == (FIXTURE, 76)


def test_pta012_flags_divergent_cond_and_pairing(monkeypatch):
    fs = _pta012_findings(_report_with(collective_issues=[
        {"kind": "rank-divergent-cond",
         "branch_schedules": [["psum"], []]},
        {"kind": "alltoall-pairing", "axis": "ep",
         "first": [0, 1], "second": [0, 1]}]), monkeypatch)
    sev = {f.anchor: f.severity for f in fs}
    assert sev["trace:ep:rank-divergent-cond"] == "error"
    assert sev["trace:ep:alltoall-pairing:ep"] == "warning"


def test_pta012_quiet_on_clean_stats_and_broken_entrypoints(monkeypatch):
    assert _pta012_findings(_report_with(), monkeypatch) == []
    # a build failure is PTA009's finding; PTA012 must not double-report
    assert _pta012_findings(_report_with(error="boom"), monkeypatch) == []


def test_pta012_killable_by_baseline(monkeypatch):
    fs = _pta012_findings(_report_with(collective_issues=[{
        "kind": "broken-permutation", "axis": "r", "axis_size": 4,
        "perm": [[0, 1]], "classification": "partial",
        "covered_ranks": [0, 1]}]), monkeypatch)
    baseline = baseline_payload(fs)["findings"]
    new, baselined, expired = split_findings(fs, baseline)
    assert new == [] and len(baselined) == 1 and expired == []


def test_pta012_killable_by_noqa(tmp_path, monkeypatch):
    # trace findings anchor at the registration site: a noqa on that
    # line suppresses them like any AST finding
    reg = tmp_path / "reg.py"
    reg.write_text("register_entrypoint('ep', f)"
                   "  # noqa: PTA012 -- seeded broken ring, negative test\n")
    fs = _pta012_findings(_report_with(collective_issues=[{
        "kind": "broken-permutation", "axis": "r", "axis_size": 4,
        "perm": [[0, 1]], "classification": "partial",
        "covered_ranks": [0, 1]}]), monkeypatch)
    fs = [dataclasses.replace(f, path="reg.py", line=1) for f in fs]
    project = Project(str(tmp_path), ["reg.py"])
    kept, suppressed = filter_noqa(project, fs)
    assert kept == [] and len(suppressed) == 1


def test_pta012_end_to_end_on_seeded_broken_ring():
    from tests.fixtures.spmd_seeded import broken_ring_body
    fn = jax.shard_map(broken_ring_body, mesh=_mesh(4, "r"),
                       in_specs=P("r"), out_specs=P("r"), check_vma=False)
    spec = AuditSpec(fn=fn, make_args=lambda v: (
        jnp.full((8, 4), float(v), jnp.float32),))
    st = audit_spec("seeded_ring", spec)
    assert st.error == ""
    assert [i["kind"] for i in st.collective_issues] == \
        ["broken-permutation"]
    assert st.collective_bytes > 0


# -- collective_bytes audit gate ----------------------------------------------

def _gate():
    sys.path.insert(0, os.path.join(REPO, "tools"))
    import check_audit_regression as gate
    return gate


def test_collective_bytes_regression_fails_gate():
    gate = _gate()
    name = gate.ENTRYPOINTS[0]
    counters = {"host_transfers": 0, "large_consts": 0,
                "donatable_inputs": 0, "retraces": 0,
                "fingerprint_unstable": 0, "copy_fraction": 0.0,
                "collective_bytes": 1000, "collective_issues": 0}
    base = {name: dict(counters)}
    ok = {name: dict(counters, collective_bytes=1040)}     # within 5%
    bad = {name: dict(counters, collective_bytes=1100)}    # beyond
    assert not any("collective_bytes" in p
                   for p in gate.compare(base, ok))
    problems = gate.compare(base, bad)
    assert any("collective_bytes regressed 1000 -> 1100" in p
               for p in problems)
    # a new schedule-invariant violation is a regression even when the
    # byte count stays flat
    worse = {name: dict(counters, collective_issues=1)}
    assert any("collective_issues" in p
               for p in gate.compare(base, worse))


def test_gate_summarize_reads_collective_fields():
    gate = _gate()
    payload = {"entrypoints": {
        gate.ENTRYPOINTS[0]: {
            "transfers": [], "large_consts": [], "donation": None,
            "trace_count": 1, "fingerprint_stable": True,
            "hlo": {"instructions": 10, "copies": 0},
            "collectives": [{"primitive": "psum", "bytes": 256}],
            "collective_bytes": 256, "collective_issues": []}}}
    cur = gate.summarize(payload)[gate.ENTRYPOINTS[0]]
    assert cur["collective_bytes"] == 256
    assert cur["collective_issues"] == 0


def test_committed_baseline_has_collective_bytes_for_mesh_entrypoints():
    with open(os.path.join(REPO, "bench_audit_baseline.json")) as f:
        entries = json.load(f)["entrypoints"]
    gate = _gate()
    assert set(gate.ENTRYPOINTS) == set(entries)
    for name in ("pipeline_train_step", "moe_train_step",
                 "compressed_allreduce_train_step",
                 "gpt_ring_flash_train_step"):
        assert entries[name]["collective_bytes"] > 0, name


# -- satellites ---------------------------------------------------------------

def test_docs_rules_table_matches_list_rules():
    proc = _driver(["--list-rules"])
    assert proc.returncode == 0
    listed = set(re.findall(r"^(PTA\d{3})", proc.stdout, re.M))
    docs = open(os.path.join(REPO, "docs", "static_analysis.md")).read()
    documented = set(re.findall(r"^\| (PTA\d{3}) \|", docs, re.M))
    # PTA000 (syntax error) is synthesized by the core, not a registered
    # rule — it is documented but never listed
    assert documented - {"PTA000"} == listed
    assert "PTA000" in documented


def test_changed_only_scopes_to_diffed_files(tmp_path):
    def git(*argv):
        subprocess.run(["git", "-c", "user.email=t@t", "-c",
                        "user.name=t"] + list(argv), cwd=tmp_path,
                       check=True, capture_output=True)

    (tmp_path / "a.py").write_text("x = 1\n")
    (tmp_path / "skipme.py").write_text("def broken(:\n")
    git("init", "-q")
    git("add", "a.py", "skipme.py")
    git("commit", "-qm", "seed")

    # no changes: clean exit, nothing analyzed
    proc = _driver(["--root", str(tmp_path), "--changed-only",
                    "--baseline", "none", "."])
    assert proc.returncode == 0, proc.stdout
    assert "no changed .py files" in proc.stdout

    # one modified + one untracked file: both analyzed, the committed
    # (unchanged) broken file is NOT — proof of scoping
    (tmp_path / "a.py").write_text("def broken(:\n")
    (tmp_path / "b.py").write_text("def broken(:\n")
    proc = _driver(["--root", str(tmp_path), "--changed-only",
                    "--baseline", "none", "--json", "."])
    assert proc.returncode == 1, proc.stdout
    found = json.loads(proc.stdout)["findings"]
    assert sorted(f["path"] for f in found) == ["a.py", "b.py"]
    assert all(f["rule"] == "PTA000" for f in found)


def test_exit_2_overwrites_stale_sarif_with_valid_notification(
        tmp_path, monkeypatch):
    import tools.analyze.__main__ as main_mod
    out = tmp_path / "analysis.sarif"
    out.write_text("STALE NOT JSON")

    def boom(*a, **k):
        raise RuntimeError("seeded internal failure")

    monkeypatch.setattr(main_mod, "run_rules", boom)
    rc = main_mod.main(["--format", "sarif", "--output", str(out),
                        "--baseline", "none", FIXTURE])
    assert rc == 2
    doc = json.loads(out.read_text())   # valid JSON, not the stale blob
    run = doc["runs"][0]
    inv = run["invocations"][0]
    assert inv["executionSuccessful"] is False
    notes = inv["toolExecutionNotifications"]
    assert "seeded internal failure" in notes[0]["message"]["text"]
    assert run["results"] == []
    assert run["tool"]["driver"]["name"] == "paddle-tpu-analyze"


def test_successful_sarif_marks_execution_successful(tmp_path):
    out = tmp_path / "ok.sarif"
    proc = _driver(["--baseline", "none", "--rule", "PTA011",
                    "--format", "sarif", "--output", str(out), FIXTURE])
    assert proc.returncode == 1   # seeded findings gate
    doc = json.loads(out.read_text())
    assert doc["runs"][0]["invocations"][0]["executionSuccessful"] is True
    assert len(doc["runs"][0]["results"]) == 5
