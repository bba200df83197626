#!/usr/bin/env python
"""Sharded test runner (reference: tools/parallel_UT_rule.py +
unittests/CMakeLists.txt RUN_TYPE scheduling).

Splits the test files across worker processes, each running its shard in a
separate pytest (XLA compile caches are per-process, so file-level sharding
is the efficient cut). Default runs the fast lane (`-m "not slow"`); pass
--slow for the slow lane only or --all for both.

    python tools/run_tests.py            # fast lane, N=cpu/4 shards
    python tools/run_tests.py --all -j4  # everything, 4 shards
"""
from __future__ import annotations

import argparse
import glob
import os
import subprocess
import sys
import time

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

# Heaviest files first so the long pole starts immediately (greedy LPT).
_WEIGHT_HINTS = {
    "test_vision.py": 250, "test_graft_entry.py": 70, "test_moe.py": 70,
    "test_sequence_parallel.py": 70, "test_pipeline.py": 90,
    "test_launch_spawn.py": 60, "test_nn_layers.py": 70,
    "test_detection_round3.py": 50, "test_sampled_segment_ops.py": 50,
    "test_serving.py": 40, "test_serving_http.py": 20,
    "test_router_sharded.py": 60,
}


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("-j", "--jobs", type=int,
                    default=max(2, (os.cpu_count() or 8) // 4))
    ap.add_argument("--slow", action="store_true",
                    help="run only the slow lane")
    ap.add_argument("--all", action="store_true", help="run both lanes")
    ap.add_argument("--files", nargs="*", help="restrict to these files")
    ap.add_argument("--no-analyze", action="store_true",
                    help="skip the static-analysis gate")
    ap.add_argument("--trace-audit", action="store_true",
                    help="also run the trace tier (PTA009/PTA010/PTA012/"
                         "PTA014): compiles every registered entrypoint "
                         "under JAX_PLATFORMS=cpu and writes the trace "
                         "report (plus the PTA014 fusion_audit.json)")
    ap.add_argument("--trace-audit-output", default="trace_audit.json",
                    help="where --trace-audit writes its report (default "
                         "%(default)s, which .gitignore covers; keep "
                         "custom paths out of the tree too)")
    ap.add_argument("--bench-check", action="store_true",
                    help="opt-in gate: run the PTA009 bench-audit gate "
                         "(tools/check_audit_regression.py) against "
                         "bench_audit_baseline.json — new host transfers "
                         "/ fusion breaks on the bench step paths fail "
                         "without spending chip time")
    ap.add_argument("--bench-router", action="store_true",
                    help="opt-in gate: run tools/bench_router.py "
                         "--check-recompiles and fail if any replica "
                         "engine recompiled after warmup")
    ap.add_argument("--bench-ckpt", action="store_true",
                    help="opt-in gate: run tools/bench_ckpt.py --check and "
                         "fail unless the async checkpointer hides >=80%% "
                         "of the sync checkpoint step-time overhead")
    ap.add_argument("--bench-llm", action="store_true",
                    help="opt-in gate: run tools/bench_llm_serving.py "
                         "--prefix-trace --check (80%% shared-prefix "
                         "trace; prefix hit rate >=0.5, reuse-on TTFT "
                         "p50 beats reuse-off) then --paged-trace "
                         "--check (>=5x concurrency at byte-equal KV, "
                         "greedy bitwise parity, zero-copy prefix vs "
                         "bench_llm_paged.json)")
    ap.add_argument("--bench-fleet", action="store_true",
                    help="opt-in gate: run tools/bench_fleet.py --check "
                         "(traffic-replay chaos storm: kill + ENOSPC "
                         "scale-up + mid-storm weight roll) and fail "
                         "unless drops == 0, the fleet scaled up, the "
                         "roll was recompile-free, and SLO recovery "
                         "fits the bench_fleet_baseline.json budget; "
                         "then --migrate --check (zero-loss storm: live "
                         "streams migrate through a slow_io-widened "
                         "roll and replay through a replica kill, every "
                         "stream bitwise-equal to an undisturbed "
                         "reference, zero drops, recompile-free)")
    ap.add_argument("--bench-elastic", action="store_true",
                    help="opt-in gate: run tools/bench_elastic.py --check "
                         "(host-loss kill matrix: watchdog hang, "
                         "heartbeat silence/partition, slow link) and "
                         "fail unless every loss is detected inside its "
                         "latency budget, transient blips stay "
                         "undeclared, and watchdog overhead is <=2% "
                         "(bench_elastic_baseline.json)")
    ap.add_argument("--bench-quant", action="store_true",
                    help="opt-in gate: run tools/bench_quant.py --check "
                         "and fail unless int8 allreduce wire bytes are "
                         ">=3x smaller than dense, int8 KV fits >=1.8x "
                         "the slots, decode accuracy holds, and warm "
                         "retraces == 0 (bench_quant_baseline.json)")
    args = ap.parse_args()

    if not args.no_analyze:
        # Static analysis gates the suite: 0 clean, 1 new findings,
        # 2 analyzer internal error (python -m tools.analyze semantics).
        # --strict gates on warnings too; the SARIF sidecar feeds code
        # scanning UIs without a second analyzer run.
        t0 = time.time()
        code = subprocess.call(
            [sys.executable, "-m", "tools.analyze", "--strict",
             "--format", "sarif", "--output", "analysis.sarif",
             "paddle_tpu"], cwd=REPO)
        print(f"static analysis: exit {code} ({time.time() - t0:.0f}s)")
        if code:
            sys.exit(code)

    if args.trace_audit:
        # Opt-in: compiles real programs, so it is not part of the default
        # gate. Forces CPU so the audit never grabs an accelerator that a
        # concurrent training job owns.
        t0 = time.time()
        env = dict(os.environ, JAX_PLATFORMS="cpu")
        code = subprocess.call(
            [sys.executable, "-m", "tools.analyze", "--strict",
             "--only", "PTA009,PTA010,PTA012,PTA014",
             "--trace-report", args.trace_audit_output, "paddle_tpu"],
            cwd=REPO, env=env)
        print(f"trace audit: exit {code} ({time.time() - t0:.0f}s)")
        if code:
            sys.exit(code)

    if args.bench_check:
        # PTA009 audit gate: traces the bench step paths on CPU and fails
        # on new host transfers / retraces / copy-fraction growth vs the
        # committed baseline — catches the CAUSE of a throughput drop
        # before a chip run measures the effect.
        t0 = time.time()
        env = dict(os.environ, JAX_PLATFORMS="cpu")
        code = subprocess.call(
            [sys.executable, os.path.join("tools",
                                          "check_audit_regression.py")],
            cwd=REPO, env=env)
        print(f"bench audit gate: exit {code} ({time.time() - t0:.0f}s)")
        if code:
            sys.exit(code)

    if args.bench_router:
        # Opt-in: drives real traffic through a replica router on the CPU
        # backend and gates on the zero-post-warmup-recompiles invariant
        # (throughput numbers print but are machine-dependent, not gated).
        t0 = time.time()
        env = dict(os.environ, JAX_PLATFORMS="cpu")
        code = subprocess.call(
            [sys.executable, "-m", "tools.bench_router",
             "--requests", "192", "--check-recompiles"],
            cwd=REPO, env=env)
        print(f"bench router: exit {code} ({time.time() - t0:.0f}s)")
        if code:
            sys.exit(code)

    if args.bench_ckpt:
        # Opt-in: sync-vs-async checkpoint overhead sweep on the CPU
        # backend, gated on the >=80%-hidden acceptance bar (absolute I/O
        # times are machine-dependent; the *ratio* is the invariant).
        t0 = time.time()
        env = dict(os.environ, JAX_PLATFORMS="cpu")
        code = subprocess.call(
            [sys.executable, "-m", "tools.bench_ckpt", "--check"],
            cwd=REPO, env=env)
        print(f"bench ckpt: exit {code} ({time.time() - t0:.0f}s)")
        if code:
            sys.exit(code)

    if args.bench_llm:
        # Opt-in: the shared-prefix A/B on the CPU backend, gated on the
        # hit-rate and TTFT invariants (absolute times are machine-
        # dependent; the reuse-on-vs-off *ordering* is the invariant).
        t0 = time.time()
        env = dict(os.environ, JAX_PLATFORMS="cpu")
        code = subprocess.call(
            [sys.executable, "-m", "tools.bench_llm_serving",
             "--prefix-trace", "--check"],
            cwd=REPO, env=env)
        print(f"bench llm: exit {code} ({time.time() - t0:.0f}s)")
        if code:
            sys.exit(code)
        # the paged-KV burst A/B: >=5x concurrent sequences at a
        # byte-equal KV budget, greedy bitwise parity with the slot
        # path, and zero-copy prefix sharing, gated against the
        # committed bench_llm_paged.json
        t0 = time.time()
        code = subprocess.call(
            [sys.executable, "-m", "tools.bench_llm_serving",
             "--paged-trace", "--check"],
            cwd=REPO, env=env)
        print(f"bench llm paged: exit {code} ({time.time() - t0:.0f}s)")
        if code:
            sys.exit(code)

    if args.bench_fleet:
        # Opt-in: the self-driving-fleet chaos storm on the CPU backend,
        # gated on the structural invariants (zero drops, scale-up
        # happened, roll clean) and the relative recovery-tick budget
        # (absolute latencies are machine-dependent).
        t0 = time.time()
        env = dict(os.environ, JAX_PLATFORMS="cpu")
        code = subprocess.call(
            [sys.executable, "-m", "tools.bench_fleet", "--check"],
            cwd=REPO, env=env)
        print(f"bench fleet: exit {code} ({time.time() - t0:.0f}s)")
        if code:
            sys.exit(code)
        # Second storm: zero-loss serving (separate subprocess — each
        # storm arms its own PADDLE_TPU_FAULT_SPEC singleton). Gated on
        # bitwise stream equality, zero drops, and a recompile-free
        # migrating roll.
        t0 = time.time()
        code = subprocess.call(
            [sys.executable, "-m", "tools.bench_fleet",
             "--migrate", "--check"],
            cwd=REPO, env=env)
        print(f"bench fleet migrate: exit {code} ({time.time() - t0:.0f}s)")
        if code:
            sys.exit(code)

    if args.bench_elastic:
        # Opt-in: the host-loss kill matrix on the CPU backend, gated on
        # the detection-latency budgets (derived from the configured
        # deadlines, not the machine), the no-false-positive bar, and the
        # <=2% watchdog step overhead contract.
        t0 = time.time()
        env = dict(os.environ, JAX_PLATFORMS="cpu")
        code = subprocess.call(
            [sys.executable, "-m", "tools.bench_elastic", "--check"],
            cwd=REPO, env=env)
        print(f"bench elastic: exit {code} ({time.time() - t0:.0f}s)")
        if code:
            sys.exit(code)

    if args.bench_quant:
        # Opt-in: the quantized hot-path sweep on the CPU backend, gated
        # on the wire-bytes / slots-per-chip / accuracy / retrace bars
        # (absolute times are machine-dependent; the byte ratios and the
        # retrace count are the invariants).
        t0 = time.time()
        env = dict(os.environ, JAX_PLATFORMS="cpu")
        code = subprocess.call(
            [sys.executable, "-m", "tools.bench_quant", "--check"],
            cwd=REPO, env=env)
        print(f"bench quant: exit {code} ({time.time() - t0:.0f}s)")
        if code:
            sys.exit(code)

    files = args.files or sorted(
        glob.glob(os.path.join(REPO, "tests", "test_*.py")))
    files.sort(key=lambda f: -_WEIGHT_HINTS.get(os.path.basename(f), 10))

    # greedy longest-processing-time assignment
    shards = [[] for _ in range(min(args.jobs, len(files)))]
    loads = [0] * len(shards)
    for f in files:
        i = loads.index(min(loads))
        shards[i].append(f)
        loads[i] += _WEIGHT_HINTS.get(os.path.basename(f), 10)

    if args.all:
        mark = "slow or not slow"
    elif args.slow:
        mark = "slow"
    else:
        mark = "not slow"

    t0 = time.time()
    procs = []
    for i, shard in enumerate(shards):
        if not shard:
            continue
        cmd = [sys.executable, "-m", "pytest", "-q", "-m", mark,
               "-p", "no:cacheprovider", *shard]
        log = open(os.path.join(REPO, f".pytest_shard_{i}.log"), "w")
        procs.append((i, shard, log,
                      subprocess.Popen(cmd, cwd=REPO, stdout=log,
                                       stderr=subprocess.STDOUT)))
    rc = 0
    for i, shard, log, p in procs:
        code = p.wait()
        log.close()
        tail = open(log.name).read().strip().splitlines()
        status = tail[-1] if tail else "(no output)"
        print(f"shard {i} [{len(shard)} files] exit={code}: {status}")
        # pytest exit 5 = no tests collected in this shard's lane — fine
        if code not in (0, 5):
            rc = 1
            print("\n".join(tail[-30:]))
    print(f"total: {time.time() - t0:.0f}s, exit {rc}")
    sys.exit(rc)


if __name__ == "__main__":
    main()
