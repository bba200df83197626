#!/usr/bin/env python
"""PTA009 bench-audit gate: fail when the bench step paths pick up new
fusion breaks or host transfers.

Runs the trace audit over the bench entrypoints (``resnet_train_step``,
``gpt_train_step`` from :mod:`paddle_tpu.models.bench_audit`; the
serving-side ``llm_spec_decode_step`` from
:mod:`paddle_tpu.serving.llm.spec` — its one-fetch-per-tick contract is
exactly a host-transfer count; and the quantized hot paths
``compressed_allreduce_train_step`` / ``llm_int8_decode_step``, whose
quantize/dequantize stages must fuse in-graph) and
compares the per-entrypoint counts that move MFU — host transfers inside
the compiled region, large closed-over control-flow constants, missed
donation, retraces, and the HLO copy fraction — against the committed
``bench_audit_baseline.json``. A throughput measurement sees a
regression only after a chip run; this gate catches the *cause* (a fusion
break on the step path) on CPU in CI, before any chip time is spent.

Usage:
    python tools/check_audit_regression.py              # run audit + gate
    python tools/check_audit_regression.py --report F   # gate a saved report
    python tools/check_audit_regression.py --write-baseline

Exit 1 on regression (or an entrypoint that fails to trace), 0 otherwise.
``--report`` consumes a ``trace_audit.json``-shaped file (the
``stats_payload`` schema), the seam the gate's own tests use to inject a
seeded regression.
"""
from __future__ import annotations

import argparse
import json
import os
import sys

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
BASELINE = os.path.join(REPO, "bench_audit_baseline.json")

#: the bench step paths under the gate
ENTRYPOINTS = ("resnet_train_step", "gpt_train_step",
               "llm_spec_decode_step",
               # paged-KV serving decode (serving/llm/paged/): the block
               # table rides the device step — must stay one host fetch
               # per tick, zero retraces after warmup
               "llm_paged_decode_step",
               # quantized hot paths (docs/quantization.md): the
               # compressed-gradient dp train step and the int8 serving
               # decode step — both must keep zero host transfers
               "compressed_allreduce_train_step", "llm_int8_decode_step",
               # long-context dp×sp train path: grads through the
               # ring-flash custom_vjp backward (sequence_parallel.py) —
               # both ring walks must stay fused, zero-host-transfer
               # device programs
               "gpt_ring_flash_train_step",
               # mesh topologies the collective_bytes gate covers
               # (fleet/audit_specs.py): the pp ppermute chain and the
               # ep all_to_all dispatch/combine pair
               "pipeline_train_step", "moe_train_step")

#: copy_fraction may drift this much absolutely before failing (XLA
#: version skew moves copy counts a little; a real fusion break moves a
#: lot — the hapi conv path regression that motivated PTA009 tripled it)
COPY_FRACTION_SLACK = 0.05

#: collective_bytes may grow this much relatively before failing (shape
#: tweaks in the audit specs move it a little; a comm regression — a
#: lost donation of the capacity factor, an extra ring round, an
#: accidental full-replica gather — moves it a lot)
COLLECTIVE_BYTES_SLACK = 0.05

#: unfused_boundary_bytes (PTA014) may grow this much relatively before
#: failing: XLA version skew nudges fusion decisions a little; a real
#: de-fusion — a new elementwise stage materializing before a matmul —
#: adds a whole activation's worth of HBM traffic
FUSION_BYTES_SLACK = 0.05


def summarize(payload):
    """Reduce a stats_payload to the gated per-entrypoint counters."""
    out = {}
    for name in ENTRYPOINTS:
        st = (payload.get("entrypoints") or {}).get(name)
        if st is None or st.get("error"):
            out[name] = {"error": (st or {}).get("error",
                                                 "entrypoint missing")}
            continue
        hlo = st.get("hlo") or {}
        instrs = int(hlo.get("instructions", 0)) or 1
        don = st.get("donation") or {}
        out[name] = {
            "host_transfers": len(st.get("transfers") or []),
            "large_consts": len(st.get("large_consts") or []),
            "donatable_inputs": int(don.get("donatable_inputs", 0)),
            "retraces": max(0, int(st.get("trace_count", 1)) - 1),
            "fingerprint_unstable":
                0 if st.get("fingerprint_stable", True) else 1,
            "copy_fraction": round(int(hlo.get("copies", 0)) / instrs, 4),
            "collective_bytes": int(st.get("collective_bytes", 0)),
            "collective_issues": len(st.get("collective_issues") or []),
            "unfused_boundary_bytes":
                int(st.get("unfused_boundary_bytes", 0)),
        }
    return out


def compare(baseline, current):
    """List of regression strings (empty == pass): any gated counter
    above baseline, copy_fraction above baseline + slack."""
    problems = []
    for name in ENTRYPOINTS:
        base, cur = baseline.get(name), current.get(name)
        if cur is None or "error" in cur:
            problems.append(
                f"{name}: failed to trace: "
                f"{(cur or {}).get('error', 'missing')}".strip())
            continue
        if base is None:
            problems.append(f"{name}: no baseline entry — rerun with "
                            f"--write-baseline")
            continue
        for key in ("host_transfers", "large_consts", "donatable_inputs",
                    "retraces", "fingerprint_unstable",
                    "collective_issues"):
            if cur.get(key, 0) > base.get(key, 0):
                problems.append(
                    f"{name}: {key} regressed "
                    f"{base.get(key, 0)} -> {cur.get(key, 0)}")
        allowed = base.get("copy_fraction", 0.0) + COPY_FRACTION_SLACK
        if cur.get("copy_fraction", 0.0) > allowed:
            problems.append(
                f"{name}: copy_fraction regressed "
                f"{base.get('copy_fraction', 0.0):.4f} -> "
                f"{cur.get('copy_fraction', 0.0):.4f} "
                f"(allowed <= {allowed:.4f}) — a fusion broke on the "
                f"step path")
        base_bytes = int(base.get("collective_bytes", 0))
        cur_bytes = int(cur.get("collective_bytes", 0))
        if cur_bytes > base_bytes * (1.0 + COLLECTIVE_BYTES_SLACK):
            problems.append(
                f"{name}: collective_bytes regressed "
                f"{base_bytes} -> {cur_bytes} (allowed <= "
                f"{int(base_bytes * (1.0 + COLLECTIVE_BYTES_SLACK))}) — "
                f"the step is putting more traffic on the wire per "
                f"iteration")
        base_fus = int(base.get("unfused_boundary_bytes", 0))
        cur_fus = int(cur.get("unfused_boundary_bytes", 0))
        if cur_fus > base_fus * (1.0 + FUSION_BYTES_SLACK):
            problems.append(
                f"{name}: unfused_boundary_bytes regressed "
                f"{base_fus} -> {cur_fus} (allowed <= "
                f"{int(base_fus * (1.0 + FUSION_BYTES_SLACK))}) — a "
                f"fusion boundary opened around a matmul; see "
                f"`python -m tools.analyze --only PTA014` for the "
                f"ranked misses")
    return problems


def run_bench_audit():
    """Trace just the bench entrypoints (forces CPU) and return the
    stats payload."""
    os.environ.setdefault("JAX_PLATFORMS", "cpu")
    # the ring-flash entrypoint shards over a dp×sp mesh: give the CPU
    # gate the same 8 virtual devices the test suite uses (conftest.py)
    # so its audited program is the multi-rank ring, not a 1×1 fallback.
    # Only provision when the flag is absent — never override an
    # operator's explicit device-count choice.
    flags = os.environ.get("XLA_FLAGS", "")
    if "xla_force_host_platform_device_count" not in flags:
        os.environ["XLA_FLAGS"] = (
            flags + " --xla_force_host_platform_device_count=8").strip()
    if REPO not in sys.path:
        sys.path.insert(0, REPO)
    from tools.analyze.trace import run_audit
    return run_audit(list(ENTRYPOINTS)).stats_payload()


def main(argv=None):
    ap = argparse.ArgumentParser()
    ap.add_argument("--report", help="gate an existing trace_audit.json "
                                     "instead of running the audit")
    ap.add_argument("--baseline", default=BASELINE)
    ap.add_argument("--write-baseline", action="store_true",
                    help="record the current counts as the new baseline")
    args = ap.parse_args(argv)

    if args.report:
        with open(args.report) as f:
            payload = json.load(f)
    else:
        payload = run_bench_audit()
    if payload.get("error"):
        print(f"audit gate: trace audit unavailable:\n{payload['error']}")
        return 1
    current = summarize(payload)

    if args.write_baseline:
        with open(args.baseline, "w") as f:
            json.dump({"version": 1, "entrypoints": current}, f, indent=1,
                      sort_keys=True)
            f.write("\n")
        print(f"audit gate: baseline written to {args.baseline}")
        return 0

    try:
        with open(args.baseline) as f:
            baseline = json.load(f).get("entrypoints", {})
    except FileNotFoundError:
        print(f"audit gate: no baseline at {args.baseline}; run "
              f"--write-baseline first")
        return 1

    problems = compare(baseline, current)
    for name in ENTRYPOINTS:
        cur = current.get(name, {})
        print(f"audit gate [{name}]: " + (", ".join(
            f"{k}={v}" for k, v in sorted(cur.items()))))
    if problems:
        print("FAIL:")
        for p in problems:
            print(f"  - {p}")
        return 1
    print("OK")
    return 0


if __name__ == "__main__":
    sys.exit(main())
