"""Run one cell of BENCHMARK.json and print its result line.

    python3 -m benchmark.run --workload <cell> --seed <n> --seconds <s> --trace <0|1>

The last line of standard output is one JSON object: ``correct``,
``attempted``, ``failed``, ``metrics``, ``device`` and, traced,
``breakdown``. With ``--trace 0`` the metrics are the cell's end-to-end
metrics, with ``--trace 1`` its per-layer metrics. Without a TPU (or with
fewer chips than the cell asks for) the run exits 1 and prints no result;
``--rehearse-on-cpu`` drives the same control flow at the toy widths the
configuration and traffic files carry, and prints no metric.
"""
from __future__ import annotations

import time

PROCESS_START = time.perf_counter()

import argparse  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402

from . import check, harness, spec  # noqa: E402


def parse(argv):
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    ap.add_argument("--rehearse-on-cpu", action="store_true",
                    help="toy widths on the CPU: control flow only, no metric")
    return ap.parse_args(argv)


def main(argv=None, control_modes=()) -> int:
    args = parse(argv)
    bench = spec.load_benchmark()
    harness.place_compile_cache(spec.ROOT)
    device = harness.device_description()
    rehearsal = False
    if device["platform"] != "tpu":
        if not args.rehearse_on_cpu:
            print(f"benchmark: no TPU (jax runs on {device['platform']!r}); "
                  f"no result", file=sys.stderr)
            return 1
        rehearsal = True
        print("REHEARSAL on the CPU at toy widths: control flow only, not a "
              "measurement.", flush=True)
    cell = spec.load_cell(bench, args.workload, rehearsal=rehearsal)
    if device["count"] < cell["chips"] and not rehearsal:
        print(f"benchmark: cell needs {cell['chips']} chips, jax sees "
              f"{device['count']}; no result", file=sys.stderr)
        return 1
    print(f"cell {cell['name']}: config {cell['config']}, traffic "
          f"{cell['traffic']}, seed {args.seed}, {args.seconds} s, device "
          f"{device}", flush=True)
    ctx = {"process_start": PROCESS_START, "rehearsal": rehearsal,
           "compiles": harness.CompileCounter(),
           "control_modes": control_modes,
           "out_dir": os.path.join(spec.ROOT, "chiprun_out", "benchmark",
                                   cell["name"])}
    os.makedirs(ctx["out_dir"], exist_ok=True)
    run = spec.load_driver(cell["traffic_data"]["driver"])(cell, args, ctx)
    run.update(cell=cell, seconds=args.seconds, device=device)
    if rehearsal:   # shares of an infinite peak are 0: the readers run,
        run["peaks"] = {"flops_per_s": float("inf"),    # nothing is printed
                        "bytes_per_s": float("inf")}
    else:
        from .peaks import peaks_for
        run["peaks"] = peaks_for(device["kind"])

    correct = check.judge(run["numbers"], cell["limits"])
    if control_modes:   # a calibration run: the control beside the program
        print("control: " + json.dumps(run["numbers"]), flush=True)
    end_to_end = dict(run.get("end_to_end", {}), setup_s=run["setup_s"])
    print(f"set-up {run['setup_s']:.3f} s; end to end {end_to_end}",
          flush=True)
    device = dict(device, memory_peak_bytes=run["peak_bytes"])
    result = {"correct": bool(correct), "attempted": run["attempted"],
              "failed": run["failed"], "metrics": {}, "device": device}
    if rehearsal:
        result["rehearsal"] = True
        if args.trace:
            result["readers_that_found_something"] = [
                m["name"]
                for m in spec.metrics_for(bench, "per_layer", cell["name"])
                if spec.load_reader(m["name"])(run) is not None]
    elif not args.trace:
        for m in spec.metrics_for(bench, "end_to_end", cell["name"]):
            if m["name"] in end_to_end:
                result["metrics"][m["name"]] = {
                    "value": end_to_end[m["name"]], "unit": m["unit"]}
    else:
        trace = run["trace"]
        device.update(busy_s=trace["busy_s"], window_s=trace["window_s"])
        result["breakdown"] = {"device_ops": trace["device_ops"],
                               "idle_gaps": trace["idle_gaps"]}
        for m in spec.metrics_for(bench, "per_layer", cell["name"]):
            value = spec.load_reader(m["name"])(run)
            if value is not None:
                result["metrics"][m["name"]] = {"value": float(value),
                                                "unit": m["unit"]}
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
