"""Driver ``open``: requests sent at their due times whether or not earlier
ones have finished; latency counts from the due time. ``warm_requests``
untimed requests run first, one after another."""
from __future__ import annotations

import threading
import time

import numpy as np

from .. import harness, traffic as traffic_mod
from . import serving


def histogram_lines(values, lo, hi, bins=16, width=50):
    counts, edges = np.histogram(np.minimum(values, hi), bins=bins,
                                 range=(lo, hi))
    top = max(int(counts.max()), 1)
    return [f"  {edges[i]:8.1f}-{edges[i + 1]:8.1f} ms {c:4d} "
            + "#" * int(round(width * c / top))
            for i, c in enumerate(counts)]


def drive(served, requests, seconds) -> dict:
    """One window: send each request at its due time, wait for the window's
    end and for what is in flight, and reduce the records."""
    t0 = served.open_window()
    threads = []
    for request in requests:
        due = t0 + request["due_s"]
        if request["due_s"] >= seconds:
            break
        served.memory.sample()
        delay = due - time.perf_counter()
        if delay > 0:
            time.sleep(delay)
        t = threading.Thread(target=served.send, args=(request, due, True),
                             name="bench-request", daemon=True)
        t.start()
        threads.append(t)
    served.sleep_until(t0 + seconds)
    run = served.finish_window(threads)

    lat = serving.latencies(run)
    run.update(lat)
    if lat["itl_ms"]:
        run["end_to_end"] = {
            "itl_p99_ms": harness.percentile(lat["itl_ms"], 99)}
    late = [(r["submitted"] - r["due"]) * 1e3 for r in run["records"]]
    run["gen_late_ms"] = late
    # a request found a prefill ahead of it if it waited longer than one
    # decode tick on top of its own prefill
    prefill = run["hist"].get("prefill_ms", {}).get("p50", 0.0)
    tick = run["hist"].get("decode_tick_ms", {}).get("p50", 0.0)
    waited = [t for t in lat["ttft_ms"] if t > prefill + 1.5 * tick]
    run["waited_share"] = len(waited) / max(len(lat["ttft_ms"]), 1)
    print(f"open loop: {len(requests)} requests drawn, {run['attempted']} "
          f"due in the window, {run['failed']} failed; prefill p50 "
          f"{prefill:.1f} ms, tick p50 {tick:.1f} ms; share that found a "
          f"prefill ahead {run['waited_share']:.3f}; generator late p99 "
          f"{harness.percentile(late, 99):.3f} ms", flush=True)
    if lat["ttft_ms"]:
        print(f"time to first token: p50 "
              f"{harness.percentile(lat['ttft_ms'], 50):.3f} ms, p90 "
              f"{harness.percentile(lat['ttft_ms'], 90):.3f} ms", flush=True)
        for line in histogram_lines(lat["ttft_ms"], 0.0, 5 * prefill):
            print(line, flush=True)
    return run


def run(cell, args, ctx):
    served = serving.Served(cell, args, ctx)
    tr, cfg = served.tr, served.cfg
    served.warm_up(traffic_mod.open_requests(
        dict(tr, rate_per_s=1.0, output_len=tr["warm_output_len"]),
        args.seed + 1, tr["warm_requests"],
        cfg["vocab_size"]))   # n = rate * seconds = warm_requests
    run = drive(served, traffic_mod.open_requests(
        tr, args.seed, args.seconds, cfg["vocab_size"]), args.seconds)
    served.shutdown()
    run["numbers"] = served.compare(run)
    return run
