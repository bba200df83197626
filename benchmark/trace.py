"""Reduction of a profiler trace (``.xplane.pb``) to what the metrics read.

A trace is first flattened to ``{"device": [[name, start_ns, dur_ns], ...]
per chip, "host": [[name, start_ns, dur_ns], ...]}``: the device operations
(the ``XLA Ops`` line of each ``/device:TPU:n`` plane) and the host's named
annotations (every span the benchmark or the program opened while the
profiler ran). The reductions below work on that form, so a small recorded
trace kept as JSON checks them.

- busy: the union of the intervals in which an operation ran on a chip,
  averaged over the chips; idle share is ``1 - busy / window``.
- kernel time: summed durations of the operations whose name holds the
  kernel's name.
- gaps: every idle interval of chip 0 inside the window, charged to the
  innermost host span open at its middle (``_no_span_open_`` if none).
"""
from __future__ import annotations

import glob
import os
import re

OPS_LINE = "XLA Ops"
CPU_CLIENT_LINE = "tf_XLAPjRtCpuClient"
DEVICE_PLANE = re.compile(r"^/device:TPU:(\d+)$")
WINDOW_SPAN = "bench/trace_window"
NO_SPAN = "_no_span_open_"
SMALL_GAP_NS = 5_000


def find_xplane(log_dir: str) -> str:
    paths = sorted(glob.glob(os.path.join(log_dir, "plugins", "profile", "*",
                                          "*.xplane.pb")))
    if not paths:
        raise FileNotFoundError(f"no .xplane.pb under {log_dir}")
    return paths[-1]


def flatten(xplane_path: str, span_prefixes=("bench/", "train/", "serving."),
            rehearsal: bool = False) -> dict:
    """Read the profiler's file with nothing but JAX. In a CPU rehearsal
    the XLA CPU client's threads stand in for the chip, so that the same
    reductions run; nothing read from them is ever printed as a metric."""
    from jax.profiler import ProfileData
    data = ProfileData.from_file(xplane_path)
    device, host = {}, []
    for plane in data.planes:
        m = DEVICE_PLANE.match(plane.name)
        for line in plane.lines:
            if m and line.name == OPS_LINE:
                device[int(m.group(1))] = [
                    [ev.name, int(ev.start_ns), int(ev.duration_ns)]
                    for ev in line.events]
            elif rehearsal and line.name.startswith(CPU_CLIENT_LINE):
                device.setdefault(0, []).extend(
                    [ev.name, int(ev.start_ns), int(ev.duration_ns)]
                    for ev in line.events if ev.duration_ns > 0
                    and not ev.name.startswith(("Threadpool", "end:")))
            elif not m and plane.name.startswith("/host"):
                host.extend([ev.name, int(ev.start_ns), int(ev.duration_ns)]
                            for ev in line.events
                            if ev.name.startswith(span_prefixes))
    return {"device": [device[k] for k in sorted(device)], "host": host}


def window_of(flat: dict) -> tuple:
    """(start_ns, end_ns) of the traced window: the benchmark's own span
    round it, or failing that the extent of the device operations."""
    for name, start, dur in flat["host"]:
        if name == WINDOW_SPAN:
            return start, start + dur
    evs = [e for chip in flat["device"] for e in chip]
    if not evs:
        raise ValueError("the trace holds no device operation")
    return min(e[1] for e in evs), max(e[1] + e[2] for e in evs)


def _union(intervals):
    """Merged, sorted ``[start, end]`` intervals."""
    out = []
    for s, e in sorted(intervals):
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return out


def _clipped(chip, lo, hi):
    return _union((max(s, lo), min(s + d, hi)) for _, s, d in chip
                  if s < hi and s + d > lo and d > 0)


def busy_seconds(flat: dict) -> dict:
    """``{"busy_s", "window_s"}``: busy averaged over the chips."""
    lo, hi = window_of(flat)
    if not flat["device"]:
        raise ValueError("the trace holds no device plane")
    busy = [sum(e - s for s, e in _clipped(chip, lo, hi))
            for chip in flat["device"]]
    return {"busy_s": sum(busy) / len(busy) / 1e9, "window_s": (hi - lo) / 1e9}


def kernel_seconds(flat: dict, needle: str) -> tuple:
    """(seconds, calls) of chip 0's operations inside the window whose name
    holds ``needle``."""
    lo, hi = window_of(flat)
    hits = [d for name, s, d in flat["device"][0]
            if needle in name and lo <= s < hi]
    return sum(hits) / 1e9, len(hits)


_OP = re.compile(r"^%?([A-Za-z_\-]+(?:[._][A-Za-z_\-]+)*?)(?:\.\d+)*"
                 r"(?: = \(?([a-z0-9]+\[[0-9,]*\]))?")


def short_name(name: str) -> str:
    """An operation's name without its instance number, with the shape of
    its (first) result where the trace gives the instruction's text:
    ``%copy.148 = f32[401,24,16,16,128]{...} copy(...)`` and ``copy.149``
    of the same shape fall under ``copy f32[401,24,16,16,128]``."""
    m = _OP.match(name)
    if not m:
        return name[:80]
    return (m.group(1) + (" " + m.group(2) if m.group(2) else ""))[:80]


def top_device_ops(flat: dict, n: int = 10) -> list:
    lo, hi = window_of(flat)
    total = {}
    for name, s, d in flat["device"][0]:
        if lo <= s < hi:
            key = short_name(name)
            total[key] = total.get(key, 0) + d
    top = sorted(total.items(), key=lambda kv: -kv[1])[:n]
    return [[k, v / 1e9] for k, v in top]


def idle_gaps(flat: dict, n: int = 10) -> list:
    """Idle time of chip 0 by the innermost host span open at the middle of
    each gap; gaps under 5 us are pooled."""
    lo, hi = window_of(flat)
    busy = _clipped(flat["device"][0], lo, hi)
    edges = [lo] + [t for iv in busy for t in iv] + [hi]
    spans = [(s, s + d, name) for name, s, d in flat["host"]
             if name != WINDOW_SPAN]
    total = {}
    for a, b in zip(edges[0::2], edges[1::2]):
        if b <= a:
            continue
        if b - a < SMALL_GAP_NS:
            key = "_gaps_under_5_us_"
        else:
            mid = (a + b) // 2
            open_ = [(e - s, name) for s, e, name in spans if s <= mid < e]
            key = min(open_)[1] if open_ else NO_SPAN
        total[key] = total.get(key, 0) + (b - a)
    top = sorted(total.items(), key=lambda kv: -kv[1])[:n]
    return [[k, v / 1e9] for k, v in top]


def reduce(flat: dict) -> dict:
    """Everything the per-layer metrics and the result line read."""
    out = busy_seconds(flat)
    out["device_ops"] = top_device_ops(flat)
    out["idle_gaps"] = idle_gaps(flat)
    out["flat"] = flat
    return out
