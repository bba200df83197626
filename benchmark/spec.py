"""Finding a cell's files by the names in BENCHMARK.json."""
from __future__ import annotations

import importlib.util
import json
import os

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
HERE = os.path.join(ROOT, "benchmark")


def _load_json(path):
    with open(path) as f:
        return json.load(f)


def load_benchmark() -> dict:
    return _load_json(os.path.join(ROOT, "BENCHMARK.json"))


def _merged(base: dict, over: dict) -> dict:
    out = dict(base)
    for k, v in over.items():
        out[k] = _merged(out[k], v) if isinstance(v, dict) \
            and isinstance(out.get(k), dict) else v
    return out


def load_cell(bench: dict, workload: str, rehearsal: bool = False) -> dict:
    """The cell's entry with its configuration, traffic and limits loaded.
    In a rehearsal each file's own ``rehearsal`` block overrides it (toy
    widths, short lengths)."""
    cells = {c["name"]: c for c in bench["workloads"]}
    if workload not in cells:
        raise SystemExit(f"no workload {workload!r} in BENCHMARK.json; "
                         f"known: {sorted(cells)}")
    cell = dict(cells[workload])
    configs = {c["name"]: c for c in bench["configs"]}
    config = _load_json(os.path.join(ROOT, configs[cell["config"]]["file"]))
    traffic = _load_json(os.path.join(HERE, "traffic",
                                      cell["traffic"] + ".json"))
    if rehearsal:
        config = _merged(config, config.get("rehearsal", {}))
        traffic = _merged(traffic, traffic.get("rehearsal", {}))
    cell["config_data"], cell["traffic_data"] = config, traffic
    cell["limits"] = _load_json(os.path.join(
        HERE, "limits", workload + ".json"))["limits"]
    return cell


def metrics_for(bench: dict, group: str, workload: str) -> list:
    """The ``end_to_end`` or ``per_layer`` entries this cell reports."""
    return [m for m in bench[group]
            if "workloads" not in m or workload in m["workloads"]]


def load_reader(name: str):
    """``read(run)`` of ``benchmark/layer_metrics/<name>.py``. A name with a
    dotted suffix (``device_idle_pct.open``: one quantity split by the
    end-to-end metric its cells report) falls back to the file of the name
    before the suffix, so one reader serves every split."""
    folder = os.path.join(HERE, "layer_metrics")
    path = os.path.join(folder, name + ".py")
    if not os.path.isfile(path) and "." in name:
        path = os.path.join(folder, name.rsplit(".", 1)[0] + ".py")
    if not os.path.isfile(path):
        raise SystemExit(f"no reader for the per-layer metric {name!r} "
                         f"under {folder}")
    spec = importlib.util.spec_from_file_location(
        "benchmark.layer_metrics." + name.replace(".", "_"), path)
    module = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(module)
    return module.read


def load_driver(name: str):
    return importlib.import_module(f"benchmark.drivers.{name}").run
