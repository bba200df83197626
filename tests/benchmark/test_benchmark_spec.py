"""BENCHMARK.json against the files it names, and the proof that a cell, a
configuration, a traffic mix and a per-layer metric are each added as files
plus one entry, with no edit to a file that is there."""
import json
import os
import re
import shutil
import subprocess
import sys

import pytest

from benchmark import spec

BENCH = spec.load_benchmark()
NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.\-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.\-]{1,16}$")


def test_every_name_and_unit_is_legal():
    names = [m["name"] for m in BENCH["end_to_end"] + BENCH["per_layer"]]
    names += [c["name"] for c in BENCH["configs"]]
    names += [w[k] for w in BENCH["workloads"]
              for k in ("name", "config", "traffic")]
    names += [k for c in BENCH["configs"] for k in c["reduced"]]
    assert all(NAME.match(n) for n in names), names
    metrics = [m["name"] for m in BENCH["end_to_end"] + BENCH["per_layer"]]
    assert len(set(metrics)) == len(metrics)
    assert all(UNIT.match(m["unit"])
               for m in BENCH["end_to_end"] + BENCH["per_layer"])
    assert all(m["better"] in ("lower", "higher")
               for m in BENCH["end_to_end"] + BENCH["per_layer"])
    assert len(json.dumps(BENCH)) < 64 * 1024
    assert 1 <= BENCH["run_seconds"] <= 51


def test_every_file_the_benchmark_names_exists():
    for c in BENCH["configs"]:
        assert os.path.isfile(os.path.join(spec.ROOT, c["file"])), c["file"]
        assert c["file"].startswith(tuple(BENCH["paths"]))
    for w in BENCH["workloads"]:
        cell = spec.load_cell(BENCH, w["name"])
        assert cell["traffic_data"]["driver"] in ("fit", "closed", "open")
        assert cell["limits"], w["name"]
        assert w["chips"] == 1 and len(w["why"]) <= 200
    for m in BENCH["per_layer"]:
        assert callable(spec.load_reader(m["name"])), m["name"]


@pytest.mark.parametrize("name", [m["name"] for m in BENCH["per_layer"]])
def test_a_reader_that_finds_nothing_to_read_returns_nothing(name):
    """A run of another driver, or an untraced one, has none of what this
    reader reads: the harness then leaves the metric out of the line."""
    assert spec.load_reader(name)({"records": [], "hist": {}}) is None


def test_a_split_name_finds_the_reader_of_the_name_before_its_suffix():
    run = {"compiles_in_window": 3}
    assert spec.load_reader("compiles_in_window.open")(run) == 3
    assert spec.load_reader("compiles_in_window.some-later-split")(run) == 3
    with pytest.raises(SystemExit, match="no reader"):
        spec.load_reader("no_such_metric.open")


def test_every_cell_reports_setup_another_end_to_end_and_a_layer_metric():
    end = {m["name"] for m in BENCH["end_to_end"]}
    for w in BENCH["workloads"]:
        e2e = [m["name"] for m in spec.metrics_for(BENCH, "end_to_end",
                                                   w["name"])]
        assert "setup_s" in e2e and len(e2e) >= 2, w["name"]
        layer = spec.metrics_for(BENCH, "per_layer", w["name"])
        assert layer and all(m["moves"] in e2e for m in layer), w["name"]
    assert all(m["moves"] in end for m in BENCH["per_layer"])
    layers = {m["layer"] for m in BENCH["per_layer"]}
    with open(os.path.join(spec.ROOT, "PERF.md")) as f:
        perf = f.read()
    assert all(layer in perf for layer in layers)


def test_published_sizes_are_in_the_configuration_files():
    by = {c["name"]: json.load(open(os.path.join(spec.ROOT, c["file"])))
          for c in BENCH["configs"]}
    g, c = by["gpt2-small"], by["cerebras-gpt-1.3b"]
    assert (g["n_embd"], g["n_layer"], g["n_head"], g["n_inner"]) \
        == (768, 12, 12, 3072)
    assert (c["n_embd"], c["n_layer"], c["n_head"], c["n_inner"],
            c["vocab_size"], c["n_positions"]) \
        == (2048, 24, 16, 8192, 50257, 2048)
    for cfg, entry in zip((g, c), BENCH["configs"]):
        assert set(cfg["changed"]) == set(entry["reduced"])


@pytest.mark.timeout_s(600)
def test_a_cell_a_config_a_traffic_mix_and_a_metric_are_added_as_files(
        tmp_path):
    """In a copy of the benchmark: four new files and four new entries, no
    file that was there edited, and the new cell runs."""
    root = tmp_path / "copy"
    shutil.copytree(os.path.join(spec.ROOT, "benchmark"), root / "benchmark",
                    ignore=shutil.ignore_patterns("__pycache__"))
    before = {p: p.read_bytes() for p in (root / "benchmark").rglob("*")
              if p.is_file()}
    cfg = json.load(open(os.path.join(
        spec.ROOT, "benchmark", "configs", "gpt2-small.json")))
    cfg.update(name="gpt2-medium", n_embd=1024, n_layer=24, n_head=16,
               n_inner=4096)
    (root / "benchmark/configs/gpt2-medium.json").write_text(json.dumps(cfg))
    tr = json.load(open(os.path.join(
        spec.ROOT, "benchmark", "traffic", "open-long-in-short-out.json")))
    tr.update(rate_per_s=3.0,
              output_len={"dist": "constant", "value": 4})
    (root / "benchmark/traffic/open-fast.json").write_text(json.dumps(tr))
    (root / "benchmark/limits/serve-gpt2m-fast.json").write_text(
        json.dumps({"limits": {"served_token_gap": 1e-4}}))
    (root / "benchmark/layer_metrics/requests_sent.py").write_text(
        "def read(run):\n    return len(run['records'])\n")
    bench = json.loads(json.dumps(BENCH))
    bench["configs"].append({
        "name": "gpt2-medium", "source": "https://huggingface.co/"
        "openai-community/gpt2-medium", "reduced": [], "why": "test",
        "file": "benchmark/configs/gpt2-medium.json"})
    bench["workloads"].append({
        "name": "serve-gpt2m-fast", "config": "gpt2-medium",
        "traffic": "open-fast", "chips": 1, "why": "test"})
    bench["per_layer"].append({
        "name": "requests_sent", "unit": "count", "better": "higher",
        "source": "program_counter", "layer": "load generator (benchmark/)",
        "moves": "itl_p99_ms", "workloads": ["serve-gpt2m-fast"]})
    # a split of a quantity that is there needs no file: the entry alone
    bench["per_layer"].append({
        "name": "device_idle_pct.fast", "unit": "%", "better": "lower",
        "source": "device_trace", "layer": "device",
        "moves": "itl_p99_ms", "workloads": ["serve-gpt2m-fast"]})
    for m in bench["end_to_end"]:
        if m["name"] == "itl_p99_ms":
            m["workloads"].append("serve-gpt2m-fast")
    (root / "BENCHMARK.json").write_text(json.dumps(bench))

    env = dict(os.environ, JAX_PLATFORMS="cpu",
               JAX_COMPILATION_CACHE_DIR=str(tmp_path / "jax_cache"),
               PYTHONPATH=os.pathsep.join([str(root), spec.ROOT]))
    out = subprocess.run(
        [sys.executable, *BENCH["command"][1:], "--workload",
         "serve-gpt2m-fast", "--seed", "3", "--seconds", "1", "--trace",
         "1", "--rehearse-on-cpu"], cwd=root, env=env, capture_output=True,
        text=True, timeout=500)
    assert out.returncode == 0, out.stderr[-2000:]
    last = json.loads(out.stdout.strip().splitlines()[-1])
    assert last["correct"] is True and last["attempted"] > 0
    assert {"requests_sent", "device_idle_pct.fast"} \
        <= set(last["readers_that_found_something"])
    assert f"cell serve-gpt2m-fast: config gpt2-medium" in out.stdout
    after = {p: p.read_bytes() for p in (root / "benchmark").rglob("*")
             if p.is_file() and "__pycache__" not in p.parts}
    assert all(after[p] == b for p, b in before.items())
    assert len(after) == len(before) + 4
