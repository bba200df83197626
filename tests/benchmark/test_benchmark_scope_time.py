"""The per-layer metrics that read device time by the program's own scopes
(benchmark/scope_time.py over ``paddle_tpu.observability.opscope``): hand
counts, what a program from before ``opscope`` gives, the entries, and the
three GPT cells' rehearsals with the new readers at work."""
import json
import os
import subprocess
import sys

import pytest

from benchmark import scope_time, spec, trace

BENCH = spec.load_benchmark()
MS = 1_000_000
NEW = {"train_fwd_ms_per_step": [""], "train_recompute_ms_per_step": [""],
       "train_bwd_ms_per_step": [""], "train_opt_ms_per_step": [""],
       "train_loss_head_ms_per_step": [""], "tick_norm_ms": [".closed"],
       "tick_matmul_ms": [".closed"], "tick_head_ms": [".closed"],
       "device_unscoped_pct": [".train", ".closed", ".open"]}
NAMES = [n + s for n, ss in NEW.items() for s in ss]


def read(name, run):
    return spec.load_reader(name)(run)


def _run(found, spans):
    """A traced run whose join is already made: ``found`` by ``(program,
    scope, phase)``, and host spans ``[name, start_ms, dur_ms]`` inside a
    window of 100 ms."""
    host = [[trace.WINDOW_SPAN, 0, 100 * MS]] + [
        [n, s * MS, d * MS] for n, s, d in spans]
    return {"trace": {"flat": {"device": [[["x", 0, MS]]], "host": host}},
            "scope_seconds": found}


TRAIN = {
    ("jit_step", "gpt/mlp", "forward"): 0.020,
    ("jit_step", "gpt/attn/flash_fwd", "forward"): 0.030,
    ("jit_step", "gpt/loss_head", "forward"): 0.010,
    ("jit_step", "gpt/mlp", "recompute"): 0.040,
    ("jit_step", "gpt/norm", "backward"): 0.050,
    ("jit_step", "gpt/loss_head", "backward"): 0.030,
    ("jit_step", "train/optimizer", "optimizer"): 0.008,
    ("jit_step", "_unscoped_", "-"): 0.002,
    ("-", "_unscoped_", "-"): 0.010,
}
TICK = {
    ("jit__step", "gpt/norm", "-"): 0.003,
    ("jit__step", "gpt/qkv", "-"): 0.010,
    ("jit__step", "gpt/proj", "-"): 0.020,
    ("jit__step", "gpt/mlp", "-"): 0.030,
    ("jit__step", "gpt/attn/paged_attn", "-"): 0.015,
    ("jit__step", "decode/head", "-"): 0.004,
    ("jit__step", "decode/sample", "-"): 0.002,
    ("jit__prefill", "gpt/mlp", "-"): 0.050,     # another program's
    ("jit__prefill", "decode/head", "-"): 0.001,
    ("-", "_unscoped_", "-"): 0.015,
}


@pytest.mark.parametrize("name, want", [
    ("train_fwd_ms_per_step", 60.0 / 2),
    ("train_recompute_ms_per_step", 40.0 / 2),
    ("train_bwd_ms_per_step", 80.0 / 2),
    ("train_opt_ms_per_step", 8.0 / 2),
    ("train_loss_head_ms_per_step", 40.0 / 2),
    ("device_unscoped_pct.train", 100 * 0.012 / 0.200),
])
def test_a_train_reader_against_a_hand_count(name, want):
    # two steps lie wholly in the window; the third is cut by its end
    run = _run(dict(TRAIN), [["bench/step", 5, 30], ["bench/step", 40, 30],
                             ["bench/step", 90, 30]])
    assert read(name, run) == pytest.approx(want)


@pytest.mark.parametrize("name, want", [
    ("tick_norm_ms.closed", 3.0 / 4),
    ("tick_matmul_ms.closed", 60.0 / 4),
    ("tick_head_ms.closed", 6.0 / 4),
    ("device_unscoped_pct.closed", 100 * 0.015 / 0.150),
    ("device_unscoped_pct.open", 100 * 0.015 / 0.150),
])
def test_a_tick_reader_against_a_hand_count(name, want):
    run = _run(dict(TICK), [["serving.llm/decode_tick", 10 * i, 9]
                            for i in range(1, 5)])
    assert read(name, run) == pytest.approx(want)


def test_a_scope_holds_what_stands_inside_it():
    assert scope_time.within("gpt/attn/paged_attn", "gpt/attn")
    assert scope_time.within("trinity/attn_full/trinity/chunk_walk",
                             "trinity/chunk_walk")
    assert not scope_time.within("gpt/attn_extra", "gpt/attn")
    assert not scope_time.within("_unscoped_", "gpt/attn")


@pytest.mark.parametrize("name", NAMES)
def test_a_run_with_no_span_to_divide_by_or_no_join_gives_nothing(name):
    assert read(name, {"records": [], "hist": {}}) is None      # untraced
    assert read(name, _run(None, [["bench/step", 5, 30]])) is None
    if not name.startswith("device_unscoped_pct"):
        assert read(name, _run(dict(TRAIN), [])) is None


def test_the_join_is_made_once_a_run_over_the_window_alone(monkeypatch):
    from paddle_tpu.observability import opscope
    asked = []

    def by_scope(events):
        asked.append(events)
        return {("jit__step", "gpt/mlp", "-"): 0.004,
                ("-", "_unscoped_", "-"): 0.001}
    monkeypatch.setattr(opscope, "by_scope", by_scope)
    run = _run(None, [["serving.llm/decode_tick", 10, 9],
                      ["serving.llm/decode_tick", 20, 9]])
    del run["scope_seconds"]
    run["trace"]["flat"]["device"] = [[
        ["before", -5 * MS, MS], ["a", 0, MS], ["b", 50 * MS, MS],
        ["after", 100 * MS, MS]]]
    assert read("tick_matmul_ms.closed", run) == pytest.approx(2.0)
    assert read("device_unscoped_pct.closed", run) == pytest.approx(20.0)
    assert [[e[0] for e in events] for events in asked] == [["a", "b"]]
    # every event unscoped: the join found nothing, and no reader prints
    monkeypatch.setattr(opscope, "by_scope", lambda events: {
        ("-", "_unscoped_", "-"): 0.005})
    del run["scope_seconds"]
    assert read("device_unscoped_pct.closed", run) is None


@pytest.mark.parametrize("name", NAMES)
def test_a_program_from_before_opscope_gives_nothing(name, monkeypatch):
    """The parent commit has no ``observability/opscope.py``: the readers
    are laid over it too, and must give nothing without raising."""
    import paddle_tpu.observability as obs
    monkeypatch.delattr(obs, "opscope")
    monkeypatch.setitem(sys.modules, "paddle_tpu.observability.opscope", None)
    run = _run(None, [["bench/step", 5, 30],
                      ["serving.llm/decode_tick", 40, 9]])
    del run["scope_seconds"]
    assert read(name, run) is None


def test_the_new_entries_are_served_by_nine_files_and_known_layers():
    entries = {m["name"]: m for m in BENCH["per_layer"]}
    assert len(NAMES) == 11 and set(NAMES) <= set(entries)
    # appended behind what was there, in one block
    assert [m["name"] for m in BENCH["per_layer"]][-11:] == NAMES
    folder = os.path.join(spec.HERE, "layer_metrics")
    assert all(os.path.isfile(os.path.join(folder, n + ".py")) for n in NEW)
    assert not any(os.path.isfile(os.path.join(folder, n + ".py"))
                   for n in NAMES if "." in n)
    old_layers = {m["layer"] for m in BENCH["per_layer"]
                  if m["name"] not in NAMES}
    gpt_cells = {"train-gpt2s-s4096", "serve-gpt1p3b-decode",
                 "serve-gpt1p3b-longprompt"}
    for n in NAMES:
        m = entries[n]
        assert m["layer"] in old_layers
        assert m["source"] == "device_trace" and m["better"] == "lower"
        assert m["unit"] == ("%" if n.startswith("device_unscoped") else "ms")
        assert set(m["workloads"]) <= gpt_cells and len(m["workloads"]) == 1
    assert len(json.dumps(BENCH)) < 64 * 1024


@pytest.mark.timeout_s(600)
@pytest.mark.parametrize("cell, readers", [
    ("train-gpt2s-s4096", [n for n in NAMES if n.startswith("train_")]
     + ["device_unscoped_pct.train"]),
    ("serve-gpt1p3b-decode", [n for n in NAMES if n.endswith(".closed")]),
    ("serve-gpt1p3b-longprompt", ["device_unscoped_pct.open"]),
])
def test_a_gpt_cell_rehearses_correct_with_the_new_readers_at_work(
        cell, readers, tmp_path):
    """Off the chip the XLA CPU client's threads stand in for it and name
    an event by its instruction alone: the join is made all the same."""
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               JAX_COMPILATION_CACHE_DIR=str(tmp_path / "jax_cache"),
               PYTHONPATH=spec.ROOT)
    out = subprocess.run(
        [sys.executable, *BENCH["command"][1:], "--workload", cell, "--seed",
         str(2**31 + 36), "--seconds", "1", "--trace", "1",
         "--rehearse-on-cpu"], cwd=spec.ROOT, env=env, capture_output=True,
        text=True, timeout=500)
    assert out.returncode == 0, out.stderr[-2000:]
    last = json.loads(out.stdout.strip().splitlines()[-1])
    assert last["correct"] is True and last["failed"] == 0
    assert last["metrics"] == {}
    assert set(readers) <= set(last["readers_that_found_something"])
