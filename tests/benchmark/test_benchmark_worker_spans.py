"""The per-layer metrics that read the LLM worker's phase counters and
spans: each reader against a hand count on a hand-made run, and the idle
gaps of a small trace recorded on the chip (one admission and two ticks of
the long-prompt cell) charged to the worker's child spans."""
import json
import os

import pytest

from benchmark import spec, trace, worker_phases

BENCH = spec.load_benchmark()
RECORDED = os.path.join(spec.HERE, "data", "recorded_worker_trace.json")
NEW = {
    "worker_idle_wait_pct": (".open",),
    "worker_host_pct": (".closed", ".open"),
    "tick_host_ms_mean": (".closed", ".open"),
    "admit_host_ms_mean": ("",),
    "engine_queue_wait_ms_mean": ("",),
    "admit_pages_ms_mean": ("",),
    "idle_unattributed_pct": (".train", ".closed", ".open"),
}

# 50 s of a worker: 8 s waiting for a request, 50 admissions of 180 ms of
# which 110 ms wait for the first token, 1,000 ticks of 31 ms of which 28 ms
# wait for the device, and 2 s of the loop's own
COUNTERS = {
    "worker.loop_s": 50.0, "worker.idle_wait_s": 8.0, "worker.admit_s": 9.0,
    "worker.admit_pages_s": 2.0, "worker.prefill_dispatch_s": 1.0,
    "worker.first_token_fetch_s": 5.5, "worker.tick_capacity_s": 0.5,
    "worker.tick_dispatch_s": 2.0, "worker.tick_fetch_s": 28.0,
    "worker.tick_emit_s": 0.5, "prefills": 50, "queue_wait_s": 2.0,
    "tokens_generated": 8000, "worker.not_seconds": 3}
RUN = {"counters": COUNTERS, "records": [],
       "hist": {"decode_tick_ms": {"count": 1000, "p50": 30.0}}}


def read(name, run):
    return spec.load_reader(name)(run)


@pytest.mark.parametrize("name, value", [
    ("worker_idle_wait_pct.open", 100 * 8.0 / 50.0),
    ("worker_host_pct.open", 100 * (50.0 - 8.0 - 28.0 - 5.5) / 50.0),
    ("worker_host_pct.closed", 17.0),
    ("tick_host_ms_mean.open", 1e3 * (0.5 + 2.0 + 0.5) / 1000),
    ("tick_host_ms_mean.closed", 3.0),
    ("admit_host_ms_mean", 1e3 * (9.0 - 5.5) / 50),
    ("admit_pages_ms_mean", 40.0),
    ("engine_queue_wait_ms_mean", 40.0),
])
def test_a_reader_of_the_phase_counters_against_a_hand_count(name, value):
    assert read(name, RUN) == pytest.approx(value)


def test_the_phases_are_the_counters_named_worker_x_s():
    w = worker_phases.phase_seconds(RUN)
    assert w["loop"] == 50.0 and w["prefill_dispatch"] == 1.0
    assert "not_second" not in w and len(w) == 10
    assert worker_phases.ticks(RUN) == 1000
    assert worker_phases.admissions(RUN) == 50


@pytest.mark.parametrize("name", [n + s for n, ss in NEW.items() for s in ss])
def test_a_program_from_before_the_counters_gives_nothing(name):
    """The parent commit under this benchmark: the engine has the old
    counters and histograms, none of the worker's."""
    old = {k: v for k, v in COUNTERS.items()
           if not k.startswith("worker.") and k != "queue_wait_s"}
    run = {"counters": old, "hist": RUN["hist"], "records": []}
    assert read(name, run) is None
    # a closed-loop window with no admission, or no tick, divides by nothing
    quiet = dict(RUN, counters=dict(COUNTERS, prefills=0), hist={})
    if name.startswith(("tick_host", "admit_", "engine_queue")):
        assert read(name, quiet) is None


def test_the_share_of_idle_that_no_span_names_against_a_hand_count():
    # a 10 ms window: the chip runs 0-2, 3-4 and 6-10 ms; a tick's span
    # covers 2.5-7 ms. The gap 2-3 ms has its middle in the span, the gap
    # 4-6 ms too; a third gap of 1 ms is made by moving the span's start
    ms = 1_000_000
    flat = {"device": [[["a", 0, 2 * ms], ["b", 3 * ms, 1 * ms],
                        ["c", 6 * ms, 4 * ms]]],
            "host": [[trace.WINDOW_SPAN, 0, 10 * ms],
                     ["serving.llm/decode_tick", int(2.5 * ms),
                      int(4.5 * ms)]]}
    run = {"trace": trace.reduce(flat)}
    assert read("idle_unattributed_pct.open", run) == 0.0
    flat["host"][1] = ["serving.llm/decode_tick", 4 * ms, 3 * ms]
    run = {"trace": trace.reduce(flat)}    # now the 1 ms gap is unnamed
    assert read("idle_unattributed_pct.closed", run) \
        == pytest.approx(100 * 1.0 / 3.0)
    assert read("idle_unattributed_pct.train", run) \
        == pytest.approx(100 * 1.0 / 3.0)
    # a chip that never idles has no share to report
    busy = {"device": [[["a", 0, 10 * ms]]], "host": flat["host"]}
    assert read("idle_unattributed_pct.open",
                {"trace": trace.reduce(busy)}) is None


def test_the_new_entries_are_served_by_seven_files_and_known_layers():
    entries = {m["name"]: m for m in BENCH["per_layer"]}
    names = [n + s for n, ss in NEW.items() for s in ss]
    assert len(names) == 11 and set(names) <= set(entries)
    folder = os.path.join(spec.HERE, "layer_metrics")
    assert all(os.path.isfile(os.path.join(folder, n + ".py")) for n in NEW)
    assert not any(os.path.isfile(os.path.join(folder, n + ".py"))
                   for n in names if "." in n)
    # a layer's name is one a metric from before this file already gave
    old_layers = {m["layer"] for m in BENCH["per_layer"]
                  if m["name"] not in names}
    for n in names:
        m = entries[n]
        assert m["layer"] in old_layers
        assert m["source"] == ("device_trace" if n.startswith("idle_")
                               else "program_counter")
        assert m["unit"] == ("ms" if "_ms_" in n else "%")
    assert entries["worker_idle_wait_pct.open"]["better"] == "higher"


# -- the recorded stretch -------------------------------------------------------

@pytest.fixture(scope="module")
def flat():
    with open(RECORDED) as f:
        data = json.load(f)
    return {"device": data["device"], "host": data["host"]}


def test_the_recorded_stretch_holds_one_admission_and_two_ticks(flat):
    lo, hi = trace.window_of(flat)
    names = [n for n, s, d in flat["host"] if lo <= s and s + d <= hi]
    assert names.count("serving.llm/admit") == 1
    assert names.count("serving.llm/decode_tick") == 2
    for child in ("admit_pages", "prefill", "first_token_fetch",
                  "tick_capacity", "tick_dispatch", "tick_fetch",
                  "tick_emit", "loop"):
        assert "serving.llm/" + child in names, child


def test_each_idle_gap_is_charged_to_the_child_span(flat):
    gaps = dict(trace.idle_gaps(flat, n=100))
    busy = trace.busy_seconds(flat)
    idle = busy["window_s"] - busy["busy_s"]
    assert sum(gaps.values()) == pytest.approx(idle, rel=1e-6)
    # the chip waits longest while the host maps the prompt's 39 pages, one
    # small dispatch each; then for each tick's fetch to return and the
    # next step to be dispatched
    order = sorted((k for k in gaps if k.startswith("serving.llm/")),
                   key=lambda k: -gaps[k])
    assert order == ["serving.llm/" + k for k in (
        "admit_pages", "tick_fetch", "first_token_fetch", "tick_dispatch",
        "prefill", "tick_emit")]
    assert gaps["serving.llm/admit_pages"] == pytest.approx(0.0359, abs=1e-4)
    assert gaps["serving.llm/tick_fetch"] == pytest.approx(0.0048, abs=1e-4)
    # no gap is left to a parent whose children cover it, or to nothing
    assert not {"serving.llm/decode_tick", "serving.llm/admit",
                "serving.llm/loop", trace.NO_SPAN} & set(gaps)
    run = {"trace": trace.reduce(flat)}
    assert read("idle_unattributed_pct.open", run) == 0.0


def test_a_fetch_returns_a_fixed_lag_after_the_chips_last_operation(flat):
    """One clock for spans and device operations: each fetch (the two
    ticks', the admission's) returns 2.3-2.5 ms after the end of the last
    operation of any length the chip ran for it: the copy to the host and
    the wake-up, or an offset between the two clocks, the same every time."""
    ops = [(s + d) for _, s, d in flat["device"][0] if d > 20_000]
    fetches = [s + d for n, s, d in flat["host"] if n.endswith("_fetch")]
    assert len(fetches) == 3
    lags = [end - max(e for e in ops if e <= end) for end in fetches]
    assert all(2_200_000 < lag < 2_600_000 for lag in lags), lags
