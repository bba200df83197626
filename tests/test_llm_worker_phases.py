"""The LLM worker's phases (serving/llm/scheduler.py, paged/batcher.py):
every stretch of ``_worker_loop`` is a ``serving.llm/<phase>`` span with the
tracer on and a ``worker.<phase>_s`` counter always, on both KV layouts; the
spans nest as the code does (``parent`` ids), and the counters' books
balance against ``worker.loop_s``."""
import threading
import time

import numpy as np
import pytest

import paddle_tpu as paddle
from paddle_tpu.core.monitor import StatRegistry
from paddle_tpu.models import GPTConfig, GPTForCausalLM
from paddle_tpu.observability import export as trace_export
from paddle_tpu.observability import tracer
from paddle_tpu.serving.llm import LLMEngine, LLMEngineConfig
from paddle_tpu.serving.llm.scheduler import _PHASE_COUNTERS

LAYOUTS = ("slot", "paged")
P = "serving.llm/"


def _tiny_model(seed=0, layers=2):
    paddle.seed(seed)
    net = GPTForCausalLM(GPTConfig(
        vocab_size=64, hidden_size=32, num_layers=layers, num_heads=4,
        max_position_embeddings=128, hidden_dropout_prob=0.0,
        attention_dropout_prob=0.0))
    net.eval()
    return net


@pytest.fixture(scope="module")
def model():
    return _tiny_model()


@pytest.fixture
def traced():
    """The default tracer on and empty for one test, off and empty after."""
    tracer.default_tracer().clear()
    tracer.enable()
    yield tracer.default_tracer()
    tracer.disable()
    tracer.default_tracer().clear()


def _engine(model, layout, **kw):
    cfg = dict(num_slots=4, max_seq=64, prefill_buckets=(8, 16, 40),
               warmup=True, seed=3, kv_layout=layout, page_size=8)
    cfg.update(kw)
    draft = cfg.pop("draft_model", None)
    return LLMEngine(model, LLMEngineConfig(**cfg), registry=StatRegistry(),
                     draft_model=draft)


def _serve(model, layout, prompts, max_new_tokens=6, **kw):
    """Run the prompts to their end and stop the worker, so that every
    phase has been published when the counters are read."""
    eng = _engine(model, layout, **kw)
    reqs = [eng.submit(p, max_new_tokens=max_new_tokens) for p in prompts]
    for r in reqs:
        assert len(r.result(timeout=120)["tokens"]) == max_new_tokens
    eng.drain(timeout=60)
    stats = eng.stats()
    pre = eng.config.stat_prefix + "."
    counters = {k[len(pre):]: v for k, v in stats["stats"].items()}
    hists = {k[len(pre):]: v for k, v in stats["histograms"].items()}
    return reqs, counters, hists


def _prompt(n, seed=0):
    return np.random.RandomState(seed).randint(1, 60, size=n)


def _named(spans, name):
    return [s for s in spans if s["name"] == P + name]


def _children(spans, parent):
    return {s["name"][len(P):] for s in spans if s["parent"] == parent["id"]}


# -- spans --------------------------------------------------------------------

@pytest.mark.parametrize("layout", LAYOUTS)
def test_one_request_gives_the_span_tree_of_the_code(model, traced, layout):
    (req,), _, _ = _serve(model, layout, [_prompt(12)])
    spans = traced.spans()
    by_id = {s["id"]: s for s in spans}
    assert len(by_id) == len(spans)                     # ids are unique

    (admit,) = _named(spans, "admit")
    assert admit["attrs"] == {"req": req.req_id, "prompt_len": 12}
    assert by_id[admit["parent"]]["name"] == P + "loop"
    assert _children(spans, admit) == {"admit_pages", "prefill",
                                       "first_token_fetch"}
    # the prefill span opens on both lanes, and names its request
    (prefill,) = _named(spans, "prefill")
    assert prefill["attrs"] == {"req": req.req_id}

    ticks = _named(spans, "decode_tick")
    assert len(ticks) == 5                  # 6 tokens: one from the prefill
    for tick in ticks:
        assert by_id[tick["parent"]]["name"] == P + "loop"
        assert _children(spans, tick) == {"tick_dispatch", "tick_fetch",
                                          "tick_emit"}
    capacity = _named(spans, "tick_capacity")
    assert len(capacity) == (len(ticks) if layout == "paged" else 0)
    assert all(by_id[c["parent"]]["name"] == P + "loop" for c in capacity)
    # every loop iteration is a root on the worker's thread; waiting for a
    # request is a span of its own under it
    loops = _named(spans, "loop")
    assert loops and all(s["parent"] == 0 and s["depth"] == 0 for s in loops)
    assert all(by_id[s["parent"]]["name"] == P + "loop"
               for s in _named(spans, "idle_wait"))
    assert {s["thread"] for s in spans if s["name"] in
            {span for span, _ in _PHASE_COUNTERS.values()}} \
        == {"paddle-tpu-llm-worker"}


@pytest.mark.parametrize("layout", LAYOUTS)
def test_a_child_lies_inside_its_parent_and_self_time_is_not_negative(
        model, traced, layout):
    _serve(model, layout, [_prompt(9), _prompt(14, seed=1)])
    spans = traced.spans()
    by_id = {s["id"]: s for s in spans}
    covered = {}
    for s in spans:
        if s["parent"]:
            p = by_id[s["parent"]]
            assert p["ts_ns"] <= s["ts_ns"]
            assert s["ts_ns"] + s["dur_ns"] <= p["ts_ns"] + p["dur_ns"]
            assert s["depth"] == p["depth"] + 1
            covered[p["id"]] = covered.get(p["id"], 0) + s["dur_ns"]
    # self time as the guide defines it: a span less what its children cover
    assert all(by_id[i]["dur_ns"] - c >= 0 for i, c in covered.items())
    assert len(_named(spans, "admit")) == 2
    assert {s["attrs"]["req"] for s in _named(spans, "admit")} \
        == {s["attrs"]["req"] for s in _named(spans, "prefill")}


@pytest.mark.parametrize("layout", LAYOUTS)
def test_the_speculative_tick_has_the_same_three_children(model, traced,
                                                          layout):
    _serve(model, layout, [_prompt(10)], spec_k=2,
           draft_model=_tiny_model(seed=1, layers=1))
    spans = traced.spans()
    ticks = _named(spans, "spec_tick")
    assert ticks
    for tick in ticks:
        assert _children(spans, tick) == {"tick_dispatch", "tick_fetch",
                                          "tick_emit"}


def test_a_parked_request_is_prefilled_by_the_tick_that_finds_it_room(
        model, traced):
    """Paged lane, a pool one request fills: the second request parks, and
    its admission runs inside a later tick's capacity pass. Its prefill
    span still names it, and its queue wait holds the parking."""
    eng = _engine(model, "paged", num_pages=8)
    first = eng.submit(_prompt(30), max_new_tokens=12)
    second = eng.submit(_prompt(30, seed=1), max_new_tokens=3)
    assert len(first.result(timeout=120)["tokens"]) == 12
    assert len(second.result(timeout=120)["tokens"]) == 3
    eng.drain(timeout=60)
    spans = traced.spans()
    by_id = {s["id"]: s for s in spans}
    late = [s for s in _named(spans, "prefill")
            if s["attrs"]["req"] == second.req_id]
    assert len(late) == 1
    assert by_id[late[0]["parent"]]["name"] == P + "tick_capacity"
    assert eng.stats()["stats"]["serving.llm.prefills"] == 2
    # the second waited at least the first's decode ticks
    waited = eng.stats()["stats"]["serving.llm.queue_wait_s"]
    ticks = eng.stats()["histograms"]["serving.llm.decode_tick_ms"]
    assert waited * 1e3 >= 8 * ticks["min"]


def test_with_the_tracer_off_the_ring_stays_empty(model):
    assert not tracer.is_enabled()
    tracer.default_tracer().clear()
    _, counters, _ = _serve(model, "paged", [_prompt(12)])
    assert tracer.default_tracer().spans() == []
    assert counters["worker.loop_s"] > 0


# -- counters -----------------------------------------------------------------

@pytest.mark.parametrize("tracing", ("tracer-off", "tracer-on"))
@pytest.mark.parametrize("layout", LAYOUTS)
def test_the_books_balance(model, layout, tracing, request):
    if tracing == "tracer-on":
        request.getfixturevalue("traced")
    t0 = time.perf_counter()
    reqs, c, h = _serve(model, layout,
                        [_prompt(9 + 3 * i, seed=i) for i in range(4)],
                        max_new_tokens=8)
    wall = time.perf_counter() - t0
    w = {k[len("worker."):]: v for k, v in c.items()
         if k.startswith("worker.")}
    # every phase that can run on this lane has advanced
    expected = set(counter[len("worker."):]
                   for _, counter in _PHASE_COUNTERS.values())
    if layout == "slot":
        expected.discard("tick_capacity_s")
    # chunked prefill is off here (and no GPT decoder offers it)
    expected.discard("prefill_chunk_s")
    assert set(w) == expected and all(v > 0 for v in w.values())
    # the loop is the worker's whole life, inside the engine's
    assert 0 < w["loop_s"] <= wall
    top = (w["idle_wait_s"] + w["admit_s"] + w.get("tick_capacity_s", 0.0)
           + w["tick_dispatch_s"] + w["tick_fetch_s"] + w["tick_emit_s"])
    assert top <= w["loop_s"]
    admit_children = (w["admit_pages_s"] + w["prefill_dispatch_s"]
                      + w["first_token_fetch_s"])
    assert admit_children <= w["admit_s"]
    # an admission is mostly its three children, even at toy size
    assert admit_children >= 0.5 * w["admit_s"]
    # decode_tick_ms is a tick's period: from the end of the fetch before
    # it (the tick runs one step ahead, so its dispatch came earlier still)
    # to the end of its own fetch; only a tick with nothing ahead of it
    # starts at its own dispatch. So the periods hold every fetch, and lie
    # inside the worker's loop less its waiting for requests
    tick_s = h["decode_tick_ms"]["sum"] / 1e3
    assert w["tick_fetch_s"] * 0.95 <= tick_s
    assert tick_s <= (w["loop_s"] - w["idle_wait_s"]) * 1.05
    ticks = h["decode_tick_ms"]["count"]
    assert c["tokens_generated"] - c["prefills"] <= 4 * ticks
    assert 0 < c["ticks_overlapped"] <= ticks
    assert c["prefills"] == 4
    assert h["decode_tick_ms"]["count"] >= 7


@pytest.mark.parametrize("layout", LAYOUTS)
def test_queue_wait_is_the_time_to_first_token_less_the_admission(
        model, layout):
    reqs, c, h = _serve(model, layout, [_prompt(10, seed=i) for i in range(3)])
    assert c["prefills"] == 3 and 0 < c["queue_wait_s"]
    # time to first token holds the wait and the admission after it
    assert h["ttft_ms"]["sum"] - 1e3 * c["queue_wait_s"] \
        == pytest.approx(h["prefill_ms"]["sum"], rel=1e-3)


def test_an_idle_worker_counts_its_time_as_waiting(model):
    eng = _engine(model, "paged")
    time.sleep(0.5)
    eng.drain(timeout=60)
    c = eng.stats()["stats"]
    loop, idle = c["serving.llm.worker.loop_s"], \
        c["serving.llm.worker.idle_wait_s"]
    assert 0.3 < loop and idle > 0.9 * loop
    assert "serving.llm.worker.admit_s" not in c


def test_a_phase_costs_under_20_us_a_loop_iteration_with_tracing_off(model):
    """What a busy iteration of the worker pays for its phases: the loop,
    the capacity pass and the tick's three, each with its counter."""
    assert not tracer.is_enabled()
    eng = _engine(model, "paged", warmup=False)
    eng.drain(timeout=60)
    batcher = eng._batcher
    names = ("tick_capacity", "tick_dispatch", "tick_fetch", "tick_emit")

    def iterations(n):
        # this thread's own CPU time: the suite's other workers share the
        # cores, and time spent descheduled is not the helper's cost
        t0 = time.thread_time()
        for _ in range(n):
            with batcher.phase("loop"):
                for name in names:
                    with batcher.phase(name):
                        pass
        return (time.thread_time() - t0) / n

    iterations(200)
    best = min(iterations(1000) for _ in range(5))
    assert best < 20e-6, f"{best * 1e6:.1f} us a loop iteration"
    assert tracer.default_tracer().spans() == []


# -- the tracer's record ------------------------------------------------------

def test_a_span_records_its_id_and_the_enclosing_span_of_its_thread():
    t = tracer.SpanTracer()
    with t.span_always("outer") as outer:
        with t.span_always("inner") as inner:
            seen = []
            th = threading.Thread(target=lambda: seen.append(
                t.span_always("elsewhere").__enter__()))
            th.start()
            th.join(10)
        with t.span_always("second") as second:
            pass
    assert outer.parent == 0 and outer.id > 0
    assert inner.parent == outer.id and second.parent == outer.id
    assert seen[0].parent == 0              # another thread, another stack
    assert len({outer.id, inner.id, second.id, seen[0].id}) == 4
    rec = {s["name"]: s for s in t.spans()}
    assert rec["inner"]["parent"] == rec["outer"]["id"]
    assert rec["inner"]["depth"] == 1 and rec["outer"]["parent"] == 0


def test_the_chrome_export_carries_id_parent_and_request(tmp_path):
    t = tracer.SpanTracer()
    with t.span_always("serving.llm/admit", {"req": 7}):
        with t.span_always("serving.llm/prefill"):
            pass
    path = str(tmp_path / "trace.json")
    assert trace_export.export_chrome_trace(path, tracer=t) == 2
    events = {e["name"]: e["args"] for e in
              trace_export.load_chrome_trace(path)["traceEvents"]
              if e["ph"] == "X"}
    admit, prefill = events["serving.llm/admit"], \
        events["serving.llm/prefill"]
    assert admit["req"] == 7 and admit["parent_id"] == 0
    assert prefill["parent_id"] == admit["span_id"] and prefill["depth"] == 1
