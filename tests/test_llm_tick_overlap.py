"""The decode tick runs one step ahead (serving/llm/scheduler.py,
paged/batcher.py): step t+1 is dispatched before step t's tokens are fetched.

Most cases drive a batcher by hand (no worker thread, so the ticks are the
test's) on three lanes at toy width: the slot GPT, the paged GPT and LFM2 on
pages. MiniCPM-SALA on pages, its prompts entering in chunks of 8, is the
fourth lane of the cases that hold for a serial tick too: an engine that
prefills in chunks does not run ahead. The serial reference is the same batcher told that it has no room to run
ahead (``_room_ahead``: what the paged lane answers by itself when the pool
is short), so every tick of it is dispatch, fetch, emit, as the loop was
before. What is held:

- streams equal the serial loop's token for token: greedy whatever joins and
  leaves, seeded sampling when the joiner is admitted behind the same number
  of dispatched steps (a sampling key is drawn per dispatch, in order);
- a finish is seen one tick behind and changes nothing a client can see;
- a step's tokens go to the requests that held the slots at its dispatch;
- capacity counts the step in flight, a row run ahead costs no one a page,
  and a row computed for a request that has ended writes the trash page or
  a page its slot alone holds;
- whatever touches the slots from outside the tick settles the step in
  flight first; the speculative tick stays serial; the counters add up.
"""
import json
import os
import time

import numpy as np
import pytest

import paddle_tpu as paddle
from benchmark import lfm2_adapter, sala_adapter, spec as bench_spec
from paddle_tpu.core.monitor import StatRegistry
from paddle_tpu.models import GPTConfig, GPTForCausalLM
from paddle_tpu.serving.llm import LLMEngine, LLMEngineConfig
from paddle_tpu.serving.llm.decode import GPTStaticDecoder, SamplingParams
from paddle_tpu.serving.llm.paged import PagedBatcher, paged_decoder_class
from paddle_tpu.serving.llm.scheduler import (_STREAM_END, ContinuousBatcher,
                                              GenerationRequest)
from paddle_tpu.serving.request import Deadline

pytestmark = pytest.mark.timeout_s(900)
PAGE = 8
PRE = "serving.llm."
LANES = ("slot", "paged", "lfm2")     # the lanes whose tick runs ahead
PAGED = LANES[1:]
ALL = LANES + ("sala",)                 # and the chunked one, which is serial


def _gpt(seed=0, layers=2):
    paddle.seed(seed)
    net = GPTForCausalLM(GPTConfig(
        vocab_size=64, hidden_size=32, num_layers=layers, num_heads=4,
        max_position_embeddings=128, hidden_dropout_prob=0.0,
        attention_dropout_prob=0.0))
    net.eval()
    return net


def _toy(name, adapter):
    with open(os.path.join(bench_spec.HERE, "configs", name + ".json")) as f:
        cfg = json.load(f)
    cfg = bench_spec._merged(cfg, cfg["rehearsal"])
    net = adapter.build_net(cfg)
    adapter.load_weights(net, cfg, 11)
    net.eval()
    return net, cfg["vocab_size"]


class Lane:
    """One way of serving at toy width: the model, and the engine options."""

    def __init__(self, name):
        self.name = name
        if name in ("slot", "paged"):
            self.net, self.vocab = _gpt(), 64
            self.opts = dict(max_seq=64, prefill_buckets=(8, 16, 40))
            self.prompts = (5, 12, 9, 16, 3)
        elif name == "lfm2":
            self.net, self.vocab = _toy("lfm2-8b-a1b", lfm2_adapter)
            self.opts = dict(max_seq=48, prefill_buckets=(16,))
            self.prompts = (5, 12, 9, 16, 3)
        else:
            self.net, self.vocab = _toy("minicpm-sala", sala_adapter)
            self.opts = dict(max_seq=96, prefill_buckets=(16, 32, 64),
                             prefill_chunk=8)
            self.prompts = (13, 27, 9, 40, 21)
        self.opts.update(num_slots=3, max_top_k=4, warmup=False)
        if name != "slot":
            self.opts.update(kv_layout="paged", page_size=PAGE,
                             paged_attn_impl="gather")
        self.max_seq = self.opts["max_seq"]
        #: the longest prompt one admission takes
        self.long_prompt = (self.max_seq - 16 if name == "sala"
                            else self.opts["prefill_buckets"][-1])

    def config(self, **over):
        return LLMEngineConfig(**dict(self.opts, **over))

    def batcher(self, **over):
        """A batcher nobody drives but the test, and its registry."""
        cfg, reg = self.config(**over), StatRegistry()
        if self.name == "slot":
            return ContinuousBatcher(
                GPTStaticDecoder(self.net, max_top_k=4), cfg, reg), reg
        dec = paged_decoder_class(self.net)(
            self.net, max_top_k=4, page_size=PAGE, num_pages=cfg.num_pages,
            attn_impl="gather")
        return PagedBatcher(dec, cfg, reg), reg

    def engine(self, **over):
        over.setdefault("warmup", False)
        return LLMEngine(self.net, self.config(**over),
                         registry=StatRegistry())

    def prompt(self, n, seed=0):
        return np.random.default_rng(100 + seed).integers(
            1, self.vocab, n).astype(np.int32)


_LANES = {}


@pytest.fixture
def lane(request):
    """The lane named by the test's ``lane`` parameter, built once."""
    name = request.param
    if name not in _LANES:
        _LANES[name] = Lane(name)
    return _LANES[name]


def lanes(names=LANES):
    return pytest.mark.parametrize("lane", names, indirect=True)


def _request(prompt, deadline=None, **sampling):
    return GenerationRequest(prompt, SamplingParams(**sampling),
                             deadline=deadline, stream=True)


class Drive:
    """Drives a batcher tick by tick. ``plan``: one dict a request, with its
    ``prompt``, its sampling options and ``at``: the request is admitted, in
    the plan's order, once that many tick calls have been made (or, with
    ``by_steps``, once that many decode steps have been dispatched) and a
    slot is free."""

    def __init__(self, batcher, serial=False):
        self.b, self.steps, self.calls = batcher, 0, 0
        inner = batcher._dispatch_step

        def counted(ahead):
            self.steps += 1
            return inner(ahead)

        batcher._dispatch_step = counted
        if serial:
            batcher._room_ahead = lambda: False

    def run(self, plan, by_steps=False, before_tick=None, limit=600):
        self.reqs = reqs = [None] * len(plan)
        todo = list(range(len(plan)))
        while todo or self.b.active:
            while todo:
                spec = dict(plan[todo[0]])
                when = self.steps if by_steps else self.calls
                if spec.pop("at") > when or self.b.free_slots < 1:
                    break
                reqs[todo.pop(0)] = req = _request(**spec)
                self.b.admit(req)
            if before_tick is not None:
                before_tick(self)
            self.b.tick()
            self.calls += 1
            assert self.calls < limit, "the batcher never came to an end"
        return reqs


def _outcome(req):
    """What a client saw of a request: its tokens, how it ended, and its
    stream, item by item."""
    exc = req.future.exception(timeout=0)
    items = list(req._stream_q.queue)
    return (list(req.tokens),
            req.finish_reason if exc is None else type(exc).__name__,
            [type(i).__name__ if isinstance(i, BaseException) else
             "end" if i is _STREAM_END else i for i in items])


def _both(lane, plan, by_steps=False, hook=None, **over):
    """The plan through the loop that runs ahead and through the serial
    one: ``(outcomes, registry)`` of each."""
    got = []
    for serial in (False, True):
        b, reg = lane.batcher(**over)
        drive = Drive(b, serial=serial)
        reqs = drive.run(plan, by_steps=by_steps,
                         before_tick=None if hook is None else hook())
        assert b._inflight is None and b.active == 0
        if lane.name != "slot":
            assert b.kv.pool.pages_in_use == (
                0 if b.prefix_store is None
                else b.prefix_store.stats()["pages"])
        got.append(([_outcome(r) for r in reqs], reg))
    return got


def _plan(lane, max_new, at, **sampling):
    """A request a length in ``max_new``, on the lane's own prompts."""
    return [dict(prompt=lane.prompt(n, seed=i), max_new_tokens=m, at=a,
                 **sampling)
            for i, (n, m, a) in enumerate(zip(lane.prompts, max_new, at))]


# -- (a) streams --------------------------------------------------------------

@lanes()
def test_greedy_streams_equal_the_serial_loops_with_joins_and_leaves(lane):
    """Five requests on three slots: two start together, three join as the
    ticks go and as slots come free, each leaves at its own length."""
    plan = _plan(lane, max_new=(9, 4, 12, 6, 7), at=(0, 0, 2, 5, 9))
    (ahead, reg), (serial, sreg) = _both(lane, plan)
    assert ahead == serial
    assert [len(o[0]) for o in ahead] == [9, 4, 12, 6, 7]
    assert all(o[1] == "length" for o in ahead)
    assert reg.get(PRE + "ticks_overlapped") > 10
    assert sreg.get(PRE + "ticks_overlapped") == 0
    assert reg.get(PRE + "completed") == sreg.get(PRE + "completed") == 5


@lanes()
def test_sampled_streams_equal_the_serial_loops(lane):
    """Seeded sampling, three requests: two from the start, of which one
    leaves first, and one that joins behind five dispatched steps, in either
    loop (the loop that runs ahead has then fetched four of them, the serial
    one five: the keys are drawn in the same order, and the third slot is
    the free one in both, which matters because a slot's noise is its row
    of the key's)."""
    new, at = (9, 14, 8), (0, 0, 5)
    plan = _plan(lane, max_new=new, at=at, do_sample=True, temperature=0.9,
                 top_k=4)
    (ahead, reg), (serial, _) = _both(lane, plan, by_steps=True)
    assert ahead == serial
    assert [len(o[0]) for o in ahead] == list(new)
    assert reg.get(PRE + "ticks_overlapped") > 10
    # and the sampling was no greedy decoding in disguise
    (greedy, _), _ = _both(lane, _plan(lane, new, at), by_steps=True)
    assert [o[0] for o in greedy] != [o[0] for o in ahead]


# -- (b) finishes -------------------------------------------------------------

class FakeClock:
    def __init__(self):
        self.t = 0.0

    def __call__(self):
        return self.t


@lanes()
@pytest.mark.parametrize("reason", ("eos", "max_new_tokens", "max_seq",
                                    "deadline"))
def test_a_finish_is_seen_one_tick_behind_and_shows_as_before(lane, reason):
    """A request that ends beside one that goes on: reason, tokens, the
    ``completed`` count and the stream (every token once, then the end and
    nothing after it) are the serial loop's."""
    other = dict(prompt=lane.prompt(7, seed=9), max_new_tokens=20, at=0)
    ends = dict(prompt=lane.prompt(11, seed=8), max_new_tokens=30, at=0)
    hook, want, clock = None, "length", FakeClock()
    if reason == "eos":
        b, _ = lane.batcher()
        (probe,) = Drive(b, serial=True).run([dict(ends)])
        i = next(i for i in range(2, 30)
                 if probe.tokens[i] not in probe.tokens[:i])
        ends.update(eos_token_id=probe.tokens[i])
        want, n = "stop", i + 1
    elif reason == "max_new_tokens":
        ends.update(max_new_tokens=5)
        n = 5
    elif reason == "max_seq":
        ends.update(prompt=lane.prompt(lane.long_prompt, seed=8),
                    max_new_tokens=200)
        n = lane.max_seq - lane.long_prompt
    else:
        ends.update(deadline=Deadline(5.0, clock=clock))
        want = "DeadlineExceeded"

        def hook():
            clock.t = 0.0
            # six tokens in, the deadline has passed: the fetch after that
            # evicts the request, in either loop

            def before_tick(drive):
                if drive.reqs[0] is not None and len(drive.reqs[0].tokens) >= 6:
                    clock.t = 10.0
            return before_tick
    (ahead, reg), (serial, sreg) = _both(lane, [ends, other], hook=hook)
    assert ahead == serial
    tokens, how, stream = ahead[0]
    assert how == want
    assert len(tokens) == (6 if reason == "deadline" else n)
    assert stream == tokens + ([how] if reason == "deadline" else []) \
        + ["end"]
    assert len(ahead[1][0]) == 20 and ahead[1][2][-1] == "end"
    for r in (reg, sreg):
        assert r.get(PRE + "completed") == (1 if reason == "deadline" else 2)
        assert r.get(PRE + "evicted_midstream") == (reason == "deadline")


# -- (c) whose token ----------------------------------------------------------

@lanes()
def test_a_slot_reused_between_dispatch_and_fetch_keeps_its_tokens_apart(
        lane):
    """A short request ends while a long one runs: the step in flight holds
    a row of it. A new request takes its slot before that step is fetched,
    and is served what it is served alone."""
    stays, short, new = (dict(prompt=lane.prompt(n, seed=s), max_new_tokens=m)
                         for n, s, m in ((9, 1, 30), (6, 2, 4), (13, 3, 10)))
    alone = {}
    for name, spec in (("stays", stays), ("new", new)):
        b, _ = lane.batcher(num_slots=2)
        (req,) = Drive(b, serial=True).run([dict(spec, at=0)])
        alone[name] = list(req.tokens)

    b, reg = lane.batcher(num_slots=2)
    r_stays, r_short = _request(**stays), _request(**short)
    b.admit(r_stays)
    b.admit(r_short)
    while not r_short.future.done():
        b.tick()
    flight = b._inflight
    (slot,) = [s for s, r in flight.reqs.items() if r is r_short]
    assert slot not in b._reqs and len(r_short.tokens) == 4
    r_new = _request(**new)
    b.admit(r_new)
    assert b._inflight is flight        # the admission left it in flight
    assert reg.get(PRE + "ticks_settled_early") == 0
    assert b._reqs[slot] is r_new       # the slot is reused
    while b.active:
        b.tick()
    assert list(r_new.tokens) == alone["new"]
    assert list(r_stays.tokens) == alone["stays"]
    assert len(r_short.tokens) == 4 and r_short.finish_reason == "length"


# -- (d) capacity -------------------------------------------------------------

@lanes(PAGED)
def test_a_row_is_mapped_before_its_dispatch_and_a_dropped_row_costs_no_page(
        lane):
    """Write positions that open a page, with a step in flight: every row
    that will be delivered has its page when its step is dispatched. The
    second request's last token lands on a page's last row, so the row
    after it would open a page: it ends by length, the row is known to be
    dropped, and the pool hands out the pages the serial loop hands out."""
    n0, n1 = lane.prompts[0], lane.prompts[1]
    plan = [dict(prompt=lane.prompt(n0), max_new_tokens=30, at=0),
            dict(prompt=lane.prompt(n1, seed=1),
                 max_new_tokens=PAGE + (1 - n1) % PAGE, at=0)]
    assert (n1 + plan[1]["max_new_tokens"] - 1) % PAGE == 0
    allocs = []
    for serial in (False, True):
        b, _ = lane.batcher()
        drive, unmapped = Drive(b, serial=serial), []

        def checked(ahead, b=b, counted=b._dispatch_step, unmapped=unmapped):
            for slot, req in b._reqs.items():
                behind = b._behind(ahead, slot, req)
                if b.kv.mapped_tokens(slot) < req.seq_len + behind:
                    assert behind and b._ends_by_length(req)
                    unmapped.append(req)
            return counted(ahead)

        b._dispatch_step = checked
        reqs = drive.run(plan)
        assert [len(r.tokens) for r in reqs] == [
            30, plan[1]["max_new_tokens"]]
        allocs.append(b.kv.pool.total_allocs)
        if not serial:      # the row behind the second request's last
            assert unmapped == [reqs[1]]
    assert allocs[0] == allocs[1]


DRY = {   # pages of the pool; (prompt, tokens) of the old and the young one
    "paged": (10, (12, 40), (10, 40)),
    "lfm2": (8, (12, 30), (10, 30)),
}


@lanes(PAGED)
def test_the_pool_runs_dry_with_a_step_in_flight(lane):
    """Two requests that outgrow the pool together. The step in flight is
    settled before anyone loses a page, so the youngest is evicted where the
    serial loop evicts it, at the old one's same length, with every token
    it was served up to there, and the old one is served to its end. On the
    GPT lane a prefix entry nobody holds goes first."""
    pages, old, young = DRY[lane.name]
    over = dict(num_slots=2, num_pages=pages)
    plan = [dict(prompt=lane.prompt(n, seed=i), max_new_tokens=m, at=0)
            for i, (n, m) in enumerate((old, young))]
    if lane.name == "paged":    # a finished request leaves its head cached
        over.update(prefix_cache=True)
        plan.insert(0, dict(prompt=lane.prompt(16, seed=7),
                            max_new_tokens=2, at=0))
        plan[1]["at"] = plan[2]["at"] = 3
    (ahead, reg), (serial, sreg) = _both(lane, plan, **over)
    assert ahead[:-1] == serial[:-1]
    assert ahead[-2][1] == "length" and len(ahead[-2][0]) == old[1]
    # the young one joined behind a step in flight, so it may be a token
    # behind the serial loop's when the old one's next page is missing
    alone = _alone(lane, plan[-1]["prompt"], young[1], **over)
    for got in (ahead[-1], serial[-1]):
        tokens, how, stream = got
        assert how == "PagesExhausted" and 0 < len(tokens) < young[1]
        assert tokens == alone[:len(tokens)]
        assert stream == tokens + [how, "end"]
    assert 0 <= len(serial[-1][0]) - len(ahead[-1][0]) <= 1
    for r in (reg, sreg):
        assert r.get(PRE + "pages_evicted_midstream") == 1
        if lane.name == "paged":    # the finished request's cached head
            assert r.get(PRE + "prefix.evictions") >= 1
    assert reg.get(PRE + "ticks_settled_early") >= 1
    assert reg.get(PRE + "ticks_overlapped") > 10


# -- (e) the dropped row ------------------------------------------------------

def _watch_dropped_rows(b):
    """Before every dispatch, where each slot's row will be written (from the
    device's own lengths and block tables); at every fetch, and when a step
    is dropped, the rows whose request had ended by then. Returns the list
    they are appended to."""
    kv, log, seen = b.kv, {}, []
    dispatch, finish = b._dispatch_step, b._finish_step

    def watched_dispatch(ahead):
        lengths = kv.host_lengths()
        tables = np.asarray(kv.block_tables)
        rows = {}
        for slot in range(kv.num_slots):
            pos = int(lengths[slot])
            pid = int(tables[slot, min(pos // PAGE, kv.pages_per_seq - 1)])
            rows[slot] = dict(
                pos=pos, pid=pid, own=pid in kv.slot_page_ids(slot),
                holders=0 if pid == kv.trash else kv.pool.refcount(pid))
            if slot not in b._reqs and slot not in b._prefilling:
                assert (tables[slot] == kv.trash).all()
        step = dispatch(ahead)
        log[id(step)] = (step, rows)
        return step

    def watched_finish(step):
        for slot, req in step.reqs.items():
            if b._reqs.get(slot) is not req:
                seen.append(dict(log[id(step)][1][slot], req=req))
        return finish(step)

    def watched_drop():     # nobody is left: every row of it is dropped
        step = drop()
        if step is not None:
            seen.extend(dict(log[id(step)][1][slot], req=req)
                        for slot, req in step.reqs.items())
        return step

    drop = b._drop_inflight
    b._dispatch_step, b._finish_step = watched_dispatch, watched_finish
    b._drop_inflight = watched_drop
    return seen


@lanes(PAGED)
def test_a_row_of_an_ended_request_writes_its_own_page_or_the_trash_page(
        lane):
    """Requests that end at ``max_seq``, at their length and, on the GPT
    lane, beside a request that shares their prompt's first pages through
    the prefix store: the one row computed behind each one's end lies inside
    the slot's table (``paged_row_index`` would clip a position of
    ``max_seq`` into the last page) and in a page no one else holds."""
    over = {}
    head = lane.prompt(16, seed=5)
    plan = [dict(prompt=lane.prompt(lane.long_prompt, seed=6),
                 max_new_tokens=200, at=0),
            dict(prompt=np.concatenate([head, lane.prompt(3, seed=1)]),
                 max_new_tokens=5, at=0),
            dict(prompt=np.concatenate([head, lane.prompt(4, seed=2)]),
                 max_new_tokens=25, at=1),
            dict(prompt=np.concatenate([head, lane.prompt(2, seed=3)]),
                 max_new_tokens=3, at=3)]
    if lane.name == "paged":
        over.update(prefix_cache=True)
    if lane.name == "lfm2":     # one bucket of 16: no room behind the head
        for spec in plan[1:]:
            spec["prompt"] = spec["prompt"][4:]
    b, reg = lane.batcher(**over)
    drive = Drive(b)
    dropped = _watch_dropped_rows(b)
    reqs = drive.run(plan)
    assert len(reqs[0].tokens) == lane.max_seq - lane.long_prompt
    assert [len(r.tokens) for r in reqs[1:]] == [5, 25, 3]
    # one row behind each one's end
    assert sorted(id(d["req"]) for d in dropped) == sorted(map(id, reqs))
    for d in dropped:
        assert d["pos"] < lane.max_seq
        assert d["pid"] == b.kv.trash or (d["own"] and d["holders"] == 1)
    by_req = {id(d["req"]): d for d in dropped}
    assert by_req[id(reqs[0])]["pos"] == lane.max_seq - 1
    if lane.name == "paged":
        assert reg.get(PRE + "prefix.hits") >= 2    # pages were shared
    # and nobody's stream shows it
    serial, _ = lane.batcher(**over)
    again = Drive(serial, serial=True).run(plan)
    assert [list(r.tokens) for r in again] == [list(r.tokens) for r in reqs]


# -- settle, admission, chunks ------------------------------------------------

@lanes()
def test_settle_makes_the_hosts_lengths_the_devices(lane):
    b, reg = lane.batcher()
    reqs = [_request(lane.prompt(n, seed=i), max_new_tokens=20)
            for i, n in enumerate(lane.prompts[:2])]
    for r in reqs:
        b.admit(r)
    for _ in range(3):
        b.tick()
    assert b._inflight is not None
    had = [len(r.tokens) for r in reqs]
    b.settle()
    assert b._inflight is None
    assert [len(r.tokens) for r in reqs] == [n + 1 for n in had]
    assert reg.get(PRE + "ticks_settled_early") == 1
    lengths = np.asarray(b.kv.lengths)
    for slot, req in b._reqs.items():
        assert lengths[slot] == req.seq_len - 1     # its last token's row
    b.settle()                                      # nothing left: no-op
    assert reg.get(PRE + "ticks_settled_early") == 1
    ticks = reg.histogram(PRE + "decode_tick_ms")["count"]
    assert ticks == max(had) and reg.get(PRE + "tokens_generated") == sum(
        len(r.tokens) for r in reqs)
    b.abort_all(lambda req: RuntimeError("stopped"))
    assert b.active == 0 and b._inflight is None


@lanes(("sala",))
def test_an_engine_that_prefills_in_chunks_keeps_the_tick_serial(lane):
    """Chunked prefill is one chunk between two ticks, and its histogram is
    defined against a loop that fetches what it dispatched: such a batcher
    never has a step in flight, whoever joins or leaves, and every chunk
    gives its sample."""
    b, reg = lane.batcher()
    drive = Drive(b)
    reqs = drive.run(_plan(lane, max_new=(9, 4, 12, 6, 7),
                           at=(0, 0, 2, 5, 9)),
                     before_tick=lambda d: d.b._inflight is None or 1 / 0)
    assert [len(r.tokens) for r in reqs] == [9, 4, 12, 6, 7]
    ticks = reg.histogram(PRE + "decode_tick_ms")["count"]
    assert drive.steps == ticks > 10
    assert reg.get(PRE + "ticks_overlapped") == 0
    assert reg.get(PRE + "ticks_settled_early") == 0
    chunks = sum(-(-n // 8) for n in lane.prompts)
    assert reg.get(PRE + "prefill_chunks") == chunks \
        == reg.histogram(PRE + "prefill_chunk_ms")["count"]


# -- (f) from outside the tick ------------------------------------------------

def _wait_for(pred, timeout=60.0):
    end = time.monotonic() + timeout
    while time.monotonic() < end and not pred():
        time.sleep(0.01)
    return pred()


def _alone(lane, prompt, n, **over):
    b, _ = lane.batcher(**over)
    (req,) = Drive(b, serial=True).run(
        [dict(prompt=prompt, max_new_tokens=n, at=0)])
    return list(req.tokens)


@lanes(("paged",))
def test_export_and_import_settle_the_step_in_flight(lane):
    prompt, n = lane.prompt(8), 40
    ref = _alone(lane, prompt, n)
    a, b = lane.engine(num_slots=2), lane.engine(num_slots=2)
    try:
        req = a.submit(prompt, max_new_tokens=n, stream=True)
        assert _wait_for(lambda: len(req.tokens) >= 3)
        a.pause_admission()
        (man,) = a.export_sequences(timeout=30)
        # the cache shipped holds what the client has, less the newest
        # token: no step was left dispatched and undelivered
        assert man.n_cached_tokens == len(prompt) + len(man.tokens) - 1
        assert a._batcher._inflight is None
        assert a.stats()["stats"][PRE + "ticks_settled_early"] >= 1
        assert b.import_sequence(man, timeout=30)
        assert list(req.iter_tokens(timeout=120)) == ref
    finally:
        a.drain(timeout=30)
        b.drain(timeout=30)


@lanes(("slot", "paged"))
def test_a_weight_swap_finds_nothing_in_flight(lane):
    prompt = lane.prompt(8)
    # a model of its own (the lane's weights, seed 0): the swap rewrites it
    eng = LLMEngine(_gpt(), lane.config(), registry=StatRegistry())
    other = _gpt(seed=1)
    try:
        long = eng.submit(lane.prompt(6, seed=1), max_new_tokens=30)
        before = eng.generate(prompt, max_new_tokens=8)["tokens"]
        eng.pause_admission()
        assert eng.swap_weights(other.state_dict(), timeout=60) == 1
        # the swap saw the batch empty; the worker drops the step behind
        # the last request's end in the same breath
        assert _wait_for(lambda: eng._batcher._inflight is None)
        eng.resume_admission()
        after = eng.generate(prompt, max_new_tokens=8)
        assert after["weights_version"] == 1
        assert len(long.result(timeout=0)["tokens"]) == 30
        assert long.result()["weights_version"] == 0
    finally:
        eng.drain(timeout=30)
    assert before == _alone(lane, prompt, 8)
    fresh = LLMEngine(other, lane.config(), registry=StatRegistry())
    try:
        assert fresh.generate(prompt, max_new_tokens=8)["tokens"] \
            == after["tokens"] != before
    finally:
        fresh.drain(timeout=30)


@lanes(ALL)
def test_a_kill_with_recovery_loses_and_repeats_no_token(lane):
    prompt, n = lane.prompt(9), 24
    ref = _alone(lane, prompt, n)
    a, b = lane.engine(), lane.engine()
    try:
        a.enable_recovery()
        req = a.submit(prompt, max_new_tokens=n, stream=True)
        assert _wait_for(lambda: len(req.tokens) >= 4)
        a.kill("test kill")
        assert a._stopped.wait(timeout=30)
        assert a._batcher._inflight is None
        (evacuated,) = a.take_evacuated()
        assert evacuated is req and not req.future.done()
        had = list(req.tokens)
        assert had == ref[:len(had)]
        rec = a.journal.lookup(req.req_id)
        assert b.resubmit_for_recovery(
            req, rec.tokens if rec is not None else [])
        assert req.result(timeout=120)["tokens"] == ref
        assert list(req.iter_tokens(timeout=5)) == ref      # each once
    finally:
        a.drain(timeout=30)
        b.drain(timeout=30)


@lanes(ALL)
def test_a_drain_serves_everything_and_leaves_nothing_in_flight(lane):
    eng = lane.engine()
    plan = [(lane.prompt(n, seed=i), m) for i, (n, m) in enumerate(
        zip(lane.prompts, (9, 4, 12, 6, 7)))]
    reqs = [eng.submit(p, max_new_tokens=m, stream=True) for p, m in plan]
    eng.drain(timeout=240)
    assert eng._batcher._inflight is None and eng._batcher.active == 0
    for req, (p, m) in zip(reqs, plan):
        assert list(req.iter_tokens(timeout=0)) == req.result(0)["tokens"] \
            == _alone(lane, p, m)
    st = eng.stats()
    assert st["stats"][PRE + "completed"] == 5
    if lane.name == "sala":
        assert st["tick_overlap_share"] == 0
    else:
        assert 0 < st["tick_overlap_share"] <= 1


# -- (g) the speculative tick -------------------------------------------------

@pytest.mark.parametrize("layout", ("slot", "paged"))
def test_the_speculative_tick_stays_serial(layout):
    over = dict(kv_layout=layout, page_size=PAGE) if layout == "paged" else {}
    # the model drafts for itself: every candidate is accepted, three
    # tokens a tick
    eng = LLMEngine(_gpt(), LLMEngineConfig(
        num_slots=3, max_seq=64, prefill_buckets=(8, 16, 40), warmup=False,
        spec_k=2, **over), registry=StatRegistry(), draft_model=_gpt())
    try:
        reqs = [eng.submit(np.arange(1, 6 + i), max_new_tokens=100)
                for i in range(3)]
        # to the end of their 64 rows: a prompt of 5 stands at 63 tokens
        # with no room for k+1 candidates, and the plain step it falls back
        # to stays serial too
        out = [r.result(timeout=240)["tokens"] for r in reqs]
        assert [len(t) for t in out] == [59, 58, 57]
        st = eng.stats()
        assert eng._batcher._inflight is None
        assert st["stats"].get(PRE + "ticks_overlapped", 0) == 0
        assert st["stats"].get(PRE + "ticks_settled_early", 0) == 0
        assert st["stats"][PRE + "spec.ticks"] > 0
        assert st["stats"][PRE + "spec.fallback_ticks"] > 0
        assert st["tick_overlap_share"] == 0.0
    finally:
        eng.drain(timeout=60)


# -- (h) the counters ---------------------------------------------------------

@lanes()
def test_a_full_batch_overlaps_every_tick_but_its_first(lane):
    """Three requests on three slots from the start: of the ticks all but
    the first follow a step that was unfetched when they were dispatched;
    ``tokens_generated`` less ``prefills`` counts the rows delivered (a row
    behind a request's end is not one), one ``decode_tick_ms`` sample a
    tick; the one step never fetched is the one behind the last end."""
    new = (21, 17, 21)
    b, reg = lane.batcher()
    drive = Drive(b)
    reqs = drive.run(_plan(lane, max_new=new, at=(0, 0, 0)))
    assert [len(r.tokens) for r in reqs] == list(new)
    ticks = reg.histogram(PRE + "decode_tick_ms")["count"]
    assert ticks == reg.histogram(PRE + "tpot_ms")["count"]
    assert ticks == 20
    assert drive.steps == ticks + 1     # the one step never fetched
    assert reg.get(PRE + "ticks_overlapped") == ticks - 1
    assert reg.get(PRE + "ticks_settled_early") == 1
    assert reg.get(PRE + "prefills") == 3
    assert reg.get(PRE + "tokens_generated") - 3 == sum(n - 1 for n in new)
    if lane.name != "slot":
        # a table row for every row dispatched: a request's delivered rows
        # and the one behind its end
        assert reg.get(PRE + "paged_attn.pages_table") == (
            sum(new) * (lane.max_seq // PAGE))


@lanes(("paged",))
def test_the_engine_reports_the_share_of_ticks_that_overlapped(lane):
    eng = lane.engine()
    try:
        assert eng.stats()["tick_overlap_share"] is None
        reqs = [eng.submit(lane.prompt(6, seed=i), max_new_tokens=40)
                for i in range(3)]
        for r in reqs:
            assert len(r.result(timeout=120)["tokens"]) == 40
        st = eng.stats()
        ticks = st["histograms"][PRE + "decode_tick_ms"]["count"]
        assert st["tick_overlap_share"] == (
            st["stats"][PRE + "ticks_overlapped"] / ticks)
        assert st["tick_overlap_share"] > 0.8
    finally:
        eng.drain(timeout=30)
