"""The one traffic generator: stratified draws repeat their marginals for
every seed, and the serving drivers count latency from the due time and a
request that fails or does not finish as failed."""
import threading

import numpy as np
import pytest

from benchmark import spec, traffic
from benchmark.drivers import serving

OPEN = spec.load_cell(spec.load_benchmark(),
                      "serve-gpt1p3b-longprompt")["traffic_data"]
CLOSED = spec.load_cell(spec.load_benchmark(),
                        "serve-gpt1p3b-decode")["traffic_data"]
BIG = 2**31 + 12345   # the driver's seeds do not fit 32 signed bits


def _lens(reqs):
    return (sorted(len(r["prompt"]) for r in reqs),
            sorted(r["max_new_tokens"] for r in reqs),
            np.sort(np.diff([r["due_s"] for r in reqs])))


@pytest.mark.parametrize("seed_a,seed_b", [(1, 2), (7, BIG)])
def test_every_seed_draws_the_same_marginals(seed_a, seed_b):
    n = round(OPEN["rate_per_s"] * 51)
    a = traffic.open_requests(OPEN, seed_a, 51, 50257)
    b = traffic.open_requests(OPEN, seed_b, 51, 50257)
    assert len(a) == len(b) == n
    (pa, oa, ga), (pb, ob, gb) = _lens(a), _lens(b)
    # value i is the quantile at (i + u) / n: two seeds differ by less
    # than one stratum of the declared distribution
    p, o = OPEN["prompt_len"], OPEN["output_len"]
    assert max(abs(x - y) for x, y in zip(pa, pb)) \
        <= (p["hi"] - p["lo"] + 1) / n + 1
    assert max(abs(x - y) for x, y in zip(oa, ob)) \
        <= (o["hi"] - o["lo"] + 1) / n + 1
    assert min(pa) >= p["lo"] and max(pa) <= p["hi"]
    # same total of gaps to within the last stratum's tail
    assert abs(ga[:-1].sum() - gb[:-1].sum()) < 0.1 * ga.sum()


def test_seeds_differ_in_order_and_content_and_one_seed_repeats():
    a = traffic.open_requests(OPEN, 5, 51, 50257)
    b = traffic.open_requests(OPEN, 6, 51, 50257)
    again = traffic.open_requests(OPEN, 5, 51, 50257)
    assert [len(r["prompt"]) for r in a] != [len(r["prompt"]) for r in b]
    assert [r["due_s"] for r in a] == [r["due_s"] for r in again]
    assert all(np.array_equal(x["prompt"], y["prompt"])
               for x, y in zip(a, again))
    assert not np.array_equal(a[0]["prompt"][:32], b[0]["prompt"][:32])
    assert all(x["due_s"] <= y["due_s"] for x, y in zip(a, a[1:]))


def test_closed_loop_blocks_are_stratified_per_client():
    per_client = traffic.closed_requests(CLOSED, BIG, 50257, count=8)
    assert len(per_client) == CLOSED["clients"]
    lo, hi = CLOSED["output_len"]["lo"], CLOSED["output_len"]["hi"]
    width = (hi - lo + 1) / CLOSED["block"]
    for reqs in per_client:
        for start in range(0, 8, CLOSED["block"]):
            outs = sorted(r["max_new_tokens"]
                          for r in reqs[start:start + CLOSED["block"]])
            for i, v in enumerate(outs):    # one value per stratum
                assert lo + i * width - 1 <= v <= lo + (i + 1) * width
    assert per_client == [] or not np.array_equal(
        per_client[0][0]["prompt"][:16], per_client[1][0]["prompt"][:16])


@pytest.mark.parametrize("spec_", [
    {"dist": "constant", "value": 3.0},
    {"dist": "uniform_int", "lo": 520, "hi": 1024},
    {"dist": "exponential", "mean": 2.0},
])
def test_quantiles_are_monotone_and_keep_their_mean(spec_):
    q = (np.arange(4000) + 0.5) / 4000
    v = traffic.quantile(spec_, q)
    assert np.all(np.diff(v) >= 0)
    if "mean" in spec_:
        assert abs(v.mean() - spec_["mean"]) < 0.03 * spec_["mean"]
    if "lo" in spec_:
        assert v.min() == spec_["lo"] and v.max() == spec_["hi"]


def test_a_distribution_the_generator_does_not_know_is_refused():
    with pytest.raises(ValueError, match="unknown distribution"):
        traffic.quantile({"dist": "weibull", "mean": 1.0, "shape": 0.6},
                         np.array([0.5]))


def test_every_distribution_a_traffic_file_declares_is_known():
    for w in spec.load_benchmark()["workloads"]:
        tr = spec.load_cell(spec.load_benchmark(), w["name"])["traffic_data"]
        for block in (tr, tr.get("rehearsal", {})):
            for v in block.values():
                if isinstance(v, dict) and "dist" in v:
                    traffic.quantile(v, np.array([0.0, 0.5]))


# -- what the drivers count ----------------------------------------------------

class _Request:
    def __init__(self, tokens, fail_after=None):
        self.tokens, self.fail_after = tokens, fail_after

    def iter_tokens(self, timeout=None):
        for i, t in enumerate(self.tokens):
            if self.fail_after is not None and i >= self.fail_after:
                raise RuntimeError("evicted")
            yield t


class _Engine:
    """Refuses prompts of length 1, cuts prompts of length 2 short."""

    def submit(self, prompt, max_new_tokens, stream):
        if len(prompt) == 1:
            raise ValueError("refused")
        return _Request(list(range(max_new_tokens)),
                        fail_after=1 if len(prompt) == 2 else None)


def _served():
    s = object.__new__(serving.Served)
    s.engine, s.records, s.lock = _Engine(), [], threading.Lock()
    s.tr = {"request_timeout_s": 1}
    s.closing = False
    return s


def test_latency_counts_from_the_due_time_not_from_submission():
    s = _served()
    rec = s.send({"prompt": np.arange(5), "max_new_tokens": 3}, due=-2.0,
                 timed=True)
    lat = serving.latencies({"records": s.records})
    assert rec["finished"] and len(rec["times"]) == 3
    # due 2 s before the benchmark's clock started: at least 2000 ms
    assert lat["ttft_ms"][0] >= 2000.0
    assert lat["ttft_ms"][0] == pytest.approx(
        (rec["times"][0] - rec["due"]) * 1e3)
    assert len(lat["itl_ms"]) == 2


@pytest.mark.parametrize("prompt_len,why", [(1, "refused"), (2, "evicted")])
def test_a_refused_or_unfinished_request_is_failed_and_has_no_latency(
        prompt_len, why):
    s = _served()
    rec = s.send({"prompt": np.arange(prompt_len), "max_new_tokens": 4},
                 due=0.0, timed=True)
    assert not rec["finished"] and why in rec["error"]
    assert sum(1 for r in s.records if not r["finished"]) == 1
    assert serving.latencies({"records": s.records}) == {"ttft_ms": [],
                                                         "itl_ms": []}


def test_a_request_cut_by_the_window_end_is_neither_attempted_nor_failed():
    s = _served()
    req = {"prompt": np.arange(2), "max_new_tokens": 4}
    failed = s.send(req, due=0.0, timed=True)      # a fault inside the window
    s.closing = True                               # the window has closed
    cut = s.send(req, due=0.0, timed=True)
    assert failed["error"] and not failed["cut"]
    assert cut["cut"] and cut["error"] is None and not cut["finished"]
    assert len(cut["tokens"]) == 1      # its tokens still count in the window
