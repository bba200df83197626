"""paddle_tpu.serving: dynamic batching, shape buckets, executable cache,
deadlines/backpressure, graceful drain, and the end-to-end acceptance run
(64 concurrent mixed-size requests, bitwise vs the serial Predictor)."""
import signal
import threading
import time

import numpy as np
import pytest

import paddle_tpu as paddle
import paddle_tpu.nn as nn
from paddle_tpu.core.monitor import StatRegistry
from paddle_tpu.distributed.elastic import PreemptionGuard
from paddle_tpu.inference import Config, create_predictor
from paddle_tpu.serving import (
    BatchQueue, BucketSpec, DynamicBatcher, Engine, EngineConfig,
    EngineDraining, ExecutableCache, InferenceRequest, QueueFull,
    RequestTooLarge, pow2_buckets)
from paddle_tpu.serving.batcher import Batch
from paddle_tpu.serving.buckets import pad_rows, pad_seq, unpad_rows
from paddle_tpu.static import InputSpec
from paddle_tpu.utils.resilience import Deadline, DeadlineExceeded


def _identity_model(*arrays):
    return [np.asarray(a) * 2.0 for a in arrays]


def _mk_engine(model=_identity_model, **cfg):
    cfg.setdefault("max_batch", 8)
    cfg.setdefault("max_batch_delay", 0.01)
    return Engine(model, EngineConfig(**cfg), registry=StatRegistry())


# ---------------------------------------------------------------------------
class TestBuckets:
    def test_pow2(self):
        assert pow2_buckets(16) == (1, 2, 4, 8, 16)
        assert pow2_buckets(12) == (1, 2, 4, 8, 12)

    def test_bucket_for(self):
        spec = BucketSpec(max_batch=16)
        assert spec.batch_bucket_for(1) == 1
        assert spec.batch_bucket_for(5) == 8
        assert spec.batch_bucket_for(16) == 16
        assert spec.batch_bucket_for(17) is None

    def test_seq_buckets(self):
        spec = BucketSpec(max_batch=8, seq_buckets=[16, 64])
        assert spec.seq_bucket_for(5) == 16
        assert spec.seq_bucket_for(64) == 64
        assert spec.seq_bucket_for(100) == 100  # above the largest: as-is
        assert BucketSpec(max_batch=8).seq_bucket_for(7) == 7

    def test_pad_unpad_roundtrip(self):
        x = np.arange(12, dtype=np.float32).reshape(3, 4)
        padded = pad_rows([x], 8)[0]
        assert padded.shape == (8, 4)
        assert np.array_equal(padded[:3], x)
        assert not padded[3:].any()
        assert np.array_equal(unpad_rows([padded], 3)[0], x)

    def test_pad_seq(self):
        x = np.ones((2, 5), np.float32)
        y = pad_seq([x], 16)[0]
        assert y.shape == (2, 16)
        assert y[:, :5].all() and not y[:, 5:].any()
        # rank-1 arrays (e.g. lengths) are left alone
        lens = np.array([5, 5])
        assert pad_seq([lens], 16)[0] is lens


# ---------------------------------------------------------------------------
class TestExecutableCache:
    def test_hit_miss_counters(self):
        c = ExecutableCache(capacity=4)
        calls = []
        f = c.get_or_compile("k1", lambda: calls.append(1) or "exe1")
        assert f == "exe1" and c.misses == 1 and c.hits == 0
        f = c.get_or_compile("k1", lambda: calls.append(1) or "exe1b")
        assert f == "exe1" and c.hits == 1 and len(calls) == 1

    def test_lru_eviction(self):
        c = ExecutableCache(capacity=2)
        c.get_or_compile("a", lambda: "A")
        c.get_or_compile("b", lambda: "B")
        c.get_or_compile("a", lambda: "A")   # refresh a
        c.get_or_compile("c", lambda: "C")   # evicts b (LRU)
        assert c.evictions == 1
        assert c.contains("a") and c.contains("c") and not c.contains("b")

    def test_stats_shape(self):
        s = ExecutableCache().stats()
        assert set(s) == {"size", "capacity", "hits", "misses", "evictions"}


# ---------------------------------------------------------------------------
class TestMonitorHistogram:
    def test_observe_quantile(self):
        reg = StatRegistry()
        for v in range(1, 101):
            reg.observe("lat", float(v))
        assert reg.quantile("lat", 0.5) == pytest.approx(50.5)
        assert reg.quantile("lat", 0.99) == pytest.approx(99.01)
        s = reg.histogram("lat")
        assert s["count"] == 100 and s["min"] == 1.0 and s["max"] == 100.0

    def test_bounded_reservoir(self):
        reg = StatRegistry()
        for v in range(10):
            reg.observe("x", float(v), max_samples=4)
        s = reg.histogram("x")
        assert s["count"] == 10          # all-time count
        assert s["min"] == 0.0
        assert reg.quantile("x", 0.0) == 6.0  # window kept newest 4

    def test_missing_and_reset(self):
        reg = StatRegistry()
        assert reg.quantile("nope", 0.5, default=-1.0) == -1.0
        reg.observe("y", 3.0)
        reg.reset("y")
        assert reg.histogram("y")["count"] == 0

    def test_module_level_helpers(self):
        from paddle_tpu.core.monitor import stat_observe, stat_quantile
        stat_observe("test.serving.hist", 7.0)
        assert stat_quantile("test.serving.hist", 0.5) == 7.0


# ---------------------------------------------------------------------------
class TestBatchQueue:
    def test_fifo_and_fits(self):
        q = BatchQueue(max_size=4)
        a = InferenceRequest([np.zeros((2, 3))])
        b = InferenceRequest([np.zeros((5, 3))])
        q.put(a)
        q.put(b)
        got = q.take(timeout=0.1, fits=lambda r: r.nrows <= 2)
        assert got is a
        # head b does not fit: stays queued, take returns None
        assert q.take(timeout=0.05, fits=lambda r: r.nrows <= 2) is None
        assert len(q) == 1

    def test_admission_reject_when_full(self):
        q = BatchQueue(max_size=1)
        q.put(InferenceRequest([np.zeros((1, 1))]))
        with pytest.raises(QueueFull):
            q.put(InferenceRequest([np.zeros((1, 1))]), block=False)
        with pytest.raises(QueueFull):
            q.put(InferenceRequest([np.zeros((1, 1))]), timeout=0.05)

    def test_close_unblocks_putter(self):
        q = BatchQueue(max_size=1)
        q.put(InferenceRequest([np.zeros((1, 1))]))
        errs = []

        def blocked_put():
            try:
                q.put(InferenceRequest([np.zeros((1, 1))]), timeout=5.0)
            except EngineDraining as e:
                errs.append(e)

        t = threading.Thread(target=blocked_put)
        t.start()
        time.sleep(0.05)
        q.close()
        t.join(2.0)
        assert len(errs) == 1

    def test_deadline_eviction_at_head(self):
        q = BatchQueue(max_size=4)
        dead = InferenceRequest([np.zeros((1, 1))], deadline=Deadline(0))
        live = InferenceRequest([np.zeros((1, 1))])
        q.put(dead)
        q.put(live)
        got = q.take(timeout=0.1)
        assert got is live
        assert q.evicted_expired == 1
        with pytest.raises(DeadlineExceeded):
            dead.future.result(0)


# ---------------------------------------------------------------------------
class TestDynamicBatcher:
    def test_empty_queue_timeout_flush(self):
        q = BatchQueue()
        b = DynamicBatcher(q, BucketSpec(max_batch=8), max_batch_delay=0.005)
        t0 = time.monotonic()
        assert b.next_batch(timeout=0.05) is None
        assert time.monotonic() - t0 < 1.0

    def test_coalesces_and_buckets(self):
        q = BatchQueue()
        for n in (2, 3, 1):
            q.put(InferenceRequest([np.zeros((n, 4))]))
        b = DynamicBatcher(q, BucketSpec(max_batch=8), max_batch_delay=0.05)
        batch = b.next_batch(timeout=0.1)
        assert len(batch.requests) == 3 and batch.rows == 6
        assert batch.bucket_rows == 8 and not batch.oversize
        assert batch.fill_ratio == pytest.approx(6 / 8)

    def test_stops_at_max_bucket(self):
        q = BatchQueue()
        for n in (6, 6):
            q.put(InferenceRequest([np.zeros((n, 4))]))
        b = DynamicBatcher(q, BucketSpec(max_batch=8), max_batch_delay=0.05)
        batch = b.next_batch(timeout=0.1)
        assert [r.nrows for r in batch.requests] == [6]
        assert batch.bucket_rows == 8
        assert len(q) == 1  # second request left for the next batch

    def test_oversize_flag(self):
        q = BatchQueue()
        q.put(InferenceRequest([np.zeros((20, 4))]))
        b = DynamicBatcher(q, BucketSpec(max_batch=8), max_batch_delay=0.0)
        batch = b.next_batch(timeout=0.1)
        assert batch.oversize and batch.bucket_rows is None


# ---------------------------------------------------------------------------
class TestEngine:
    def test_submit_and_result(self):
        eng = _mk_engine()
        x = np.arange(6, dtype=np.float32).reshape(3, 2)
        out, = eng.submit([x]).result(10)
        assert np.array_equal(out, x * 2.0)
        eng.drain()

    def test_submit_many(self):
        eng = _mk_engine()
        xs = [[np.full((n, 2), float(n), np.float32)] for n in (1, 2, 3)]
        futs = eng.submit_many(xs)
        for n, f in zip((1, 2, 3), futs):
            out, = f.result(10)
            assert out.shape == (n, 2) and np.all(out == 2.0 * n)
        eng.drain()

    def test_oversize_split_matches(self):
        eng = _mk_engine(max_batch=4, oversize_policy="split")
        x = np.random.RandomState(0).randn(11, 3).astype(np.float32)
        out, = eng.submit([x]).result(10)
        assert np.array_equal(out, x * 2.0)
        assert eng.registry.get("serving.oversize_splits") == 1
        eng.drain()

    def test_oversize_reject(self):
        eng = _mk_engine(max_batch=4, oversize_policy="reject")
        with pytest.raises(RequestTooLarge):
            eng.submit([np.zeros((5, 3), np.float32)])
        eng.drain()

    def test_deadline_expired_request_evicted(self):
        release = threading.Event()

        def slow_model(x):
            release.wait(5.0)
            return [np.asarray(x)]

        eng = _mk_engine(model=slow_model, max_batch=1, max_batch_delay=0.0)
        f_block = eng.submit([np.zeros((1, 2), np.float32)])
        time.sleep(0.05)  # worker is now stuck inside slow_model
        f_dead = eng.submit([np.zeros((1, 2), np.float32)], deadline=0.01)
        time.sleep(0.1)   # deadline passes while queued
        release.set()
        with pytest.raises(DeadlineExceeded):
            f_dead.result(10)
        assert f_block.result(10)[0].shape == (1, 2)
        eng.drain()

    def test_drain_with_inflight_returns_all_futures(self):
        def slow_model(x):
            time.sleep(0.03)
            return [np.asarray(x) * 2.0]

        eng = _mk_engine(model=slow_model, max_batch=1, max_batch_delay=0.0)
        futs = [eng.submit([np.full((1, 2), i, np.float32)])
                for i in range(6)]
        inflight = eng.drain(timeout=30)
        assert len(inflight) >= 1          # drain began with work in flight
        assert all(f.done() for f in futs)
        for i, f in enumerate(futs):
            assert np.all(f.result(0)[0] == 2.0 * i)
        with pytest.raises(EngineDraining):
            eng.submit([np.zeros((1, 2), np.float32)])

    def test_preemption_guard_triggers_drain(self):
        eng = _mk_engine()
        guard = PreemptionGuard(install=False)
        eng.arm_preemption(guard)
        f = eng.submit([np.ones((2, 2), np.float32)])
        f.result(10)
        guard.preempt()
        assert eng._stopped.wait(10)
        assert eng.draining
        assert eng.registry.get("serving.preemption_drains") == 1

    def test_queue_full_backpressure(self):
        release = threading.Event()

        def slow_model(x):
            release.wait(5.0)
            return [np.asarray(x)]

        eng = _mk_engine(model=slow_model, max_batch=1, max_batch_delay=0.0,
                         max_queue=1, admission_block=False)
        eng.submit([np.zeros((1, 1), np.float32)])
        time.sleep(0.05)  # worker busy; next two fill + overflow the queue
        eng.submit([np.zeros((1, 1), np.float32)])
        with pytest.raises(QueueFull):
            eng.submit([np.zeros((1, 1), np.float32)])
        assert eng.registry.get("serving.rejected_queue_full") == 1
        release.set()
        eng.drain()


# ---------------------------------------------------------------------------
class TestSignalChaining:
    """Regression: serving drain + elastic PreemptionGuard must chain, not
    clobber, each other's signal handlers (either install order)."""

    SIG = signal.SIGUSR1

    def test_guard_then_engine(self):
        original = signal.getsignal(self.SIG)
        eng = _mk_engine()
        guard = PreemptionGuard(signals=(self.SIG,))
        chain = eng.install_drain_signal_handler(signals=(self.SIG,))
        try:
            signal.raise_signal(self.SIG)
            assert guard.preempted           # earlier handler still fired
            assert eng.draining              # new handler fired too
        finally:
            chain.uninstall()
            guard.uninstall()
            eng.drain()
        assert signal.getsignal(self.SIG) == original

    def test_engine_then_guard(self):
        original = signal.getsignal(self.SIG)
        eng = _mk_engine()
        chain = eng.install_drain_signal_handler(signals=(self.SIG,))
        guard = PreemptionGuard(signals=(self.SIG,))
        try:
            signal.raise_signal(self.SIG)
            assert guard.preempted
            assert eng.draining
        finally:
            guard.uninstall()
            chain.uninstall()
            eng.drain()
        assert signal.getsignal(self.SIG) == original


# ---------------------------------------------------------------------------
class TestServingE2E:
    """Acceptance: >= 64 concurrent mixed-size requests through Engine are
    bitwise-identical to serial Predictor.run, with coalescing, zero
    executable-cache misses after warmup, and live /statsz percentiles."""

    def _export(self, tmp_path):
        paddle.seed(0)

        class Net(nn.Layer):
            def __init__(self):
                super().__init__()
                self.fc1 = nn.Linear(6, 16)
                self.fc2 = nn.Linear(16, 5)

            def forward(self, x):
                return nn.functional.softmax(
                    self.fc2(nn.functional.relu(self.fc1(x))), axis=-1)

        net = Net()
        prefix = str(tmp_path / "served")
        # None batch dim -> shape-polymorphic StableHLO artifact
        paddle.jit.save(net, prefix,
                        input_spec=[InputSpec([None, 6], "float32", "x")])
        return prefix

    @pytest.mark.timeout_s(240)
    def test_e2e_64_concurrent_requests(self, tmp_path):
        from concurrent.futures import ThreadPoolExecutor
        prefix = self._export(tmp_path)
        pred = create_predictor(Config(prefix))

        rng = np.random.RandomState(42)
        sizes = [1, 2, 3, 4, 5, 6, 7, 8] * 8          # 64 mixed-size
        payloads = [rng.randn(n, 6).astype(np.float32) for n in sizes]
        serial = [pred.run([x])[0] for x in payloads]  # serial reference

        reg = StatRegistry()
        eng = Engine(pred, EngineConfig(max_batch=16, max_batch_delay=0.02,
                                        max_queue=128), registry=reg)
        # warmup: compile every bucket shape once
        for b in (1, 2, 4, 8, 16):
            eng.submit([np.zeros((b, 6), np.float32)]).result(60)
        misses_after_warmup = eng.cache.stats()["misses"]

        with ThreadPoolExecutor(16) as ex:
            futs = list(ex.map(lambda x: eng.submit([x]), payloads))
        outs = [f.result(60) for f in futs]

        # the serial Predictor's rows, to the last places: a row of a
        # padded batch and the same row alone go through executables of
        # different shapes, which this CPU backend rounds apart by an ulp
        for (out,), ref in zip(outs, serial):
            np.testing.assert_array_max_ulp(out, ref, maxulp=4)
        # at least one batch actually coalesced >= 2 requests
        assert reg.get("serving.coalesced_batches") >= 1
        # zero cache misses after warmup: every batch hit a bucketed shape
        assert eng.cache.stats()["misses"] == misses_after_warmup
        # latency + fill observability
        assert reg.quantile("serving.latency_ms", 0.5) > 0
        assert reg.quantile("serving.batch_fill", 0.5) > 0

        # /statsz over HTTP reports the same non-zero percentiles
        import json
        import urllib.request
        from paddle_tpu.serving.http import make_server
        srv = make_server(eng, port=0)
        port = srv.server_address[1]
        t = threading.Thread(target=srv.serve_forever, daemon=True)
        t.start()
        try:
            with urllib.request.urlopen(
                    f"http://127.0.0.1:{port}/statsz") as r:
                stats = json.loads(r.read())
        finally:
            srv.shutdown()
            srv.server_close()
        lat = stats["histograms"]["serving.latency_ms"]
        fill = stats["histograms"]["serving.batch_fill"]
        assert lat["p50"] > 0 and lat["p99"] >= lat["p50"]
        assert 0 < fill["p50"] <= 1.0
        assert stats["executable_cache"]["misses"] == misses_after_warmup

        inflight = eng.drain(timeout=30)
        assert all(f.done() for f in inflight)

    def test_predictor_no_recompile_on_batch_churn(self, tmp_path):
        """Satellite: standalone Predictor stops recompiling when batch
        size oscillates — same signature == cache hit."""
        prefix = self._export(tmp_path)
        pred = create_predictor(Config(prefix))
        cache = pred._exec_cache
        m0 = cache.stats()["misses"]
        for n in (1, 3, 1, 3, 1, 3, 7, 7, 7):
            pred.run([np.zeros((n, 6), np.float32)])
        s = cache.stats()
        assert s["misses"] - m0 == 3      # one compile per distinct shape
        assert s["hits"] >= 6
