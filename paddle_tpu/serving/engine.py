"""Engine: the async dynamic-batching inference facade.

One worker thread runs the dispatch loop: form a bucketed batch
(:class:`DynamicBatcher`), concatenate + zero-pad request rows up to the
bucket, execute through the shape-keyed :class:`ExecutableCache`, slice
the padded output apart, and resolve each request's future. Everything is
observable through a ``StatRegistry`` (queue depth, batch fill, latency
percentiles, recompiles) and drain is graceful: admission stops, queued
work flushes, every admitted future resolves.

Preemption wiring: ``engine.arm_preemption(guard)`` makes the worker begin
a drain the moment the elastic :class:`PreemptionGuard` observes SIGTERM —
serve traffic until the platform takes the machine, never strand a future.
``install_drain_signal_handler`` arms the engine's own SIGTERM hook via
the chained-handler substrate, so it composes with (not clobbers) the
guard's handler.
"""
from __future__ import annotations

import threading
import time
from concurrent.futures import InvalidStateError
from typing import Any, Callable, List, Optional, Sequence, Union

import numpy as np

from ..core import monitor as _mon
from ..distributed.elastic import ChainedSignalHandler, PreemptionGuard
from ..observability import flight as _flight
from ..observability import tracer as _otrace
from .batcher import Batch, DynamicBatcher
from .buckets import BucketSpec, pad_rows, pad_seq, unpad_rows
from .cache import ExecutableCache, default_cache, signature_of
from .queue import BatchQueue
from .request import (Deadline, EngineDraining, EngineKilled,
                      InferenceRequest, RequestTooLarge)

ModelT = Union[str, Callable[..., Any], "object"]


class EngineConfig:
    """Tunables for the serving engine (see docs/serving.md)."""

    def __init__(self,
                 batch_buckets: Sequence[int] = (),
                 seq_buckets: Optional[Sequence[int]] = None,
                 max_batch: int = 64,
                 max_queue: int = 256,
                 max_batch_delay: float = 0.005,
                 admission_block: bool = True,
                 admission_timeout: Optional[float] = 2.0,
                 oversize_policy: str = "split",
                 default_deadline: Optional[float] = None,
                 stat_prefix: str = "serving"):
        self.buckets = BucketSpec(batch_buckets, seq_buckets,
                                  max_batch=max_batch)
        self.max_queue = int(max_queue)
        self.max_batch_delay = float(max_batch_delay)
        self.admission_block = bool(admission_block)
        self.admission_timeout = admission_timeout
        if oversize_policy not in ("split", "reject"):
            raise ValueError(
                f"oversize_policy must be 'split' or 'reject', "
                f"got {oversize_policy!r}")
        self.oversize_policy = oversize_policy
        self.default_deadline = default_deadline
        self.stat_prefix = stat_prefix


class DrainableEngineBase:
    """Drain/preemption/signal plumbing shared by the classifier
    :class:`Engine` and the LLM :class:`~paddle_tpu.serving.llm.LLMEngine`.

    Subclasses call :meth:`_init_serving_base` in ``__init__``, own a
    ``BatchQueue`` in ``self._queue``, and run a single worker thread that
    polls :attr:`draining` — ``_on_drain_signal`` is flag-only
    (async-signal-safe: closing the queue takes its lock, which the
    interrupted thread may hold), and the worker performs the actual
    ``queue.close()`` at its next poll point.
    """

    def _init_serving_base(self, registry: Optional[_mon.StatRegistry],
                           stat_prefix: str):
        self._registry = registry or _mon.default_registry()
        self._prefix = stat_prefix
        self._draining = threading.Event()
        self._stopped = threading.Event()
        self._guard: Optional[PreemptionGuard] = None
        self._signal_chain: Optional[ChainedSignalHandler] = None
        self._drain_signaled = False  # set (only) from _on_drain_signal
        self._admission_paused = threading.Event()
        self._killed = threading.Event()
        self._kill_reason = ""

    @property
    def registry(self) -> _mon.StatRegistry:
        return self._registry

    @property
    def draining(self) -> bool:
        return self._draining.is_set()

    def arm_preemption(self, guard: Optional[PreemptionGuard] = None):
        """Begin a graceful drain when ``guard`` observes preemption. With
        no argument a fresh guard is installed (chained signal handlers)."""
        self._guard = guard if guard is not None else PreemptionGuard()
        return self._guard

    def install_drain_signal_handler(self, signals=None):
        """Arm SIGTERM/SIGINT (or ``signals``) to trigger drain, chaining —
        not replacing — any handler already installed (e.g. a
        PreemptionGuard's)."""
        if self._signal_chain is not None and self._signal_chain.installed:
            return self._signal_chain
        kwargs = {} if signals is None else {"signals": tuple(signals)}
        self._signal_chain = ChainedSignalHandler(
            self._on_drain_signal, **kwargs)
        self._signal_chain.install()
        return self._signal_chain

    def _on_drain_signal(self, signum, frame):
        """Async-signal-safe drain trigger: only sets the flag. Closing the
        queue takes its lock — if the signal lands while the interrupted
        thread holds that lock, a close() here would self-deadlock — so the
        worker loop performs the close at its next poll. The flight dump
        happens on the worker thread for the same reason (file IO here
        would run in signal context)."""
        self._drain_signaled = True
        self._draining.set()

    def begin_drain(self):
        """Stop admission and let the worker flush the queue (non-blocking).
        Thread-safe, but NOT for signal context: closing the queue acquires
        its lock — signal handlers must go through ``_on_drain_signal``."""
        self._draining.set()
        self._queue.close()

    # -- fleet control plane (pause / hard-kill) ----------------------------
    @property
    def admission_paused(self) -> bool:
        return self._admission_paused.is_set()

    def pause_admission(self):
        """Stop admitting new requests WITHOUT draining: queued and
        in-flight work completes, the worker stays alive, and
        :meth:`resume_admission` reopens the front door. The weight
        hot-swap path uses this to quiesce a replica."""
        self._admission_paused.set()

    def resume_admission(self):
        self._admission_paused.clear()

    @property
    def was_killed(self) -> bool:
        return self._killed.is_set()

    def kill(self, reason: str = "killed") -> List[dict]:
        """Hard-kill (in-process SIGKILL analog): fail every queued request
        with :class:`EngineKilled` immediately — unlike drain, nothing is
        flushed — and flag the worker to abort in-flight work at its next
        poll point. Returns one snapshot record per failed request
        (``{"req_id", "phase", "tokens"}``) so recovery paths can
        enumerate what was in the engine. Safe to call from any thread;
        idempotent."""
        self._kill_reason = str(reason)
        self._killed.set()
        self._draining.set()
        return self._queue.fail_all(
            lambda: EngineKilled(
                f"engine hard-killed ({self._kill_reason}); "
                f"request aborted before execution"))

    def _stat_add(self, name: str, v):
        self._registry.add(f"{self._prefix}.{name}", v)

    def _stat_set(self, name: str, v):
        self._registry.set(f"{self._prefix}.{name}", v)

    def _stat_observe(self, name: str, v):
        self._registry.observe(f"{self._prefix}.{name}", v)


class Engine(DrainableEngineBase):
    """submit()/submit_many()/drain() over a batched, cached model.

    ``model`` may be:
      * an :class:`~paddle_tpu.inference.Predictor` (or anything with a
        compatible ``run(list_of_arrays) -> list_of_arrays``),
      * a path prefix of a ``jit.save`` artifact (a Predictor is created),
      * a plain callable ``fn(*arrays) -> array-or-list`` (tests, benches).
    """

    def __init__(self, model: ModelT, config: Optional[EngineConfig] = None,
                 registry: Optional[_mon.StatRegistry] = None,
                 cache: Optional[ExecutableCache] = None):
        self._config = config or EngineConfig()
        self._init_serving_base(registry, self._config.stat_prefix)
        self._model_fn, self._cache, self._model_key, self._wrap_in_cache = \
            self._resolve_model(model, cache)
        self._queue = BatchQueue(max_size=self._config.max_queue)
        self._batcher = DynamicBatcher(
            self._queue, self._config.buckets,
            max_batch_delay=self._config.max_batch_delay)
        # admitted-but-unresolved futures, keyed to their request id so
        # kill() can return an exact snapshot of what was in flight
        self._inflight: dict = {}
        self._inflight_lock = threading.Lock()
        self._worker = threading.Thread(
            target=self._worker_loop, name="paddle-tpu-serving-worker",
            daemon=True)
        self._worker.start()

    # -- model resolution ---------------------------------------------------
    def _resolve_model(self, model: ModelT, cache: Optional[ExecutableCache]):
        if isinstance(model, str):
            from ..inference import Config, create_predictor
            model = create_predictor(Config(model))
        run = getattr(model, "run", None)
        if callable(run):
            # Predictor path: its run() already goes through the shared
            # default ExecutableCache; reuse that cache for stats so the
            # engine's recompile counter reflects reality.
            pred_cache = getattr(model, "_exec_cache", None)
            # pick the first cache that EXISTS (`is not None`), not the
            # first truthy one — an empty ExecutableCache has len() == 0
            # and is falsy, so `or`-chaining would silently drop it
            use = cache if cache is not None else pred_cache
            return (lambda arrays: run(arrays)), \
                (use if use is not None else default_cache()), \
                ("predictor", id(model)), False
        if callable(model):
            fn = model

            def _call(arrays: List[np.ndarray]) -> List[Any]:
                out = fn(*arrays)
                return list(out) if isinstance(out, (list, tuple)) else [out]
            # plain callables share the process-wide cache; the key holds
            # the fn OBJECT (not id(fn) — ids are reused after GC, and in
            # a shared cache a recycled id would alias two models). A miss
            # marks the first time a padded signature is seen (== a jit
            # compile when fn is jitted).
            return _call, \
                (cache if cache is not None else default_cache()), \
                ("callable", fn), True
        raise TypeError(
            f"model must be a Predictor, artifact path prefix, or callable; "
            f"got {type(model).__name__}")

    # -- public API ---------------------------------------------------------
    @property
    def config(self) -> EngineConfig:
        return self._config

    @property
    def cache(self) -> ExecutableCache:
        return self._cache

    def submit(self, inputs: Sequence[np.ndarray],
               deadline: Optional[Union[Deadline, float]] = None):
        """Enqueue one request; returns a ``concurrent.futures.Future``
        whose result is the list of output arrays (rows matching the
        request's rows)."""
        if self._killed.is_set():
            self._stat_add("rejected_killed", 1)
            raise EngineKilled(
                f"engine was hard-killed ({self._kill_reason}); "
                f"submit rejected")
        if self._draining.is_set():
            self._stat_add("rejected_draining", 1)
            raise EngineDraining("engine is draining; submit rejected")
        if self._admission_paused.is_set():
            self._stat_add("rejected_paused", 1)
            raise EngineDraining(
                "engine admission is paused (fleet control); "
                "submit rejected")
        if deadline is None and self._config.default_deadline is not None:
            deadline = self._config.default_deadline
        if deadline is not None and not isinstance(deadline, Deadline):
            deadline = Deadline(float(deadline))
        req = InferenceRequest(inputs, deadline=deadline)
        if (self._config.oversize_policy == "reject"
                and req.nrows > self._config.buckets.max_batch):
            self._stat_add("rejected_oversize", 1)
            raise RequestTooLarge(
                f"request has {req.nrows} rows but the largest batch bucket "
                f"is {self._config.buckets.max_batch} and oversize_policy="
                f"'reject'; split the request or raise max_batch")
        try:
            self._queue.put(req, block=self._config.admission_block,
                            timeout=self._config.admission_timeout)
        except Exception:
            self._stat_add("rejected_queue_full", 1)
            raise
        with self._inflight_lock:
            self._inflight[req.future] = req.req_id
        req.future.add_done_callback(self._forget_future)
        self._stat_set("queue_depth", len(self._queue))
        return req.future

    def submit_many(self, requests: Sequence[Sequence[np.ndarray]],
                    deadline: Optional[Union[Deadline, float]] = None):
        return [self.submit(inputs, deadline=deadline)
                for inputs in requests]

    def kill(self, reason: str = "killed") -> List[dict]:
        """Hard-kill, returning records for queued requests (failed here)
        AND the admitted-but-unresolved ones the worker will abort at its
        next poll point (``phase: "inflight"``)."""
        records = list(super().kill(reason))
        seen = {r["req_id"] for r in records}
        with self._inflight_lock:
            records += [{"req_id": rid, "phase": "inflight", "tokens": 0}
                        for rid in self._inflight.values()
                        if rid not in seen]
        return records

    def drain(self, timeout: Optional[float] = None) -> List:
        """Graceful drain: stop admission, flush every queued request, wait
        for the worker, and return the futures of all requests that were
        in flight when the drain began (all resolved on return)."""
        with self._inflight_lock:
            inflight = list(self._inflight)
        self.begin_drain()
        self._stopped.wait(timeout)
        if self._signal_chain is not None:
            self._signal_chain.uninstall()
        self._stat_set("queue_depth", 0)
        return inflight

    close = drain

    def __enter__(self):
        return self

    def __exit__(self, *exc):
        self.drain()
        return False

    def stats(self) -> dict:
        """Scalar stats + histogram summaries + cache counters (the
        ``/statsz`` payload)."""
        pre = self._prefix + "."
        scalars = self._registry.stats_with_prefix(pre)
        hists = self._registry.histograms_with_prefix(pre)
        return {"stats": scalars, "histograms": hists,
                "executable_cache": self._cache.stats(),
                "draining": self.draining,
                "queue_depth": len(self._queue)}

    # -- worker -------------------------------------------------------------
    def _forget_future(self, fut):
        with self._inflight_lock:
            self._inflight.pop(fut, None)

    def _worker_loop(self):
        poll = max(0.01, self._config.max_batch_delay)
        try:
            while True:
                if self._killed.is_set():
                    break
                if self._guard is not None and self._guard.preempted \
                        and not self._draining.is_set():
                    self._stat_add("preemption_drains", 1)
                    self.begin_drain()
                elif self._draining.is_set() and not self._queue.closed:
                    # the flag came from _on_drain_signal (which cannot
                    # touch the queue lock); finish the drain here
                    self._queue.close()
                batch = self._batcher.next_batch(timeout=poll)
                self._stat_set("queue_depth", len(self._queue))
                self._stat_set("deadline_evicted",
                               self._queue.evicted_expired)
                if batch is None:
                    if self._draining.is_set() and len(self._queue) == 0:
                        break
                    continue
                self._execute(batch)
                self._publish_cache_stats()
        finally:
            if self._killed.is_set():
                # hard-kill: fail whatever was admitted but not yet
                # resolved (queued requests were failed by kill() itself;
                # this catches the batch the worker never finished)
                with self._inflight_lock:
                    victims = list(self._inflight)
                exc = EngineKilled(
                    f"engine hard-killed ({self._kill_reason}); "
                    f"in-flight request aborted")
                for fut in victims:
                    try:
                        fut.set_exception(exc)
                    except InvalidStateError:
                        pass  # resolved by a racing complete; verdict stands
                _flight.record_event(
                    "engine_killed",
                    {"engine": self._prefix, "reason": self._kill_reason,
                     "aborted": len(victims)})
            if self._drain_signaled:
                # SIGTERM-initiated drain: leave the post-mortem timeline
                # (worker thread — never in signal context)
                _flight.record_event("sigterm_drain",
                                     {"engine": self._prefix})
                _flight.dump_if_armed("sigterm_drain")
            self._stopped.set()

    def _publish_cache_stats(self):
        s = self._cache.stats()
        self._stat_set("cache.hits", s["hits"])
        self._stat_set("cache.misses", s["misses"])
        self._stat_set("cache.evictions", s["evictions"])
        self._stat_set("recompiles", s["misses"])

    def _dispatch(self, arrays: List[np.ndarray]) -> List[np.ndarray]:
        """Run one padded, bucket-shaped batch through the cached model.

        Predictor models already route through the shared ExecutableCache
        inside run(); wrapping them again here would double-count hits."""
        if self._wrap_in_cache:
            sig = signature_of(arrays)
            runner = self._cache.get_or_compile(
                (self._model_key, sig), lambda: self._model_fn)
            outs = runner(arrays)
        else:
            outs = self._model_fn(arrays)
        return [np.asarray(o) for o in outs]

    def _execute(self, batch: Batch):
        with _otrace.span("serving/execute_batch"):
            self._execute_inner(batch)

    def _execute_inner(self, batch: Batch):
        t0 = time.monotonic()
        reqs = batch.requests
        try:
            if batch.oversize:
                # one request wider than every bucket: run it alone in
                # max-bucket chunks and stitch the rows back together
                outs = self._execute_oversize(reqs[0], batch.seq_bucket)
                self._finish(reqs[0], outs)
            else:
                n_in = len(reqs[0].inputs)
                padded_inputs = [pad_seq(r.inputs, batch.seq_bucket)
                                 for r in reqs]
                cols = [np.concatenate([p[i] for p in padded_inputs], axis=0)
                        for i in range(n_in)]
                padded = pad_rows(cols, batch.bucket_rows)
                outs = self._dispatch(padded)
                outs = unpad_rows(outs, batch.rows)
                offset = 0
                for r in reqs:
                    self._finish(r, [o[offset:offset + r.nrows]
                                     if getattr(o, "ndim", 0) > 0 else o
                                     for o in outs])
                    offset += r.nrows
                self._stat_observe("batch_fill", batch.fill_ratio)
                self._stat_observe("batch_requests", len(reqs))
                if len(reqs) > 1:
                    self._stat_add("coalesced_batches", 1)
            self._stat_add("batches", 1)
            self._stat_add("rows", batch.rows)
            self._stat_observe("batch_exec_ms",
                               (time.monotonic() - t0) * 1000.0)
        except Exception as e:
            self._stat_add("batch_errors", 1)
            for r in reqs:
                r.fail(e)

    def _execute_oversize(self, req: InferenceRequest,
                          seq_bucket) -> List[np.ndarray]:
        spec = self._config.buckets
        step = spec.max_batch
        chunks: List[List[np.ndarray]] = []
        inputs = pad_seq(req.inputs, seq_bucket)
        for start in range(0, req.nrows, step):
            part = [a[start:start + step] for a in inputs]
            rows = part[0].shape[0]
            padded = pad_rows(part, spec.batch_bucket_for(rows))
            outs = self._dispatch(padded)
            chunks.append(unpad_rows(outs, rows))
        self._stat_add("oversize_splits", 1)
        return [np.concatenate([c[i] for c in chunks], axis=0)
                for i in range(len(chunks[0]))]

    def _finish(self, req: InferenceRequest, outs: List[np.ndarray]):
        if req.expired:
            req.fail_expired()
            return
        if not req.future.done():
            self._stat_observe(
                "latency_ms", (time.monotonic() - req.t_enqueue) * 1000.0)
            self._stat_add("completed", 1)
            req.future.set_result(outs)


# -- trace-audit registration (tools/analyze/trace, PTA009/PTA010) -----------

def _audit_serving_predict_spec():
    """The engine's hot path for a callable model: a functionalized Layer
    forward jitted per padded signature. Audited on a tiny Linear so the
    program is small but structurally the production one."""
    import jax
    import jax.numpy as jnp
    import numpy as np
    from ..core import audit
    from ..jit.functionalize import build_pure
    from .. import nn

    lin = nn.Linear(6, 3)
    params = list(lin.parameters())
    pure, _meta = build_pure(lin.forward, params)
    base_params = [np.asarray(p._data) for p in params]

    def predict(param_raws, x, key):
        # static_kwargs pinned to None: the serving engine calls the
        # forward with positional arrays only
        return pure(list(param_raws), (x,), key, None)

    def make_args(variant):
        rng = np.random.default_rng(77 + variant)
        param_raws = [jnp.asarray(b) for b in base_params]
        x = jnp.asarray(rng.standard_normal((4, 6)), jnp.float32)
        return (param_raws, x, jax.random.PRNGKey(variant))

    return audit.AuditSpec(fn=predict, make_args=make_args, jit_kwargs={})


def _register_audit_entrypoints():
    from ..core import audit
    audit.register_entrypoint("serving_predict", _audit_serving_predict_spec,
                              tags=("serving",))


_register_audit_entrypoints()
