"""What the ``closed`` and ``open`` drivers share: the engine behind its
public API, the clients that time every token on the benchmark's own clock,
and the comparison with the reference once the engine is gone."""
from __future__ import annotations

import gc
import threading
import time

from .. import check, gpt_adapter, harness, weights

HISTOGRAMS = ("decode_tick_ms", "prefill_ms", "intertoken_ms", "ttft_ms",
              "tpot_ms")


def page_bytes(cfg: dict, page_size: int, itemsize: int = 4) -> int:
    """Bytes of one KV page: K and V rows of every layer."""
    return 2 * page_size * cfg["n_layer"] * cfg["n_embd"] * itemsize


class Served:
    """The engine with seeded weights, and the records of its requests."""

    def __init__(self, cell, args, ctx):
        from paddle_tpu.core.monitor import StatRegistry
        from paddle_tpu.serving.llm import LLMEngine, LLMEngineConfig
        self.cfg, self.tr = cell["config_data"], cell["traffic_data"]
        self.args, self.ctx = args, ctx
        eng = self.tr["engine"]
        positions = self.cfg["n_positions"]
        phases = harness.Phases(ctx["process_start"])
        phases.done("imports and device")
        net = gpt_adapter.build_net(self.cfg, positions)
        phases.done("the program builds its model")
        gpt_adapter.load_weights(
            net, weights.make_gpt_weights(self.cfg, args.seed, positions))
        net.eval()
        phases.done("seeded weights made and loaded")
        self.registry = StatRegistry()
        # the arena is a byte budget, so that every configuration served
        # under this traffic fills the same share of the chip
        num_pages = eng["kv_arena_bytes"] // page_bytes(self.cfg,
                                                        eng["page_size"])
        self.engine = LLMEngine(net, LLMEngineConfig(
            kv_layout="paged", num_slots=eng["num_slots"],
            max_seq=eng["max_seq"], page_size=eng["page_size"],
            num_pages=int(num_pages), prefill_buckets=eng["prefill_buckets"],
            max_top_k=eng["max_top_k"], seed=args.seed % (1 << 31),
            max_queue=eng["max_queue"], admission_timeout=None,
            paged_attn_impl="kernel" if ctx["rehearsal"] else "auto"),
            registry=self.registry)
        phases.done("engine built and warmed")
        self.memory = harness.MemoryPeak()
        self.prefix = self.engine.config.stat_prefix + "."
        self.records = []
        self.lock = threading.Lock()
        self.closing = False
        self.tracer = harness.TraceWindow(
            ctx["out_dir"], ctx["rehearsal"]) if args.trace else None
        self._trace_thread = None
        print(f"engine: {int(num_pages)} pages of {eng['page_size']} tokens, "
              f"{eng['num_slots']} slots, max_seq {eng['max_seq']}, paged "
              f"attention lane {self.engine.stats()['paged_attn_impl']!r}",
              flush=True)

    # -- one request, timed on the benchmark's clock -------------------------
    def send(self, request: dict, due: float, timed: bool) -> dict:
        """Submit and consume one request on the calling thread."""
        rec = {"prompt": request["prompt"], "due": due, "timed": timed,
               "tokens": [], "times": [], "finished": False, "error": None,
               "cut": False}
        with self.lock:
            self.records.append(rec)
        try:
            with harness.span("bench/submit"):
                rec["submitted"] = time.perf_counter()
                req = self.engine.submit(
                    request["prompt"],
                    max_new_tokens=request["max_new_tokens"], stream=True)
            for tok in req.iter_tokens(timeout=self.tr["request_timeout_s"]):
                rec["times"].append(time.perf_counter())
                rec["tokens"].append(int(tok))
            rec["finished"] = len(rec["tokens"]) == request["max_new_tokens"]
        except Exception as e:  # a failed request is a result, not a crash
            if self.closing:    # cut by the window's end, not by a fault
                rec["cut"] = True
            else:
                rec["error"] = f"{type(e).__name__}: {e}"
        return rec

    # -- the window -----------------------------------------------------------
    def open_window(self) -> float:
        """Forget set-up's samples and start the clock."""
        for h in HISTOGRAMS:
            self.registry.reset(self.prefix + h)
        self.counters0 = dict(self.engine.stats()["stats"])
        self.compiles0 = self.ctx["compiles"].requests
        self.t0 = time.perf_counter()
        if self.tracer is not None:
            self._trace_thread = threading.Thread(
                target=self._trace, name="bench-trace", daemon=True)
            self._trace_thread.start()
        return self.t0

    def sleep_until(self, t: float):
        """Sleep to ``t`` on the benchmark's clock, sampling memory."""
        while True:
            self.memory.sample()
            left = t - time.perf_counter()
            if left <= 0:
                return
            time.sleep(min(left, 0.5))

    def _trace(self):
        time.sleep(self.tr["trace_from_s"])
        self.tracer.start()
        time.sleep(self.tr["trace_seconds"])
        self.tracer.stop()

    def warm_up(self, requests):
        """Untimed requests, one after another, run to their end: they pay
        the admission path's and the release path's small compiles."""
        for request in requests:
            rec = self.send(request, due=time.perf_counter(), timed=False)
            if not rec["finished"]:
                raise RuntimeError(f"warm-up request failed: {rec['error']}")

    def finish_window(self, threads, cut: bool = False) -> dict:
        """Read the engine once the requests have ended. An open loop lets
        what is in flight finish (a request due in the window that has not
        ended when the drain does is failed). A closed loop is ``cut``: a
        request in flight at the window's end is no failure and no metric
        needs its end, so the engine is stopped under the clients and those
        requests count as neither attempted nor failed."""
        if cut:
            self.memory.sample()
            self.closing = True
            self.engine.kill("the window closed")
        deadline = time.perf_counter() + self.tr["drain_seconds"]
        for t in threads:
            t.join(max(deadline - time.perf_counter(), 0.0))
        if self._trace_thread is not None:
            self._trace_thread.join()
        stats = self.engine.stats()
        compiles = self.ctx["compiles"].requests - self.compiles0
        peak = self.memory.sample()
        t_end = self.t0 + self.args.seconds
        timed = [r for r in self.records if r["timed"] and not r["cut"]]
        tokens_in_window = sum(1 for r in self.records for t in r["times"]
                               if self.t0 <= t <= t_end)
        run = {
            "window_s": float(self.args.seconds),
            "records": timed, "all_records": self.records,
            "t0": self.t0,
            "hist": {k[len(self.prefix):]: v
                     for k, v in stats["histograms"].items()},
            "counters": {k[len(self.prefix):]: v - self.counters0.get(k, 0)
                         for k, v in stats["stats"].items()
                         if isinstance(v, (int, float))},
            "compiles_in_window": compiles, "peak_bytes": peak,
            "setup_s": self.t0 - self.ctx["process_start"],
            "attempted": len(timed),
            "failed": sum(1 for r in timed if not r["finished"]),
            "tokens_in_window": tokens_in_window,
        }
        if self.tracer is not None:
            run["trace"] = self.tracer.reduced()
            run["trace_span"] = (self.tracer.t_start, self.tracer.t_stop)
        for r in timed:
            if r["error"]:
                print(f"failed request: {r['error']}", flush=True)
        return run

    def shutdown(self):
        """Stop the engine and free its memory (before the reference runs)."""
        self.engine.drain(timeout=10.0)   # after a kill: waits for the worker
        self.engine = None
        gc.collect()

    # -- the comparison --------------------------------------------------------
    def compare(self, run: dict) -> dict:
        """Reference logits over a seeded sample of the finished requests
        (the longest among them), after the engine's memory is freed."""
        sample = check.sample_finished(run["records"], self.args.seed,
                                       self.tr["check_requests"])
        if not sample:
            return {"served_token_gap": float("inf")}
        t = time.perf_counter()
        w = weights.make_gpt_weights(self.cfg, self.args.seed,
                                     self.cfg["n_positions"])
        w["wte"].block_until_ready()
        print(f"reference: weights made again in "
              f"{time.perf_counter() - t:.1f} s", flush=True)
        out = check.serve_gaps(
            w, self.cfg, sample, pad_len=self.tr["engine"]["max_seq"],
            max_new=int(self.tr["output_len"]["hi"]),
            control_modes=self.ctx.get("control_modes") or ())
        print(f"reference: {len(sample)} requests, {out['tokens_compared']} "
              f"served tokens in {time.perf_counter() - t:.1f} s", flush=True)
        return out


def latencies(run: dict) -> dict:
    """Time to first token (from the due time) and the gaps between one
    request's consecutive tokens, over the timed requests that finished."""
    ttft = [(r["times"][0] - r["due"]) * 1e3
            for r in run["records"] if r["finished"]]
    itl = [(b - a) * 1e3 for r in run["records"] if r["finished"]
           for a, b in zip(r["times"], r["times"][1:])]
    return {"ttft_ms": ttft, "itl_ms": itl}
