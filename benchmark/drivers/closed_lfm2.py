"""Driver ``closed_lfm2``: the ``closed`` loop (as many clients as slots, each
sending its next request when its last one ends, the window cut at its end)
over the LFM2-MoE engine. It reuses ``serving.Served``'s clients, window and
records and replaces two things: construction (the program's LFM2 model with
``lfm2_weights``, an arena of KV pages that counts attention layers and KV
heads) and the comparison (``lfm2_ref``, with the routing near-tie rule).

**Near-ties.** Top-k routing is discontinuous: where the last chosen and the
first rejected score lie within rounding of each other the program and the
reference may choose differently, and one flipped expert moves the logits as
much as a lower precision would. The reference therefore returns each
position's smallest routing margin over the expert layers, and a request is
compared up to, not including, its first position (prompt included) whose
margin is under ``routing_margin_tau`` of the limits file. The rule reads
the reference alone, never what the program served. The share of sampled
tokens left out is a number of ``correct`` too (``left_out_share``).
"""
from __future__ import annotations

import functools
import gc
import json
import os
import threading
import time

import jax
import jax.numpy as jnp
import numpy as np

from .. import (check, harness, lfm2_adapter, lfm2_weights, spec,
                worker_phases)
from .. import traffic as traffic_mod
from ..reference import lfm2_ref as ref
from . import serving


def page_bytes(cfg: dict, page_size: int, itemsize: int = 4) -> int:
    """Bytes of one KV page: K and V rows of every ATTENTION layer, over
    the KV heads."""
    kv_layers = cfg["layer_types"][:cfg["num_hidden_layers"]].count(
        "full_attention")
    head_dim = cfg["hidden_size"] // cfg["num_attention_heads"]
    return (2 * page_size * kv_layers * cfg["num_key_value_heads"] * head_dim
            * itemsize)


class ServedLFM2(serving.Served):
    """``Served`` over the LFM2-MoE engine: its clients, window and records;
    its own construction and comparison."""

    def __init__(self, cell, args, ctx):  # noqa: D107 -- replaces Served's
        from paddle_tpu.core.monitor import StatRegistry
        from paddle_tpu.serving.llm import LLMEngine, LLMEngineConfig
        self.cfg, self.tr = cell["config_data"], cell["traffic_data"]
        self.args, self.ctx, self.cell_name = args, ctx, cell["name"]
        eng = self.tr["engine"]
        phases = harness.Phases(ctx["process_start"])
        phases.done("imports and device")
        net = lfm2_adapter.build_net(self.cfg)
        phases.done("the program builds its model")
        lfm2_adapter.load_weights(net, self.cfg, args.seed)
        net.eval()
        phases.done("seeded weights made and loaded")
        self.registry = StatRegistry()
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
        del net
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

    def compare(self, run: dict) -> dict:
        """Reference logits over a seeded sample of the finished requests,
        after the engine's memory is freed, each request up to its first
        routing near-tie."""
        sample = check.sample_finished(run["records"], self.args.seed,
                                       self.tr["check_requests"])
        if not sample:
            return {"served_token_gap": float("inf"), "left_out_share": 1.0}
        with open(os.path.join(spec.HERE, "limits",
                               self.cell_name + ".json")) as f:
            tau = float(json.load(f)["routing_margin_tau"])
        t = time.perf_counter()
        w = lfm2_weights.make_lfm2_weights(self.cfg, self.args.seed)
        jax.block_until_ready(w)
        print(f"reference: weights made again in "
              f"{time.perf_counter() - t:.1f} s", flush=True)
        out = serve_gaps(
            w, ref.arch_of(self.cfg), sample, tau,
            pad_len=self.tr["engine"]["max_seq"],
            max_new=int(self.tr["output_len"]["hi"]),
            control_modes=self.ctx.get("control_modes") or ())
        print(f"reference: {len(sample)} requests, {out['tokens_compared']} "
              f"of {out['tokens_sampled']} served tokens compared "
              f"({out['tokens_compared_long']} of them from a context of "
              f"{LONG_SHARE:.0%} of max_seq or more), "
              f"{out['tokens_sampled'] - out['tokens_compared']} left out "
              f"behind a routing margin under {tau:g} (smallest margin seen "
              f"{out['smallest_margin']:.3g}), in "
              f"{time.perf_counter() - t:.1f} s", flush=True)
        return out


# -- the comparison -------------------------------------------------------------

#: a compared token counts as long-context from this share of ``max_seq`` on:
#: the rule cuts a request's tail, so this says how much of the long walks
#: of ``paged_attn`` a run still holds to the reference
LONG_SHARE = 2 / 3


@functools.partial(jax.jit, static_argnums=(1,))
def _served_gaps(w, arch, seq, rows, served):
    """Per served position, how far the served token's reference logit lies
    below the reference's best; and each position's routing margin."""
    hidden, margin = ref.hidden_states(w, arch, seq)
    logits = ref.logits_of(w, hidden[rows])
    best = jnp.max(logits, axis=-1)
    return (best - jnp.take_along_axis(logits, served[:, None], -1)[:, 0],
            margin)


@functools.partial(jax.jit, static_argnums=(1, 4))
def _first_tokens(w, arch, seq, rows, mode):
    """The token a pass in the lower precision ``mode`` puts first at each
    of ``rows``: what a program computing in that precision would serve."""
    hidden, _ = ref.hidden_states(w, arch, seq, mode)
    return jnp.argmax(ref.logits_of(w, hidden[rows], mode),
                      axis=-1).astype(jnp.int32)


def compared_tokens(margin, plen: int, n: int, tau: float) -> int:
    """How many of a request's ``n`` served tokens are compared: token ``j``
    is predicted from position ``plen - 1 + j`` and is compared while no
    position up to that one has a routing margin under ``tau``."""
    low = np.flatnonzero(np.asarray(margin[:plen + n - 1]) < tau)
    cut = int(low[0]) if low.size else plen + n - 1
    return int(np.clip(cut - (plen - 1), 0, n))


def serve_gaps(w, arch, sample, tau, pad_len, max_new, control_modes=()):
    """Widest served-token gap over the compared tokens of the sample, how
    many were sampled and compared and, for each control mode, the widest
    gap of the tokens that precision puts first at the same positions."""
    out = {"served_token_gap": 0.0, "tokens_compared": 0,
           "tokens_compared_long": 0, "tokens_sampled": 0,
           "smallest_margin": float("inf")}
    out.update({f"control_{m}_token_gap": 0.0 for m in control_modes})
    for r in sample:
        plen, n = len(r["prompt"]), len(r["tokens"])
        seq = np.zeros(pad_len, np.int32)
        seq[:plen] = r["prompt"]
        seq[plen:plen + n - 1] = r["tokens"][:-1]
        rows = np.zeros(max_new, np.int32)
        rows[:n] = plen - 1 + np.arange(n)
        served = np.zeros(max_new, np.int32)
        served[:n] = r["tokens"]
        seq, rows = jnp.asarray(seq), jnp.asarray(rows)
        gap, margin = _served_gaps(w, arch, seq, rows, jnp.asarray(served))
        gaps = {"served_token_gap": gap}
        for m in control_modes:
            gaps[f"control_{m}_token_gap"] = _served_gaps(
                w, arch, seq, rows, _first_tokens(w, arch, seq, rows, m))[0]
        keep = compared_tokens(margin, plen, n, tau)
        for k, g in gaps.items():
            if keep:
                out[k] = max(out[k], float(jnp.max(g[:keep])))
        out["tokens_compared"] += keep
        # token j is predicted from position plen - 1 + j
        out["tokens_compared_long"] += int(np.sum(
            plen - 1 + np.arange(keep) >= LONG_SHARE * pad_len))
        out["tokens_sampled"] += n
        out["smallest_margin"] = min(
            out["smallest_margin"], float(jnp.min(margin[:plen + n - 1])))
    out["left_out_share"] = 1.0 - out["tokens_compared"] / out["tokens_sampled"]
    return out


# -- what stalls the whole process, seen from the host -----------------------------

class StallWatch:
    """The longest garbage collection and the longest gap between two
    wake-ups of a thread that sleeps ``PERIOD_S``, while it is open. A tick
    that takes seconds (one in ten runs had one, PERF.md) is then either a
    collection, a process that did not run (the gap is as long as the tick)
    or the device and its runtime (neither shows)."""

    PERIOD_S = 0.05

    def __init__(self):
        self.gc_ms_max = self.gap_ms_max = 0.0
        self._gc_start = None
        self._stop = threading.Event()
        self._thread = threading.Thread(target=self._beat, daemon=True,
                                        name="bench-stall-watch")

    def _on_gc(self, phase, info):
        if phase == "start":
            self._gc_start = time.perf_counter()
        elif self._gc_start is not None:
            self.gc_ms_max = max(
                self.gc_ms_max, (time.perf_counter() - self._gc_start) * 1e3)

    def _beat(self):
        last = time.perf_counter()
        while not self._stop.wait(self.PERIOD_S):
            now = time.perf_counter()
            self.gap_ms_max = max(self.gap_ms_max, (now - last) * 1e3)
            last = now

    def open(self):
        gc.callbacks.append(self._on_gc)
        self._thread.start()

    def close(self) -> dict:
        self._stop.set()
        self._thread.join()
        gc.callbacks.remove(self._on_gc)
        return {"gc_ms_max": self.gc_ms_max,
                "host_gap_ms_max": self.gap_ms_max}


# -- the loop (as drivers/closed.py runs it) ------------------------------------

def run(cell, args, ctx):
    served = ServedLFM2(cell, args, ctx)
    tr, cfg = served.tr, served.cfg
    per_client = traffic_mod.closed_requests(
        tr, args.seed, cfg["vocab_size"], count=tr["requests_per_client"])
    served.warm_up(traffic_mod.closed_requests(
        dict(tr, clients=1, output_len=tr["warm_output_len"]), args.seed + 1,
        cfg["vocab_size"], count=tr["warm_requests"])[0])
    stop = threading.Event()

    def client(requests):
        for request in requests:
            if stop.is_set():
                return
            served.send(request, due=time.perf_counter(), timed=True)

    clients = [threading.Thread(target=client, args=(reqs,),
                                name=f"bench-client-{i}", daemon=True)
               for i, reqs in enumerate(per_client)]
    for t in clients:
        t.start()
    time.sleep(tr["warm_seconds"])
    watch = StallWatch()
    watch.open()
    t0 = served.open_window()
    served.sleep_until(t0 + args.seconds)
    stalls = watch.close()
    stop.set()
    run = served.finish_window(clients, cut=True)
    served.shutdown()
    run["end_to_end"] = {
        "serve_tok_s": run["tokens_in_window"] / run["window_s"]}
    done = [r for r in run["records"] if r["finished"]]
    print(f"closed loop: {run['tokens_in_window']} tokens in the window, "
          f"{len(done)} of {run['attempted']} requests finished", flush=True)
    # what an untraced run's rate rests on: the ticks and what they read,
    # the admissions, and where the worker's time went
    counted = dict(run, cell=cell)
    tick = run["hist"].get("decode_tick_ms", {})
    print("window: " + json.dumps({
        "ticks": tick.get("count", 0),
        "tick_ms": {k: tick.get(k) for k in ("p50", "mean", "p99", "max")},
        "prefills": run["counters"].get("prefills", 0),
        "prefill_ms_mean": run["hist"].get("prefill_ms", {}).get("mean"),
        **{m: spec.load_reader(m)(counted) for m in (
            "tick_batch_mean", "moe_experts_active_mean",
            "moe_load_max_mean")},
        "worker_s": worker_phases.phase_seconds(run), **stalls}), flush=True)
    run["numbers"] = served.compare(run)
    return run
