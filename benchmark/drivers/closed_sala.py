"""Driver ``closed_sala``: the ``closed`` loop (as many clients as slots, each
sending its next request when its last one ends, the window cut at its end)
over the MiniCPM-SALA engine with chunked prefill. It reuses
``serving.Served``'s clients, window and records and ``closed_lfm2``'s stall
watch and near-tie rule, and replaces three things: construction (the
program's SALA model with ``sala_weights``, an arena of KV pages that counts
the sparse layers' KV heads, ``prefill_chunk``), the requests (lengths
REPLAYED from the traffic file's lists, client ``c`` starting at entry ``c
mod len``; the seed draws the token ids only: at ten requests a window drawn
lengths alone would spread the rate by several percent from seed to seed),
and the comparison (``sala_ref``, a layer's weights at a time).

**Near-ties.** Block top-k is as discontinuous as expert top-k: where the
last block chosen by score and the first rejected lie within rounding of each
other the program and the reference may read different blocks, and the token
predicted at that position then differs as it would under a lower precision.
The reference returns each position's smallest such margin over the sparse
layers and KV heads, and the limits file names the rule (``near_tie_rule``)
and its ``selection_margin_tau``: ``own`` leaves out the served tokens whose
OWN predicting position has a margin under tau; ``cut`` (``closed_lfm2``'s
rule) compares a request up to, not including, its first served position
with such a margin. Neither holds the positions inside the PROMPT to the
rule: a prompt of 12k-32k tokens makes 16k-98k selections past ``dense_len``
and always holds near-ties, and a flipped block there (or at an earlier
served position) moves one row of tens of thousands: what that does to later
tokens is in ``served_token_gap``'s readings (limits file, PERF.md section
2). Both rules read the reference alone, never what the program served. The
share of sampled tokens left out is a number of ``correct`` too
(``left_out_share``). Every comparison prints, for both rules and a sweep of
tau, the widest gap and the tokens compared (``by tau``): the readings the
limits file is set by.

Control modes (calibration runs, ``run.main(argv, control_modes=...)``):
``high`` and ``bfloat16`` are the reference's own lower-precision passes, as
in the other serving cells; ``program_topk_short`` builds the ENGINE with
``sparse_topk - 1`` (the reference keeps the configuration's), so that run's
own ``served_token_gap`` is the reading of a program that selects one block
too few.
"""
from __future__ import annotations

import functools
import json
import os
import threading
import time

import jax
import jax.numpy as jnp
import numpy as np

from .. import check, harness, sala_adapter, sala_weights, spec, worker_phases
from ..reference import sala_ref as ref
from ..traffic import rng_for
from . import serving
from .closed_lfm2 import StallWatch, compared_tokens

RULES = ("own", "cut")

PROGRAM_TOPK_SHORT = "program_topk_short"
#: the comparison prints what these values of ``selection_margin_tau`` would
#: have compared beside what the limits file's did: the readings it is set by
TAU_SWEEP = (0.0, 1e-9, 1e-8, 1e-7, 1e-6, 1e-5)


def page_bytes(cfg: dict, page_size: int, itemsize: int = 4) -> int:
    """Bytes of one KV page: K and V rows of every SPARSE layer, over the
    KV heads (the linear layers keep no rows)."""
    layers = cfg["mixer_types"][:cfg["num_hidden_layers"]].count(ref.SPARSE)
    return (2 * page_size * layers * cfg["num_key_value_heads"]
            * cfg["head_dim"] * itemsize)


def replayed_requests(tr: dict, seed: int, vocab: int) -> list:
    """Per client ``requests_per_client`` requests: lengths from the traffic
    file's lists in order, client ``c`` starting at entry ``c mod len``;
    token ids from the seed."""
    rng = rng_for(seed, "tokens")
    plens, olens = tr["prompt_lens"], tr["output_lens"]
    return [[{"prompt": rng.integers(0, vocab, plens[(c + i) % len(plens)],
                                     dtype=np.int64).astype(np.int32),
              "max_new_tokens": int(olens[(c + i) % len(olens)])}
             for i in range(tr["requests_per_client"])]
            for c in range(tr["clients"])]


class ServedSALA(serving.Served):
    """``Served`` over the MiniCPM-SALA engine: its clients, window and
    records; its own construction, trace counters and comparison."""

    def __init__(self, cell, args, ctx):  # noqa: D107 -- replaces Served's
        from paddle_tpu.core.monitor import StatRegistry
        from paddle_tpu.serving.llm import LLMEngine, LLMEngineConfig
        self.cfg, self.tr = cell["config_data"], cell["traffic_data"]
        self.args, self.ctx, self.cell_name = args, ctx, cell["name"]
        eng = self.tr["engine"]
        phases = harness.Phases(ctx["process_start"])
        phases.done("imports and device")
        over = {}
        if PROGRAM_TOPK_SHORT in (ctx.get("control_modes") or ()):
            over["sparse_topk"] = self.cfg["assumed"]["sparse_topk"] - 1
            print(f"control: the engine selects {over['sparse_topk']} blocks",
                  flush=True)
        net = sala_adapter.build_net(self.cfg, **over)
        phases.done("the program builds its model")
        sala_adapter.load_weights(net, self.cfg, args.seed)
        net.eval()
        phases.done("seeded weights made and loaded")
        self.registry = StatRegistry()
        num_pages = eng["kv_arena_bytes"] // page_bytes(self.cfg,
                                                        eng["page_size"])
        self.engine = LLMEngine(net, LLMEngineConfig(
            kv_layout="paged", num_slots=eng["num_slots"],
            max_seq=eng["max_seq"], page_size=eng["page_size"],
            num_pages=int(num_pages), prefill_chunk=eng["prefill_chunk"],
            prefill_buckets=[eng["prefill_chunk"]],
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
        self.trace_counters = None
        print(f"engine: {int(num_pages)} pages of {eng['page_size']} tokens, "
              f"{eng['num_slots']} slots, max_seq {eng['max_seq']}, chunks "
              f"of {eng['prefill_chunk']}, paged attention lane "
              f"{self.engine.stats()['paged_attn_impl']!r}", flush=True)

    def open_window(self) -> float:
        self.registry.reset(self.prefix + "prefill_chunk_ms")
        return super().open_window()

    def _trace(self):
        """``Served._trace`` with the engine's counters read at both ends of
        the traced stretch: what a per-layer metric counts beside a kernel's
        device time in the trace."""
        time.sleep(self.tr["trace_from_s"])
        self.tracer.start()
        before = dict(self.engine.stats()["stats"])
        time.sleep(self.tr["trace_seconds"])
        after = dict(self.engine.stats()["stats"])
        self.tracer.stop()
        self.trace_counters = {
            k[len(self.prefix):]: v - before.get(k, 0)
            for k, v in after.items() if isinstance(v, (int, float))}

    def compare(self, run: dict) -> dict:
        """Reference logits over a seeded sample of the finished requests,
        after the engine's memory is freed, each request up to its first
        served position with a selection near-tie."""
        sample = check.sample_finished(run["records"], self.args.seed,
                                       self.tr["check_requests"])
        if not sample:
            return {"served_token_gap": float("inf"), "left_out_share": 1.0}
        with open(os.path.join(spec.HERE, "limits",
                               self.cell_name + ".json")) as f:
            limits = json.load(f)
        tau, rule = float(limits["selection_margin_tau"]), limits[
            "near_tie_rule"]
        t = time.perf_counter()
        out = serve_gaps(
            self.cfg, self.args.seed, sample, tau, rule=rule,
            pad_len=self.tr["engine"]["max_seq"],
            max_new=max(self.tr["output_lens"]),
            control_modes=[m for m in (self.ctx.get("control_modes") or ())
                           if m in ref.MODES])
        print(f"reference: {len(sample)} requests, {out['tokens_compared']} "
              f"of {out['tokens_sampled']} served tokens compared, "
              f"{out['tokens_sampled'] - out['tokens_compared']} left out "
              f"by rule {rule!r} at a selection margin under {tau:g} "
              f"(smallest margin at "
              f"a served position {out['smallest_margin']:.3g}, anywhere "
              f"{out['smallest_margin_anywhere']:.3g}), in "
              f"{time.perf_counter() - t:.1f} s", flush=True)
        print("by tau (widest gap, tokens compared): " + json.dumps(
            {r: {f"{alt:g}": v for alt, v in by.items()}
             for r, by in out.pop("by_tau").items()}), flush=True)
        return out


# -- the comparison -------------------------------------------------------------

@jax.jit
def _gaps_at(top, hidden, rows, served):
    """Per served position, how far the served token's reference logit lies
    below the reference's best."""
    logits = ref.logits_of(top, hidden[rows])
    best = jnp.max(logits, axis=-1)
    return best - jnp.take_along_axis(logits, served[:, None], -1)[:, 0]


@functools.partial(jax.jit, static_argnums=(3,))
def _first_at(top, hidden, rows, mode):
    return jnp.argmax(ref.logits_of(top, hidden[rows], mode),
                      axis=-1).astype(jnp.int32)


def compared(margin, plen: int, n: int, tau: float, rule: str):
    """Which of a request's ``n`` served tokens are compared (bool ``[n]``):
    token ``j`` is predicted from position ``plen - 1 + j``."""
    if rule == "cut":
        return np.arange(n) < compared_tokens(margin, plen, n, tau)
    if rule == "own":
        return np.asarray(margin[plen - 1:plen - 1 + n]) >= tau
    raise ValueError(f"near_tie_rule must be one of {RULES}, got {rule!r}")


def serve_gaps(cfg, seed, sample, tau, pad_len, max_new, control_modes=(),
               rule="own"):
    """Widest served-token gap over the compared tokens of the sample, how
    many were sampled and compared and, for each control mode, the widest
    gap of the tokens that precision puts first at the same positions. The
    reference asks for a layer's weights when it reaches the layer."""
    arch = ref.arch_of(cfg)
    top = sala_weights.make_top(cfg, seed)

    def layer(i):
        return sala_weights.make_layer(cfg, seed, i)

    out = {"served_token_gap": 0.0, "tokens_compared": 0,
           "tokens_sampled": 0, "smallest_margin": float("inf"),
           "smallest_margin_anywhere": float("inf")}
    out.update({f"control_{m}_token_gap": 0.0 for m in control_modes})
    #: what other values of tau would have compared: (widest gap, tokens)
    out["by_tau"] = {r: {alt: (0.0, 0) for alt in TAU_SWEEP} for r in RULES}
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
        hidden, margin = ref.hidden_states(top, layer, arch, seq)
        gaps = {"served_token_gap": _gaps_at(top, hidden, rows,
                                             jnp.asarray(served))}
        for m in control_modes:
            low, _ = ref.hidden_states(top, layer, arch, seq, m)
            gaps[f"control_{m}_token_gap"] = _gaps_at(
                top, hidden, rows, _first_at(top, low, rows, m))
        margin = np.array(margin[:plen + n - 1])
        out["smallest_margin_anywhere"] = min(
            out["smallest_margin_anywhere"], float(margin.min()))
        margin[:plen - 1] = np.inf      # the prompt is not held to the rule
        served_gap = np.asarray(gaps["served_token_gap"][:n])
        for r, by in out["by_tau"].items():
            for alt, (widest, kept) in by.items():
                keep = compared(margin, plen, n, alt, r)
                by[alt] = (float(max([widest, *served_gap[keep]])),
                           kept + int(keep.sum()))
        keep = compared(margin, plen, n, tau, rule)
        for k, g in gaps.items():
            out[k] = float(max([out[k], *np.asarray(g[:n])[keep]]))
        out["tokens_compared"] += int(keep.sum())
        out["tokens_sampled"] += n
        out["smallest_margin"] = min(out["smallest_margin"],
                                     float(margin.min()))
    sala_weights.clear_programs()
    out["left_out_share"] = 1.0 - out["tokens_compared"] / out["tokens_sampled"]
    return out


# -- the loop (as drivers/closed.py runs it) ------------------------------------

def run(cell, args, ctx):
    served = ServedSALA(cell, args, ctx)
    tr, cfg = served.tr, served.cfg
    per_client = replayed_requests(tr, args.seed, cfg["vocab_size"])
    warm = rng_for(args.seed + 1, "tokens")
    served.warm_up([
        {"prompt": warm.integers(0, cfg["vocab_size"], tr["warm_prompt_len"],
                                 dtype=np.int64).astype(np.int32),
         "max_new_tokens": tr["warm_output_len"]}
        for _ in range(tr["warm_requests"])])
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
    if run["failed"]:      # a client the kill did not release: where is the
        import sys          # worker? (one run in nine, PERF.md section 7)
        import traceback
        frames = sys._current_frames()
        for th in threading.enumerate():
            if "llm-worker" in th.name and th.ident in frames:
                print("worker thread, after the drain:\n" + "".join(
                    traceback.format_stack(frames[th.ident])), flush=True)
    served.shutdown()
    run["trace_counters"] = served.trace_counters
    run["end_to_end"] = {
        "serve_tok_s": run["tokens_in_window"] / run["window_s"]}
    done = [r for r in run["records"] if r["finished"]]
    print(f"closed loop: {run['tokens_in_window']} tokens in the window, "
          f"{len(done)} of {run['attempted']} requests finished", flush=True)
    # what an untraced run's rate rests on: the ticks, the chunks and what
    # the selections read, and where the worker's time went
    counted = dict(run, cell=cell)
    tick = run["hist"].get("decode_tick_ms", {})
    chunk = run["hist"].get("prefill_chunk_ms", {})
    print("window: " + json.dumps({
        "ticks": tick.get("count", 0),
        "tick_ms": {k: tick.get(k) for k in ("p50", "mean", "p99", "max")},
        "chunks": run["counters"].get("prefill_chunks", 0),
        "chunk_ms": {k: chunk.get(k) for k in ("p50", "mean", "p99", "max")},
        "prefills": run["counters"].get("prefills", 0),
        **{m: spec.load_reader(m)(counted) for m in (
            "tick_batch_mean", "sparse_selected_page_share",
            "prefill_chunk_share_pct")},
        "blocks_computed_over_selected": (
            run["counters"].get("sparse_prefill.blocks_computed", 0)
            / max(run["counters"].get("sparse_prefill.blocks_selected", 0),
                  1)),
        "compiles": run["compiles_in_window"],
        "cache_misses": run["counters"].get("cache.misses"),
        "worker_s": worker_phases.phase_seconds(run), **stalls}), flush=True)
    run["numbers"] = served.compare(run)
    return run
