"""Driver ``closed_trinity``: the ``closed`` loop (as many clients as slots,
each sending its next request when its last one ends, the window cut at its
end) over the Trinity engine with chunked prefill and two page groups. It
reuses ``serving.Served``'s clients, window and records, ``closed_sala``'s
replayed lengths, trace counters and near-tie rules and ``closed_lfm2``'s
stall watch, and replaces three things: construction (the program's Trinity
model with ``trinity_weights``, the chip's share of experts and vocabulary,
a page count for the full attention layers' group; the window layers' group
is sized by the decoder and checked against the traffic file), the clients'
start (``client_stagger_s`` apart, so that the prompts do not arrive in
waves) and the comparison (``trinity_ref``, a layer's weights at a time, one
sampled request of each prompt length).

**Near-ties.** Top-8 of 128 is discontinuous: where the eighth and the ninth
of ``s + expert_bias`` lie within rounding the program and the reference may
choose differently, and here a flipped choice moves that position's state by
a large part of itself (about one held expert a token, and the layer's result
is normed), so every later token that attends to the row differs too: a
prompt of 16k tokens always holds near-ties, and a rule that looks at a
token's OWN position alone let gaps of 0.1-0.3 through in six runs of seven
(PERF.md section 6, PR 32). The reference therefore returns, beside each
position's smallest routing margin, its ``risk``: 1 where the position's own
margin is under ``routing_margin_tau``, and otherwise how much of what its
attention reads rests on such positions (``trinity_ref``'s docstring). The
limits file names the rule (``near_tie_rule``): ``attended`` leaves out a
served token when the risk at its predicting position is ``risk_rho`` or
more; ``own`` and ``cut`` are ``closed_sala``'s, kept for the readings. All
read the reference alone, and the prompt's positions are sources of risk and
never compared themselves. The share of sampled tokens left out is a number
of ``correct`` too (``left_out_share``). Every comparison prints, for every
rule and a sweep of its parameter, the widest gap and the tokens compared
(``by tau``, ``by rho``): the readings the limits file is set by.

Control modes (calibration runs, ``run.main(argv, control_modes=...)``):
``high`` and ``bfloat16`` are the reference's own lower-precision passes;
``program_window_short`` builds the ENGINE with ``sliding_window - 1`` and
``program_no_shared`` builds it without the shared expert (the reference
keeps the configuration's): such a run's own ``served_token_gap`` is the
reading of a program that is wrong in that way.
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

from .. import harness, spec, trinity_adapter, trinity_weights, worker_phases
from ..reference import trinity_ref as ref
from ..traffic import rng_for
from .closed_lfm2 import StallWatch
from .closed_sala import RULES, TAU_SWEEP, ServedSALA, compared, \
    replayed_requests

#: the rule of this cell, beside closed_sala's two
ATTENDED = "attended"
#: the comparison prints what these values of ``risk_rho`` would have compared
RHO_SWEEP = (1.0, 0.3, 0.1, 0.05, 0.03, 0.02, 0.01, 0.005, 0.003, 0.001)

#: a calibration mode: the comparison also prints what the risk under these
#: values of ``routing_margin_tau`` would have compared (a forward each)
RISK_TAU_SWEEP = "risk_tau_sweep"
SWEPT_TAUS = (3e-5,)

PROGRAM_WINDOW_SHORT = "program_window_short"
PROGRAM_NO_SHARED = "program_no_shared"


def page_bytes(cfg: dict, page_size: int, kind: str, itemsize: int = 4) -> int:
    """Bytes of one KV page of the group of ``kind`` layers
    (``full_attention`` or ``sliding_attention``): K and V rows of every
    layer of that kind, over the KV heads."""
    layers = cfg["layer_types"][:cfg["num_hidden_layers"]].count(kind)
    return (2 * page_size * layers * cfg["num_key_value_heads"]
            * cfg["head_dim"] * itemsize)


def sample_by_length(records, seed: int, lengths) -> list:
    """One finished request of each prompt length in ``lengths``, drawn by
    the seed among those the window finished, the longest first."""
    rng = rng_for(seed, "check")
    out = []
    for plen in sorted(set(lengths), reverse=True):
        done = [r for r in records if r["finished"] and r["tokens"]
                and len(r["prompt"]) == plen]
        if done:
            out.append(done[int(rng.integers(len(done)))])
    return out


class ServedTrinity(ServedSALA):
    """``Served`` over the Trinity engine: its clients, window and records,
    ``ServedSALA``'s trace counters; its own construction and comparison."""

    def __init__(self, cell, args, ctx):  # noqa: D107 -- replaces Served's
        from paddle_tpu.core.monitor import StatRegistry
        from paddle_tpu.serving.llm import LLMEngine, LLMEngineConfig
        self.cfg, self.tr = cell["config_data"], cell["traffic_data"]
        self.args, self.ctx, self.cell_name = args, ctx, cell["name"]
        eng = self.tr["engine"]
        phases = harness.Phases(ctx["process_start"])
        phases.done("imports and device")
        over, modes = {}, ctx.get("control_modes") or ()
        if PROGRAM_WINDOW_SHORT in modes:
            over["sliding_window"] = self.cfg["sliding_window"] - 1
        if PROGRAM_NO_SHARED in modes:
            over["num_shared_experts"] = 0
        if over:
            print(f"control: the engine is built with {over}", flush=True)
        net = trinity_adapter.build_net(self.cfg, **over)
        phases.done("the program builds its model")
        trinity_adapter.load_weights(net, self.cfg, args.seed)
        net.eval()
        phases.done("seeded weights made and loaded")
        self.registry = StatRegistry()
        self.engine = LLMEngine(net, LLMEngineConfig(
            kv_layout="paged", num_slots=eng["num_slots"],
            max_seq=eng["max_seq"], page_size=eng["page_size"],
            num_pages=eng["num_pages_full"],
            prefill_chunk=eng["prefill_chunk"],
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
        stats = self.engine.stats()
        groups = {("window" if g.window else "full"): g.num_pages
                  for g in self.engine._batcher.kv.groups}
        if groups != {"full": eng["num_pages_full"],
                      "window": eng["num_pages_window"]}:
            raise RuntimeError(f"the engine's page groups {groups} are not "
                               f"the traffic file's")
        print(f"engine: page groups {groups} of {eng['page_size']} tokens "
              f"({ {k[len(self.prefix):]: v for k, v in stats['stats'].items() if 'kv_group_bytes' in k} } "
              f"bytes), {eng['num_slots']} slots, max_seq {eng['max_seq']}, "
              f"chunks of {eng['prefill_chunk']}, paged attention lane "
              f"{stats['paged_attn_impl']!r}", flush=True)

    def compare(self, run: dict) -> dict:
        """Reference logits over one finished request of each prompt length,
        after the engine's memory is freed, under the limits file's
        near-tie rule."""
        sample = sample_by_length(run["records"], self.args.seed,
                                  self.tr["prompt_lens"])
        if not sample:
            return {"served_token_gap": float("inf"), "left_out_share": 1.0}
        with open(os.path.join(spec.HERE, "limits",
                               self.cell_name + ".json")) as f:
            limits = json.load(f)
        tau, rule = float(limits["routing_margin_tau"]), limits[
            "near_tie_rule"]
        t = time.perf_counter()
        out = serve_gaps(
            self.cfg, self.args.seed, sample, tau, rule=rule,
            rho=float(limits["risk_rho"]),
            pad_len=self.tr["engine"]["max_seq"],
            max_new=max(self.tr["output_lens"]),
            control_modes=[m for m in (self.ctx.get("control_modes") or ())
                           if m in ref.MODES],
            swept_taus=SWEPT_TAUS if RISK_TAU_SWEEP in (
                self.ctx.get("control_modes") or ()) else ())
        print(f"reference: {len(sample)} requests (prompts "
              f"{[len(r['prompt']) for r in sample]}), "
              f"{out['tokens_compared']} of {out['tokens_sampled']} served "
              f"tokens compared, "
              f"{out['tokens_sampled'] - out['tokens_compared']} left out "
              f"by rule {rule!r} at a routing margin under {tau:g} "
              f"(smallest margin at a served position "
              f"{out['smallest_margin']:.3g}, anywhere "
              f"{out['smallest_margin_anywhere']:.3g}), in "
              f"{time.perf_counter() - t:.1f} s", flush=True)
        print("by tau (widest gap, tokens compared): " + json.dumps(
            {r: {f"{alt:g}": v for alt, v in by.items()}
             for r, by in out.pop("by_tau").items()}), flush=True)
        print(f"by rho at tau {tau:g} (widest gap, tokens compared): "
              + json.dumps({f"{alt:g}": v
                            for alt, v in out.pop("by_rho").items()}),
              flush=True)
        for alt, by in out.pop("by_swept_tau").items():
            print(f"by rho at tau {alt:g} (widest gap, tokens compared): "
                  + json.dumps({f"{r:g}": v for r, v in by.items()}),
                  flush=True)
        print("by request (prompt, widest gap of all its tokens, tokens "
              "over 1e-3, largest risk; the widest-gap token's index, "
              "margin and risk): "
              + json.dumps(out.pop("by_request")), flush=True)
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


def keeps(margin, risk, plen: int, n: int, tau: float, rule: str,
         rho: float):
    """Which of a request's ``n`` served tokens are compared (bool ``[n]``):
    token ``j`` is predicted from position ``plen - 1 + j``."""
    if rule == ATTENDED:
        return np.asarray(risk[plen - 1:plen - 1 + n]) < rho
    return compared(margin, plen, n, tau, rule)


def serve_gaps(cfg, seed, sample, tau, pad_len, max_new, control_modes=(),
               rule=ATTENDED, rho=1.0, swept_taus=()):
    """Widest served-token gap over the compared tokens of the sample, how
    many were sampled and compared and, for each control mode, the widest
    gap of the tokens that precision puts first at the same positions. The
    reference asks for a layer's weights when it reaches the layer."""
    arch = ref.arch_of(cfg)
    top = trinity_weights.make_top(cfg, seed)

    def layer(i):
        return trinity_weights.make_layer(cfg, seed, i)

    out = {"served_token_gap": 0.0, "tokens_compared": 0,
           "tokens_sampled": 0, "smallest_margin": float("inf"),
           "smallest_margin_anywhere": float("inf")}
    out.update({f"control_{m}_token_gap": 0.0 for m in control_modes})
    #: what other values of tau would have compared: (widest gap, tokens)
    out["by_tau"] = {r: {alt: (0.0, 0) for alt in TAU_SWEEP} for r in RULES}
    out["by_rho"] = {alt: (0.0, 0) for alt in RHO_SWEEP}
    out["by_request"] = []
    out["by_swept_tau"] = {alt: {r: (0.0, 0) for r in RHO_SWEEP}
                           for alt in swept_taus}
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
        hidden, margin, risk = ref.hidden_states(top, layer, arch, seq,
                                                 tau=tau)
        gaps = {"served_token_gap": _gaps_at(top, hidden, rows,
                                             jnp.asarray(served))}
        for m in control_modes:
            low, _, _ = ref.hidden_states(top, layer, arch, seq, m)
            gaps[f"control_{m}_token_gap"] = _gaps_at(
                top, hidden, rows, _first_at(top, low, rows, m))
        margin = np.array(margin[:plen + n - 1])
        out["smallest_margin_anywhere"] = min(
            out["smallest_margin_anywhere"], float(margin.min()))
        margin[:plen - 1] = np.inf      # the prompt is not held to the rule
        served_gap = np.asarray(gaps["served_token_gap"][:n])
        for r_, by in out["by_tau"].items():
            for alt, (widest, kept) in by.items():
                keep = compared(margin, plen, n, alt, r_)
                by[alt] = (float(max([widest, *served_gap[keep]])),
                           kept + int(keep.sum()))
        risk = np.asarray(risk)
        for alt, (widest, count) in out["by_rho"].items():
            keep = keeps(margin, risk, plen, n, tau, ATTENDED, alt)
            out["by_rho"][alt] = (float(max([widest, *served_gap[keep]])),
                                  count + int(keep.sum()))
        for alt, by in out["by_swept_tau"].items():
            other = np.asarray(ref.hidden_states(top, layer, arch, seq,
                                                 tau=alt)[2])
            for r_, (widest, count) in by.items():
                keep = keeps(margin, other, plen, n, alt, ATTENDED, r_)
                by[r_] = (float(max([widest, *served_gap[keep]])),
                          count + int(keep.sum()))
        worst = int(served_gap.argmax())
        out["by_request"].append(
            [plen, float(served_gap.max()), int((served_gap > 1e-3).sum()),
             float(risk[plen - 1:plen - 1 + n].max()), worst,
             float(margin[plen - 1 + worst]), float(risk[plen - 1 + worst])])
        keep = keeps(margin, risk, plen, n, tau, rule, rho)
        for k, g in gaps.items():
            out[k] = float(max([out[k], *np.asarray(g[:n])[keep]]))
        out["tokens_compared"] += int(keep.sum())
        out["tokens_sampled"] += n
        out["smallest_margin"] = min(out["smallest_margin"],
                                     float(margin.min()))
    trinity_weights.clear_programs()
    out["left_out_share"] = 1.0 - out["tokens_compared"] / out["tokens_sampled"]
    return out


# -- the loop (as drivers/closed.py runs it) ------------------------------------

def run(cell, args, ctx):
    served = ServedTrinity(cell, args, ctx)
    tr, cfg = served.tr, served.cfg
    per_client = replayed_requests(tr, args.seed, cfg["vocab_size"])
    warm = rng_for(args.seed + 1, "tokens")
    served.warm_up([
        {"prompt": warm.integers(0, cfg["vocab_size"], tr["warm_prompt_len"],
                                 dtype=np.int64).astype(np.int32),
         "max_new_tokens": tr["warm_output_len"]}
        for _ in range(tr["warm_requests"])])
    stop = threading.Event()

    def client(index, requests):
        # client c sends its first request c stagger-steps into the loop
        if stop.wait(index * tr["client_stagger_s"]):
            return
        for request in requests:
            if stop.is_set():
                return
            served.send(request, due=time.perf_counter(), timed=True)

    clients = [threading.Thread(target=client, args=(i, reqs),
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
    run["trace_counters"] = served.trace_counters
    run["end_to_end"] = {
        "serve_tok_s": run["tokens_in_window"] / run["window_s"]}
    done = [r for r in run["records"] if r["finished"]]
    print(f"closed loop: {run['tokens_in_window']} tokens in the window, "
          f"{len(done)} of {run['attempted']} requests finished", flush=True)
    # what an untraced run's rate rests on: the ticks, the chunks, what the
    # windows spared, and where the worker's time went
    counted = dict(run, cell=cell)
    tick = run["hist"].get("decode_tick_ms", {})
    chunk = run["hist"].get("prefill_chunk_ms", {})
    print("window: " + json.dumps({
        "ticks": tick.get("count", 0),
        "tick_ms": {k: tick.get(k) for k in ("p50", "mean", "p99", "max")},
        "chunks": run["counters"].get("prefill_chunks", 0),
        "chunk_ms": {k: chunk.get(k) for k in ("p50", "mean", "p99", "max")},
        "chunk_stalls": run["counters"].get("prefill_chunk_stalls", 0),
        "prefills": run["counters"].get("prefills", 0),
        **{m: spec.load_reader(m)(counted) for m in (
            "tick_batch_mean", "window_walk_page_share",
            "window_held_page_share", "prefill_chunk_share_pct",
            "moe_experts_active_mean", "moe_load_max_mean")},
        "compiles": run["compiles_in_window"],
        "cache_misses": run["counters"].get("cache.misses"),
        "worker_s": worker_phases.phase_seconds(run), **stalls}), flush=True)
    run["numbers"] = served.compare(run)
    return run
