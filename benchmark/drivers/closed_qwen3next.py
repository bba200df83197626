"""Driver ``closed_qwen3next``: the ``closed`` loop (as many clients as slots,
each sending its next request when its last one ends, the window cut at its
end) over the Qwen3-Next engine with chunked prefill: pages for the full
layers, a gated delta-rule state and a convolution window a slot and linear
layer. It reuses ``serving.Served``'s clients, window and records,
``closed_sala``'s replayed lengths and trace counters, ``closed_lfm2``'s
stall watch, ``closed_trinity``'s staggered clients and near-tie rule
``attended`` and ``closed_moonlight``'s sample (one finished request of each
ENTRY of the replayed lists) and sparing, and replaces construction (the
program's Qwen3-Next model with ``qwen3next_weights``, the chip's share of
experts and vocabulary, an arena of the full layers' pages whose bytes are
checked against the traffic file) and the comparison (``qwen3next_ref``, the
recurrence a token a step, a layer's weights at a time).

**Near-ties.** Top-10 of 512 is discontinuous and a 16k prompt makes 131k
choices: ``closed_trinity``'s docstring tells what a flipped choice reaches
through attention. Here it ALSO reaches every later token through a linear
layer's state, for as long as the head that took it remembers, so the
reference's ``risk`` follows both (``qwen3next_ref.py``, "Near-ties"). A
served token is left out when the risk at its predicting position is
``risk_rho`` or more, or when the routing margin of its OWN predicting
position is under ``own_margin_tau`` (``closed_moonlight``'s two halves); the
rule reads the reference alone, and the share left out is a number of
``correct`` (``left_out_share``). Every comparison prints the widest gap and
the tokens compared for a sweep of ``risk_rho`` (``by rho``): the readings
the limits file is set by.

**What is judged**: what ``serve-moonlight-longgen`` judges, for its reasons
(``closed_moonlight``'s docstring): ``served_not_first_share``, the share of
the compared tokens that are not the reference's first choice (the head's
paired columns make that a count of roundings); ``served_token_gap``, the
widest gap but for the ``spared_share`` widest; ``first_token_gap``, the
widest gap of the requests' FIRST tokens, which the chunk program makes and
nothing spares; and ``left_out_share``. The widest gap of all is printed
beside them (``widest_token_gap``).

Control modes (calibration runs, ``run.main(argv, control_modes=...)``):
``high`` and ``bfloat16`` are the reference's own lower-precision passes; four
build the ENGINE wrong in one way each (the reference keeps the
configuration's): ``program_no_decay`` (``alpha = 1``: the rule's gate left
out, in the chunk program and the step), ``program_no_correction`` (``r_t =
v_t``: the delta left out, a plain gated linear attention),
``program_rotary_all`` (the rotary positions over all 256 columns of a head)
and ``program_shared_ungated`` (the shared expert unweighted). Such a run's
own numbers are the reading of a program that is wrong in that way.
"""
from __future__ import annotations

import contextlib
import functools
import json
import os
import threading
import time

import jax
import jax.numpy as jnp
import numpy as np

from .. import harness, qwen3next_adapter, qwen3next_weights, spec, \
    worker_phases
from ..reference import qwen3next_ref as ref
from ..traffic import rng_for
from .closed_lfm2 import StallWatch
from .closed_moonlight import sample_by_entry, spared_widest
from .closed_sala import ServedSALA, replayed_requests
from .closed_trinity import ATTENDED, RHO_SWEEP, keeps

PROGRAM_NO_DECAY = "program_no_decay"
PROGRAM_NO_CORRECTION = "program_no_correction"
PROGRAM_ROTARY_ALL = "program_rotary_all"
PROGRAM_SHARED_UNGATED = "program_shared_ungated"
PROGRAM_MODES = (PROGRAM_NO_DECAY, PROGRAM_NO_CORRECTION, PROGRAM_ROTARY_ALL,
                 PROGRAM_SHARED_UNGATED)


def page_bytes(cfg: dict, page_size: int, itemsize: int = 4) -> int:
    """Bytes of one page: ``page_size`` rows of ``[K | V]`` of every KV head
    in every FULL layer (the linear layers keep no rows)."""
    full = cfg["num_hidden_layers"] // cfg["full_attention_interval"]
    return (page_size * full * cfg["num_key_value_heads"]
            * 2 * cfg["head_dim"] * itemsize)


def _uncorrected_step(q, k, v, g, beta, state):
    """``gated_delta_step`` with ``r_t = v_t``."""
    hv = v.shape[1]
    q, k = (jnp.repeat(x, hv // x.shape[1], axis=1) for x in (q, k))
    state = state * jnp.exp(g)[..., None, None] \
        + (beta[..., None] * k)[..., None] * v[..., None, :]
    return jnp.sum(state * q[..., None], axis=-2), state


def _uncorrected_chunked(q, k, v, g, beta, state, lens):
    """``gated_delta_chunked`` with ``r_t = v_t``, a token a step."""
    real = jnp.arange(q.shape[1])[None] < lens[:, None]
    g = jnp.where(real[..., None], g, 0.0)
    beta = jnp.where(real[..., None], beta, 0.0)

    def token(s, xs):
        o, s = _uncorrected_step(*xs, s)
        return s, o

    state, o = jax.lax.scan(token, state, tuple(
        jnp.moveaxis(x, 1, 0) for x in (q, k, v, g, beta)))
    return jnp.moveaxis(o, 0, 1), state


@contextlib.contextmanager
def faulty_program(modes):
    """The program as a calibration run wants it: wrong in the ways ``modes``
    name. Yields the configuration class to build the model with; the
    program's own is put back on the way out."""
    from paddle_tpu.models import qwen3next as model
    from paddle_tpu.serving.llm.paged import qwen3next as paged
    config_cls = model.Qwen3NextConfig
    sound = (paged.gated_delta_step, paged.gated_delta_chunked,
             model.gated_shared_expert)
    if PROGRAM_ROTARY_ALL in modes:
        class RotaryAll(model.Qwen3NextConfig):
            rotary_dim = property(lambda c: c.head_dim)
        config_cls = RotaryAll
        print("control: the engine rotates all the columns of a head",
              flush=True)
    if PROGRAM_NO_DECAY in modes:
        step, chunked = sound[:2]
        paged.gated_delta_step = lambda q, k, v, g, *rest: step(
            q, k, v, jnp.zeros_like(g), *rest)
        paged.gated_delta_chunked = lambda q, k, v, g, *rest: chunked(
            q, k, v, jnp.zeros_like(g), *rest)
        print("control: the engine's gated delta rule does not decay "
              "(alpha = 1)", flush=True)
    if PROGRAM_NO_CORRECTION in modes:
        paged.gated_delta_step = _uncorrected_step
        paged.gated_delta_chunked = _uncorrected_chunked
        print("control: the engine's gated delta rule writes v_t "
              "uncorrected (r_t = v_t)", flush=True)
    if PROGRAM_SHARED_UNGATED in modes:
        model.gated_shared_expert = lambda f, lp: model.swiglu(
            f, lp["s1"][0], lp["s3"][0], lp["s2"][0])
        print("control: the engine's shared expert is not gated", flush=True)
    programs = (paged.get_qwen3next_paged_decode_step,
                paged.get_qwen3next_paged_chunk_fn)
    for cached in programs:
        cached.cache_clear()
    try:
        yield config_cls
    finally:
        (paged.gated_delta_step, paged.gated_delta_chunked,
         model.gated_shared_expert) = sound
        for cached in programs:
            cached.cache_clear()


class ServedQwen3Next(ServedSALA):
    """``Served`` over the Qwen3-Next engine: its clients, window and
    records, ``ServedSALA``'s trace counters; its own construction and
    comparison."""

    def __init__(self, cell, args, ctx):  # noqa: D107 -- replaces Served's
        from paddle_tpu.core.monitor import StatRegistry
        from paddle_tpu.serving.llm import LLMEngine, LLMEngineConfig
        self.cfg, self.tr = cell["config_data"], cell["traffic_data"]
        self.args, self.ctx, self.cell_name = args, ctx, cell["name"]
        eng = self.tr["engine"]
        phases = harness.Phases(ctx["process_start"])
        phases.done("imports and device")
        with faulty_program(ctx.get("control_modes") or ()) as config_cls:
            net = qwen3next_adapter.build_net(self.cfg, config_cls)
            phases.done("the program builds its model")
            qwen3next_adapter.load_weights(net, self.cfg, args.seed)
            net.eval()
            phases.done("seeded weights made and loaded")
            self.registry = StatRegistry()
            self.engine = LLMEngine(net, LLMEngineConfig(
                kv_layout="paged", num_slots=eng["num_slots"],
                max_seq=eng["max_seq"], page_size=eng["page_size"],
                num_pages=eng["num_pages"],
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
        kv = self.engine._batcher.kv
        #: what the engine says of its cache, once (a gauge does not move)
        self.gauges = {
            "kv_row_bytes": stats["stats"][self.prefix + "kv_row_bytes"],
            "gdn_state_bytes":
                stats["stats"][self.prefix + "gdn_state_bytes"],
            "kv_bytes": kv.kv_bytes(),
            "paged_attn_recurrence": stats["paged_attn_recurrence"]}
        held = page_bytes(self.cfg, eng["page_size"]) * (eng["num_pages"] + 1)
        if kv.kv_bytes() != held or kv.v.size:
            raise RuntimeError(
                f"the engine's cache holds {kv.kv_bytes()} bytes (a second "
                f"arena of {kv.v.size}), not ONE arena of {held}")
        print(f"engine: {eng['num_pages']} pages of {eng['page_size']} rows "
              f"of {self.gauges['kv_row_bytes']} bytes a full layer, "
              f"{self.gauges['kv_bytes']} bytes in ONE arena; "
              f"{self.gauges['gdn_state_bytes']} bytes of linear-layer "
              f"state; {eng['num_slots']} slots, max_seq {eng['max_seq']}, "
              f"chunks of {eng['prefill_chunk']}, paged attention lane "
              f"{stats['paged_attn_impl']!r}, recurrence "
              f"{stats['paged_attn_recurrence']!r}", flush=True)

    def compare(self, run: dict) -> dict:
        """Reference logits over one finished request of each entry of the
        replayed lists, after the engine's memory is freed, under the limits
        file's near-tie rule."""
        sample = sample_by_entry(run["records"], self.args.seed,
                                 self.tr["prompt_lens"],
                                 self.tr["output_lens"])
        if not sample:
            return {"served_token_gap": float("inf"), "left_out_share": 1.0}
        with open(os.path.join(spec.HERE, "limits",
                               self.cell_name + ".json")) as f:
            limits = json.load(f)
        tau, rho = float(limits["routing_margin_tau"]), float(
            limits["risk_rho"])
        own_tau = float(limits["own_margin_tau"])
        if limits["near_tie_rule"] != ATTENDED:
            raise ValueError("this cell's near_tie_rule is 'attended'")
        t = time.perf_counter()
        out = serve_gaps(
            self.cfg, self.args.seed, sample, tau, rho, own_tau=own_tau,
            spared_share=float(limits["spared_share"]),
            pad_len=self.tr["engine"]["max_seq"],
            max_new=max(self.tr["output_lens"]),
            control_modes=[m for m in (self.ctx.get("control_modes") or ())
                           if m in ref.MODES])
        print(f"reference: {len(sample)} requests (prompts and outputs "
              f"{[(len(r['prompt']), len(r['tokens'])) for r in sample]}), "
              f"{out['tokens_compared']} of {out['tokens_sampled']} served "
              f"tokens compared, "
              f"{out['tokens_sampled'] - out['tokens_compared']} left out "
              f"by rule 'attended' at a routing margin under {tau:g} and a "
              f"risk of {rho:g} or more, or by an own margin under "
              f"{own_tau:g} (smallest margin at a served position "
              f"{out['smallest_margin']:.3g}, anywhere "
              f"{out['smallest_margin_anywhere']:.3g}), in "
              f"{time.perf_counter() - t:.1f} s; widest gap of all compared "
              f"{out['widest_token_gap']:.6g}, {out['tokens_spared']} "
              f"spared, {out['tokens_not_first']} not the reference's first",
              flush=True)
        print(f"by rho at tau {tau:g} (widest gap, tokens compared): "
              + json.dumps({f"{alt:g}": v
                            for alt, v in out.pop("by_rho").items()}),
              flush=True)
        print("by request (prompt, widest gap of all its tokens, tokens "
              "over 1e-3, tokens not first, largest risk; the widest-gap "
              "token's index, margin and risk): "
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


def serve_gaps(cfg, seed, sample, tau, rho, pad_len, max_new,
               control_modes=(), own_tau=None, spared_share=0.0):
    """Over the compared tokens of the sample: the widest served-token gap
    but for the spared (``served_token_gap``), the widest of all
    (``widest_token_gap``) and of the requests' first tokens
    (``first_token_gap``), the share that is not the reference's first
    choice (``served_not_first_share``), how many were sampled and compared
    and, for each control mode, the same of the tokens that precision puts
    first at the same positions (``control_<mode>_token_gap``,
    ``control_<mode>_not_first_share``). The reference asks for a layer's
    weights when it reaches the layer. A token is compared when the risk at
    its predicting position is under ``rho`` and that position's own margin
    is at least ``own_tau`` (``tau`` if None)."""
    own_tau = tau if own_tau is None else own_tau
    arch = ref.arch_of(cfg)
    top = qwen3next_weights.make_top(cfg, seed)

    def layer(i):
        return qwen3next_weights.make_layer(cfg, seed, i)

    out = {"first_token_gap": 0.0, "tokens_compared": 0,
           "tokens_sampled": 0, "smallest_margin": float("inf"),
           "smallest_margin_anywhere": float("inf")}
    names = {"served_token_gap": "served_not_first_share"}
    names.update({f"control_{m}_token_gap": f"control_{m}_not_first_share"
                  for m in control_modes})
    compared = {k: [] for k in names}
    #: what other values of rho would have compared: (widest gap, tokens)
    out["by_rho"] = {alt: (0.0, 0) for alt in RHO_SWEEP}
    out["by_request"] = []
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
        risk = np.asarray(risk)
        for alt, (widest, count) in out["by_rho"].items():
            keep = keeps(margin, risk, plen, n, tau, ATTENDED, alt)
            out["by_rho"][alt] = (float(max([widest, *served_gap[keep]])),
                                  count + int(keep.sum()))
        worst = int(served_gap.argmax())
        out["by_request"].append(
            [plen, float(served_gap.max()), int((served_gap > 1e-3).sum()),
             int((served_gap > 0).sum()),
             float(risk[plen - 1:plen - 1 + n].max()), worst,
             float(margin[plen - 1 + worst]), float(risk[plen - 1 + worst])])
        keep = keeps(margin, risk, plen, n, tau, ATTENDED, rho) \
            & (margin[plen - 1:plen - 1 + n] >= own_tau)
        for k, g in gaps.items():
            compared[k].append(np.asarray(g[:n])[keep])
        if keep[0]:
            out["first_token_gap"] = max(out["first_token_gap"],
                                         float(served_gap[0]))
        out["tokens_compared"] += int(keep.sum())
        out["tokens_sampled"] += n
        out["smallest_margin"] = min(out["smallest_margin"],
                                     float(margin.min()))
    qwen3next_weights.clear_programs()
    for k, share in names.items():
        g = np.concatenate(compared[k]) if compared[k] else np.zeros(0)
        out[k], spared = spared_widest(g, spared_share)
        out[share] = float((g > 0).mean()) if len(g) else 0.0
        if k == "served_token_gap":
            out["widest_token_gap"] = float(g.max()) if len(g) else 0.0
            out["tokens_spared"] = spared
            out["tokens_not_first"] = int((g > 0).sum())
    out["left_out_share"] = 1.0 - out["tokens_compared"] / out["tokens_sampled"]
    return out


# -- the loop (as drivers/closed_moonlight.py runs it) --------------------------

def run(cell, args, ctx):
    served = ServedQwen3Next(cell, args, ctx)
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
    run["gauges"] = served.gauges
    run["end_to_end"] = {
        "serve_tok_s": run["tokens_in_window"] / run["window_s"]}
    done = [r for r in run["records"] if r["finished"]]
    print(f"closed loop: {run['tokens_in_window']} tokens in the window, "
          f"{len(done)} of {run['attempted']} requests finished", flush=True)
    # what an untraced run's rate rests on: the ticks, the chunks, the rows
    # the linear layers scanned and stepped, where the worker's time went
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
        "gdn_chunk_rows": run["counters"].get("gdn.chunk_rows", 0),
        "gdn_step_rows": run["counters"].get("gdn.step_rows", 0),
        "pages_live_a_tick": (
            run["counters"].get("paged_attn.pages_live", 0)
            / tick["count"] if tick.get("count") else None),
        **served.gauges,
        **{m: spec.load_reader(m)(counted) for m in (
            "tick_batch_mean", "prefill_chunk_share_pct",
            "moe_load_max_mean")},
        "held_experts_active_a_tick_and_layer": (
            run["counters"].get("moe_experts_active", 0) / tick["count"]
            / cfg["num_hidden_layers"] if tick.get("count") else None),
        "compiles": run["compiles_in_window"],
        "cache_misses": run["counters"].get("cache.misses"),
        "worker_s": worker_phases.phase_seconds(run), **stalls}), flush=True)
    run["numbers"] = served.compare(run)
    return run
