"""Driver ``fit``: ``paddle.Model.fit`` over seeded token batches.

One ``fit`` call holds set-up's steps and the window: the first compiles,
the first three feed the comparison with the reference, and after
``warm_steps`` the window opens on the same compiled step and state. The
window closes at the end of the first step that ends at or after
``--seconds``; the rate is the tokens of its steps over its length.
"""
from __future__ import annotations

import gc
import time

import numpy as np

from .. import check, gpt_adapter, harness, traffic as traffic_mod, weights


def run(cell, args, ctx):
    import paddle_tpu as paddle
    from paddle_tpu.models import GPTPretrainingCriterion

    cfg, tr = cell["config_data"], cell["traffic_data"]
    batch, seq, opt_cfg = tr["batch"], tr["seq_len"], tr["optimizer"]
    warm_steps = max(int(tr["warm_steps"]), 3)
    positions = max(cfg["n_positions"], seq)

    phases = harness.Phases(ctx["process_start"])
    phases.done("imports and device")
    net = gpt_adapter.build_net(cfg, positions, recompute=tr["recompute"])
    params = gpt_adapter.load_weights(
        net, weights.make_gpt_weights(cfg, args.seed, positions,
                                      sharp_attention=False))
    phases.done("model built, seeded weights made and loaded")
    memory = harness.MemoryPeak()
    opt = paddle.optimizer.AdamW(
        learning_rate=opt_cfg["lr"], beta1=opt_cfg["beta1"],
        beta2=opt_cfg["beta2"], epsilon=opt_cfg["epsilon"],
        parameters=list(params.values()),
        weight_decay=opt_cfg["weight_decay"])
    names = list(params)
    model = paddle.Model(net)
    model.prepare(opt, GPTPretrainingCriterion())

    class Rows(paddle.io.IterableDataset):
        stop = False

        def __iter__(self):
            for row in traffic_mod.train_rows(tr, args.seed,
                                              cfg["vocab_size"]):
                if self.stop:
                    return
                yield row, row.astype(np.int64)

    rows = Rows()
    tracer = harness.TraceWindow(ctx["out_dir"], ctx["rehearsal"]) if args.trace else None
    got = {"losses": []}
    steps = []                    # (begin, end) of the window's steps
    state = {"t0": None, "compiles0": 0}

    class Drive(paddle.callbacks.Callback):
        def on_train_batch_begin(self, step, logs=None):
            self.begin = time.perf_counter()
            self.span = harness.span("bench/step")
            self.span.__enter__()

        def on_train_batch_end(self, step, logs=None):
            self.span.__exit__(None, None, None)
            now = time.perf_counter()
            if step < 3:
                got["losses"].append(float(logs["loss"]))
            if step == 0:       # Adam's first moment is (1 - beta1) * gradient
                sd = opt.state_dict()
                got["grad_norms"] = check.leaf_norms(
                    {k: sd[f"param_{i}.moment1"]._data / (1 - opt_cfg["beta1"])
                     for i, k in enumerate(names)})
            if step == 2:
                # the start is made again from the seed, between steps, so
                # that no copy of it sits in memory while a step runs
                start = weights.make_gpt_weights(
                    cfg, args.seed, positions, sharp_attention=False)
                got["delta_norms"] = check.leaf_norms(
                    {k: params[k]._data - start[k] for k in names})
            if step == 0:
                phases.done("first step (trace, lower, compile or cache)")
            if step + 1 == warm_steps:
                phases.done(f"steps 2 to {warm_steps} and the readings")
                state["t0"] = now
                state["compiles0"] = ctx["compiles"].requests
                return
            if state["t0"] is None:
                return
            steps.append((self.begin, now))
            memory.sample()
            if tracer is not None:
                if len(steps) == tr["trace_from_step"]:
                    tracer.start()
                elif len(steps) == tr["trace_from_step"] + tr["trace_steps"]:
                    tracer.stop()
            if now - state["t0"] >= args.seconds:
                rows.stop = True

    with paddle.amp.auto_cast(enable=True, dtype=tr["autocast"]):
        model.fit(rows, batch_size=batch, epochs=1, shuffle=False, verbose=0,
                  callbacks=[Drive()])
    t0, t1 = state["t0"], steps[-1][1]
    run = {"window_s": t1 - t0, "steps": steps,
           "tokens_per_step": batch * seq,
           "compiles_in_window": ctx["compiles"].requests - state["compiles0"],
           "peak_bytes": memory.peak,
           "setup_s": t0 - ctx["process_start"],
           "attempted": len(steps), "failed": 0}
    run["end_to_end"] = {
        "train_tok_s": len(steps) * batch * seq / run["window_s"]}
    if tracer is not None:
        run["trace"] = tracer.reduced()
    print(f"train: {len(steps)} steps in {run['window_s']:.3f} s, first "
          f"losses {got['losses']}", flush=True)

    # the reference, once the program's state is freed
    del model, opt, net, params
    gc.collect()
    w = weights.make_gpt_weights(cfg, args.seed, positions,
                                 sharp_attention=False)
    stream = traffic_mod.train_rows(tr, args.seed, cfg["vocab_size"])
    batches = [np.stack([next(stream) for _ in range(batch)])
               for _ in range(3)]
    t_ref = time.perf_counter()
    want = check.reference_train_numbers(w, cfg, batches, opt_cfg)
    run["numbers"] = check.train_gaps(got, want)
    print(f"reference: three steps in {time.perf_counter() - t_ref:.1f} s, "
          f"losses {want['losses']}", flush=True)
    for mode in ctx.get("control_modes") or ():   # never in the benchmark's
        low = check.reference_train_numbers(w, cfg, batches, opt_cfg,  # own runs
                                            mode=mode)
        run["numbers"].update({f"control_{mode}_{k}": v for k, v in
                               check.train_gaps(low, want).items()})
    return run
