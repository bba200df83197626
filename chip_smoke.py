"""chip_smoke.py — the quickest proof that the system still starts on the chip.

Drives the main path once, in one process, through the entry points a user
calls, at GPT-small width (``GPTConfig(vocab_size=50304, hidden_size=768,
num_layers=12, num_heads=12, max_position_embeddings=4096)``, random weights
from a seed):

0. kernels — every Pallas kernel on the path (flash forward, dQ, dK/dV, paged
   decode) at the path's real shapes and committed block configs, against its
   reference lane (dense attention, the page-gather lane).
1. train   — ``paddle.Model.fit`` over a seeded in-memory dataset, batch 4 x
   S=4096, bf16 autocast, per-block recompute: finite falling loss, and the
   lowered train step holds the flash fwd/dQ/dKV kernels as Mosaic calls.
2. serve   — the same network behind ``LLMEngine(kv_layout="paged")``:
   streamed requests of different lengths, greedy tokens equal to
   ``model.generate``, the paged kernel a Mosaic call in the decode step, no
   engine compile after warm-up.

It exits non-zero, printing no result line, unless JAX runs on a TPU, every
check holds, and no Pallas call was interpreted. Timings it prints are smoke
timings (one run, compile included where it says so), not benchmark results.

    python chip_smoke.py                    # on the chip
    python chip_smoke.py --rehearse-on-cpu  # toy width, CPU, control flow only
"""
from __future__ import annotations

import argparse
import collections
import functools
import glob
import json
import os
import re
import shutil
import sys
import time

import numpy as np

HERE = os.path.dirname(os.path.abspath(__file__))
OUT_DIR = os.path.join(HERE, "chiprun_out", "chip_smoke")
IR_DIR = os.path.join(OUT_DIR, "ir")

SEED = 0
AMP_DTYPE = "bfloat16"
# stated tolerances (max abs error unless noted)
FLASH_FWD_ATOL = 2e-2        # bf16 output vs f32 dense attention
FLASH_GRAD_RTOL = 2.0 ** -6  # bf16 grads: two ulps at the reference's abs max
PAGED_ATOL = 1e-4            # f32 paged kernel vs the page-gather lane
GREEDY_LOGIT_GAP = 1e-3      # a differing greedy token must sit on a gap below

TRAIN_KERNELS = ("flash_fwd", "flash_bwd_dq", "flash_bwd_dkv")
SERVE_KERNEL = "paged_attn"


def check(cond, what):
    if not cond:
        raise AssertionError(f"chip_smoke check failed: {what}")
    print(f"  ok: {what}", flush=True)


def mosaic_kernel_calls(module_glob):
    """{kernel_name: count} over the Mosaic custom calls in the lowered
    (StableHLO) modules jax dumped under IR_DIR matching ``module_glob``.
    A kernel inside a private function (a jitted helper that a program's
    layers share, as ``paged_attn``'s is) counts once a call of it."""
    counts = {}
    for path in glob.glob(os.path.join(IR_DIR, module_glob)):
        with open(path) as f:
            text = f.read()
        called = collections.Counter(re.findall(r"\bcall @([\w.]+)", text))
        func = None
        for line in text.splitlines():
            m = re.search(r"func\.func (?:\w+ )?@([\w.]+)", line)
            if m:
                func = m.group(1)
            elif "@tpu_custom_call" in line:
                m = re.search(r'kernel_name = "([^"]+)"', line)
                if m:
                    counts[m.group(1)] = (counts.get(m.group(1), 0)
                                          + (called.get(func) or 1))
    return counts


class CompileCounter:
    """Counts jax's compile requests and persistent-cache hits."""

    def __init__(self):
        from jax import monitoring
        self.requests = 0
        self.hits = 0
        monitoring.register_event_listener(self._on_event)

    def _on_event(self, name, **_kw):
        if name == "/jax/compilation_cache/compile_requests_use_cache":
            self.requests += 1
        elif name == "/jax/compilation_cache/cache_hits":
            self.hits += 1

    def snapshot(self):
        return self.requests, self.hits


# -- phase 0: kernels against their reference lanes ---------------------------

def phase_kernels(sizes):
    import jax
    import jax.numpy as jnp
    import paddle_tpu as paddle
    from paddle_tpu.ops.paged_attention import paged_attention
    from paddle_tpu.serving.llm.paged.pool import paged_gather_rows

    heads, seq = sizes["heads"], sizes["seq"]
    hd = sizes["hidden"] // heads
    scale = 1.0 / np.sqrt(hd)
    ks = jax.random.split(jax.random.PRNGKey(SEED), 7)
    amp_dt = jnp.dtype(AMP_DTYPE)

    # flash forward + backward through the public op, the shapes and dtype
    # the train step gives it (one batch row: blocks depend on S, D, dtype)
    q, k, v, do = (jax.random.normal(kk, (1, seq, heads, hd), jnp.float32)
                   .astype(amp_dt) for kk in ks[:4])

    def flash(a, b, c):
        o, _ = paddle.nn.functional.flash_attention(
            paddle.Tensor(a), paddle.Tensor(b), paddle.Tensor(c), causal=True)
        return o._data

    def dense(a, b, c):
        a, b, c = (jnp.moveaxis(x.astype(jnp.float32), 2, 1)
                   for x in (a, b, c))                     # [1, H, S, D]
        s = jnp.einsum("bhqd,bhkd->bhqk", a, b) * scale
        s = jnp.where(jnp.tril(jnp.ones((seq, seq), bool)), s, -1e30)
        o = jnp.einsum("bhqk,bhkd->bhqd", jax.nn.softmax(s, axis=-1), c)
        return jnp.moveaxis(o, 1, 2)

    def with_grads(fn):
        def loss(a, b, c):
            o = fn(a, b, c)
            return jnp.sum(o.astype(jnp.float32)
                           * do.astype(jnp.float32)), o
        return jax.jit(jax.value_and_grad(loss, argnums=(0, 1, 2),
                                          has_aux=True))

    t0 = time.perf_counter()
    (_, o_k), g_k = with_grads(flash)(q, k, v)
    (_, o_r), g_r = with_grads(dense)(q, k, v)
    err = float(jnp.max(jnp.abs(o_k.astype(jnp.float32) - o_r)))
    check(err <= FLASH_FWD_ATOL,
          f"flash forward vs dense attention: max err {err:.2e} <= "
          f"{FLASH_FWD_ATOL:.0e} ({amp_dt.name}, S={seq}, H={heads}, D={hd})")
    for name, gk, gr in zip(("dQ", "dK", "dV"), g_k, g_r):
        ref_max = float(jnp.max(jnp.abs(gr)))
        err = float(jnp.max(jnp.abs(gk.astype(jnp.float32)
                                    - gr.astype(jnp.float32))))
        check(err <= FLASH_GRAD_RTOL * ref_max,
              f"flash backward {name} vs dense: max err {err:.2e} <= "
              f"{FLASH_GRAD_RTOL:.2e} x {ref_max:.2f}")

    # paged decode attention at the engine's arena geometry (f32 weights)
    slots, page, pps = sizes["slots"], sizes["page"], sizes["pages_per_seq"]
    n_pages = slots * pps
    qd = jax.random.normal(ks[4], (slots, heads, hd), jnp.float32)
    layers, li = 2, 1      # the kernel reads one layer out of a whole arena
    ka = jax.random.normal(ks[5], (n_pages + 1, layers, page, heads, hd),
                           jnp.float32)
    va = jax.random.normal(ks[6], ka.shape, jnp.float32)
    bt = jnp.asarray(np.random.RandomState(SEED).permutation(n_pages)
                     .reshape(slots, pps), jnp.int32)
    pos = jnp.asarray(np.linspace(0, pps * page - 1, slots), jnp.int32)

    def gather_lane(a, b, c, t, p):
        kd = paged_gather_rows(b, t, li)                   # [S, max, H, D]
        vd = paged_gather_rows(c, t, li)
        s = jnp.einsum("shd,sthd->sht", a, kd) * scale
        j = jnp.arange(kd.shape[1])[None, None, :]
        s = jnp.where(j <= p[:, None, None], s, -1e30)
        return jnp.einsum("sht,sthd->shd", jax.nn.softmax(s, axis=-1), vd)

    got = jax.jit(functools.partial(paged_attention, layer=li))(
        qd, ka, va, bt, pos)
    ref = jax.jit(gather_lane)(qd, ka, va, bt, pos)
    err = float(jnp.max(jnp.abs(got - ref)))
    check(err <= PAGED_ATOL,
          f"paged kernel vs gather lane: max err {err:.2e} <= "
          f"{PAGED_ATOL:.0e} (f32, H={heads}, D={hd}, page={page}, "
          f"{pps} pages/seq)")
    print(f"  smoke timing: kernels phase {time.perf_counter() - t0:.1f} s "
          f"(compiles included)", flush=True)


# -- phase 1: train ------------------------------------------------------------

def build_network(sizes):
    import paddle_tpu as paddle
    from paddle_tpu.distributed.fleet.utils import recompute
    from paddle_tpu.models import GPTConfig, GPTForCausalLM

    cfg = GPTConfig(vocab_size=sizes["vocab"], hidden_size=sizes["hidden"],
                    num_layers=sizes["layers"], num_heads=sizes["heads"],
                    max_position_embeddings=sizes["seq"],
                    hidden_dropout_prob=0.0, attention_dropout_prob=0.0)
    paddle.seed(SEED)
    net = GPTForCausalLM(cfg)
    # recompute every decoder block: trade FLOPs for HBM so S=4096 fits
    for blk in net.gpt.decoder.layers:
        blk.forward = (lambda *a, __f=blk.forward, **k:
                       recompute(__f, *a, **k))
    return net


def phase_train(net, sizes, counter):
    import paddle_tpu as paddle
    from paddle_tpu.models import GPTPretrainingCriterion

    batch, seq, steps = sizes["batch"], sizes["seq"], sizes["train_steps"]
    n_params = sum(int(np.prod(p.shape)) for p in net.parameters())
    print(f"  model: {n_params / 1e6:.1f} M parameters, batch {batch} x "
          f"S={seq}, {steps} steps after the compile step", flush=True)
    # a learnable stream: tokens from a 256-word corner of the vocabulary
    rng = np.random.RandomState(SEED)
    ids = rng.randint(0, 256, ((steps + 1) * batch, seq)).astype(np.int32)
    data = paddle.io.TensorDataset([ids, ids.astype(np.int64)])

    class Record(paddle.callbacks.Callback):
        def __init__(self):
            super().__init__()
            self.losses, self.walls = [], []

        def on_train_batch_begin(self, step, logs=None):
            self._t0 = time.perf_counter()

        def on_train_batch_end(self, step, logs=None):
            self.walls.append(time.perf_counter() - self._t0)
            self.losses.append(float(logs["loss"]))

    model = paddle.Model(net)
    model.prepare(paddle.optimizer.AdamW(learning_rate=3e-4,
                                         parameters=net.parameters(),
                                         weight_decay=0.01),
                  GPTPretrainingCriterion())
    rec = Record()
    req0, hit0 = counter.snapshot()
    with paddle.amp.auto_cast(enable=True, dtype=AMP_DTYPE):
        model.fit(data, batch_size=batch, epochs=1, shuffle=False,
                  num_iters=steps + 1, verbose=0, callbacks=[rec])
    req1, hit1 = counter.snapshot()
    steady = float(np.median(rec.walls[1:]))
    print(f"  losses: {' '.join(f'{l:.4f}' for l in rec.losses)}", flush=True)
    print(f"  smoke timing: train compile step {rec.walls[0]:.1f} s "
          f"(compile ~{rec.walls[0] - steady:.1f} s), then "
          f"{steady * 1e3:.0f} ms/step median of {steps}; persistent compile "
          f"cache: {hit1 - hit0} hits of {req1 - req0} requests", flush=True)
    check(len(rec.losses) == steps + 1, f"{steps + 1} train steps ran")
    check(all(np.isfinite(rec.losses)), "every loss finite")
    check(rec.losses[-1] < rec.losses[0],
          f"last loss {rec.losses[-1]:.4f} below first {rec.losses[0]:.4f}")


# -- phase 2: serve ------------------------------------------------------------

def phase_serve(net, sizes, counter, rehearsal):
    import paddle_tpu as paddle
    from paddle_tpu.serving.llm import LLMEngine, LLMEngineConfig

    net.eval()
    max_new = sizes["max_new_tokens"]
    rng = np.random.RandomState(SEED + 1)
    prompts = [rng.randint(0, sizes["vocab"], (n,)).astype(np.int32)
               for n in sizes["prompt_lens"]]
    cfg = LLMEngineConfig(
        kv_layout="paged", num_slots=sizes["slots"],
        max_seq=sizes["page"] * sizes["pages_per_seq"],
        page_size=sizes["page"], prefill_buckets=sizes["prefill_buckets"],
        max_top_k=8, seed=SEED,
        # the rehearsal names the kernel lane so the interpreter runs it;
        # on the chip "auto" must resolve to it by itself
        paged_attn_impl="kernel" if rehearsal else "auto")
    req0, hit0 = counter.snapshot()
    t0 = time.perf_counter()
    engine = LLMEngine(net, cfg)            # warm-up compiles in here
    warm_s = time.perf_counter() - t0
    req1, hit1 = counter.snapshot()
    print(f"  smoke timing: engine warm-up {warm_s:.1f} s (compiles "
          f"{len(cfg.prefill_buckets)} prefill buckets + the decode step); "
          f"persistent compile cache: {hit1 - hit0} hits of {req1 - req0} "
          f"requests", flush=True)
    stats0 = engine.stats()
    check(stats0["paged_attn_impl"] == "kernel",
          f"paged attention lane is the kernel "
          f"(stats: {stats0['paged_attn_impl']!r})")
    misses0 = stats0["executable_cache"]["misses"]

    t0 = time.perf_counter()
    reqs = [engine.submit(p, max_new_tokens=max_new, stream=True)
            for p in prompts[:-1]]
    reqs.append(engine.submit(prompts[-1], max_new_tokens=max_new,
                              do_sample=True, temperature=0.8, top_k=8,
                              stream=True))
    streams = [list(r.iter_tokens(timeout=600)) for r in reqs]
    serve_s = time.perf_counter() - t0
    stats1 = engine.stats()
    req2, _ = counter.snapshot()
    engine.drain(timeout=60)
    n_tok = sum(len(s) for s in streams)
    print(f"  smoke timing: {len(reqs)} requests (prompts "
          f"{list(sizes['prompt_lens'])}), {n_tok} tokens streamed in "
          f"{serve_s:.2f} s = {serve_s / n_tok * 1e3:.1f} ms/token overall; "
          f"jax compile requests while serving: {req2 - req1}", flush=True)
    for r, s in zip(reqs, streams):
        check(len(s) == max_new and r.result(timeout=60) is not None,
              f"request {r.req_id} (prompt {r.prompt_len}) completed with "
              f"{len(s)} tokens")
    check(all(0 <= t < sizes["vocab"] for s in streams for t in s),
          "every token inside the vocabulary")
    check(stats1["executable_cache"]["misses"] == misses0,
          f"engine compile counter unmoved after warm-up "
          f"({misses0} -> {stats1['executable_cache']['misses']})")
    # the decode tick runs one step ahead: all but a batch's first tick
    share = stats1["tick_overlap_share"]
    print(f"  smoke: tick_overlap_share {share:.3f}", flush=True)
    check(share > 0.5,
          f"most decode ticks were dispatched ahead of the fetch before "
          f"them (tick_overlap_share {share:.3f} > 0.5)")

    # greedy lanes against model.generate on the same prompt
    for p, s in zip(prompts[:-1], streams[:-1]):
        ref = net.generate(paddle.to_tensor(p[None]), max_length=max_new)
        ref = [int(t) for t in np.asarray(ref.numpy())[0, len(p):]]
        if s == ref:
            print(f"  ok: greedy tokens equal model.generate "
                  f"(prompt {len(p)}, {max_new} tokens)", flush=True)
            continue
        # tolerance-equal, not bitwise: the first differing token must sit
        # on a near-tie of the reference logits (later ones follow from it)
        i = next(j for j, (a, b) in enumerate(zip(s, ref)) if a != b)
        prefix = np.concatenate([p, np.asarray(ref[:i], np.int32)])
        logits = np.asarray(net(paddle.to_tensor(prefix[None])).numpy(),
                            np.float32)[0, -1]
        gap = abs(float(logits[s[i]]) - float(logits[ref[i]]))
        print(f"  greedy token {i} differs (prompt {len(p)}): engine "
              f"{s[i]} vs generate {ref[i]}, logit gap {gap:.2e}", flush=True)
        check(gap < GREEDY_LOGIT_GAP,
              f"differing token sits on a logit gap {gap:.2e} < "
              f"{GREEDY_LOGIT_GAP:.0e}")


# -- driver --------------------------------------------------------------------

REAL = dict(vocab=50304, hidden=768, layers=12, heads=12,
            seq=4096, batch=4, train_steps=5,
            slots=4, page=16, pages_per_seq=64, max_new_tokens=16,
            prompt_lens=(5, 100, 700, 33), prefill_buckets=(8, 128, 1024))
# toy width for the CPU rehearsal; S stays 4096 so attention still takes
# the flash path exactly as at full width
TOY = dict(REAL, vocab=512, hidden=64, layers=2, heads=2,
           batch=1, train_steps=2, pages_per_seq=8,
           max_new_tokens=4, prompt_lens=(5, 20, 100, 9),
           prefill_buckets=(8, 32, 128))


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--rehearse-on-cpu", action="store_true",
                    help="run both phases at toy width on the CPU with the "
                         "kernels interpreted: proves control flow only, "
                         "never a chip result")
    args = ap.parse_args(argv)

    shutil.rmtree(IR_DIR, ignore_errors=True)
    os.makedirs(IR_DIR, exist_ok=True)

    import jax
    # lowered modules land here as text, cold or warm (jax dumps before it
    # consults the persistent cache) — the Mosaic-call checks read them
    jax.config.update("jax_dump_ir_to", IR_DIR)
    from paddle_tpu.core.pallas_mode import (chosen_modes,
                                             chosen_operand_dtypes)
    from paddle_tpu.serving.cache import place_jax_compilation_cache
    cache_dir = place_jax_compilation_cache()

    dev = jax.devices()[0]
    device = {"platform": dev.platform, "kind": dev.device_kind,
              "count": len(jax.devices())}
    print(f"device: platform={dev.platform} device_kind={dev.device_kind!r} "
          f"count={len(jax.devices())}", flush=True)
    rehearsal = False
    if dev.platform != "tpu":
        if not args.rehearse_on_cpu:
            print(f"chip_smoke: no TPU — jax runs on {dev.platform!r}; "
                  f"this script proves nothing without the chip",
                  file=sys.stderr)
            return 1
        rehearsal = True
        print("REHEARSAL: toy width on the CPU, Pallas kernels interpreted. "
              "This checks control flow only and is not a chip result.",
              flush=True)
    sizes = TOY if rehearsal else REAL
    print(f"compile cache: {cache_dir}", flush=True)
    counter = CompileCounter()
    t_start = time.perf_counter()

    print("phase 0: kernels vs reference lanes", flush=True)
    phase_kernels(sizes)

    print("phase 1: train (paddle.Model.fit)", flush=True)
    net = build_network(sizes)
    phase_train(net, sizes, counter)

    print("phase 2: serve (LLMEngine, paged KV)", flush=True)
    phase_serve(net, sizes, counter, rehearsal)

    print("lowered programs", flush=True)
    modes = chosen_modes()
    for kname in TRAIN_KERNELS + (SERVE_KERNEL,):
        check(kname in modes, f"kernel {kname} was traced on the path")
    # phase 0 and the autocast train step are the only flash callers here
    operands = chosen_operand_dtypes()
    for kname in TRAIN_KERNELS:
        check(operands.get(kname) == (AMP_DTYPE,),
              f"every {kname} was built with {AMP_DTYPE} MXU operands "
              f"({operands.get(kname)})")
    if rehearsal:
        check(all(modes.values()), "rehearsal: every kernel interpreted "
                                   "(Mosaic lowering is not checked here)")
    else:
        check(not any(modes.values()),
              f"no Pallas call ran interpreted ({modes})")
        train_calls = mosaic_kernel_calls("*jit_step_compile.mlir")
        for kname in TRAIN_KERNELS:
            check(train_calls.get(kname, 0) >= sizes["layers"],
                  f"lowered train step holds {train_calls.get(kname, 0)} "
                  f"Mosaic calls of {kname}")
        decode_calls = mosaic_kernel_calls("*jit__step_compile.mlir")
        check(decode_calls.get(SERVE_KERNEL, 0) >= sizes["layers"],
              f"lowered decode step holds "
              f"{decode_calls.get(SERVE_KERNEL, 0)} Mosaic calls of "
              f"{SERVE_KERNEL}")
    # keep only the two programs the checks read; the rest is every small
    # eager op's module
    for path in glob.glob(os.path.join(IR_DIR, "*")):
        if not re.search(r"jit__?step_compile\.mlir$", path):
            os.remove(path)

    req, hit = counter.snapshot()
    print(f"smoke timing: whole run {time.perf_counter() - t_start:.0f} s; "
          f"persistent compile cache {hit} hits of {req} requests "
          f"({'warm' if hit else 'cold'})", flush=True)
    result = {"ok": True, "device": device}
    if rehearsal:
        result["rehearsal"] = True
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
