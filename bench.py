"""Benchmark: jit-train ResNet-50 (BASELINE config 2) and a BERT-base
encoder (config 3) with the framework's fused train step; print ONE JSON
line with throughput + MFU.

Headline metric: ResNet-50 imgs/sec/chip in bf16 autocast (the BASELINE.md
north star). ``vs_baseline`` is measured throughput / target, where target =
85% of a single A100's MLPerf-class ResNet-50 fp16 throughput (~2500 imgs/s
→ target 2125 imgs/s/chip), per BASELINE.md "within 85% of A100x8 step-time"
scaled per chip. The transformer result rides along in "extras".

Runs the real shapes on an accelerator (the loop is pipelined: no host syncs
between steps). On the CPU it only proves the program path at toy shapes: the
result names its device, carries no peak and no MFU, and none of its timings
is a device metric.
"""
from __future__ import annotations

import json
import os
import time

import numpy as np


def _tuned_flash(seq, head_dim, dtype, causal=True):
    """True when this model's step runs the Pallas flash kernel with an
    autotuned block config (tuner winner resolved for its shape key) —
    False for dense-attention or non-Pallas models, so the BENCH
    trajectory shows which numbers are autotuned."""
    from paddle_tpu import tuner
    if seq < 4096:          # transformer auto-impl crossover: dense
        return False
    return tuner.get_flash_blocks(seq, seq, head_dim, dtype,
                                  causal) is not None


def _drive(model, opt, x_np, y_np, steps, use_amp, amp_dtype="bfloat16"):
    """Compile the fused train step once, then run `steps` pipelined steps.
    Returns seconds per step (excluding compile)."""
    import jax
    import jax.numpy as jnp
    import paddle_tpu as paddle
    from paddle_tpu.core import generator as _gen

    x = paddle.to_tensor(x_np)
    y = paddle.to_tensor(y_np)
    if use_amp:
        with paddle.amp.auto_cast(enable=True, dtype=amp_dtype):
            model.train_batch([x], [y])   # traces + compiles with bf16 casts
    else:
        model.train_batch([x], [y])

    ts = model._train_step_fn
    from paddle_tpu.core.tensor import stable_uid
    opt_states = [opt._state[stable_uid(p)] for p in ts["trainable"]]
    train_raws = [p._data for p in ts["trainable"]]
    fixed_raws = [ts["state"][i]._data for i in ts["fixed_pos"]]
    x_raws = [x._data]
    y_raws = [y._data]
    lr = jnp.asarray(opt.get_lr(), jnp.float32)

    # warmup (donated-buffer path)
    loss, _, train_raws, opt_states, _ = ts["fn"](
        train_raws, fixed_raws, opt_states, x_raws, y_raws,
        _gen.next_key(), lr, jnp.asarray(2.0, jnp.float32))
    jax.block_until_ready(loss)

    t0 = time.perf_counter()
    for i in range(steps):
        loss, _, train_raws, opt_states, _ = ts["fn"](
            train_raws, fixed_raws, opt_states, x_raws, y_raws,
            _gen.next_key(), lr, jnp.asarray(float(3 + i), jnp.float32))
    jax.block_until_ready(loss)
    dt = (time.perf_counter() - t0) / steps
    assert np.isfinite(float(loss)), "bench loss diverged"
    return dt


def bench_resnet50(on_tpu: bool):
    import paddle_tpu as paddle
    import paddle_tpu.optimizer as optim
    from paddle_tpu.vision import models

    if on_tpu:
        batch, size, steps = 256, 224, 20
    else:
        batch, size, steps = 4, 32, 2
    paddle.seed(0)
    net = models.resnet50(num_classes=1000)
    opt = optim.Momentum(learning_rate=0.1, momentum=0.9,
                         parameters=net.parameters(), weight_decay=1e-4)
    model = paddle.Model(net)
    model.prepare(opt, paddle.nn.CrossEntropyLoss())
    rng = np.random.RandomState(0)
    x = rng.rand(batch, 3, size, size).astype(np.float32)
    y = rng.randint(0, 1000, (batch,)).astype(np.int64)
    sec_per_step = _drive(model, opt, x, y, steps, use_amp=on_tpu)
    imgs_per_sec = batch / sec_per_step
    # fwd+bwd+update ≈ 3x fwd FLOPs; ResNet-50 fwd @224 = 4.09 GFLOPs/img
    flops_per_img = 3 * 4.09e9 * (size / 224.0) ** 2
    return {
        "imgs_per_sec": imgs_per_sec,
        "sec_per_step": sec_per_step,
        "batch": batch,
        "image_size": size,
        "train_tflops": imgs_per_sec * flops_per_img / 1e12,
        "tuned": False,           # conv/matmul path: XLA-scheduled, no
                                  # tunable Pallas kernel in the step
    }


def bench_bert(on_tpu: bool):
    import paddle_tpu as paddle
    import paddle_tpu.optimizer as optim
    from paddle_tpu.models import BertConfig, BertModel
    from paddle_tpu.nn.layer_base import Layer
    from paddle_tpu import nn

    if on_tpu:
        cfg = BertConfig()              # base: 12L, 768h
        # B=256: the 6ND MFU plateau (docs/perf_notes.md "BERT")
        batch, seq, steps = 256, 128, 6
    else:
        cfg = BertConfig(vocab_size=1000, hidden_size=64, num_layers=2,
                         num_heads=2, intermediate_size=128,
                         max_position_embeddings=64)
        batch, seq, steps = 2, 16, 2

    class MLMHead(Layer):
        def __init__(self):
            super().__init__()
            self.bert = BertModel(cfg)
            self.head = nn.Linear(cfg.hidden_size, cfg.vocab_size)

        def forward(self, ids):
            seq_out, _ = self.bert(ids)
            return self.head(seq_out)

    class FlatCE(Layer):
        def forward(self, logits, labels):
            from paddle_tpu import ops
            v = logits.shape[-1]
            return nn.functional.cross_entropy(
                ops.reshape(logits, [-1, v]), ops.reshape(labels, [-1]))

    paddle.seed(0)
    net = MLMHead()
    opt = optim.AdamW(learning_rate=1e-4, parameters=net.parameters(),
                      weight_decay=0.01)
    model = paddle.Model(net)
    model.prepare(opt, FlatCE())
    rng = np.random.RandomState(0)
    ids = rng.randint(0, cfg.vocab_size, (batch, seq)).astype(np.int32)
    sec_per_step = _drive(model, opt, ids, ids.astype(np.int64), steps,
                          use_amp=on_tpu)
    tokens_per_sec = batch * seq / sec_per_step
    n_params = sum(int(np.prod(p.shape)) for p in net.parameters())
    return {
        "tokens_per_sec": tokens_per_sec,
        "sec_per_step": sec_per_step,
        "batch": batch,
        "seq_len": seq,
        "n_params": n_params,
        # 6ND approximation for transformer train FLOPs
        "train_tflops": tokens_per_sec * 6 * n_params / 1e12,
        "tuned": _tuned_flash(seq, cfg.hidden_size // cfg.num_heads,
                              "bfloat16" if on_tpu else "float32"),
    }


def bench_yolov3(on_tpu: bool):
    """BASELINE workload 4: YOLOv3-DarkNet53 train step (static 416
    bucket, fixed 50 gt slots). The reference trains this shape via
    PaddleDetection over fluid yolov3_loss; here the whole 3-scale loss
    is one fused jit region."""
    import paddle_tpu as paddle
    import paddle_tpu.optimizer as optim
    from paddle_tpu.vision.models import YOLOv3, YOLOv3Loss

    if on_tpu:
        batch, size, steps, width = 32, 416, 6, 1.0
    else:
        batch, size, steps, width = 1, 64, 2, 0.125
    paddle.seed(0)
    net = YOLOv3(num_classes=80, width_mult=width, num_max_boxes=50)
    opt = optim.Momentum(learning_rate=1e-3, momentum=0.9,
                         parameters=net.parameters(), weight_decay=5e-4)
    model = paddle.Model(net)
    model.prepare(opt, YOLOv3Loss(net))
    rng = np.random.RandomState(0)
    x = rng.rand(batch, 3, size, size).astype(np.float32)
    gt_box = np.zeros((batch, 50, 4), np.float32)
    gt_label = np.zeros((batch, 50), np.int64)
    for i in range(batch):
        for b in range(rng.randint(1, 8)):
            cx, cy = rng.uniform(0.2, 0.8, 2)
            w, h = rng.uniform(0.05, 0.4, 2)
            gt_box[i, b] = [cx, cy, w, h]
            gt_label[i, b] = rng.randint(0, 80)

    import jax
    import jax.numpy as jnp
    from paddle_tpu.core import generator as _gen
    from paddle_tpu.core.tensor import stable_uid
    xt = paddle.to_tensor(x)
    yb, yl = paddle.to_tensor(gt_box), paddle.to_tensor(gt_label)
    if on_tpu:
        with paddle.amp.auto_cast(enable=True, dtype="bfloat16"):
            model.train_batch([xt], [yb, yl])
    else:
        model.train_batch([xt], [yb, yl])
    ts = model._train_step_fn
    opt_states = [opt._state[stable_uid(p)] for p in ts["trainable"]]
    train_raws = [p._data for p in ts["trainable"]]
    fixed_raws = [ts["state"][i]._data for i in ts["fixed_pos"]]
    lr = jnp.asarray(opt.get_lr(), jnp.float32)
    loss, _, train_raws, opt_states, _ = ts["fn"](
        train_raws, fixed_raws, opt_states, [xt._data],
        [yb._data, yl._data], _gen.next_key(), lr,
        jnp.asarray(2.0, jnp.float32))
    jax.block_until_ready(loss)
    t0 = time.perf_counter()
    for i in range(steps):
        loss, _, train_raws, opt_states, _ = ts["fn"](
            train_raws, fixed_raws, opt_states, [xt._data],
            [yb._data, yl._data], _gen.next_key(), lr,
            jnp.asarray(float(3 + i), jnp.float32))
    jax.block_until_ready(loss)
    best = (time.perf_counter() - t0) / steps
    assert np.isfinite(float(loss)), "yolo bench loss diverged"
    imgs_per_sec = batch / best
    # fwd+bwd+update ≈ 3x fwd; YOLOv3-DarkNet53 fwd @608 = 65.86 GFLOPs
    flops_per_img = 3 * 65.86e9 * (size / 608.0) ** 2
    return {
        "imgs_per_sec": imgs_per_sec,
        "sec_per_step": best,
        "batch": batch,
        "image_size": size,
        "train_tflops": imgs_per_sec * flops_per_img / 1e12,
        "tuned": False,           # train path is conv-only; the tuned
                                  # NMS kernel runs in eval/postprocess
    }


def bench_gpt_longseq(on_tpu: bool):
    """Round-5: long-sequence single-chip train step — GPT-small at
    S=4096 with the Pallas flash-attention kernel (auto-selected at the
    measured S>=4096 crossover) and per-layer recompute (jax.checkpoint)
    so the activations fit HBM. Exercises the 5.7 long-context stack on
    the chip rather than only in CPU-mesh tests."""
    import paddle_tpu as paddle
    import paddle_tpu.optimizer as optim
    from paddle_tpu.models import (GPTConfig, GPTForCausalLM,
                                   GPTPretrainingCriterion)
    from paddle_tpu.distributed.fleet import utils as fleet_utils

    if on_tpu:
        cfg = GPTConfig(vocab_size=50304, hidden_size=768, num_layers=12,
                        num_heads=12, max_position_embeddings=4096,
                        hidden_dropout_prob=0.0,
                        attention_dropout_prob=0.0, attn_impl="auto")
        batch, seq, steps = 4, 4096, 3
    else:
        cfg = GPTConfig(vocab_size=512, hidden_size=64, num_layers=2,
                        num_heads=2, max_position_embeddings=128,
                        hidden_dropout_prob=0.0,
                        attention_dropout_prob=0.0, attn_impl="auto")
        batch, seq, steps = 1, 64, 2
    paddle.seed(0)
    net = GPTForCausalLM(cfg)
    # recompute every decoder block: trade FLOPs for HBM so S=4096 fits
    for name, sub in net.named_sublayers():
        if name.endswith(tuple(f"layers.{i}" for i in range(cfg.num_layers))):
            orig = sub.forward
            sub.forward = (lambda *a, __f=orig, **k:
                           fleet_utils.recompute(__f, *a, **k))
    opt = optim.AdamW(learning_rate=1e-4, parameters=net.parameters(),
                      weight_decay=0.01)
    model = paddle.Model(net)
    model.prepare(opt, GPTPretrainingCriterion())
    rng = np.random.RandomState(0)
    ids = rng.randint(0, cfg.vocab_size, (batch, seq)).astype(np.int32)
    sec_per_step = _drive(model, opt, ids, ids.astype(np.int64), steps,
                          use_amp=on_tpu)
    tokens_per_sec = batch * seq / sec_per_step
    n_params = sum(int(np.prod(p.shape)) for p in net.parameters())
    return {
        "tokens_per_sec": tokens_per_sec,
        "sec_per_step": sec_per_step,
        "batch": batch,
        "seq_len": seq,
        "n_params": n_params,
        "attn": "pallas_flash+recompute" if seq >= 4096 else "dense",
        # 6ND ignores attention FLOPs; at S=4096 add 12*L*h*S^2-ish? keep
        # the standard 6ND for comparability with the BERT entry
        "train_tflops": tokens_per_sec * 6 * n_params / 1e12,
        "tuned": _tuned_flash(seq, cfg.hidden_size // cfg.num_heads,
                              "bfloat16" if on_tpu else "float32"),
    }


def bench_gpt_ring_flash(on_tpu: bool):
    """Long-context dp×sp train step: a GPT-style decoder stack whose
    attention is ring-flash (sequence dim sharded over "sp", flash kernel
    per chunk, backward through the ring-flash custom_vjp). On TPU this
    is the S=32k ROADMAP-item-2 configuration (dp=2 × sp=4 on 8 chips);
    off-TPU a shrunk interpret-mode shape proves the same program path.
    The 6ND tokens/s→TFLOPs convention matches the other GPT entries."""
    import jax
    import jax.numpy as jnp
    from paddle_tpu.distributed.fleet import sequence_parallel as sp

    n = len(jax.devices())
    dp = 2 if n >= 2 and n % 2 == 0 else 1
    spn = n // dp
    devices = np.array(jax.devices()).reshape(dp, spn)
    mesh = jax.sharding.Mesh(devices, ("dp", "sp"))
    if on_tpu:
        batch, seq, n_layers, H, D, steps = 2 * dp, 32768, 4, 8, 64, 3
        dtype = jnp.bfloat16
    else:
        batch, seq, n_layers, H, D, steps = dp, 16 * spn * 2, 2, 2, 16, 2
        dtype = jnp.float32
    E = H * D

    def layer_fn(h, lp):
        wq, wk, wv, wo, w1, w2 = lp
        B, T = h.shape[0], h.shape[1]

        def heads(w):
            return (h @ w).reshape(B, T, H, D).transpose(0, 2, 1, 3)

        o = sp.ring_flash_attention(heads(wq), heads(wk), heads(wv),
                                    mesh=mesh, axis="sp", causal=True,
                                    batch_axes="dp")
        h = h + o.transpose(0, 2, 1, 3).reshape(B, T, E) @ wo
        return h + jax.nn.gelu(h @ w1) @ w2

    def train_step(params, x, y):
        def loss_fn(ps):
            h = x
            for lp in ps:
                h = layer_fn(h, lp)
            return jnp.mean((h - y).astype(jnp.float32) ** 2)

        loss, grads = jax.value_and_grad(loss_fn)(params)
        new = jax.tree_util.tree_map(lambda p, g: p - 0.1 * g, params,
                                     grads)
        return new, loss

    step = jax.jit(train_step, donate_argnums=(0,))
    rng = np.random.RandomState(0)

    def w(*shape):
        return jnp.asarray(rng.randn(*shape) * 0.1, dtype)

    params = [(w(E, E), w(E, E), w(E, E), w(E, E), w(E, 2 * E),
               w(2 * E, E)) for _ in range(n_layers)]
    x = jnp.asarray(rng.randn(batch, seq, E), dtype)
    y = jnp.asarray(rng.randn(batch, seq, E), dtype)
    params, loss = step(params, x, y)          # compile + warm
    best = None
    for _ in range(steps):
        t0 = time.perf_counter()
        params, loss = step(params, x, y)
        jax.block_until_ready(loss)
        dt = time.perf_counter() - t0
        best = dt if best is None else min(best, dt)
    lv = float(np.asarray(loss))
    assert np.isfinite(lv), "ring-flash bench loss diverged"
    tokens_per_sec = batch * seq / best
    n_params = sum(int(np.prod(p.shape)) for lp in params for p in lp)
    Tl = seq // spn
    from paddle_tpu import tuner
    tuned = tuner.get_flash_blocks(Tl, Tl, D,
                                   "bfloat16" if on_tpu else "float32",
                                   False, ring=True, bwd=True) is not None
    return {
        "tokens_per_sec": tokens_per_sec,
        "sec_per_step": best,
        "batch": batch,
        "seq_len": seq,
        "mesh": f"dp{dp}xsp{spn}",
        "n_params": n_params,
        "attn": "ring_flash(custom_vjp bwd)",
        "train_tflops": tokens_per_sec * 6 * n_params / 1e12,
        "tuned": tuned,
    }


def main():
    import jax
    from paddle_tpu.observability.stepmeter import default_peak_flops
    from paddle_tpu.serving.cache import place_jax_compilation_cache

    place_jax_compilation_cache()
    dev = jax.devices()[0]
    on_tpu = dev.platform != "cpu"
    peak = default_peak_flops()    # None on the CPU; unlisted chip raises

    extras = {"platform": dev.platform, "device_kind": dev.device_kind,
              "device_count": len(jax.devices())}
    for name, fn in (("resnet50", bench_resnet50),
                     ("bert_base", bench_bert),
                     ("yolov3_darknet53", bench_yolov3),
                     ("gpt_small_s4096", bench_gpt_longseq),
                     ("gpt_ring_flash_s32k", bench_gpt_ring_flash)):
        r = fn(on_tpu)
        if peak is not None:
            r["mfu"] = r["train_tflops"] * 1e12 / peak
        extras[name] = r

    r = extras["resnet50"]
    target = 2125.0  # 85% of ~2500 imgs/s/A100 (MLPerf-class fp16 ResNet-50)
    print(json.dumps({
        "metric": "resnet50_imgs_per_sec_per_chip",
        "value": round(r["imgs_per_sec"], 2),
        "unit": "imgs/s",
        "vs_baseline": round(r["imgs_per_sec"] / target, 4),
        "extras": extras,
    }))


if __name__ == "__main__":
    main()
