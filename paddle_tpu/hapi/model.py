"""paddle.Model: the Keras-like high-level API.

Reference: python/paddle/hapi/model.py:876 Model (fit :1519, evaluate,
predict, save/load, summary; DynamicGraphAdapter :659 / StaticGraphAdapter
:250). TPU design: one adapter — the train step is functionalized and
jit-compiled whole (forward + loss + backward + optimizer update in a single
XLA program, buffers donated), which is the role the StaticGraphAdapter's
compiled Program served, with the dygraph API surface.
"""
from __future__ import annotations

import functools
import time as _time
from typing import List, Optional

import numpy as np
import jax
import jax.numpy as jnp

from ..core.tensor import Tensor, Parameter, stable_uid
from ..core import generator as _gen
from ..core import autograd_engine as _ag
from ..nn.layer_base import Layer
from ..metric import Metric
from ..io import DataLoader, Dataset
from ..jit.functionalize import trace_context, swap_params
from ..observability import opscope as _opscope, tracer as _otrace
from .. import amp as _amp
from .callbacks import config_callbacks
from .. import framework_io



def _noted_jit(fn, **jit_kwargs):
    """``jax.jit`` of ``fn`` whose traces are noted for
    ``observability.opscope`` under the compiled module's name
    (``jit_step``). The note is made inside the traced body: once a trace,
    never on a call; ``fn`` itself (the auditor's ``raw_step``, the scan's
    body) stays as it is."""
    @functools.wraps(fn)
    def body(*args):
        # lowered again later, outside the caller's ``auto_cast``: the
        # autocast state is Python's, not JAX's, so the note carries it
        st = _amp._STATE
        _opscope.note("jit_" + fn.__name__, jitted, args, functools.partial(
            _amp.auto_cast, st["enabled"], set(st["custom_white"]),
            set(st["custom_black"]), st["level"],
            st["dtype"] or "bfloat16"))
        return fn(*args)

    jitted = jax.jit(body, **jit_kwargs)
    return jitted


def _mark_first_compile(tag, jitted):
    """Wrap a jitted callable so its first invocation — where jax traces,
    lowers and compiles — lands on the span timeline as ``jit/compile``.
    Later calls pay one list check (~ns against a ms-scale step)."""
    done = []

    def call(*args):
        if not done:
            done.append(1)
            with _otrace.span("jit/compile", {"fn": tag}):
                return jitted(*args)
        return jitted(*args)

    return call


def _effect_fixed_indices(ts):
    """Positions (within the fixed-buffer list) of every state-effect
    holder, or None when some holder is not a registered non-trainable
    buffer (e.g. set_value on an ad-hoc Tensor during forward) — callers
    must then fall back to the per-step train_batch path, which applies
    effects by identity without needing positions."""
    holders = ts["meta"].get("effect_holders", [])
    id2pos = {id(t): i for i, t in enumerate(ts["state"])}
    fixed_of = {p: j for j, p in enumerate(ts["fixed_pos"])}
    out = []
    for h in holders:
        pos = id2pos.get(id(h))
        if pos is None or pos not in fixed_of:
            return None
        out.append(fixed_of[pos])
    return out


class Model:
    def __init__(self, network: Layer, inputs=None, labels=None):
        self.network = network
        self._inputs = inputs
        self._labels = labels
        self._optimizer = None
        self._loss = None
        self._metrics: List[Metric] = []
        self.stop_training = False
        self._eval_fns_max = 64         # LRU bound (cf. dispatch cache)
        self._step_meter = None         # opt-in MFU meter (attach_step_meter)
        self._invalidate_compiled()

    def attach_step_meter(self, meter=None):
        """Opt into live MFU accounting: publishes ``train.mfu`` /
        ``train.flops_per_step`` / ``train.step_ms`` per train_batch.
        FLOPs come from one extra XLA cost-analysis compile per train-step
        signature (docs/observability.md)."""
        if meter is None:
            from ..observability.stepmeter import StepMeter
            meter = StepMeter(prefix="train")
        self._step_meter = meter
        return meter

    def _invalidate_compiled(self):
        """Drop every compiled program. The step/loop closures capture the
        optimizer's update rule, clip/decay vectors and its _state dict;
        the eval programs capture the loss; all of them capture parameter
        objects — any of prepare()/load() invalidates them or a stale
        program keeps running with the old configuration."""
        self._train_step_fn = None
        self._train_sig = None
        self._fused_loop_key = None
        self._fused_loop = None
        self._multi_step_key = None
        self._multi_step_fn = None
        from collections import OrderedDict
        self._eval_fns = OrderedDict()  # (sig, mode) -> compiled program
        # sig -> compiled train step; bounded LRU so size-bucketed
        # multi-scale training (YOLO) switches buckets without recompiling
        self._train_fns = OrderedDict()

    def _get_train_step(self, sig):
        ts = self._train_fns.get(sig)
        if ts is None:
            self.network.train()
            ts = self._build_train_step(sig)
            if len(self._train_fns) >= 16:
                self._train_fns.popitem(last=False)
            self._train_fns[sig] = ts
        else:
            self._train_fns.move_to_end(sig)
        self._train_step_fn = ts
        self._train_sig = sig
        return ts

    def prepare(self, optimizer=None, loss=None, metrics=None, amp_configs=None):
        self._optimizer = optimizer
        self._loss = loss
        self._invalidate_compiled()
        if metrics is None:
            self._metrics = []
        elif isinstance(metrics, Metric):
            self._metrics = [metrics]
        else:
            self._metrics = list(metrics)

    # ------------------------------------------------------------------
    def _state(self):
        ps = [p for _, p in self.network.named_parameters()]
        bs = [b for _, b in self.network.named_buffers()]
        return ps, bs

    def _build_train_step(self, sig):
        """Compile (params, opt_state, x, y, key, lr, step) -> (loss, preds,
        new_params, new_state, effects) — one XLA program per signature."""
        params, buffers = self._state()
        state = params + buffers
        trainable = [p for p in params if not p.stop_gradient]
        t_pos = [i for i, p in enumerate(state) if not p.stop_gradient
                 and i < len(params)]
        fixed_pos = [i for i in range(len(state)) if i not in set(t_pos)]
        opt = self._optimizer
        loss_fn = self._loss
        net = self.network
        reg_coeffs = [opt._regularized_grad(p, None) for p in trainable]
        clip = opt._grad_clip
        ctxs = opt._param_update_ctx(trainable)

        meta = {}

        # materializing predictions is an extra HBM write per step (a
        # [B, S, vocab] logits tensor for LM heads); skip it when no
        # metric consumes them
        want_preds = bool(self._metrics)

        def fwd_loss(train_raws, fixed_raws, x_raws, y_raws, key):
            full = [None] * len(state)
            for pos, r in zip(fixed_pos, fixed_raws):
                full[pos] = r
            for pos, r in zip(t_pos, train_raws):
                full[pos] = r
            with jax.named_scope("train/forward"), \
                    trace_context(key) as ctx:
                with swap_params(state, full):
                    with _ag.no_grad():
                        xs = [Tensor(r) for r in x_raws]
                        ys = [Tensor(r) for r in y_raws]
                        preds = net.forward(*xs)
                        preds_t = preds if isinstance(preds, (list, tuple)) \
                            else [preds]
                        loss = loss_fn(*preds_t, *ys)
                effects = [r for _, r in ctx.state_effects]
                meta["effect_holders"] = [h for h, _ in ctx.state_effects]
            loss_raw = loss._data if isinstance(loss, Tensor) else loss
            out_preds = [p._data for p in preds_t] if want_preds else []
            return loss_raw, (out_preds, effects)

        def step(train_raws, fixed_raws, opt_states, x_raws, y_raws, key, lr,
                 step_no):
            (loss, (preds, effects)), grads = jax.value_and_grad(
                fwd_loss, has_aux=True)(train_raws, fixed_raws, x_raws,
                                        y_raws, key)
            grads = list(grads)
            with jax.named_scope("train/optimizer"):
                # clip first, then regularize — same order as Optimizer.step
                if clip is not None:
                    grads = clip._clip_raw(trainable, grads)
                for i, rc in enumerate(reg_coeffs):
                    if rc is not None:
                        grads[i] = grads[i] + rc * train_raws[i]
                new_p, new_s = [], []
                for pr, g, st, ctx in zip(train_raws, grads, opt_states,
                                          ctxs):
                    p2, s2 = opt._update(pr, g.astype(pr.dtype), st, lr,
                                         step_no, ctx)
                    new_p.append(p2)
                    new_s.append(s2)
            return loss, preds, new_p, new_s, effects

        def grads_only(train_raws, fixed_raws, x_raws, y_raws, key):
            # update=False form (gradient accumulation): raw grads, no
            # clip/regularize/update — those belong to the eventual step
            (loss, (preds, effects)), grads = jax.value_and_grad(
                fwd_loss, has_aux=True)(train_raws, fixed_raws, x_raws,
                                        y_raws, key)
            return loss, preds, list(grads), effects

        jitted = _noted_jit(step, donate_argnums=(0, 2))
        return {"fn": _mark_first_compile("train_step", jitted),
                "grads_fn": _mark_first_compile("train_grads",
                                                jax.jit(grads_only)),
                "raw_step": step, "fwd_loss": fwd_loss, "meta": meta,
                "state": state, "trainable": trainable, "t_pos": t_pos,
                "fixed_pos": fixed_pos}

    def _prepare_multi_step(self, name, inputs, labels):
        """Shared preamble of train_batches/train_loop: normalize stacked
        inputs, (re)build the compiled step for the per-step signature,
        init optimizer state, reject configurations the multi-step paths
        cannot honor, and make sure effect metadata exists."""
        if self._metrics:
            raise ValueError(
                f"{name}: detach metrics (prepare(..., metrics=None)); "
                "per-step predictions are not materialized")
        inputs = inputs if isinstance(inputs, (list, tuple)) else [inputs]
        labels = labels if isinstance(labels, (list, tuple)) else (
            [labels] if labels is not None else [])
        xs = [i._data if isinstance(i, Tensor) else jnp.asarray(i)
              for i in inputs]
        ys = [l._data if isinstance(l, Tensor) else jnp.asarray(l)
              for l in labels]
        K = int(xs[0].shape[0])
        # per-step signature drives the same compiled-step cache
        sig = (tuple((tuple(r.shape[1:]), str(r.dtype)) for r in xs + ys),
               False)
        ts = self._get_train_step(sig)
        opt = self._optimizer
        if any(p._grad is not None for p in ts["trainable"]):
            raise RuntimeError(
                f"{name}: pending accumulated gradients from "
                "train_batch(update=False); finish the accumulation window "
                "with train_batch(update=True) first")
        for p in ts["trainable"]:
            if stable_uid(p) not in opt._state:
                opt._state[stable_uid(p)] = opt._init_state(p)
        opt._accumulators_built = True
        if "effect_holders" not in ts["meta"]:
            # one abstract evaluation populates meta (no compile)
            opt_states = [opt._state[stable_uid(p)] for p in ts["trainable"]]
            sds = lambda r: jax.ShapeDtypeStruct(r.shape, r.dtype)
            jax.eval_shape(
                ts["raw_step"],
                [sds(p._data) for p in ts["trainable"]],
                [sds(ts["state"][i]._data) for i in ts["fixed_pos"]],
                jax.tree_util.tree_map(sds, opt_states),
                [sds(x[0]) for x in xs], [sds(y[0]) for y in ys],
                jax.ShapeDtypeStruct((2,), np.uint32),
                jax.ShapeDtypeStruct((), np.float32),
                jax.ShapeDtypeStruct((), np.float32))
        return ts, opt, xs, ys, K

    def train_batches(self, inputs, labels=None):
        """Run K fused train steps in ONE compiled program.

        ``inputs``/``labels`` carry a leading steps axis ([K, batch, ...]
        per tensor). The K-step loop runs as one on-device ``lax.scan`` —
        one host dispatch instead of K, the TPU analog of the reference's
        C++ executor owning the whole train loop (fluid Executor.run
        executes the full Program per call; here the program IS K steps).

        BN running stats and other state effects thread through the scan
        carry, so K calls of :meth:`train_batch` and one call of
        ``train_batches`` compute identical state (pinned by
        tests/test_train_multi_step.py). Note the rolled scan pays
        per-iteration carry copies for the donated parameter buffers —
        on big models per-step :meth:`train_batch` dispatch is usually as
        fast or faster (measured: docs/perf_notes.md round 4); this API
        is about dispatch-count, not step time. Not available while
        metrics are attached (per-step predictions are not materialized).
        Returns the list of K losses.
        """
        ts, opt, xs, ys, K = self._prepare_multi_step(
            "train_batches", inputs, labels)
        opt_states = [opt._state[stable_uid(p)] for p in ts["trainable"]]
        train_raws = [p._data for p in ts["trainable"]]
        fixed_raws = [ts["state"][i]._data for i in ts["fixed_pos"]]
        keys = jnp.stack([_gen.next_key() for _ in range(K)])
        lr = jnp.asarray(opt.get_lr(), jnp.float32)
        step0 = jnp.asarray(opt._global_step + 1, jnp.float32)
        eff_idx = _effect_fixed_indices(ts)
        if eff_idx is None:
            raise ValueError(
                "train_batches: the forward records state effects on "
                "tensors that are not registered buffers; the scan cannot "
                "thread them — use train_batch")
        mk = (self._train_sig, K)
        if getattr(self, "_multi_step_key", None) != mk:
            self._multi_step_fn = self._build_multi_step(ts)
            self._multi_step_key = mk
        losses, new_p, new_fixed, new_s = self._multi_step_fn(
            train_raws, fixed_raws, opt_states, xs, ys, keys, lr, step0)
        for p, npr, ns in zip(ts["trainable"], new_p, new_s):
            p._data = npr
            p._inplace_version += 1
            opt._state[stable_uid(p)] = ns
        holders = ts["meta"].get("effect_holders", [])
        for h, fj in zip(holders, eff_idx):
            h._data = new_fixed[fj]
            h._inplace_version += 1
        opt._global_step += K
        return [float(v) for v in np.asarray(losses)]

    def _build_multi_step(self, ts):
        """jit( scan over raw_step ) with BN/state effects threaded
        through the carry."""
        step = ts["raw_step"]
        eff_fixed_idx = _effect_fixed_indices(ts) or []

        def multi(train_raws, fixed_raws, opt_states, xs, ys, keys, lr,
                  step0):
            def body(carry, inp):
                tr, fx, st, i = carry
                x_sl, y_sl, key = inp
                loss, _preds, tr, st, effects = step(
                    list(tr), list(fx), list(st), list(x_sl), list(y_sl),
                    key, lr, step0 + i)
                fx = list(fx)
                for j, e in zip(eff_fixed_idx, effects):
                    fx[j] = e
                return (tuple(tr), tuple(fx), tuple(st), i + 1.0), loss
            init = (tuple(train_raws), tuple(fixed_raws), tuple(opt_states),
                    jnp.asarray(0.0, jnp.float32))
            # rolled scan only: unroll=True produced WRONG parameter
            # updates for K >= 3 with donated buffers (XLA aliasing across
            # the unrolled iterations; reproduced in
            # tests/test_train_multi_step.py history) — and measured no
            # faster anyway once compile time is counted
            (tr, fx, st, _), losses = jax.lax.scan(
                body, init, (tuple(xs), tuple(ys), keys))
            return losses, list(tr), list(fx), list(st)
        return jax.jit(multi, donate_argnums=(0, 2))

    def train_loop(self, inputs, labels=None):
        """Coalesced multi-step training (reference:
        operators/coalesce_tensor_op.cc + the fused optimizer family,
        operators/optimizers/distributed_fused_lamb*).

        ``inputs``/``labels`` carry a leading steps axis ([K, batch, ...]).
        Trainable parameters and optimizer states are packed ONCE into one
        flat buffer per dtype, the per-step program takes ~6 device arrays
        instead of ~600, and state unpacks at loop exit. With hundreds of
        parameter buffers the per-step dispatch is a per-buffer host cost
        (not measured on the current rig);
        this path removes it while keeping step math identical — the flat
        buffer is sliced back into per-parameter views inside the trace,
        and elementwise optimizers (SGD/Momentum/Adam/AdamW) apply
        directly on the flat buffers with per-element decay/clip masks.

        Falls back to per-step :meth:`train_batch` calls when the
        optimizer/clip configuration is not elementwise-safe (per-param
        trust ratios, non-global-norm clips, multi_precision masters).
        Returns the list of K losses.
        """
        ts, opt, xs, ys, K = self._prepare_multi_step(
            "train_loop", inputs, labels)

        fused = self._build_fused_loop(ts)
        if fused is None:
            out = []
            for k in range(K):
                loss, _ = self.train_batch([x[k] for x in xs],
                                           [y[k] for y in ys])
                out.append(loss)
            return out
        pack, unpack_back, fused_fn, eff_fixed_idx = fused

        train_raws = [p._data for p in ts["trainable"]]
        states = [opt._state[stable_uid(p)] for p in ts["trainable"]]
        fixed = [ts["state"][i]._data for i in ts["fixed_pos"]]
        flat_ps, flat_sts = pack(train_raws, states)
        lr = jnp.asarray(opt.get_lr(), jnp.float32)
        losses = []
        for k in range(K):
            step_no = jnp.asarray(opt._global_step + 1 + k, jnp.float32)
            loss, flat_ps, flat_sts, effects = fused_fn(
                flat_ps, fixed, flat_sts, [x[k] for x in xs],
                [y[k] for y in ys], _gen.next_key(), lr, step_no)
            for j, e in zip(eff_fixed_idx, effects):
                fixed[j] = e
            losses.append(loss)
        opt._global_step += K
        unpack_back(flat_ps, flat_sts, fixed)
        return [float(np.asarray(l)) for l in losses]

    def _build_fused_loop(self, ts):
        """Coalesced-buffer step builder; returns None when the optimizer
        or clip configuration is not elementwise-safe on flat buffers."""
        if getattr(self, "_fused_loop_key", None) == self._train_sig:
            return self._fused_loop
        from ..nn.clip import ClipGradByGlobalNorm, _clips
        opt = self._optimizer
        clip = opt._grad_clip
        trainable = ts["trainable"]
        result = None
        while True:  # single-pass "try"; break = fallback
            if not getattr(opt, "_elementwise_update", False):
                break  # LAMB/LARS-style cross-element terms can't coalesce
            if clip is not None and not isinstance(clip,
                                                   ClipGradByGlobalNorm):
                break
            states = [opt._state[stable_uid(p)] for p in trainable]
            key_sets = {tuple(sorted(s.keys())) for s in states}
            if len(key_sets) != 1:
                break
            state_keys = sorted(states[0].keys())
            if "master" in state_keys:
                break  # per-param master copies: layouts diverge
            ctxs = opt._param_update_ctx(trainable)
            ctx_mode = None
            if all(c is None for c in ctxs):
                ctx_mode = "none"
            elif all(isinstance(c, tuple) and len(c) == 2
                     and all(isinstance(v, (int, float)) for v in c)
                     for c in ctxs):
                ctx_mode = "vec2"
            else:
                break
            reg_coeffs = [opt._regularized_grad(p, None) for p in trainable]
            if not all(rc is None or np.isscalar(rc) or getattr(
                    rc, "ndim", 1) == 0 for rc in reg_coeffs):
                break

            # -- group by param dtype ------------------------------------
            groups = {}
            for i, p in enumerate(trainable):
                groups.setdefault(str(p._data.dtype), []).append(i)
            gmeta = []
            for dt, idxs in groups.items():
                offs, n = [], 0
                for i in idxs:
                    sz = int(np.prod(trainable[i]._data.shape)) or 1
                    offs.append((n, sz, tuple(trainable[i]._data.shape)))
                    n += sz
                gmeta.append((dt, idxs, offs, n))

            def vec_of(values, gi, dtype=jnp.float32):
                dt, idxs, offs, n = gmeta[gi]
                v = np.zeros((n,), np.float32)
                for (o, sz, _), i in zip(offs, idxs):
                    v[o:o + sz] = values[i]
                return jnp.asarray(v, dtype)

            reg_vecs, ctx_vecs, clip_masks = [], [], []
            for gi, (dt, idxs, offs, n) in enumerate(gmeta):
                if any(reg_coeffs[i] is not None for i in idxs):
                    reg_vecs.append(vec_of(
                        [float(reg_coeffs[i]) if reg_coeffs[i] is not None
                         else 0.0 for i in range(len(trainable))], gi))
                else:
                    reg_vecs.append(None)
                if ctx_mode == "vec2":
                    c0 = vec_of([float(c[0]) for c in ctxs], gi)
                    c1 = vec_of([float(c[1]) for c in ctxs], gi)
                    ctx_vecs.append((c0, c1))
                else:
                    ctx_vecs.append(None)
                clip_masks.append(vec_of(
                    [1.0 if _clips(p) else 0.0 for p in trainable], gi))

            holders = ts["meta"].get("effect_holders", [])
            eff_fixed_idx = _effect_fixed_indices(ts)
            if eff_fixed_idx is None and holders:
                break  # effects on unregistered tensors: per-step fallback
            eff_fixed_idx = eff_fixed_idx or []
            fwd_loss = ts["fwd_loss"]

            def unpack(flats):
                raws = [None] * len(trainable)
                for (dt, idxs, offs, n), buf in zip(gmeta, flats):
                    for (o, sz, shp), i in zip(offs, idxs):
                        raws[i] = jax.lax.dynamic_slice(
                            buf, (o,), (sz,)).reshape(shp)
                return raws

            def fused_step(flat_ps, fixed_raws, flat_sts, x_raws, y_raws,
                           key, lr, step_no):
                # differentiate w.r.t. the UNPACKED per-param list — the
                # flat buffer stays outside the grad so the transpose is a
                # per-param cotangent list, re-coalesced with one
                # concatenate per group (grad w.r.t. the flat buffer would
                # transpose every slice into a serialized
                # dynamic-update-slice chain over the whole buffer:
                # measured 2.7x slower than the per-step path)
                raws = unpack(flat_ps)

                def loss_over_list(raw_list):
                    return fwd_loss(raw_list, fixed_raws, x_raws,
                                    y_raws, key)
                (loss, (_preds, effects)), grads = jax.value_and_grad(
                    loss_over_list, has_aux=True)(raws)
                flat_grads = []
                for (dt, idxs, offs, n), pbuf in zip(gmeta, flat_ps):
                    flat_grads.append(jnp.concatenate(
                        [grads[i].reshape(-1) for i in idxs]).astype(
                            pbuf.dtype))
                with jax.named_scope("train/optimizer"):
                    if clip is not None:
                        gn = jnp.sqrt(sum(
                            jnp.sum((g.astype(jnp.float32) * m) ** 2)
                            for g, m in zip(flat_grads, clip_masks)))
                        scale = clip.clip_norm / jnp.maximum(
                            gn, clip.clip_norm)
                        flat_grads = [
                            jnp.where(m > 0, g * scale.astype(g.dtype), g)
                            for g, m in zip(flat_grads, clip_masks)]
                    new_ps, new_sts = [], []
                    for gi, (pbuf, g, st) in enumerate(
                            zip(flat_ps, flat_grads, flat_sts)):
                        if reg_vecs[gi] is not None:
                            g = g + reg_vecs[gi].astype(pbuf.dtype) * pbuf
                        ctx = ctx_vecs[gi]
                        p2, s2 = opt._update(pbuf, g, dict(st), lr, step_no,
                                             ctx)
                        new_ps.append(p2)
                        new_sts.append(s2)
                return loss, new_ps, new_sts, effects

            fused_jit = _noted_jit(fused_step, donate_argnums=(0, 2))

            def pack(train_raws, states):
                flat_ps, flat_sts = [], []
                for dt, idxs, offs, n in gmeta:
                    flat_ps.append(jnp.concatenate(
                        [train_raws[i].reshape(-1) for i in idxs]))
                    st = {}
                    for k in state_keys:
                        st[k] = jnp.concatenate(
                            [states[i][k].reshape(-1) for i in idxs])
                    flat_sts.append(st)
                return flat_ps, flat_sts

            def unpack_back(flat_ps, flat_sts, fixed):
                for (dt, idxs, offs, n), buf, st in zip(gmeta, flat_ps,
                                                        flat_sts):
                    for (o, sz, shp), i in zip(offs, idxs):
                        p = trainable[i]
                        p._data = buf[o:o + sz].reshape(shp)
                        p._inplace_version += 1
                        opt._state[stable_uid(p)] = {
                            k: st[k][o:o + sz].reshape(shp)
                            for k in state_keys}
                for h, fj in zip(holders, eff_fixed_idx):
                    h._data = fixed[fj]
                    h._inplace_version += 1

            result = (pack, unpack_back, fused_jit, eff_fixed_idx)
            break
        self._fused_loop_key = self._train_sig
        self._fused_loop = result
        return result

    def train_batch(self, inputs, labels=None, update=True):
        """One fused train step (reference: model.py train_batch)."""
        meter = self._step_meter
        if meter is None and not _otrace._ENABLED[0]:
            return self._train_batch_impl(inputs, labels, update)
        t0 = _time.perf_counter()
        with _otrace.span("train/step"):
            out = self._train_batch_impl(inputs, labels, update)
        if meter is not None:
            # the impl's float(loss) fetch synchronizes, so this wall time
            # is real device+host step time, not async-dispatch time
            ts = self._train_step_fn
            meter.step(_time.perf_counter() - t0,
                       flops=ts.get("flops") if ts else None)
        return out

    def _train_batch_impl(self, inputs, labels=None, update=True):
        inputs = inputs if isinstance(inputs, (list, tuple)) else [inputs]
        labels = labels if isinstance(labels, (list, tuple)) else (
            [labels] if labels is not None else [])
        x_raws = [i._data if isinstance(i, Tensor) else jnp.asarray(i)
                  for i in inputs]
        y_raws = [l._data if isinstance(l, Tensor) else jnp.asarray(l)
                  for l in labels]
        sig = (tuple((tuple(r.shape), str(r.dtype))
                     for r in x_raws + y_raws), bool(self._metrics))
        ts = self._get_train_step(sig)
        opt = self._optimizer
        for p in ts["trainable"]:
            if stable_uid(p) not in opt._state:
                opt._state[stable_uid(p)] = opt._init_state(p)
        opt._accumulators_built = True
        opt_states = [opt._state[stable_uid(p)] for p in ts["trainable"]]
        train_raws = [p._data for p in ts["trainable"]]
        fixed_raws = [ts["state"][i]._data for i in ts["fixed_pos"]]
        key = _gen.next_key()
        if self._step_meter is not None and "flops" not in ts:
            # once per compiled signature: XLA cost analysis of the fused
            # step (paddle.flops convention — see observability.stepmeter)
            from ..observability import stepmeter as _sm
            lr0 = jnp.asarray(opt.get_lr(), jnp.float32)
            st0 = jnp.asarray(1.0, jnp.float32)
            with _otrace.span("observability/cost_analysis"):
                ts["flops"] = _sm.compiled_flops(
                    ts["raw_step"], train_raws, fixed_raws, opt_states,
                    x_raws, y_raws, key, lr0, st0)
            self._step_meter.set_flops_per_step(ts["flops"])
        if not update:
            # gradient accumulation (reference train_batch(update=False)):
            # accumulate into .grad, defer clip/regularize/step
            loss, preds, grads, effects = ts["grads_fn"](
                train_raws, fixed_raws, x_raws, y_raws, key)
            for p, g in zip(ts["trainable"], grads):
                p._grad = g if p._grad is None else p._grad + g
        elif (any(p._grad is not None for p in ts["trainable"])
                or opt._sentinel is not None):
            # finishing an accumulation window: add this batch's grads to
            # the carried sum and let the eager optimizer (clip/regularize
            # inside step()) apply the combined update — reference
            # semantics for train_batch after update=False calls.
            # A sentinel-guarded optimizer takes this route too: its health
            # probe needs the grads materialized and its skip/rollback
            # decision happens in the Optimizer.step hook, neither of which
            # exists inside the fully-fused update program
            loss, preds, grads, effects = ts["grads_fn"](
                train_raws, fixed_raws, x_raws, y_raws, key)
            for p, g in zip(ts["trainable"], grads):
                p._grad = g if p._grad is None else p._grad + g
            if opt._sentinel is not None:
                opt._sentinel.observe(loss=loss)
            opt.step()
            opt.clear_grad()
        else:
            lr = jnp.asarray(opt.get_lr(), jnp.float32)
            step_no = jnp.asarray(opt._global_step + 1, jnp.float32)
            loss, preds, new_p, new_s, effects = ts["fn"](
                train_raws, fixed_raws, opt_states, x_raws, y_raws, key,
                lr, step_no)
            for p, npr, ns in zip(ts["trainable"], new_p, new_s):
                p._data = npr
                p._inplace_version += 1
                opt._state[stable_uid(p)] = ns
            opt._global_step += 1
        for h, v in zip(ts["meta"].get("effect_holders", []), effects):
            h._data = v
            h._inplace_version += 1
        metrics = self._update_metrics(preds, labels)
        return float(loss), metrics

    def _update_metrics(self, preds, labels):
        out = []
        for m in self._metrics:
            pt = [Tensor(p) for p in preds]
            r = m.compute(*pt, *labels)
            r = m.update(r if not isinstance(r, tuple) else r[0])
            out.append(r)
        return out

    def _eval_cache_get(self, sig):
        ef = self._eval_fns.get(sig)
        if ef is not None:
            self._eval_fns.move_to_end(sig)
        return ef

    def _eval_cache_put(self, sig, ef):
        if len(self._eval_fns) >= self._eval_fns_max:
            self._eval_fns.popitem(last=False)
        self._eval_fns[sig] = ef
        return ef

    def _build_eval_step(self, with_loss):
        """Compile (state, x, y) -> (preds, loss) — eval/predict as ONE
        cached XLA program per signature instead of per-op dispatch
        (reference: hapi/model.py:250 StaticGraphAdapter compiles a
        separate eval Program; per-op eager here would pay one dispatch
        per op)."""
        params, buffers = self._state()
        state = params + buffers
        loss_fn = self._loss
        net = self.network

        def ev(state_raws, x_raws, y_raws, key):
            with trace_context(key):
                with swap_params(state, state_raws):
                    with _ag.no_grad():
                        xs = [Tensor(r) for r in x_raws]
                        ys = [Tensor(r) for r in y_raws]
                        preds = net.forward(*xs)
                        preds_t = preds if isinstance(preds, (list, tuple)) \
                            else [preds]
                        if with_loss:
                            loss = loss_fn(*preds_t, *ys)
                            loss_raw = (loss._data if isinstance(loss, Tensor)
                                        else jnp.asarray(loss))
                        else:
                            loss_raw = jnp.zeros(())
            # eval-mode traces have no buffer effects (BN uses running
            # stats); any stray effect is deliberately not applied
            return [p._data for p in preds_t], loss_raw

        return {"fn": jax.jit(ev), "state": state}

    def eval_batch(self, inputs, labels=None):
        inputs = inputs if isinstance(inputs, (list, tuple)) else [inputs]
        labels = labels if isinstance(labels, (list, tuple)) else (
            [labels] if labels is not None else [])
        x_raws = [i._data if isinstance(i, Tensor) else jnp.asarray(i)
                  for i in inputs]
        y_raws = [l._data if isinstance(l, Tensor) else jnp.asarray(l)
                  for l in labels]
        with_loss = self._loss is not None and bool(labels)
        sig = (tuple((tuple(r.shape), str(r.dtype))
                     for r in x_raws + y_raws), with_loss)
        ef = self._eval_cache_get(sig)
        if ef is None:
            self.network.eval()
            ef = self._eval_cache_put(sig, self._build_eval_step(with_loss))
        preds, loss_raw = ef["fn"]([s._data for s in ef["state"]],
                                   x_raws, y_raws, _gen.next_key())
        loss = float(loss_raw) if with_loss else None
        metrics = self._update_metrics(preds, labels)
        return loss, metrics

    def predict_batch(self, inputs):
        inputs = inputs if isinstance(inputs, (list, tuple)) else [inputs]
        x_raws = [i._data if isinstance(i, Tensor) else jnp.asarray(i)
                  for i in inputs]
        sig = (tuple((tuple(r.shape), str(r.dtype)) for r in x_raws),
               "predict")
        ef = self._eval_cache_get(sig)
        if ef is None:
            self.network.eval()
            ef = self._eval_cache_put(
                sig, self._build_eval_step(with_loss=False))
        preds, _ = ef["fn"]([s._data for s in ef["state"]], x_raws, [],
                            _gen.next_key())
        out = [Tensor(p) for p in preds]
        return out[0] if len(out) == 1 else out

    # ------------------------------------------------------------------
    def _as_loader(self, data, batch_size, shuffle, num_workers):
        if data is None or isinstance(data, DataLoader):
            return data
        if isinstance(data, Dataset):
            return DataLoader(data, batch_size=batch_size, shuffle=shuffle,
                              num_workers=num_workers)
        return data  # assume iterable of batches

    @staticmethod
    def _split_batch(batch):
        if isinstance(batch, (list, tuple)):
            if len(batch) >= 2:
                return list(batch[:-1]), [batch[-1]]
            return [batch[0]], []
        return [batch], []

    def fit(self, train_data=None, eval_data=None, batch_size=1, epochs=1,
            eval_freq=1, log_freq=10, save_dir=None, save_freq=1, verbose=2,
            drop_last=False, shuffle=True, num_workers=0, callbacks=None,
            accumulate_grad_batches=1, num_iters=None):
        """reference: hapi/model.py:1519."""
        loader = self._as_loader(train_data, batch_size, shuffle, num_workers)
        eval_loader = self._as_loader(eval_data, batch_size, False, num_workers)
        try:
            steps = len(loader)
        except TypeError:
            steps = None
        cbks = config_callbacks(callbacks, model=self, epochs=epochs,
                                steps=steps, log_freq=log_freq,
                                verbose=verbose, save_freq=save_freq,
                                save_dir=save_dir,
                                metrics=[m.name() for m in self._metrics])
        self.stop_training = False
        cbks.on_train_begin()
        it = 0
        try:
            for epoch in range(epochs):
                cbks.on_epoch_begin(epoch)
                for m in self._metrics:
                    m.reset()
                logs = {}
                for step, batch in enumerate(loader):
                    cbks.on_train_batch_begin(step)
                    xs, ys = self._split_batch(batch)
                    self._last_batch = (xs, ys)  # for sentinel quarantine dumps
                    loss, metrics = self.train_batch(xs, ys)
                    logs = {"loss": loss}
                    for m, r in zip(self._metrics, metrics):
                        logs[m.name() if isinstance(m.name(), str) else
                             m.name()[0]] = r
                    cbks.on_train_batch_end(step, logs)
                    it += 1
                    if num_iters is not None and it >= num_iters:
                        break
                cbks.on_epoch_end(epoch, logs)
                if eval_loader is not None and (epoch + 1) % eval_freq == 0:
                    self.evaluate(eval_loader, verbose=verbose,
                                  callbacks=cbks.callbacks, _inner=True)
                if self.stop_training or (num_iters is not None
                                          and it >= num_iters):
                    break
        except Exception as e:
            # post-mortem timeline for the guarded loop; dump only when the
            # flight recorder is armed (observability.enable / env)
            from ..observability import flight as _flight
            _flight.record_event("train_loop_exception",
                                 {"error": f"{type(e).__name__}: {e}",
                                  "iteration": it})
            _flight.dump_if_armed("train_loop_exception")
            raise
        cbks.on_train_end(logs)

    def evaluate(self, eval_data, batch_size=1, log_freq=10, verbose=2,
                 num_workers=0, callbacks=None, num_samples=None, _inner=False):
        loader = self._as_loader(eval_data, batch_size, False, num_workers)
        for m in self._metrics:
            m.reset()
        losses = []
        for batch in loader:
            xs, ys = self._split_batch(batch)
            loss, _ = self.eval_batch(xs, ys)
            if loss is not None:
                losses.append(loss)
        logs = {}
        if losses:
            logs["loss"] = float(np.mean(losses))
        for m in self._metrics:
            name = m.name()
            logs[name if isinstance(name, str) else name[0]] = m.accumulate()
        if callbacks is not None and _inner:
            from .callbacks import CallbackList
            CallbackList(callbacks).on_eval_end(logs)
        elif verbose:
            print("Eval:", logs)
        return logs

    def predict(self, test_data, batch_size=1, num_workers=0,
                stack_outputs=False, callbacks=None, verbose=1):
        loader = self._as_loader(test_data, batch_size, False, num_workers)
        outputs = []
        for batch in loader:
            xs, _ = self._split_batch(batch)
            out = self.predict_batch(xs)
            outputs.append(out.numpy() if isinstance(out, Tensor)
                           else [o.numpy() for o in out])
        if stack_outputs and outputs and isinstance(outputs[0], np.ndarray):
            return [np.concatenate(outputs, 0)]
        return outputs

    # ------------------------------------------------------------------
    def save(self, path, training=True):
        framework_io.save(self.network.state_dict(), path + ".pdparams")
        if training and self._optimizer is not None:
            framework_io.save(self._optimizer.state_dict(), path + ".pdopt")

    def load(self, path, skip_mismatch=False, reset_optimizer=False):
        state = framework_io.load(path + ".pdparams")
        self.network.set_state_dict(state)
        # retire every compiled program referencing old param objects
        self._invalidate_compiled()
        import os
        if not reset_optimizer and self._optimizer is not None and \
                os.path.exists(path + ".pdopt"):
            self._optimizer.set_state_dict(framework_io.load(path + ".pdopt"))

    def parameters(self, *args, **kwargs):
        return self.network.parameters()

    def summary(self, input_size=None, dtype=None):
        return summary(self.network, input_size, dtype)


def summary(net: Layer, input_size=None, dtypes=None):
    """reference: hapi/model_summary.py — layer table + param counts."""
    rows = []
    total = 0
    trainable = 0
    for name, layer in net.named_sublayers(include_self=True):
        n_params = 0
        for _, p in layer._parameters.items():
            if p is not None:
                n_params += p.size
        for _, b in layer._buffers.items():
            if b is not None:
                n_params += b.size
        if name == "":
            continue
        rows.append((name, type(layer).__name__, n_params))
    seen = set()
    for _, p in net.named_parameters():
        if id(p) in seen:
            continue
        seen.add(id(p))
        total += p.size
        if p.trainable:
            trainable += p.size
    for _, b in net.named_buffers():
        if id(b) not in seen:
            total += b.size
            seen.add(id(b))
    print("-" * 64)
    print(f"{'Layer':<36}{'Type':<18}{'Params':>10}")
    print("=" * 64)
    for name, kind, n in rows:
        print(f"{name:<36}{kind:<18}{n:>10}")
    print("=" * 64)
    print(f"Total params: {total}")
    print(f"Trainable params: {trainable}")
    print(f"Non-trainable params: {total - trainable}")
    print("-" * 64)
    return {"total_params": total, "trainable_params": trainable}


# -- trace-audit registration (tools/analyze/trace, PTA009/PTA010) -----------

def _audit_hapi_train_spec():
    """The fused hapi train step (fwd + grad + optimizer update, donated
    param/opt buffers) built by Model._build_train_step on a tiny Linear
    regression — the production step-compilation path, minimally sized."""
    import numpy as np
    from ..core import audit
    from ..core.tensor import stable_uid
    from .. import nn, optimizer as optim
    from .. import ops as _ops

    net = nn.Linear(5, 2)
    model = Model(net)

    def mse(pred, y):
        return _ops.mean((pred - y) ** 2)

    opt = optim.SGD(learning_rate=0.1, parameters=net.parameters())
    model.prepare(optimizer=opt, loss=mse)
    x_shape, y_shape = (4, 5), (4, 2)
    sig = ((((x_shape), "float32"), ((y_shape), "float32")), False)
    ts = model._get_train_step(sig)
    for p in ts["trainable"]:
        if stable_uid(p) not in opt._state:
            opt._state[stable_uid(p)] = opt._init_state(p)
    base_train = [np.asarray(p._data)  # noqa: PTA002 -- audit-factory setup: one-time host snapshot of the init params, not a step-path sync
                  for p in ts["trainable"]]
    base_fixed = [np.asarray(ts["state"][i]._data)  # noqa: PTA002 -- audit-factory setup: one-time host snapshot, not a step-path sync
                  for i in ts["fixed_pos"]]
    base_states = jax.tree_util.tree_map(
        np.asarray, [opt._state[stable_uid(p)] for p in ts["trainable"]])

    def make_args(variant):
        # fresh arrays per call: donate_argnums=(0, 2) consumes them
        rng = np.random.default_rng(5 + variant)
        train_raws = [jnp.asarray(b) for b in base_train]
        fixed_raws = [jnp.asarray(b) for b in base_fixed]
        opt_states = jax.tree_util.tree_map(jnp.asarray, base_states)
        x_raws = [jnp.asarray(rng.standard_normal(x_shape), jnp.float32)]
        y_raws = [jnp.asarray(rng.standard_normal(y_shape), jnp.float32)]
        key = jax.random.PRNGKey(variant)
        lr = jnp.asarray(0.1, jnp.float32)
        step_no = jnp.asarray(1.0, jnp.float32)
        return (train_raws, fixed_raws, opt_states, x_raws, y_raws, key,
                lr, step_no)

    return audit.AuditSpec(fn=ts["raw_step"], make_args=make_args,
                           jit_kwargs={"donate_argnums": (0, 2)})


def _register_audit_entrypoints():
    from ..core import audit
    audit.register_entrypoint("hapi_train_step", _audit_hapi_train_spec,
                              tags=("train",))


_register_audit_entrypoints()
