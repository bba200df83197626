"""Compiled GPipe engine: rotating microbatch schedule over the "pp" axis.

TPU-native equivalent of the reference's pipeline runtime
(reference: python/paddle/distributed/fleet/meta_parallel/
pipeline_parallel.py:107 train_batch — the host loop issuing per-microbatch
forward/backward with send_v2/recv_v2 between stage processes;
framework/section_worker.cc:99 SectionWorker::TrainFiles).

Here the whole schedule is ONE compiled SPMD program ("pipelined scan",
the standard TPU formulation): every pp rank holds one stage's parameters
(stacked pytree sharded over "pp"), a lax.scan ticks M + S - 1 times, each
tick computes one stage on every rank simultaneously and rotates
activations with ppermute — warm-up/drain bubbles fall out of the tick
index arithmetic, and reverse-mode AD through scan+ppermute yields the
pipelined backward automatically (no hand-written p2p grad schedule).
"""
from __future__ import annotations

from typing import Callable

import numpy as np
import jax
import jax.numpy as jnp
from jax import lax
from jax.sharding import PartitionSpec as P

from .. import mesh as _mesh


def stack_stage_params(param_trees):
    """Stack S structurally-identical per-stage param pytrees along a new
    leading axis (to be sharded over "pp")."""
    return jax.tree_util.tree_map(lambda *xs: jnp.stack(xs), *param_trees)


def gpipe_apply(block_fn: Callable, stacked_params, mb_x, mesh=None,
                axis="pp"):
    """Apply S pipeline stages to M microbatches.

    block_fn(params, x) -> y must be shape-preserving (x and y same shape —
    the transformer-block case). For heterogeneous stages (embedding →
    blocks → head, different shapes per stage) use ``gpipe_blocks`` /
    ``gpipe_stages`` below instead. ``stacked_params``: pytree with leading
    dim S on every leaf. ``mb_x``: [M, ...] microbatched input (replicated).
    Returns [M, ...] outputs. Differentiable end-to-end.
    """
    m = mesh or _mesh.ensure_mesh()
    S = int(m.shape[axis])
    M = int(mb_x.shape[0])
    T = M + S - 1

    def per_rank(params_shard, xs):
        # params_shard leaves: [1, ...] (this rank's stage)
        params_local = jax.tree_util.tree_map(lambda a: a[0], params_shard)
        rank = lax.axis_index(axis)

        # mark the carries device-varying for shard_map's vma type system
        state0 = lax.pcast(jnp.zeros_like(xs[0]), (axis,), to="varying")
        outbuf0 = lax.pcast(jnp.zeros_like(xs), (axis,), to="varying")

        def tick(carry, t):
            state, outbuf = carry
            x_t = xs[jnp.clip(t, 0, M - 1)]
            inp = jnp.where(rank == 0, x_t, state)
            y = block_fn(params_local, inp)
            # last rank collects microbatch t-(S-1) once the pipe is full
            oi = jnp.clip(t - (S - 1), 0, M - 1)
            write = jnp.logical_and(rank == S - 1, t >= S - 1)
            cur = lax.dynamic_index_in_dim(outbuf, oi, 0, keepdims=False)
            outbuf = lax.dynamic_update_index_in_dim(
                outbuf, jnp.where(write, y, cur), oi, 0)
            # rotate activations one stage forward
            nxt = lax.ppermute(y, axis, perm=[(i, i + 1) for i in range(S - 1)])
            return (nxt, outbuf), None

        (_, outbuf), _ = lax.scan(tick, (state0, outbuf0), jnp.arange(T))
        # replicate the collected outputs from the last rank
        contrib = jnp.where(rank == S - 1, outbuf, jnp.zeros_like(outbuf))
        return lax.psum(contrib, axis)

    spec_axes_only = P(axis)
    in_specs = (jax.tree_util.tree_map(lambda _: spec_axes_only,
                                       stacked_params), P())
    return jax.shard_map(per_rank, mesh=m, in_specs=in_specs,
                         out_specs=P())(stacked_params, mb_x)


def split_microbatches(x, num_micro):
    """[B, ...] -> [M, B/M, ...] (reference: pipeline micro_batch_size)."""
    b = x.shape[0]
    if b % num_micro != 0:
        raise ValueError(f"batch {b} not divisible by {num_micro} microbatches")
    return x.reshape((num_micro, b // num_micro) + x.shape[1:])


# -- heterogeneous stages -----------------------------------------------------
#
# The reference pipeline exchanges activations of arbitrary per-stage shape
# with a runtime shape handshake (pipeline_parallel.py:272 _send_meta). On
# TPU all signatures must be static at trace time, so they are *declared /
# inferred at build time* with jax.eval_shape and validated once:
#   x_sig --embed--> carry_sig --block--> carry_sig ... --head--> out_sig
# Only the inter-stage carry rides the rotating ppermute buffer; the first
# stage reads microbatch inputs directly and the last stage writes to a
# separate output buffer, so the pipe's entry/exit types are unconstrained.


def _sig_of(tree):
    return jax.tree_util.tree_map(
        lambda a: (tuple(a.shape), str(a.dtype)), tree)


def _vary_tree(t, axes):
    """Mark every leaf device-varying on the given axis/axes for
    shard_map's vma type system (idempotent — axes already varying on a
    leaf are skipped)."""
    if isinstance(axes, str):
        axes = (axes,)

    def one(a):
        vma = jax.typeof(a).vma
        missing = tuple(ax for ax in axes if ax not in vma)
        if not missing:
            return a
        return lax.pcast(a, missing, to="varying")
    return jax.tree_util.tree_map(one, t)


def _rotating_schedule(axis, vary_axes, S, M, carry_aval, out_aval,
                       xs_local, compute):
    """The shared GPipe rotating-scan core: tick over M + S - 1 steps,
    feed stage 0 from the microbatch stream, collect the last rank's
    outputs at the pipe-depth lag, rotate carries with ppermute, and shed
    varying axes at the end. ``compute(rank, state, x_t, x_last, vary)``
    -> (carry_out, out_t) supplies the per-engine stage dispatch."""
    rank = lax.axis_index(axis)

    def vary(t):
        return _vary_tree(t, vary_axes)

    state0 = vary(jax.tree_util.tree_map(
        lambda av: jnp.zeros(av.shape, av.dtype), carry_aval))
    outbuf0 = vary(jax.tree_util.tree_map(
        lambda av: jnp.zeros((M,) + tuple(av.shape), av.dtype), out_aval))
    T = M + S - 1

    def tick(carry, t):
        state, outbuf = carry
        x_t = jax.tree_util.tree_map(
            lambda a: a[jnp.clip(t, 0, M - 1)], xs_local)
        # the microbatch the LAST stage is processing lags the pipe depth
        x_last = jax.tree_util.tree_map(
            lambda a: a[jnp.clip(t - (S - 1), 0, M - 1)], xs_local)
        c, out_t = compute(rank, state, x_t, x_last, vary)
        oi = jnp.clip(t - (S - 1), 0, M - 1)
        write = jnp.logical_and(rank == S - 1, t >= S - 1)

        def upd(buf, o):
            cur = lax.dynamic_index_in_dim(buf, oi, 0, keepdims=False)
            return lax.dynamic_update_index_in_dim(
                buf, jnp.where(write, o, cur), oi, 0)
        outbuf = jax.tree_util.tree_map(upd, outbuf, out_t)
        nxt = jax.tree_util.tree_map(
            lambda a: lax.ppermute(
                a, axis, perm=[(i, i + 1) for i in range(S - 1)]), c)
        return (nxt, outbuf), None

    (_, outbuf), _ = lax.scan(tick, (state0, outbuf0), jnp.arange(T))

    # replicate the collected outputs from the last rank, then shed any
    # remaining varying axes (dp contributions are averaged; other axes,
    # e.g. "mp" after an in-head all_gather, hold identical values so
    # pmean is an identity that satisfies out_specs=P())
    def finalize(b):
        b = lax.psum(jnp.where(rank == S - 1, b, jnp.zeros_like(b)), axis)
        vma = jax.typeof(b).vma
        rest = tuple(ax for ax in vary_axes if ax in vma)
        return lax.pmean(b, rest) if rest else b
    return jax.tree_util.tree_map(finalize, outbuf)


def infer_pipeline_signatures(embed_fn, block_fn, head_fn, embed_params,
                              block_params_one_stage, head_params, x_mb,
                              head_takes_input=False):
    """Abstract-eval the stage chain; returns (carry_aval, out_aval).
    Raises if the block does not preserve the carry signature (the static
    equivalent of a _send_meta mismatch)."""
    carry = jax.eval_shape(embed_fn, embed_params, x_mb)
    carry2 = jax.eval_shape(block_fn, block_params_one_stage, carry)
    if _sig_of(carry) != _sig_of(carry2):
        raise ValueError(
            f"pipeline block must preserve the inter-stage signature: "
            f"got {_sig_of(carry)} -> {_sig_of(carry2)}")
    if head_takes_input:
        out = jax.eval_shape(head_fn, head_params, carry, x_mb)
    else:
        out = jax.eval_shape(head_fn, head_params, carry)
    return carry, out


def gpipe_blocks(embed_fn, block_fn, head_fn, embed_params,
                 stacked_block_params, head_params, xs, mesh=None,
                 axis="pp", carry_sig=None, out_sig=None,
                 head_takes_input=False, batch_axis=None,
                 embed_specs=None, block_specs=None, head_specs=None):
    """Pipeline a full model — embed → S×blocks → head — in ONE compiled
    rotating-scan program (heterogeneous first/last stages).

    - ``embed_fn(embed_params, x_mb) -> carry`` runs as stage 0's preamble
      (e.g. token+position embedding; ``x_mb`` may be int ids).
    - ``block_fn(stage_params, carry) -> carry`` is the uniform stage body;
      ``stacked_block_params`` leaves are [S, ...] and are sharded over the
      ``axis`` mesh axis — block (the bulk) memory scales 1/S per rank.
    - ``head_fn(head_params, carry) -> out`` runs as the last stage's
      postamble (final norm + logits, or a per-microbatch loss). With
      ``head_takes_input=True`` it is called as
      ``head_fn(head_params, carry, x_mb)`` where ``x_mb`` is the
      microbatch the carry belongs to (for in-pipe loss: labels ride xs).
    - ``embed_params``/``head_params`` are replicated on every rank (for
      GPT they are the tied embedding table, needed on both ends anyway).

    ``xs``: [M, ...] microbatched inputs. Returns [M, *out.shape].
    Differentiable end-to-end (AD through scan + ppermute + cond).

    ``batch_axis``: name of a data-parallel mesh axis — each dp slice runs
    the pipe on its shard of the microbatch dim 1 and the collected outputs
    are pmean'd over it (dp×pp hybrid in one program).

    ``embed_specs``/``block_specs``/``head_specs``: PartitionSpec pytrees
    overriding the default placement (embed/head replicated, blocks
    P(axis) on dim 0) — used for tensor-parallel hybrids where block
    weights are additionally sharded over "mp" and the stage fns contain
    the matching TP collectives (declare carry_sig/out_sig then).
    """
    m = mesh or _mesh.ensure_mesh()
    S = int(m.shape[axis])
    M = int(jax.tree_util.tree_leaves(xs)[0].shape[0])

    if carry_sig is not None and out_sig is not None:
        # declared signatures (needed when stage fns contain collectives
        # that can't abstract-eval outside the mesh trace, e.g. TP psum)
        carry_aval, out_aval = carry_sig, out_sig
    else:
        block_one = jax.tree_util.tree_map(
            lambda a: a[0], stacked_block_params)
        # signatures are LOCAL (per-device) shapes: dp shards dim 1
        bs = int(m.shape[batch_axis]) if batch_axis else 1  # noqa: PTA001 -- mesh axis size is a static host int, never a tracer
        x_aval = jax.tree_util.tree_map(
            lambda a: jax.ShapeDtypeStruct(
                (a.shape[1] // bs,) + tuple(a.shape[2:]), a.dtype), xs)
        carry_aval, out_aval = infer_pipeline_signatures(
            embed_fn, block_fn, head_fn, embed_params, block_one,
            head_params, x_aval, head_takes_input=head_takes_input)

    # branches joined by cond/where must agree on varying axes, so mark
    # values varying on EVERY mesh axis; the finalize step sheds them
    vary_axes = tuple(m.axis_names)

    def per_rank(emb_p, blocks_shard, head_p, xs_local):
        block_local = jax.tree_util.tree_map(lambda a: a[0], blocks_shard)
        # Replicated inputs used inside rank-divergent cond branches must be
        # varying BEFORE the branch: the transpose of an unvarying->varying
        # use is a psum, and a psum inside a divergent branch deadlocks.
        # Varying them here moves that psum to the (uniform) shard_map
        # boundary.
        emb_p = _vary_tree(emb_p, vary_axes)
        head_p = _vary_tree(head_p, vary_axes)
        xs_local = _vary_tree(xs_local, vary_axes)

        def compute(rank, state, x_t, x_last, vary):
            # stage-0 preamble: embed this tick's microbatch; other ranks
            # use the rotated-in activation (cond executes one branch, so
            # embedding FLOPs happen on rank 0 only)
            inp = lax.cond(rank == 0,
                           lambda: vary(embed_fn(emb_p, x_t)),
                           lambda: state)
            y = block_fn(block_local, inp)
            # last-rank postamble once the pipe is full
            apply_head = ((lambda: vary(head_fn(head_p, y, x_last)))
                          if head_takes_input
                          else (lambda: vary(head_fn(head_p, y))))
            out_t = lax.cond(rank == S - 1,
                             apply_head,
                             lambda: vary(jax.tree_util.tree_map(
                                 lambda av: jnp.zeros(av.shape, av.dtype),
                                 out_aval)))
            return y, out_t

        return _rotating_schedule(axis, vary_axes, S, M, carry_aval,
                                  out_aval, xs_local, compute)

    xs_spec = P() if batch_axis is None else P(None, batch_axis)
    in_specs = (embed_specs if embed_specs is not None else
                jax.tree_util.tree_map(lambda _: P(), embed_params),
                block_specs if block_specs is not None else
                jax.tree_util.tree_map(lambda _: P(axis),
                                       stacked_block_params),
                head_specs if head_specs is not None else
                jax.tree_util.tree_map(lambda _: P(), head_params),
                jax.tree_util.tree_map(lambda _: xs_spec, xs))
    return jax.shard_map(per_rank, mesh=m, in_specs=in_specs,
                         out_specs=P())(embed_params, stacked_block_params,
                                        head_params, xs)


def gpipe_stages(stage_fns, stage_params, xs, mesh=None, axis="pp",
                 last_takes_input=False, carry_sig=None, out_sig=None):
    """Pipeline an arbitrary list of per-stage functions (the compiled path
    for heterogeneous ``PipelineLayer`` stage lists).

    ``stage_fns[s](stage_params[s], inp) -> out``; stage 0 consumes the
    microbatch input, later stages consume the previous stage's output, and
    all inter-stage signatures must agree (validated by abstract eval — the
    build-time _send_meta). Stage dispatch is ``lax.switch`` on the rank, so
    each rank computes only its own stage; params are replicated across
    ranks (arbitrary per-stage structures can't be mesh-stacked — use
    :func:`gpipe_blocks` when the bulk of the model is a uniform block
    stack and memory scaling matters).

    ``last_takes_input=True`` gives the last stage the *microbatch input*
    too — ``stage_fns[-1](params, (carry, x_mb))`` with ``x_mb`` aligned to
    the microbatch the carry belongs to (for in-pipe loss against labels
    carried in ``xs``). ``carry_sig``/``out_sig`` declare signatures when
    stage fns contain collectives that can't abstract-eval here.

    Returns [M, *out.shape] from the last stage. Differentiable.
    """
    m = mesh or _mesh.ensure_mesh()
    S = int(m.shape[axis])  # noqa: PTA001 -- mesh axis size is a static host int, never a tracer
    if len(stage_fns) != S:
        raise ValueError(f"{len(stage_fns)} stage fns for {axis}={S} mesh")
    M = int(jax.tree_util.tree_leaves(xs)[0].shape[0])  # noqa: PTA001 -- array shape is concrete at trace time

    if carry_sig is not None and out_sig is not None:
        carry_aval, out_aval = carry_sig, out_sig
    else:
        x_aval = jax.tree_util.tree_map(
            lambda a: jax.ShapeDtypeStruct(a.shape[1:], a.dtype), xs)
        sig = jax.eval_shape(stage_fns[0], stage_params[0], x_aval)
        carry_aval = sig
        for s in range(1, S):
            arg = (sig, x_aval) if (last_takes_input and s == S - 1) else sig
            nxt_sig = jax.eval_shape(stage_fns[s], stage_params[s], arg)
            if s < S - 1 and _sig_of(nxt_sig) != _sig_of(sig):
                raise ValueError(
                    f"stage {s} changes the inter-stage signature "
                    f"{_sig_of(sig)} -> {_sig_of(nxt_sig)}; only the last "
                    f"stage may (declare signatures so every middle stage "
                    f"preserves them)")
            sig = nxt_sig
        out_aval = sig

    vary_axes = tuple(m.axis_names)

    def per_rank(params_all, xs_local):
        # see gpipe_blocks: vary replicated inputs before divergent branches
        params_all = _vary_tree(params_all, vary_axes)
        xs_local = _vary_tree(xs_local, vary_axes)

        def zeros_of(aval_tree):
            return jax.tree_util.tree_map(
                lambda av: jnp.zeros(av.shape, av.dtype), aval_tree)

        def compute(rank, state, x_t, x_last, vary):
            def make_branch(s):
                def branch(operand):
                    x_in, x_tail, st = operand
                    if s == 0:
                        inp = x_in
                    elif s == S - 1 and last_takes_input:
                        inp = (st, x_tail)
                    else:
                        inp = st
                    o = stage_fns[s](params_all[s], inp)
                    # uniform return type: (carry-typed, out-typed)
                    c = o if s < S - 1 else zeros_of(carry_aval)
                    y = o if s == S - 1 else zeros_of(out_aval)
                    return vary(c), vary(y)
                return branch

            return lax.switch(rank, [make_branch(s) for s in range(S)],
                              (x_t, x_last, state))

        return _rotating_schedule(axis, vary_axes, S, M, carry_aval,
                                  out_aval, xs_local, compute)

    in_specs = (jax.tree_util.tree_map(lambda _: P(), list(stage_params)),
                P())
    return jax.shard_map(per_rank, mesh=m, in_specs=in_specs,
                         out_specs=P())(list(stage_params), xs)
