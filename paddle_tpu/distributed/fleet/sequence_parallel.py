"""Sequence/context parallelism: ring attention over an "sp" mesh axis.

The reference snapshot has NO sequence parallelism (SURVEY §5.7 verified
absent — long sequences are handled only by recompute+sharding+pipeline);
this module is the parity-plus capability the TPU build plan calls for:
scale *sequence length* across chips so attention's O(T²) memory is split
S ways while each chip's matmuls stay MXU-sized.

Design (the standard TPU ring formulation): Q/K/V are sharded on the
sequence dim over the "sp" axis. Each rank keeps its Q block resident and
walks the K/V ring — S steps of (blockwise attention + streaming-softmax
accumulation + ppermute of the K/V block to the next rank) — so ICI
carries exactly one K/V block per step, overlapped by XLA with the
block's matmuls. Numerics are exact (same streaming-max/denominator
algebra as flash attention), verified against dense attention in tests.

Two chunk-compute variants share the ring schedule:

- :func:`ring_attention` — dense [Tl, Tl] score blocks per step.
  Differentiable end-to-end: AD through scan+ppermute yields the reverse
  ring schedule automatically.
- :func:`ring_flash_attention` — the Pallas flash kernel per step, with
  a hand-written :func:`jax.custom_vjp` backward (the kernel has no AD
  rule). Forward saves per-rank (o, lse); backward walks the K/V ring a
  second time running the FlashAttention recomputation schedule per
  chunk (``ops/pallas_attention._fa_bwd_with_lse``): dQ accumulates
  locally while each K/V block's dK/dV accumulator travels the ring
  *with* its block, so after exactly S ppermute steps every accumulator
  has collected all ranks' contributions and is back home. No [Tl, Tl]
  score tensor ever materializes in either direction — see
  docs/performance.md "Long-context training".

The shard-mapped callables for both variants are cached per
(mesh, axis, causal, scale, batch_axes[, interpret]) signature so warm
eager calls reuse jit traces instead of rebuilding a fresh
``jax.shard_map`` over a new lambda each call.
"""
from __future__ import annotations

import collections
import functools
from typing import Optional

import numpy as np
import jax
import jax.numpy as jnp
from jax import lax
from jax.sharding import PartitionSpec as P

from .. import mesh as _mesh

_NEG = -1e30  # -inf stand-in: keeps the streaming-softmax algebra nan-free

#: python-side trace counter, bumped once per (re)trace of each ring
#: local function — the compile-counter regression tests assert warm
#: calls leave these untouched
_TRACE_COUNTS = collections.Counter()

#: shard-mapped ring callables keyed by signature (see _ring_callable);
#: bounded in practice by the handful of (mesh, flags) combinations a
#: process uses, so no eviction policy
_RING_CACHE = {}


def _ring_attention_local(q, k, v, axis: str, causal: bool, scale):
    """Runs INSIDE shard_map. q/k/v: local [B, H, Tl, D] blocks (sequence
    dim sharded over ``axis``). Returns local attention output."""
    _TRACE_COUNTS["ring_dense"] += 1
    S = lax.axis_size(axis)
    rank = lax.axis_index(axis)
    B, H, Tl, D = q.shape
    qpos = rank * Tl + jnp.arange(Tl)
    acc = jnp.float32  # flash-attention rule: accumulators in f32 even
    # for bf16/fp16 inputs (matches the f32-stats-in-op AMP convention)

    def step(carry, s):
        o, m, l, kc, vc = carry
        src = jnp.mod(rank - s, S)           # whose K/V block we hold now
        scores = jnp.einsum("bhqd,bhkd->bhqk", q, kc,
                            preferred_element_type=acc) * scale
        if causal:
            kpos = src * Tl + jnp.arange(Tl)
            mask = qpos[:, None] >= kpos[None, :]
            scores = jnp.where(mask[None, None], scores, _NEG)
        smax = jnp.max(scores, axis=-1)                      # [B,H,Tl]
        new_m = jnp.maximum(m, smax)
        # guard: a fully-masked block keeps new_m at _NEG; exp(0)=1 there
        # is harmless because p is all zeros
        p = jnp.exp(scores - new_m[..., None])
        p = jnp.where(scores <= _NEG, 0.0, p)
        corr = jnp.exp(jnp.clip(m - new_m, _NEG, 0.0))
        l2 = l * corr + jnp.sum(p, axis=-1)
        o2 = o * corr[..., None] + jnp.einsum(
            "bhqk,bhkd->bhqd", p, vc.astype(acc),
            preferred_element_type=acc)
        # rotate the K/V ring one step forward
        perm = [(i, (i + 1) % S) for i in range(S)]
        kn = lax.ppermute(kc, axis, perm=perm)
        vn = lax.ppermute(vc, axis, perm=perm)
        return (o2, new_m, l2, kn, vn), None

    # derive the initial carries from q so they inherit ALL of q's varying
    # axes (sp plus any batch axis the caller sharded over)
    o0 = q.astype(acc) * 0
    base = jnp.sum(o0, axis=-1)                       # [B,H,Tl], q's vma
    m0 = base + _NEG
    l0 = base
    (o, m, l, _, _), _ = lax.scan(step, (o0, m0, l0, k, v), jnp.arange(S))
    out = o / jnp.maximum(l, 1e-30)[..., None]
    return out.astype(q.dtype)


def _canon_batch_axes(batch_axes):
    return tuple(batch_axes) if isinstance(batch_axes, (list, tuple)) \
        else batch_axes


def _ring_callable(kind, mesh, axis, causal, scale, batch_axes,
                   interpret=None):
    """The shard-mapped ring callable for one signature, built once and
    cached. A fresh ``jax.shard_map`` over a new lambda per call would
    defeat jit's trace cache (the callable's identity IS the cache key),
    so every eager warm call would retrace the whole ring program."""
    key = (kind, mesh, axis, bool(causal), float(scale),  # noqa: PTA001 -- causal/scale are trace-time python config (never traced values); the cache key must be hashable
           _canon_batch_axes(batch_axes), interpret)
    fn = _RING_CACHE.get(key)
    if fn is None:
        spec = P(batch_axes, None, axis, None)
        if kind == "dense":
            # jit-wrapped: a bare shard_map call re-traces the local fn on
            # every eager invocation; pjit's trace cache (keyed on the
            # stable callable identity we cache here + avals) makes warm
            # calls zero-trace
            fn = jax.jit(jax.shard_map(
                functools.partial(_ring_attention_local, axis=axis,
                                  causal=causal, scale=scale),
                mesh=mesh, in_specs=(spec, spec, spec), out_specs=spec))
        else:
            fn = _build_ring_flash(mesh, spec, axis, causal, scale,
                                   batch_axes, interpret)
        _RING_CACHE[key] = fn
    return fn


def ring_attention(q, k, v, mesh=None, axis: str = "sp",
                   causal: bool = False, scale: Optional[float] = None,
                   batch_axes=None):
    """Exact attention with the sequence dim sharded over ``axis``.

    q/k/v: GLOBAL [B, H, T, D] arrays (T divisible by the axis size).
    Returns [B, H, T, D], sequence-sharded the same way. Pass
    ``batch_axes`` (e.g. "dp") when the batch dim is data-parallel —
    otherwise the shard_map replicates it over the other mesh axes.
    Call from un-mapped code — this wraps its own shard_map; inside an
    existing shard_map use :func:`_ring_attention_local` directly.
    """
    m = mesh or _mesh.ensure_mesh()
    if scale is None:
        scale = 1.0 / float(np.sqrt(q.shape[-1]))  # noqa: PTA001 -- head dim is a static shape, a trace-time python int
    return _ring_callable("dense", m, axis, causal, scale, batch_axes)(
        q, k, v)


def split_sequence(x, mesh=None, axis: str = "sp", seq_dim: int = 2):
    """Shard a global tensor's sequence dim over the sp axis (the
    scatter edge of sequence parallelism)."""
    m = mesh or _mesh.ensure_mesh()
    spec = [None] * x.ndim
    spec[seq_dim] = axis
    from jax.sharding import NamedSharding
    return jax.device_put(x, NamedSharding(m, P(*spec)))


def gather_sequence(x, mesh=None, axis: str = "sp", seq_dim: int = 2):
    """Gather (replicate) the sequence dim of a sequence-sharded tensor;
    other dims keep whatever sharding they had."""
    m = mesh or _mesh.ensure_mesh()
    from jax.sharding import NamedSharding
    sh = getattr(x, "sharding", None)
    spec = [None] * x.ndim
    if sh is not None and hasattr(sh, "spec"):
        cur = list(sh.spec) + [None] * (x.ndim - len(sh.spec))
        spec = cur
    spec[seq_dim] = None
    return jax.device_put(x, NamedSharding(m, P(*spec)))


def _ring_impl(qq, kk, vv, axis="sp", causal=False, batch_axes=None):
    # module-level (no closure) so the eager op cache can key it: a
    # per-call lambda over a Mesh is _UNCACHEABLE and re-traces the whole
    # ring program each call (dispatch.py cache rules)
    return ring_attention(qq, kk, vv, mesh=None, axis=axis, causal=causal,
                          batch_axes=_canon_batch_axes(batch_axes))


class RingAttention:
    """Layer-ish wrapper so models can swap their attention core for the
    sequence-parallel one (EP/CP engines in later frameworks expose the
    same shape: SURVEY §5.7 TPU build implication)."""

    def __init__(self, mesh=None, axis: str = "sp", causal: bool = False,
                 batch_axes=None, use_flash: bool = False):
        if mesh is not None and mesh is not _mesh.get_mesh():
            raise ValueError(
                "RingAttention uses the ambient mesh (set_mesh); pass "
                "mesh= only to ring_attention directly")
        self._axis = axis
        self._causal = causal
        self._batch_axes = batch_axes
        # use_flash: run the Pallas kernel per chunk. Fully trainable —
        # ring_flash_attention carries a custom_vjp whose backward runs
        # the flash recomputation schedule around the ring, so this is
        # the long-context TRAINING fast path, not just inference
        self._use_flash = use_flash

    def __call__(self, q, k, v):
        from ...ops.dispatch import apply
        # through the op funnel: tape-recorded (backprop works), visible
        # to AMP/nan-check/profiler like every other op
        if self._use_flash:
            return apply("ring_flash_attention", _ring_flash_impl,
                         q, k, v, axis=self._axis, causal=self._causal,
                         batch_axes=self._batch_axes)
        return apply("ring_attention", _ring_impl, q, k, v,
                     axis=self._axis, causal=self._causal,
                     batch_axes=self._batch_axes)


def _sanitize_ring_blocks(tuned, Tl: int):
    """Shared divisibility sanitizer for tuned ring block pairs: the ring
    path calls the kernel core without a padding wrapper, so blocks MUST
    divide Tl exactly and stay 16-row sublane multiples. Returns the
    (bq, bk) pair or None when the entry is unusable."""
    if tuned is None:
        return None
    bq, bk = int(tuned[0]), int(tuned[1])
    if (bq > 0 and bk > 0 and Tl % bq == 0 and Tl % bk == 0
            and bq % 16 == 0 and bk % 16 == 0):
        return bq, bk
    return None


def _ring_blocks(Tl: int, D: int, dtype, bwd: bool = False):
    """Block edges for the ring-flash chunk kernel (``bwd`` selects the
    backward-kernel family). Tuned winners that don't divide Tl are
    discarded by :func:`_sanitize_ring_blocks` (the tuner enumerates with
    ``require_divides=True``, so this only filters stale or hand-edited
    cache entries); a missing backward winner falls back to the forward
    family's before the heuristic default."""
    default = Tl if Tl <= 128 else (128 if Tl % 128 == 0 else 16)
    try:
        from ...tuner import get_flash_blocks
        got = _sanitize_ring_blocks(
            get_flash_blocks(Tl, Tl, D, dtype, False, ring=True, bwd=bwd),
            Tl)
        if got is None and bwd:
            got = _sanitize_ring_blocks(
                get_flash_blocks(Tl, Tl, D, dtype, False, ring=True), Tl)
    except Exception:
        got = None
    return got if got is not None else (default, default)


def _ring_flash_fwd_local(q, k, v, axis: str, causal: bool, scale,
                          interpret: bool):
    """Ring attention whose LOCAL chunk compute is the Pallas flash
    kernel (ops/pallas_attention.py) instead of a dense [Tl, Tl] block
    product — the full composition of the two long-context mechanisms:
    flash handles within-chunk memory, the ring handles cross-chip
    sequence scale. Per ring step the kernel emits (normalized chunk
    output, logsumexp rows); chunks merge by the standard lse algebra

        lse' = logaddexp(lse, lse_c)
        o'   = o * exp(lse - lse') + o_c * exp(lse_c - lse')

    Causality across chunks is positional: a K/V chunk strictly in the
    future (src > rank) is masked out entirely, the diagonal chunk runs
    the kernel's causal path, past chunks run non-causal. Runs INSIDE
    shard_map; q/k/v are local [B, H, Tl, D] blocks with Tl a multiple
    of 16 (the kernel's sublane tile).

    Returns ``(o [B,H,Tl,D], lse [B,H,Tl] f32)`` — the merged logsumexp
    rows are the backward residual (with them, per-chunk
    ``p = exp(s·scale − lse)`` IS the global softmax weight, so the
    backward never re-merges).
    """
    from ...ops.pallas_attention import _fa_fwd_with_lse

    _TRACE_COUNTS["ring_flash_fwd"] += 1
    S = lax.axis_size(axis)
    rank = lax.axis_index(axis)
    B, H, Tl, D = q.shape
    if Tl % 16:
        raise ValueError(f"ring_flash_attention: per-shard sequence {Tl} "
                         f"must be a multiple of 16")
    bq, bk = _ring_blocks(Tl, D, q.dtype)
    BH = B * H
    qb = q.reshape(BH, Tl, D)

    def kernel(kc, vc, causal_flag):
        return _fa_fwd_with_lse(qb, kc.reshape(BH, Tl, D),
                                vc.reshape(BH, Tl, D), causal_flag,
                                scale, bq, bk, interpret, Tl)

    def _r3(out_lse):
        o_c, lse_c = out_lse
        return o_c, lse_c.reshape(BH, Tl).astype(jnp.float32)

    def step(carry, s):
        o, lse, kc, vc = carry
        src = jnp.mod(rank - s, S)
        if causal:
            # 3-way switch: past chunk = full kernel, diagonal = causal
            # kernel, future chunk = no kernel launch at all (zeros,
            # masked lse) — skipping ~(S-1)/2S of the launches
            idx = jnp.where(src > rank, 2,
                            jnp.where(src == rank, 1, 0))
            o_c, lse_c = lax.switch(
                idx,
                [lambda: _r3(kernel(kc, vc, False)),
                 lambda: _r3(kernel(kc, vc, True)),
                 lambda: (jnp.zeros((BH, Tl, D), qb.dtype),
                          jnp.full((BH, Tl), _NEG, jnp.float32))])
        else:
            o_c, lse_c = kernel(kc, vc, False)
            lse_c = lse_c.reshape(BH, Tl)
        o_c = o_c.astype(jnp.float32)
        lse_new = jnp.logaddexp(lse, lse_c)
        w_old = jnp.exp(jnp.clip(lse - lse_new, _NEG, 0.0))
        w_new = jnp.exp(jnp.clip(lse_c - lse_new, _NEG, 0.0))
        o = o * w_old[..., None] + o_c * w_new[..., None]
        perm = [(i, (i + 1) % S) for i in range(S)]
        kn = lax.ppermute(kc, axis, perm=perm)
        vn = lax.ppermute(vc, axis, perm=perm)
        return (o, lse_new, kn, vn), None

    # plain initializers: check_vma=False on the enclosing shard_map, so
    # no varying-axes inheritance trick is needed (unlike the dense ring)
    o0 = jnp.zeros((BH, Tl, D), jnp.float32)
    lse0 = jnp.full((BH, Tl), _NEG, jnp.float32)
    (o, lse, _, _), _ = lax.scan(
        step, (o0, lse0, k, v), jnp.arange(S))
    return (o.reshape(B, H, Tl, D).astype(q.dtype),
            lse.reshape(B, H, Tl))


def _ring_flash_bwd_local(q, k, v, o, lse, do, axis: str, causal: bool,
                          scale, interpret: bool):
    """Backward ring schedule (runs INSIDE shard_map). Residual layout:
    per-rank local ``q/k/v/o [B,H,Tl,D]`` plus the merged ``lse
    [B,H,Tl]`` f32 rows from the forward. Because lse is the GLOBAL
    logsumexp, each chunk's ``p = exp(s·scale − lse)`` recomputed by
    ``_fa_bwd_with_lse`` is already the globally-normalized softmax
    weight — the forward's lse-merge weights are folded into the
    gradient scaling for free, and ``delta = rowsum(dO∘O)`` is computed
    ONCE per rank (it is chunk-independent).

    Schedule: walk the K/V ring again (same forward perm). dQ accumulates
    locally in f32; each K/V block travels with its own f32 dK/dV
    accumulator — block b sits on rank b+s at step s, so after S
    ppermute steps every accumulator has collected all ranks'
    contributions and is back on its home rank. The causal 3-way switch
    skips kernel launches for future chunks exactly as the forward does
    (the ppermutes stay outside the switch: every rank must participate
    in every collective).
    """
    from ...ops.pallas_attention import _fa_bwd_with_lse

    _TRACE_COUNTS["ring_flash_bwd"] += 1
    S = lax.axis_size(axis)
    rank = lax.axis_index(axis)
    B, H, Tl, D = q.shape
    bq, bk = _ring_blocks(Tl, D, q.dtype, bwd=True)
    BH = B * H
    f32 = jnp.float32
    qb = q.reshape(BH, Tl, D)
    dob = do.reshape(BH, Tl, D)
    lse_b = lse.reshape(BH, 1, Tl).astype(f32)
    delta = jnp.sum(dob.astype(f32) * o.reshape(BH, Tl, D).astype(f32),
                    axis=-1)[:, None, :]                    # [BH, 1, Tl]

    def chunk_grads(kc, vc, causal_flag):
        return _fa_bwd_with_lse(
            qb, kc.reshape(BH, Tl, D), vc.reshape(BH, Tl, D), dob, None,
            lse_b, causal_flag, scale, bq, bk, interpret, Tl, delta=delta,
            grad_dtypes=(f32, f32, f32))

    def step(carry, s):
        dq, dka, dva, kc, vc = carry
        src = jnp.mod(rank - s, S)
        if causal:
            idx = jnp.where(src > rank, 2,
                            jnp.where(src == rank, 1, 0))
            zero = lambda: (jnp.zeros((BH, Tl, D), f32),
                            jnp.zeros((BH, Tl, D), f32),
                            jnp.zeros((BH, Tl, D), f32))
            dqc, dkc, dvc = lax.switch(
                idx,
                [lambda: chunk_grads(kc, vc, False),
                 lambda: chunk_grads(kc, vc, True),
                 zero])
        else:
            dqc, dkc, dvc = chunk_grads(kc, vc, False)
        dq = dq + dqc
        dka = dka + dkc
        dva = dva + dvc
        perm = [(i, (i + 1) % S) for i in range(S)]
        return (dq,
                lax.ppermute(dka, axis, perm=perm),
                lax.ppermute(dva, axis, perm=perm),
                lax.ppermute(kc, axis, perm=perm),
                lax.ppermute(vc, axis, perm=perm)), None

    z = jnp.zeros((BH, Tl, D), f32)
    (dq, dka, dva, _, _), _ = lax.scan(step, (z, z, z, k, v),
                                       jnp.arange(S))
    shape = (B, H, Tl, D)
    return (dq.reshape(shape).astype(q.dtype),
            dka.reshape(shape).astype(k.dtype),
            dva.reshape(shape).astype(v.dtype))


def _build_ring_flash(mesh, spec, axis, causal, scale, batch_axes,
                      interpret):
    """Assemble the custom_vjp ring-flash callable for one signature.
    The custom_vjp sits OUTSIDE the shard_maps: forward shard_map returns
    (o, lse), backward shard_map consumes the saved (q, k, v, o, lse)
    residuals plus the cotangent. check_vma=False on both: pallas_call's
    out ShapeDtypeStructs carry no varying-mesh-axes annotation, which
    strict shard_map rejects; the sharding contract is fully pinned by
    in_specs/out_specs here."""
    sspec = P(batch_axes, None, axis)              # [B, H, Tl] rows
    # jit-wrapped for the same warm-call zero-trace reason as the dense
    # ring: both the eager forward and each jax.grad-driven backward hit
    # the pjit trace cache instead of re-tracing the ring program
    fwd_sm = jax.jit(jax.shard_map(
        functools.partial(_ring_flash_fwd_local, axis=axis, causal=causal,
                          scale=scale, interpret=interpret),
        mesh=mesh, in_specs=(spec, spec, spec),
        out_specs=(spec, sspec), check_vma=False))
    bwd_sm = jax.jit(jax.shard_map(
        functools.partial(_ring_flash_bwd_local, axis=axis, causal=causal,
                          scale=scale, interpret=interpret),
        mesh=mesh, in_specs=(spec, spec, spec, spec, sspec, spec),
        out_specs=(spec, spec, spec), check_vma=False))

    @jax.custom_vjp
    def ring(q, k, v):
        return fwd_sm(q, k, v)[0]

    def fwd(q, k, v):
        o, lse = fwd_sm(q, k, v)
        return o, (q, k, v, o, lse)

    def bwd(res, do):
        q, k, v, o, lse = res
        return tuple(bwd_sm(q, k, v, o, lse, do))

    ring.defvjp(fwd, bwd)
    return ring


def ring_flash_attention(q, k, v, mesh=None, axis: str = "sp",
                         causal: bool = False, scale: Optional[float] = None,
                         batch_axes=None, interpret: Optional[bool] = None):
    """Sequence-parallel attention with the Pallas flash kernel as the
    per-chunk compute (see :func:`_ring_flash_fwd_local`). Same contract
    as :func:`ring_attention`: GLOBAL [B, H, T, D] arrays, T divisible by
    the axis size, returns the same sharding. Differentiable — the
    attached custom_vjp runs the flash recomputation schedule around the
    ring (:func:`_ring_flash_bwd_local`), so ``jax.grad`` through this is
    the long-context training fast path. ``interpret=None`` leaves the
    choice to ``core.pallas_mode`` (interpreted off-TPU, so CPU-mesh tests
    run the kernel's math)."""
    m = mesh or _mesh.ensure_mesh()
    if scale is None:
        scale = 1.0 / float(np.sqrt(q.shape[-1]))  # noqa: PTA001 -- head dim is a static shape, a trace-time python int
    return _ring_callable("flash", m, axis, causal, scale, batch_axes,
                          interpret=interpret)(q, k, v)


def _ring_flash_impl(qq, kk, vv, axis="sp", causal=False, batch_axes=None):
    # module-level for the op cache (see _ring_impl)
    return ring_flash_attention(qq, kk, vv, mesh=None, axis=axis,
                                causal=causal,
                                batch_axes=_canon_batch_axes(batch_axes))
