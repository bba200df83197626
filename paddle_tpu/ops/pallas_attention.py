"""Flash attention as a Pallas TPU kernel.

The reference computes attention as unfused matmul/softmax/matmul over
materialized [B, H, S, S] score tensors (multihead_matmul fusion only at
inference). The TPU-native hot path keeps scores block-resident in VMEM
with the online-softmax recurrence (Dao et al.) — O(S) memory instead of
O(S²) HBM traffic, which is what makes long-sequence training fit at all
(the ring-attention sequence parallelism in fleet/sequence_parallel.py
shards S *across* chips; this kernel is the per-chip inner loop story).

Kernel shape: grid (B*H, S_q/block_q); each program holds one q block and
scans k/v blocks with ``lax.fori_loop``; its running (acc, m, l) live in
VMEM scratch, not in the loop's carry (a carried value too large for the
register file is copied between spill slots at both ends of every trip),
a row's statistic in every lane of the sublane its score row lies on.
Causal masking and tail padding are mask arithmetic inside the score
block — shapes stay static.

Runs in interpret mode off-TPU so tests are hardware-independent; every
``interpret`` argument below is an optional override that
``core.pallas_mode.resolve_interpret`` settles at the ``pallas_call``.
"""
from __future__ import annotations

import functools

import numpy as np
import jax
import jax.numpy as jnp
from jax import ad_checkpoint, lax

from .dispatch import apply
from ..core.pallas_mode import resolve_interpret

__all__ = ["flash_attention"]

_NEG_INF = -1e30
_FAR = np.iinfo(np.int32).max     # a padded key's position: after every query

#: historical hand-picked block edge — the fallback when the autotuner
#: has no winner for a shape (paddle_tpu.tuner consults disk winners and
#: the committed defaults table first)
DEFAULT_BLOCK = 128


def _ceil16(n: int) -> int:
    return max(16, -(-int(n) // 16) * 16)


def _sanitize_block(block: int, length: int) -> int:
    """Clamp a requested block edge to a legal Mosaic tile: a multiple of
    16 rows (the sublane tile for both f32 and bf16), at most the
    16-rounded sequence length. Tuner- or user-supplied blocks that
    violate the constraint are rounded up rather than rejected — the
    caller's padding absorbs the difference."""
    b = int(block)
    if b <= 0:
        b = DEFAULT_BLOCK
    b = _ceil16(b)
    return min(b, _ceil16(length))


def _tuned_blocks(q_len, kv_len, head_dim, dtype, causal):
    """(block_q, block_k) from the autotuner's winner cache, or None.
    Never raises: an unavailable/broken tuner degrades to the default."""
    try:
        from ..tuner import get_flash_blocks
        return get_flash_blocks(q_len, kv_len, head_dim, dtype, causal)
    except Exception:
        return None


def _mxu_dtype(*dtypes):
    """Operand dtype of a flash kernel's dots: bfloat16 when every
    operand arrives as bfloat16 (the MXU's native width; a product of
    two bf16 values is exact in the f32 accumulator), else float32 — the
    kernels then upcast their blocks exactly as they always have.
    Accumulators and row statistics are float32 either way."""
    if all(jnp.dtype(dt) == jnp.bfloat16 for dt in dtypes):
        return jnp.dtype(jnp.bfloat16)
    return jnp.dtype(jnp.float32)


def _mxu_dot(mxu_dtype):
    """``dot_general`` for a kernel whose operands are ``mxu_dtype``,
    accumulating in float32. Native operands name ``Precision.DEFAULT``:
    the package-wide ``highest`` asks Mosaic for a float32 contraction,
    which it builds from several bf16 passes for float32 operands and
    refuses ("Bad lhs type") for bf16 ones."""
    return functools.partial(
        lax.dot_general, preferred_element_type=jnp.float32,
        precision=(None if mxu_dtype == jnp.float32
                   else lax.Precision.DEFAULT))


#: width a row statistic is kept at: [rows, 128] float32, every lane
#: holding the row's value (one vreg a sublane group, as a [rows, 1] column
#: would take, but read back from VMEM already broadcast)
_STAT_LANES = 128


def _across(stat, n):
    """A lane-replicated ``[rows, w]`` statistic as ``[rows, n]``."""
    w = stat.shape[1]
    if n <= w:
        return stat[:, :n]
    if n % w == 0:
        return jnp.tile(stat, (1, n // w))
    return jnp.broadcast_to(stat[:, :1], (stat.shape[0], n))


def _visible(q_pos, k_pos, seq_len, causal):
    """Which scores of a tile count. ``q_pos`` and ``k_pos`` are the
    tile's query and key positions, a column and a row (a row and a column
    in the transposed tile of dK/dV): they meet in one compare a score and
    nothing as large as the tile is kept between trips. A padded key
    (``k_pos >= seq_len``) counts for no query."""
    if not causal:
        return k_pos < seq_len
    return q_pos >= jnp.where(k_pos < seq_len, k_pos, _FAR)


def _fa_kernel(q_ref, k_ref, v_ref, o_ref, lse_ref, acc_ref, m_ref, l_ref, *,
               scale, causal, block_q, block_k, seq_len, kv_len, mxu_dtype):
    import jax.experimental.pallas as pl

    # float32 operands: q is scaled before QK^T. Native (bf16) operands:
    # q goes to the MXU as given and the f32 scores are scaled, so no
    # scaled q is ever rounded.
    native = mxu_dtype != jnp.float32
    dot = _mxu_dot(mxu_dtype)
    qi = pl.program_id(1)
    d = q_ref.shape[-1]
    n_k = kv_len // block_k

    def body(j, carry):
        # q and the positions are read inside the trip, here and in the
        # backward loops: a value made before the loop is kept through it
        q = q_ref[0].astype(mxu_dtype)                       # [bq, D]
        if not native:
            q = q * scale
        kblk = k_ref[0, pl.ds(j * block_k, block_k), :].astype(mxu_dtype)
        vblk = v_ref[0, pl.ds(j * block_k, block_k), :].astype(mxu_dtype)
        s = dot(q, kblk, (((1,), (1,)), ((), ())))
        if native:
            s = s * scale
        q_pos = qi * block_q + lax.broadcasted_iota(jnp.int32,
                                                    (block_q, 1), 0)
        k_pos = j * block_k + lax.broadcasted_iota(jnp.int32,
                                                   (1, block_k), 1)
        s = jnp.where(_visible(q_pos, k_pos, seq_len, causal), s, _NEG_INF)
        m = m_ref[...]
        m_new = jnp.maximum(m, jnp.max(s, axis=-1, keepdims=True))
        alpha = jnp.exp(m - m_new)
        p = jnp.exp(s - _across(m_new, block_k))
        m_ref[...] = m_new
        l_ref[...] = l_ref[...] * alpha + jnp.sum(p, axis=-1, keepdims=True)
        acc_ref[...] = acc_ref[...] * _across(alpha, d) + dot(
            p.astype(mxu_dtype), vblk, (((1,), (0,)), ((), ())))
        return carry

    # acc, m, l live in VMEM scratch between trips (module docstring);
    # m, l lane-replicated (_STAT_LANES): read back already broadcast
    acc_ref[...] = jnp.zeros(acc_ref.shape, jnp.float32)
    m_ref[...] = jnp.full(m_ref.shape, _NEG_INF, jnp.float32)
    l_ref[...] = jnp.zeros(l_ref.shape, jnp.float32)
    if causal:
        # early exit: k blocks entirely above the diagonal contribute
        # nothing — trip count becomes data-independent-per-program
        # ceil(((qi+1)*block_q) / block_k), halving work on average
        n_k = jnp.minimum(n_k, (qi * block_q + block_q + block_k - 1)
                          // block_k)
    lax.fori_loop(0, n_k, body, 0)
    # fully-masked rows (padding queries) have l == 0
    l = jnp.maximum(l_ref[...], 1e-30)
    o_ref[0] = (acc_ref[...] / _across(l, d)).astype(o_ref.dtype)
    # logsumexp of the score rows: backward recomputes P from it
    # (shape [1, 1, bq]: TPU block rule needs the last two dims
    # (sublane, lane)-aligned, so the row stats ride a lane axis;
    # the column is turned into that lane row here, once a program)
    lse_ref[0] = jnp.transpose(m_ref[...] + jnp.log(l))[:1]


def flash_attention(query, key, value, dropout=0.0, causal=False,
                    return_softmax=False, *, scale=None, block_q=None,
                    block_k=None, name=None):
    """Memory-efficient exact attention (paddle's flash_attention API:
    same positional order ``(q, k, v, dropout, causal, return_softmax)``
    and the same ``(out, softmax)`` tuple return, so positionally-ported
    reference code keeps its meaning).

    query/key/value: [batch, seq, num_heads, head_dim]. Returns
    ``(out [batch, seq, num_heads, head_dim], None)`` — the attention
    probabilities are never materialized (that is the point of the
    kernel), so ``return_softmax=True`` raises, as does ``dropout > 0``
    (attention-prob dropout needs the dense path).

    ``block_q``/``block_k`` default to the autotuner's winner for the
    (shape, dtype, platform) key — falling back to the historical 128
    when no winner is cached. Explicit values win over the tuner.

    The sequence is padded to the block size internally; padded keys are
    masked, padded query rows are sliced away.
    """
    if dropout:
        raise ValueError("flash_attention: dropout inside the fused kernel "
                         "is unsupported (use the dense path for "
                         "attention-prob dropout)")
    if return_softmax:
        raise ValueError("flash_attention: the probability matrix is never "
                         "materialized; return_softmax is unsupported")

    def impl(q, kk, vv):
        b, s, h, d = q.shape
        skv = kk.shape[1]
        sc = scale if scale is not None else 1.0 / np.sqrt(d)
        bq_req, bk_req = block_q, block_k
        if bq_req is None and bk_req is None:
            tuned = _tuned_blocks(s, skv, d, q.dtype, causal)
            if tuned is not None:
                bq_req, bk_req = tuned
        if bq_req is None:
            bq_req = DEFAULT_BLOCK
        if bk_req is None:
            bk_req = DEFAULT_BLOCK
        # block shapes must stay multiples of the sublane tile (8 rows for
        # f32, 16 for bf16) or Mosaic may fail to compile (odd seq lengths
        # like 100); round to 16 so both dtypes are safe — the seq is
        # padded up to the rounded block below, padded keys masked
        bq = _sanitize_block(bq_req, s)
        bk = _sanitize_block(bk_req, skv)
        s_pad = -(-s // bq) * bq
        kv_pad = -(-skv // bk) * bk

        def to_bh(x, pad_to):
            x = jnp.moveaxis(x, 2, 1).reshape(b * h, x.shape[1], d)
            if pad_to != x.shape[1]:
                x = jnp.pad(x, ((0, 0), (0, pad_to - x.shape[1]), (0, 0)))
            return x
        qb = to_bh(q, s_pad)
        kb = to_bh(kk, kv_pad)
        vb = to_bh(vv, kv_pad)
        # real kv length for the padding mask: padded keys sit at
        # index >= skv
        out = _fa_core(qb, kb, vb, causal, sc, bq, bk, None, skv)
        out = out[:, :s, :].reshape(b, h, s, d)
        return jnp.moveaxis(out, 1, 2)
    return apply("flash_attention", impl, query, key, value), None


# -- backward kernels (FlashAttention-style recomputation) --------------------

def _fa_bwd_dq_kernel(q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref,
                      dq_ref, acc_ref, *, scale, causal, block_q, block_k,
                      seq_len, kv_len, mxu_dtype):
    import jax.experimental.pallas as pl

    native = mxu_dtype != jnp.float32      # see _fa_kernel
    dot = _mxu_dot(mxu_dtype)
    qi = pl.program_id(1)
    # The lane rows become columns here, once a program: a row copied
    # down the sublanes and turned is [bq, 128] with a query's value in every
    # lane of its sublane; the loop only repeats that along lanes.
    lse, delta = (jnp.transpose(jnp.broadcast_to(
        r[0].astype(jnp.float32), (_STAT_LANES, block_q)))
        for r in (lse_ref, delta_ref))
    n_k = kv_len // block_k
    if causal:
        n_k = jnp.minimum(n_k, (qi * block_q + block_q + block_k - 1)
                          // block_k)

    def body(j, carry):
        q = q_ref[0].astype(mxu_dtype)                        # [bq, D]
        if not native:
            q = q * scale
        do = do_ref[0].astype(mxu_dtype)
        kblk = k_ref[0, pl.ds(j * block_k, block_k), :].astype(mxu_dtype)
        vblk = v_ref[0, pl.ds(j * block_k, block_k), :].astype(mxu_dtype)
        s = dot(q, kblk, (((1,), (1,)), ((), ())))
        if native:
            s = s * scale
        q_pos = qi * block_q + lax.broadcasted_iota(jnp.int32,
                                                    (block_q, 1), 0)
        k_pos = j * block_k + lax.broadcasted_iota(jnp.int32,
                                                   (1, block_k), 1)
        p = jnp.where(_visible(q_pos, k_pos, seq_len, causal),
                      jnp.exp(s - _across(lse, block_k)), 0.0)
        dp = dot(do, vblk, (((1,), (1,)), ((), ())))
        ds = p * (dp - _across(delta, block_k))               # [bq, bk]
        acc_ref[...] += dot(ds.astype(mxu_dtype), kblk,
                            (((1,), (0,)), ((), ())))
        return carry
    # accumulated in VMEM scratch, not carried: see _fa_kernel
    acc_ref[...] = jnp.zeros(acc_ref.shape, jnp.float32)
    lax.fori_loop(0, n_k, body, 0)
    dq_ref[0] = (acc_ref[...] * scale).astype(dq_ref.dtype)


def _fa_bwd_dkv_kernel(q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref,
                       dk_ref, dv_ref, dk_acc, dv_acc, *, scale, causal,
                       block_q, block_k, seq_len, q_len, mxu_dtype):
    import jax.experimental.pallas as pl

    native = mxu_dtype != jnp.float32      # see _fa_kernel
    dot = _mxu_dot(mxu_dtype)
    ki = pl.program_id(1)
    # The score tile is computed transposed, [bk, bq]: a query row's lse
    # and delta are then read as the lane rows they are stored as and
    # broadcast along sublanes, and dV = P^T dO and dK = dS^T Q contract
    # the tile's last dimension: nothing is turned inside the loop.
    n_q = q_len // block_q

    def body(i, carry):
        kblk = k_ref[0].astype(mxu_dtype)                     # [bk, D]
        vblk = v_ref[0].astype(mxu_dtype)
        rows = pl.ds(i * block_q, block_q)
        q = q_ref[0, rows, :].astype(mxu_dtype)
        if not native:
            q = q * scale
        do = do_ref[0, rows, :].astype(mxu_dtype)
        lse = lse_ref[0, :, rows].astype(jnp.float32)         # [1, bq]
        delta = delta_ref[0, :, rows].astype(jnp.float32)
        st = dot(kblk, q, (((1,), (1,)), ((), ())))           # [bk, bq]
        if native:
            st = st * scale
        k_pos = ki * block_k + lax.broadcasted_iota(jnp.int32,
                                                    (block_k, 1), 0)
        q_pos = i * block_q + lax.broadcasted_iota(jnp.int32,
                                                   (1, block_q), 1)
        pt = jnp.where(_visible(q_pos, k_pos, seq_len, causal),
                       jnp.exp(st - lse), 0.0)
        dv_acc[...] += dot(pt.astype(mxu_dtype), do,
                           (((1,), (0,)), ((), ())))
        dpt = dot(vblk, do, (((1,), (1,)), ((), ())))
        dst = pt * (dpt - delta)
        dk_acc[...] += dot(dst.astype(mxu_dtype), q,
                           (((1,), (0,)), ((), ())))
        return carry
    if causal:
        # q blocks entirely above this k block see it masked; start there
        i0 = (ki * block_k) // block_q
    else:
        i0 = 0
    dk_acc[...] = jnp.zeros(dk_acc.shape, jnp.float32)
    dv_acc[...] = jnp.zeros(dv_acc.shape, jnp.float32)
    lax.fori_loop(i0, n_q, body, 0)
    dk = dk_acc[...]
    if native:
        dk = dk * scale           # the f32 lane folds it into q
    dk_ref[0] = dk.astype(dk_ref.dtype)
    dv_ref[0] = dv_acc[...].astype(dv_ref.dtype)


def _fa_fwd_with_lse(qb, kb, vb, causal, sc, bq, bk, interpret, true_kv):
    """Forward kernel call; also emits the [bh, 1, S] f32 logsumexp rows
    (1/(2·D) of the output bytes — cheap enough to pay on inference
    too, so there is a single forward kernel to maintain)."""
    import jax.experimental.pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    bh, s_pad, d = qb.shape
    kv_pad = kb.shape[1]
    # the grid floor-divides: a non-dividing block would silently drop the
    # tail rows/keys for direct callers (flash_attention() pads before
    # calling, but ring-flash and the tuner call this core directly)
    if s_pad % bq or kv_pad % bk:
        raise ValueError(
            f"flash attention core: block_q={bq} / block_k={bk} must "
            f"divide the (padded) sequence lengths ({s_pad}, {kv_pad}); "
            "pad the operands or pick a dividing block")
    mxu_dt = _mxu_dtype(qb.dtype, kb.dtype, vb.dtype)
    kernel = functools.partial(
        _fa_kernel, scale=sc, causal=causal, block_q=bq, block_k=bk,
        seq_len=true_kv, kv_len=kv_pad, mxu_dtype=mxu_dt)
    return pl.pallas_call(
        kernel,
        grid=(bh, s_pad // bq),
        in_specs=[
            pl.BlockSpec((1, bq, d), lambda b, i: (b, i, 0)),
            pl.BlockSpec((1, kv_pad, d), lambda b, i: (b, 0, 0)),
            pl.BlockSpec((1, kv_pad, d), lambda b, i: (b, 0, 0)),
        ],
        out_specs=[pl.BlockSpec((1, bq, d), lambda b, i: (b, i, 0)),
                   pl.BlockSpec((1, 1, bq), lambda b, i: (b, 0, i))],
        out_shape=[jax.ShapeDtypeStruct((bh, s_pad, d), qb.dtype),
                   jax.ShapeDtypeStruct((bh, 1, s_pad), jnp.float32)],
        scratch_shapes=[pltpu.VMEM((bq, d), jnp.float32),    # acc
                        pltpu.VMEM((bq, _STAT_LANES), jnp.float32),  # m
                        pltpu.VMEM((bq, _STAT_LANES), jnp.float32)],  # l
        interpret=resolve_interpret("flash_fwd", interpret, mxu_dt),
        name="flash_fwd",
    )(qb, kb, vb)


def _tuned_bwd_blocks(s_pad, kv_pad, head_dim, dtype, causal, bq, bk):
    """(block_q, block_k) for the backward kernels: the tuner's
    ``flash_bwd`` winner when one exists AND divides the padded grid
    (the backward pallas_calls floor-divide exactly like the forward),
    else the forward blocks the residuals were produced with."""
    try:
        from ..tuner import get_flash_blocks
        tuned = get_flash_blocks(s_pad, kv_pad, head_dim, dtype, causal,
                                 bwd=True)
    except Exception:
        tuned = None
    if tuned is not None:
        tbq, tbk = int(tuned[0]), int(tuned[1])
        if (tbq > 0 and tbk > 0 and s_pad % tbq == 0
                and kv_pad % tbk == 0 and tbq % 16 == 0 and tbk % 16 == 0):
            return tbq, tbk
    return bq, bk


def _fa_bwd_with_lse(qb, kb, vb, do, out, lse, causal, sc, bq, bk,
                     interpret, true_kv, delta=None, grad_dtypes=None):
    """Backward kernel calls (FlashAttention recomputation schedule):
    given the saved residuals — ``out`` and the ``[bh, 1, S]`` f32
    logsumexp rows from :func:`_fa_fwd_with_lse` — recompute P block-wise
    as ``exp(s·scale − lse)`` and emit (dQ, dK, dV) with f32 accumulators.

    ``delta`` is the rowsum(dO∘O) softmax-jacobian correction
    ``[bh, 1, S]``; computed here from ``out`` when not supplied (ring
    callers precompute it once per rank because it is chunk-independent,
    and pass ``out=None``). ``grad_dtypes`` overrides the emitted grad
    dtypes (default: the operand dtypes) — the ring backward requests f32
    so per-chunk grads accumulate without intermediate rounding."""
    import jax.experimental.pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    bh, s_pad, d = qb.shape
    kv_pad = kb.shape[1]
    if s_pad % bq or kv_pad % bk:
        raise ValueError(
            f"flash attention backward: block_q={bq} / block_k={bk} must "
            f"divide the (padded) sequence lengths ({s_pad}, {kv_pad})")
    if delta is None:
        # delta = rowsum(dO * O) — the softmax-jacobian correction term
        delta = jnp.sum(do.astype(jnp.float32) * out.astype(jnp.float32),
                        axis=-1)[:, None, :]                  # [bh, 1, s_pad]
    dq_dt, dk_dt, dv_dt = grad_dtypes or (qb.dtype, kb.dtype, vb.dtype)
    mxu_dt = _mxu_dtype(qb.dtype, kb.dtype, vb.dtype, do.dtype)

    dq_kernel = functools.partial(
        _fa_bwd_dq_kernel, scale=sc, causal=causal, block_q=bq, block_k=bk,
        seq_len=true_kv, kv_len=kv_pad, mxu_dtype=mxu_dt)
    dq = pl.pallas_call(
        dq_kernel,
        grid=(bh, s_pad // bq),
        in_specs=[
            pl.BlockSpec((1, bq, d), lambda b, i: (b, i, 0)),
            pl.BlockSpec((1, kv_pad, d), lambda b, i: (b, 0, 0)),
            pl.BlockSpec((1, kv_pad, d), lambda b, i: (b, 0, 0)),
            pl.BlockSpec((1, bq, d), lambda b, i: (b, i, 0)),
            pl.BlockSpec((1, 1, bq), lambda b, i: (b, 0, i)),
            pl.BlockSpec((1, 1, bq), lambda b, i: (b, 0, i)),
        ],
        out_specs=pl.BlockSpec((1, bq, d), lambda b, i: (b, i, 0)),
        out_shape=jax.ShapeDtypeStruct((bh, s_pad, d), dq_dt),
        scratch_shapes=[pltpu.VMEM((bq, d), jnp.float32)],
        interpret=resolve_interpret("flash_bwd_dq", interpret, mxu_dt),
        name="flash_bwd_dq",
    )(qb, kb, vb, do, lse, delta)

    dkv_kernel = functools.partial(
        _fa_bwd_dkv_kernel, scale=sc, causal=causal, block_q=bq,
        block_k=bk, seq_len=true_kv, q_len=s_pad, mxu_dtype=mxu_dt)
    dk, dv = pl.pallas_call(
        dkv_kernel,
        grid=(bh, kv_pad // bk),
        in_specs=[
            pl.BlockSpec((1, s_pad, d), lambda b, j: (b, 0, 0)),
            pl.BlockSpec((1, bk, d), lambda b, j: (b, j, 0)),
            pl.BlockSpec((1, bk, d), lambda b, j: (b, j, 0)),
            pl.BlockSpec((1, s_pad, d), lambda b, j: (b, 0, 0)),
            pl.BlockSpec((1, 1, s_pad), lambda b, j: (b, 0, 0)),
            pl.BlockSpec((1, 1, s_pad), lambda b, j: (b, 0, 0)),
        ],
        out_specs=[pl.BlockSpec((1, bk, d), lambda b, j: (b, j, 0)),
                   pl.BlockSpec((1, bk, d), lambda b, j: (b, j, 0))],
        out_shape=[jax.ShapeDtypeStruct((bh, kv_pad, d), dk_dt),
                   jax.ShapeDtypeStruct((bh, kv_pad, d), dv_dt)],
        scratch_shapes=[pltpu.VMEM((bk, d), jnp.float32),
                        pltpu.VMEM((bk, d), jnp.float32)],
        interpret=resolve_interpret("flash_bwd_dkv", interpret, mxu_dt),
        name="flash_bwd_dkv",
    )(qb, kb, vb, do, lse, delta)
    return dq, dk, dv


@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4, 5, 6, 7, 8))
def _fa_core(qb, kb, vb, causal, sc, bq, bk, interpret, true_kv):
    out, _ = _fa_fwd_with_lse(qb, kb, vb, causal, sc, bq, bk, interpret,
                              true_kv)
    return out


# what the backward needs of the forward kernel and only the kernel can make
# again: a checkpoint whose policy saves these names (fleet.utils.recompute)
# keeps them, and its backward pass launches no second flash_fwd
RESIDUAL_NAMES = ("flash_out", "flash_lse")


def _fa_core_fwd(qb, kb, vb, causal, sc, bq, bk, interpret, true_kv):
    out, lse = _fa_fwd_with_lse(qb, kb, vb, causal, sc, bq, bk, interpret,
                                true_kv)
    # the returned out and the residual out are the ONE named value: a name
    # on the residual copy alone leaves the block's backward asking for the
    # unnamed one, and the second launch comes back
    out = ad_checkpoint.checkpoint_name(out, RESIDUAL_NAMES[0])
    lse = ad_checkpoint.checkpoint_name(lse, RESIDUAL_NAMES[1])
    return out, (qb, kb, vb, out, lse)


def _fa_core_bwd(causal, sc, bq, bk, interpret, true_kv, res, do):
    qb, kb, vb, out, lse = res
    bh, s_pad, d = qb.shape
    # backward blocks may differ from the forward's (the lse/delta rows
    # are full-length arrays; only grid divisibility ties them together)
    bbq, bbk = _tuned_bwd_blocks(s_pad, kb.shape[1], d, qb.dtype, causal,
                                 bq, bk)
    return _fa_bwd_with_lse(qb, kb, vb, do, out, lse, causal, sc, bbq,
                            bbk, interpret, true_kv)


_fa_core.defvjp(_fa_core_fwd, _fa_core_bwd)
