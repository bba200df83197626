"""Paged decode attention as a Pallas TPU kernel (vLLM/PagedAttention).

One query per sequence attends over K/V rows scattered across a paged
arena: logical row ``t`` of sequence ``s`` lives at physical page
``block_tables[s, t // page_size]``, in-page offset ``t % page_size``
(see ``serving/llm/paged/pool.py``). Rather than gathering the pages
into a contiguous ``[S, max_seq, H, D]`` tensor in HBM first (the
reference lane, ``paged_gather_rows``), this kernel walks the block
table *inside* the grid: the page id rides the scalar-prefetch channel
into each K/V BlockSpec index map, so the pipeline DMAs exactly the
pages the sequence owns, one per grid step, with the online-softmax
running statistics (m, l, acc) carried across the page axis in VMEM
scratch — the flash-attention recurrence over a gathered key axis.

The kernel reads the WHOLE ``[P+1, L, page, H, D]`` arena, all layers of
it: the layer index rides the scalar-prefetch channel beside the page id
and the layer axis of the block is squeezed, so a grid step fetches the
same contiguous ``[page, block_h, D]`` chunk a single-layer arena would
give, no layer is ever cut out of the arena for the call (the decode
step updates the arena in place), and every layer's call is one and the
same Mosaic kernel.

Grouped-query heads: with ``G = Hq // Hkv`` query heads to each KV head
(query head ``j`` reads KV head ``j // G``), the block over heads walks KV
heads, and each K/V block fetched serves its ``G`` query heads: ``q`` rides
in as ``[S, G, Hkv, D]`` (group-major, so that every group is a
``[block_h, D]`` slab laid out like a K row) and the recurrence runs once a
group on the one K/V block. ``G = 1`` is plain multi-head attention.

Fused rows: with ``v_arena=None`` the one arena's rows hold a head's key
and value side by side (``[..., Hkv, 2 * D]``, what the cache keeps for a
head size under the lane width); ``q`` is padded with zeros over the value's
lanes, so the same multiply and lane reduction give ``q . k``, the block is
read once and serves as both operands, and the value's lanes of the
accumulator are the result.

Grid: ``(S, Hkv // block_h, pages_per_seq)`` — the page axis is innermost,
so on TPU (sequential grid) the scratch accumulators persist across one
sequence-head-block's page walk and reset via ``@pl.when(p == 0)``.

Masking: query at position ``positions[s]`` attends rows ``j <=
positions[s]`` (the just-written token sees itself and the whole valid
prefix — same semantics as ``kvcache.valid_mask``). Pages past the
length (including trash-page junk) zero out in the running softmax.

Off-TPU the wrapper runs in interpret mode — the same numerics, so CPU
tests cover the kernel's math; interpret-mode output matches the gather
lane to float tolerance (NOT bitwise: the blocked online-softmax sums in
a different order — the bitwise-parity contract belongs to the gather
lane).

Tuner family ``paged_attn`` (``paddle_tpu.tuner.paged_key``): the one
knob is ``block_h``, how many heads share a grid step's DMA and compute
block — a multiple of the sublane tile that divides the head count, or
all the heads (``_sanitize_block_h``). ``default_winners.json`` carries
committed entries; unknown shapes fall back to a dividing heuristic.
"""
from __future__ import annotations

import functools

import numpy as np

import jax
import jax.numpy as jnp
from jax import lax

from ..core.pallas_mode import resolve_interpret

__all__ = ["paged_attention"]

_NEG_INF = -1e30


def _sanitize_block_h(block_h, num_heads: int, itemsize: int = 4) -> int:
    """Largest legal head block <= the request
    (``tuner.space.paged_block_h_legal``), else all the heads — which is
    always legal."""
    from ..tuner.space import paged_block_h_legal
    b = max(1, min(int(block_h), num_heads))  # noqa: PTA001 -- block_h is a python config int (tuner winner / heuristic), never a traced value
    while b > 0 and not paged_block_h_legal(b, num_heads, itemsize):
        b -= 1
    return b if b > 0 else num_heads


def _tuned_block_h(num_heads, head_dim, page_size, dtype):
    """The ``paged_attn`` family's tuned block_h, or None when untuned
    (tuner import kept lazy + failure-proof, like the flash families)."""
    try:
        from ..tuner import get_paged_attn_config
        cfg = get_paged_attn_config(num_heads, head_dim, page_size, dtype)
    except Exception:
        return None
    if not cfg:
        return None
    try:
        b = int(cfg.get("block_h", 0))
    except (TypeError, ValueError):
        return None
    return b if b > 0 else None


def _paged_attn_kernel(bt_ref, len_ref, layer_ref, q_ref, k_ref, *refs,
                       scale, page_size, pages_per_seq, block_h, groups):
    """One page of one sequence's head block per grid step. With a single
    query row there is nothing for the MXU to amortize, so the whole
    recurrence stays on the VPU in the arena's own ``[page, heads, D]``
    layout: q.k is a multiply and a lane reduction, softmax statistics
    reduce over the major (page) axis, p.v is a lane broadcast and a
    major-axis sum — no transpose, no batched dot, no relayout. The K/V
    block is read once and serves each of the ``groups`` query heads of its
    KV heads in turn; without a ``v_ref`` (fused rows) it is both operands.
    ``layer_ref`` is read by the index maps only."""
    import jax.experimental.pallas as pl

    v_ref = refs[0] if len(refs) == 5 else k_ref
    o_ref, acc_ref, m_ref, l_ref = refs[-4:]
    s = pl.program_id(0)
    p = pl.program_id(2)

    @pl.when(p == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)
        m_ref[...] = jnp.full_like(m_ref, _NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)

    kblk = k_ref[0].astype(jnp.float32)               # [page, bh, D]
    vblk = kblk if v_ref is k_ref else v_ref[0].astype(jnp.float32)
    j = p * page_size + lax.broadcasted_iota(
        jnp.int32, (page_size, block_h, 1), 0)
    valid = j <= len_ref[s]
    for g in range(groups):
        q = q_ref[0, g].astype(jnp.float32) * scale   # [bh, D]
        s_blk = jnp.sum(kblk * q[None], axis=-1, keepdims=True)
        s_blk = jnp.where(valid, s_blk, _NEG_INF)     # [page, bh, 1]
        m_prev = m_ref[g, :, :1]                      # [bh, 1]
        l_prev = l_ref[g, :, :1]
        m_new = jnp.maximum(m_prev, jnp.max(s_blk, axis=0))
        alpha = jnp.exp(m_prev - m_new)
        pexp = jnp.where(valid, jnp.exp(s_blk - m_new[None]), 0.0)
        l_new = l_prev * alpha + jnp.sum(pexp, axis=0)
        acc_ref[g] = acc_ref[g] * alpha + jnp.sum(pexp * vblk, axis=0)
        m_ref[g] = jnp.broadcast_to(m_new, m_ref.shape[1:])
        l_ref[g] = jnp.broadcast_to(l_new, l_ref.shape[1:])

    @pl.when(p == pages_per_seq - 1)
    def _finalize():
        o_ref[0] = (acc_ref[...] / jnp.maximum(l_ref[:, :, :1], 1e-30)
                    ).astype(o_ref.dtype)


def paged_attention(q, k_arena, v_arena, block_tables, positions,
                    layer=0, scale=None, block_h=None, interpret=None):
    """Single-token decode attention through a paged KV arena.

    ``q``: ``[S, Hq, D]`` (one query per sequence, already projected);
    ``k_arena``/``v_arena``: the whole ``[num_pages + 1, num_layers,
    page_size, Hkv, D]`` arenas, ``Hq`` a multiple of ``Hkv`` and query
    head ``j`` reading KV head ``j // (Hq // Hkv)`` (dense — int8 arenas
    take the gather
    lane, which dequantizes in-graph); ``layer``: which layer's rows to
    attend over, an int or an int32 scalar (a kernel *input*, so every
    layer runs the same compiled kernel); ``block_tables``:
    ``[S, pages_per_seq]`` int32; ``positions``: ``[S]`` int32 — query
    ``s`` attends logical rows ``j <= positions[s]``. Returns
    ``[S, Hq, D]`` in ``q.dtype``. ``v_arena=None``: ``k_arena`` holds fused
    ``[K | V]`` rows of width ``2 * D``.
    """
    import jax.experimental.pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    fused = v_arena is None
    if isinstance(k_arena, dict) or isinstance(v_arena, dict):
        raise ValueError(
            "paged_attention kernel reads dense arenas only — the int8 "
            "lane uses the gather implementation (dequantize in-graph)")
    if fused:       # zeros over the value's lanes; scale by the true D
        scale = 1.0 / np.sqrt(q.shape[-1]) if scale is None else scale
        q = jnp.pad(q, ((0, 0), (0, 0), (0, q.shape[-1])))
    s_n, q_heads, head_dim = q.shape
    num_heads = k_arena.shape[3]                       # KV heads
    if q_heads % num_heads:
        raise ValueError(f"{q_heads} query heads are not a multiple of "
                         f"the arena's {num_heads} KV heads")
    groups = q_heads // num_heads
    page_size = k_arena.shape[2]
    pages_per_seq = block_tables.shape[1]
    if scale is None:
        scale = 1.0 / np.sqrt(head_dim)
    if block_h is None:
        block_h = _tuned_block_h(num_heads, head_dim, page_size, q.dtype)
    if block_h is None:
        # heuristic: one full f32 sublane tile of heads per step
        block_h = 8
    block_h = _sanitize_block_h(block_h, num_heads,
                                jnp.dtype(k_arena.dtype).itemsize)

    kernel = functools.partial(
        _paged_attn_kernel, scale=scale, page_size=page_size,
        pages_per_seq=pages_per_seq, block_h=block_h, groups=groups)
    bt_flat = block_tables.reshape(-1).astype(jnp.int32)
    # group-major: q_g[s, g, h] is query head h * groups + g
    q_g = jnp.swapaxes(q.reshape(s_n, num_heads, groups, head_dim), 1, 2)

    def _q_map(s, h, p, bt_ref, len_ref, layer_ref):
        return (s, 0, h, 0)

    def _kv_map(s, h, p, bt_ref, len_ref, layer_ref):
        # the block-table walk: (physical page id, layer) -> arena block
        return (bt_ref[s * pages_per_seq + p], layer_ref[0], 0, h, 0)

    q_block = (1, groups, block_h, head_dim)
    kv_block = (1, None, page_size, block_h, head_dim)
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=3,
        grid=(s_n, num_heads // block_h, pages_per_seq),
        in_specs=[pl.BlockSpec(q_block, _q_map)]
        + [pl.BlockSpec(kv_block, _kv_map)] * (1 if fused else 2),
        out_specs=pl.BlockSpec(q_block, _q_map),
        scratch_shapes=[
            pltpu.VMEM((groups, block_h, head_dim), jnp.float32),  # acc
            pltpu.VMEM((groups, block_h, 128), jnp.float32),  # running max
            pltpu.VMEM((groups, block_h, 128), jnp.float32),  # running sum
        ])
    out = pl.pallas_call(
        kernel,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((s_n, groups, num_heads, head_dim),
                                       q.dtype),
        interpret=resolve_interpret("paged_attn", interpret),
        name="paged_attn",
    )(bt_flat, positions.astype(jnp.int32),
      jnp.asarray(layer, jnp.int32).reshape(1), q_g,
      *((k_arena,) if fused else (k_arena, v_arena)))
    out = jnp.swapaxes(out, 1, 2).reshape(s_n, q_heads, head_dim)
    return out[..., head_dim // 2:] if fused else out
