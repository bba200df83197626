"""Paged decode attention as a Pallas TPU kernel (vLLM/PagedAttention).

One query per sequence attends over K/V rows scattered across a paged
arena: logical row ``t`` of sequence ``s`` lives at physical page
``block_tables[s, t // page_size]``, in-page offset ``t % page_size``
(see ``serving/llm/paged/pool.py``). Rather than gathering the pages
into a contiguous ``[S, max_seq, H, D]`` tensor in HBM first (the
reference lane, ``paged_gather_rows``), this kernel walks the block
table itself, and only as far as the sequence reaches.

Grid: ``(S, Hkv // block_h)`` — one sequence's head block a step. The
arenas stay whole in HBM (``memory_space=pl.ANY``, no BlockSpec); the
block table, the positions and the layer index ride the scalar-prefetch
channel into SMEM. The kernel reads the WHOLE ``[P+1, L, page, H, D]``
arena, all layers of it: no layer is ever cut out of the arena for the
call (the decode step updates the arena in place), and every layer's
call is one and the same Mosaic kernel.

The walk: a step has ``n = positions[s] // page_size + 1`` live pages and
loops over them ``pages_per_step`` at a time, a trip count that is the
sequence's own. Each iteration starts one ``make_async_copy`` a live
page and arena (``arena[page_id, layer, :, heads]`` -> a slot of the
other half of a double buffer in VMEM), waits for the copies of its own
half and runs the online-softmax recurrence over its pages in table
order (a page at a time, or several stacked: "Grouped-query heads"
below), with the running statistics (m, l, acc) in VMEM scratch. A page
past the live ones is never indexed, fetched or computed. The last
iteration of a step also starts the first copies of
the NEXT grid step (the grid runs in order), so no step but the first
waits for a copy nothing overlaps.

Masking: query at position ``positions[s]`` attends rows ``j <=
positions[s]`` (the just-written token sees itself and the whole valid
prefix — same semantics as ``kvcache.valid_mask``). Only the last live
page can hold rows past the position: it alone is computed under the
mask, its dead rows' values zeroed too (``0 * NaN`` is NaN), so nothing
past the end reaches the result whatever lies there.

Grouped-query heads: with ``G = Hq // Hkv`` query heads to each KV head
(query head ``j`` reads KV head ``j // G``), the block over heads walks KV
heads, and each page fetched serves its ``G`` query heads. ``G = 1`` is
plain multi-head attention.

The recurrence over a fetched page is one of two, chosen from the call's
shapes by ``tuner.space.paged_recurrence`` (no option, no tuner key):

- ``"vpu"``: ``G = 1``, and a grouped shape whose page is not whole sublane
  tiles or does not fit the buffers. With one query row a KV head there is
  nothing for the MXU to amortize: the recurrence stays in the arena's
  ``[page, heads, D]`` layout, a multiply and a lane reduction for ``q . k``,
  a lane broadcast and a major-axis sum for ``p . v``, a page at a time.
  ``q`` rides in as ``[S, G, Hkv, D]`` (group-major, so that every group is a
  ``[block_h, D]`` slab laid out like a K row) and a grouped shape runs the
  recurrence once a group on the one page.
- ``"mxu"``: ``G > 1``. The arena reaches the call as ``[P+1, L, page * Hkv,
  D]``, a page as its flat rows (row ``r`` is token ``r // Hkv``, KV head ``r
  % Hkv``: the arena's own memory order, so the reshape is a bitcast and a
  page's copy one contiguous piece), all the KV heads a grid step. The scores
  of a page for ALL ``Hq`` query heads are one product ``[Hq, D] x [D, page *
  Hkv]`` at ``Precision.HIGHEST``; the entries of another KV head's rows are
  pushed to ``-1e30`` by a bias built once a grid step (the MXU's columns are
  idle at ``Hq`` rows anyway, so the ``Hkv``-fold redundancy costs no pass)
  and weigh exactly 0 in ``p . v``, ``[Hq, page * Hkv] x [page * Hkv, D]``;
  running max and sum are a column a query head. The whole pages of a loop
  step are STACKED into one product (``tuner.space.paged_stack_pages``: the
  step's pages while the score block stays under 512 KiB, then the powers of
  two under that for what is left), because a page's chain of product,
  reductions, ``exp`` and product is latency the next page's cannot hide: a
  product a page ran SLOWER than the VPU form at LFM2's shape and the stacked
  one 2.1x faster, 5.4x at Trinity's (PERF.md, PR 35). The other way round
  (the page streamed against standing queries, ``[page * Hkv, D] x [D, Hq]``)
  measured 1.5x slower than this: its score block is quarter-filled vregs.

Fused rows: with ``v_arena=None`` the one arena's rows hold a head's key
and value side by side (``[..., Hkv, 2 * D]``, what the cache keeps for a
head size under the lane width); ``q`` is padded with zeros over the value's
lanes, so the same contraction over a row (either recurrence's) gives ``q .
k``, the page is fetched once and serves as both operands, and the value's
lanes of the accumulator are the result. Mosaic copies whole 128-lane tiles
out of an array in HBM, so two arenas of narrower rows are laid side by side
for the call (a copy; the cache fuses such rows itself to avoid it).

Selected pages: with ``selected=(tables, counts)`` a step walks a table
of its OWN in place of the sequence's block table: ``tables[s, h, :counts[s,
h]]``, the physical pages block-sparse attention chose for sequence ``s`` and
KV head ``h``, in ascending logical order, so that the page of the position
is last and alone is masked. The walk is the one above (the same double
buffer, the same hand-over of a step's first copies to the step before it),
as long as the selection and no longer. Every KV head walks pages of its own,
so a head's rows of a page must lie together: such an arena is head-major
with fused rows, ``[P+1, L * Hkv, page, 2 * D]`` (arena row ``layer * Hkv +
h`` is layer ``layer``'s KV head ``h``), and one copy brings a page's keys
and values of one head as a ``[page, 2 * D]`` slab. With the ``G`` query
heads of a KV head on the sublanes, the recurrence is two small MXU products
a page (``[G, D] x [D, page]`` and ``[G, page] x [page, D]``).

Latent rows: with ``latent=(value, rotary)`` the cache keeps ONE row a token
and layer for ALL the query heads (multi-head latent attention, absorbed:
``models/moonlight.py``): the arena is ``[P+1, L, page, row]`` and a row is a
key whole (the latent beside the shared rotary part, ``value + rotary``
columns and zeros up to ``row``) and a value in its first ``value`` columns.
It is the MXU recurrence with one KV head and every query head in its group
(``paged_recurrence`` says ``"mxu"`` from the shape): ``[Hq, row] x [row, n *
page]`` for the scores of a loop step's stacked pages, no bias (there is no
other KV head), and ``[Hq, n * page] x [n * page, value]`` over the first
columns of the SAME fetched rows for the result ``[S, Hq, value]``. The row is
whole 128-lane tiles as the cache holds it: the device pads a narrower row to
them in HBM anyway, and Mosaic refuses to copy a 576-wide slice of it
(PERF.md section 4, PR 38). Its two products are float32 products at the
precision of ``HIGHEST`` but not left to it: with 16 query rows streamed past
each standing ``[128, 128]`` tile of the fetched rows, the walk was bound by
the MXU's tile LOADS and not by its copies (36 tiles a loop step of 8 pages,
each loaded once a bfloat16 pass, six passes: 1.04 ms a layer at 48 x 4,064
rows, 480 GB/s). ``HIGHEST`` is six bfloat16 products of the operands' three
bfloat16 parts; grouped by the part of the STANDING operand they are three
loads of a tile and not six, with the streamed operand's parts stacked along
the rows, ``[48, row]``, ``[32, row]`` and ``[16, row]`` (``_stacked_dot``).
The rows are split once and the split serves both products (the value's parts
are the first columns of the key's). The same six partial products, another
order of a float32 sum: 0.80 ms, 624 GB/s, the copies' time (PERF.md section 6,
PR 39). The grouped-query walks keep float32 operands at ``HIGHEST``: Trinity's
is at its copies' time as it is (32 tiles a step for three times the bytes).

A window: with ``window=W`` query ``s`` attends the ``W`` rows ``positions[s]
- W < j <= positions[s]`` only, and its walk BEGINS at the page of row
``max(0, positions[s] - W + 1)``: the pages wholly behind the window cost no
grid step, no copy and no table read (a cache may have released them and
pointed their entries elsewhere). The first page walked is computed under
the window's mask where the window starts inside it, as the last is under
the length's. ``window=None`` is the walk above, unchanged.

Off-TPU the wrapper runs in interpret mode — the same numerics, so CPU
tests cover the kernel's math; interpret-mode output matches the gather
lane to float tolerance (NOT bitwise: the blocked online-softmax sums in
a different order — the bitwise-parity contract belongs to the gather
lane).

Tuner family ``paged_attn`` (``paddle_tpu.tuner.paged_key``): the one
knob is ``block_h``, how many heads share a grid step — a multiple of the
sublane tile that divides the head count, or all the heads
(``_sanitize_block_h``). ``default_winners.json`` carries committed
entries; an unknown shape takes all the heads where a page of them fits
the buffers (a page's rows of one layer are then one contiguous piece of
the arena: at 16 heads of 128 the all-heads walk timed 2.07 ms against
3.10 ms for two blocks of 8, PERF.md PR 29); a shape on the MXU recurrence
takes all its KV heads whatever block was asked for. ``pages_per_step`` is no
knob: ``tuner.space.paged_pages_per_step`` derives it, the largest power
of two whose double buffers fit ``PAGED_BUFFER_BUDGET`` and the table, and
``tuner.space.paged_attn_vmem_bytes`` states the footprint that follows.
"""
from __future__ import annotations

import functools

import numpy as np

import jax
import jax.numpy as jnp
from jax import lax

from ..core.pallas_mode import record_operand_dtype, resolve_interpret
from .pallas_attention import _mxu_dot

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


def _bf16_parts(x):
    """Float32 ``x`` as three bfloat16 arrays ``h, m, l``, each the rounding
    of what the parts before it leave: ``x = h + m + l`` to ``2**-24`` of
    ``x``. They are the operands of the six bfloat16 products that a float32
    product at ``Precision.HIGHEST`` is (``_stacked_dot``)."""
    h = x.astype(jnp.bfloat16)
    x = x - h.astype(jnp.float32)
    m = x.astype(jnp.bfloat16)
    return h, m, (x - m.astype(jnp.float32)).astype(jnp.bfloat16)


def _stacked_dot(a_stack, b_parts, dims):
    """``a . b`` of two float32 matrices as ``Precision.HIGHEST`` computes
    it, six bfloat16 products accumulated in float32 (``a_h b_h``, ``a_h
    b_m``, ``a_m b_h``, ``a_h b_l``, ``a_l b_h``, ``a_m b_m``; the three left
    out are ``2**-23`` of the terms), grouped by the part of ``b`` they share:
    ``(a_h, a_m, a_l) . b_h``, ``(a_h, a_m) . b_m``, ``a_h . b_l``. ``b`` is
    the operand that stands on the MXU, so each of its tiles is loaded three
    times and not six, and the parts of ``a`` stream past it stacked along
    the rows. ``a_stack`` is ``concatenate(_bf16_parts(a))``, ``[3 r, k]``;
    ``b_parts`` is ``_bf16_parts(b)``; ``dims`` contracts ``a``'s columns.
    Returns ``[r, n]`` float32, the smallest products summed first."""
    r = a_stack.shape[0] // 3
    b_h, b_m, b_l = b_parts
    dot = functools.partial(_mxu_dot(a_stack.dtype), dimension_numbers=dims)
    high = dot(a_stack, b_h)                     # a_h b_h | a_m b_h | a_l b_h
    mid = dot(a_stack[:2 * r], b_m)              # a_h b_m | a_m b_m
    low = dot(a_stack[:r], b_l)                  # a_h b_l
    return ((low + high[2 * r:] + mid[r:]) + (mid[:r] + high[r:2 * r])
            + high[:r])


def _paged_attn_kernel(bt_ref, len_ref, layer_ref, q_ref, *refs,
                       scale, page_size, pages_per_seq, pages_per_step,
                       block_h, head_blocks, groups, arenas, window=None,
                       stack=None, value_width=None):
    """One sequence's head block per grid step; the page walk is a loop in
    here, over the pages the sequence has rows in and no further. Each
    iteration starts the copies of the next ``pages_per_step`` pages
    (HBM arena -> the other half of the double buffer, a copy a page and
    arena, ids from the block table in SMEM), waits for its own and runs
    the online-softmax recurrence over them.

    ``stack=None``, the VPU recurrence, a page at a time: with a single
    query row a KV head there is nothing for the MXU to amortize, so the
    recurrence stays in the arena's own ``[page, heads, D]`` layout: q.k is
    a multiply and a lane reduction, softmax statistics reduce over the
    major (page) axis, p.v is a lane broadcast and a major-axis sum — no
    transpose, no batched dot, no relayout. A fetched page serves each of
    the ``groups`` query heads of its KV heads in turn; with one arena
    (fused rows) it is both operands.

    ``stack=n``, the MXU recurrence (the module docstring's ``"mxu"``): the
    buffers hold a page as its flat rows ``[page * Hkv, D]``, ``q`` is all
    the query heads ``[Hq, D]``, and up to ``n`` whole pages go through one
    pair of products for all of them, the rows of other KV heads biased out.

    ``value_width=n`` (latent rows, on the MXU recurrence with one KV head):
    a row is a key whole and a value in its first ``n`` columns, so the
    second product takes those columns of the page the first one took, and
    the accumulator is ``[Hq, n]``. Both products take the bfloat16 parts of
    their operands, the fetched rows split once for the two
    (``_stacked_dot``; the module docstring's "Latent rows").

    Only the page that holds row ``positions[s]`` can hold rows past it
    (and a window's first page rows before it), so only those pages pay
    for the mask, one page a product."""
    import jax.experimental.pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    hbm = refs[:arenas]
    o_ref = refs[arenas]
    bufs = refs[arenas + 1:2 * arenas + 1]
    sem, first_half, acc_ref, m_ref, l_ref = refs[2 * arenas + 1:]
    s, hb = pl.program_id(0), pl.program_id(1)
    layer = layer_ref[0]

    def position(seq):
        # a slot nobody serves keeps counting ticks: the table's last row
        # is as far as a walk goes (the gather lane's mask ends there too)
        return jnp.clip(len_ref[seq], 0, pages_per_seq * page_size - 1)

    pos = position(s)
    n_full = (pos + 1) // page_size          # pages whose every row is live
    n_pages = pos // page_size + 1           # pages with a live row

    def first_page(seq):
        """Where the walk of ``seq`` begins: the page of the first row its
        window holds."""
        return jnp.maximum(position(seq) - (window - 1), 0) // page_size

    if window is not None:
        # the walk's pages are counted from its first on; a window that
        # starts inside that page puts it under the mask too
        lo = jnp.maximum(pos - (window - 1), 0)
        p0 = lo // page_size
        n_full, n_pages = n_full - p0, n_pages - p0
        head = (lax.rem(lo, page_size) != 0).astype(jnp.int32)
    n_steps = (n_pages + pages_per_step - 1) // pages_per_step

    def copies(seq, hblk, step, half, start):
        """Start, or wait for, the copy of every live page of loop step
        ``step`` of sequence ``seq``'s head block ``hblk`` into buffer
        ``half``."""
        first = step * pages_per_step
        # all the heads: a page's rows of a layer, one contiguous piece
        heads = () if head_blocks == 1 else (
            slice(None), pl.ds(pl.multiple_of(hblk * block_h, block_h),
                               block_h))

        begin = 0 if window is None else first_page(seq)

        def one(i, carry):
            rows = (bt_ref[seq * pages_per_seq + begin + first + i],
                    layer) + heads
            for a in range(arenas):
                copy = pltpu.make_async_copy(
                    hbm[a].at[rows], bufs[a].at[half, i], sem.at[half, a])
                # not `copy.start()`: tools/analyze resolves a call by its
                # name and would walk every `start` and `wait` in the repo
                (copy.start if start else copy.wait)()
            return carry

        live = position(seq) // page_size + 1
        if window is not None:
            live = live - begin
        lax.fori_loop(0, jnp.minimum(live - first, pages_per_step), one, 0)

    # the first pages of a grid step are on their way since the step
    # before it (the grid runs in order): only the first step of all
    # starts its own
    @pl.when((s == 0) & (hb == 0))
    def _():
        first_half[0] = 0
        copies(s, hb, 0, 0, start=True)

    half0 = first_half[0]
    last_hb = hb + 1 == head_blocks
    s_next, hb_next = (jnp.where(last_hb, s + 1, s),
                       jnp.where(last_hb, 0, hb + 1))

    acc_ref[...] = jnp.zeros_like(acc_ref)
    m_ref[...] = jnp.full_like(m_ref, _NEG_INF)
    l_ref[...] = jnp.zeros_like(l_ref)
    if stack is None:
        qs = [q_ref[0, g].astype(jnp.float32) * scale for g in range(groups)]
    else:
        q_all = q_ref[0].astype(jnp.float32) * scale         # [Hq, row]
        rows = page_size * block_h                  # a page's flat rows

        def other_heads(n):
            """``_NEG_INF`` where a flat row of ``n`` stacked pages is not
            of the query head's KV head, 0 where it is: ``[Hq, n * rows]``."""
            shape = (q_all.shape[0], n * rows)
            own = lax.rem(lax.broadcasted_iota(jnp.int32, shape, 1), block_h) \
                == lax.div(lax.broadcasted_iota(jnp.int32, shape, 0), groups)
            return jnp.where(own, 0.0, _NEG_INF)

        # a product takes `stack` whole pages, or a power of two under it
        if value_width is None:
            bias = {2 ** k: other_heads(2 ** k)
                    for k in range(stack.bit_length())}
        else:
            # latent rows are of the one KV head there is, nothing to push
            # out; the queries are split once a grid step
            q_stack = jnp.concatenate(_bf16_parts(q_all), axis=0)

    def flat_pages(half, i, n, masked=False, at=None):
        """The MXU recurrence over the ``n`` page slots from ``i`` of buffer
        ``half``; under the mask ``n`` is 1 and the page the walk's ``at``."""

        def flat(buf):
            if n == 1:
                return buf[half, i].astype(jnp.float32)      # [rows, row]
            return buf[half, pl.ds(i, n)].astype(jnp.float32).reshape(
                n * rows, buf.shape[-1])

        kblk = flat(bufs[0])
        vblk = kblk if arenas == 1 else flat(bufs[1])
        if masked:    # rows past the end may hold anything: 0 * NaN is NaN
            # flat row r is live where its token r // Hkv is
            if window is None:      # only the last page is ever masked
                start, first = (n_pages - 1) * page_size, 0
            else:       # the first or the last, or one page that is both
                start = (p0 + at) * page_size
                first = (lo - start) * block_h
            past = (pos - start + 1) * block_h

            def live(shape, axis):
                r = lax.broadcasted_iota(jnp.int32, shape, axis)
                return (r >= first) & (r < past)

            vblk = jnp.where(live((rows, 1), 0), vblk, 0.0)
            if arenas == 1:
                kblk = vblk
        if value_width is not None:
            # one split of the fetched rows serves both products: the value
            # is whole lane tiles of the same rows
            k_parts = _bf16_parts(kblk)
            s_blk = _stacked_dot(q_stack, k_parts, (((1,), (1,)), ((), ())))
        else:
            s_blk = lax.dot_general(
                q_all, kblk, (((1,), (1,)), ((), ())),
                precision=lax.Precision.HIGHEST,
                preferred_element_type=jnp.float32) + bias[n]  # [Hq, n * rows]
        if masked:
            s_blk = jnp.where(live((1, rows), 1), s_blk, _NEG_INF)
        m_prev, l_prev = m_ref[:, :1], l_ref[:, :1]          # [Hq, 1]
        m_new = jnp.maximum(m_prev, jnp.max(s_blk, axis=1, keepdims=True))
        alpha = jnp.exp(m_prev - m_new)
        # a masked entry's exp(-1e30 - m) is 0: every page computed holds
        # a live row of every KV head, so m_new is a real score
        pexp = jnp.exp(s_blk - m_new)
        l_new = l_prev * alpha + jnp.sum(pexp, axis=1, keepdims=True)
        acc = acc_ref[...] * alpha
        if value_width is not None:
            acc_ref[...] = acc + _stacked_dot(
                jnp.concatenate(_bf16_parts(pexp), axis=0),
                [part[:, :value_width] for part in k_parts],
                (((1,), (0,)), ((), ())))
        else:
            acc_ref[...] = acc + lax.dot_general(
                pexp, vblk, (((1,), (0,)), ((), ())),
                precision=lax.Precision.HIGHEST,
                preferred_element_type=jnp.float32)
        m_ref[...] = jnp.broadcast_to(m_new, m_ref.shape)
        l_ref[...] = jnp.broadcast_to(l_new, l_ref.shape)

    def page(half, i, masked, at=None):
        """The recurrence over page slot ``i`` of buffer ``half``, the
        walk's page ``at`` (a window's mask needs to know)."""
        if stack is not None:
            return flat_pages(half, i, 1, masked, at)
        kblk = bufs[0][half, i].astype(jnp.float32)    # [page, bh, D]
        vblk = kblk if arenas == 1 else bufs[1][half, i].astype(jnp.float32)
        if masked:    # rows past the end may hold anything: 0 * NaN is NaN
            if window is None:      # only the last page is ever masked
                first = (n_pages - 1) * page_size
                valid = first + lax.broadcasted_iota(
                    jnp.int32, (page_size, block_h, 1), 0) <= pos
            else:       # the first or the last, or one page that is both
                row = (p0 + at) * page_size + lax.broadcasted_iota(
                    jnp.int32, (page_size, block_h, 1), 0)
                valid = (row >= lo) & (row <= pos)
            vblk = jnp.where(valid, vblk, 0.0)
            if arenas == 1:
                kblk = vblk
        for g in range(groups):
            s_blk = jnp.sum(kblk * qs[g][None], axis=-1, keepdims=True)
            if masked:
                s_blk = jnp.where(valid, s_blk, _NEG_INF)  # [page, bh, 1]
            m_prev = m_ref[g, :, :1]                      # [bh, 1]
            l_prev = l_ref[g, :, :1]
            m_new = jnp.maximum(m_prev, jnp.max(s_blk, axis=0))
            alpha = jnp.exp(m_prev - m_new)
            # a masked row's exp(-1e30 - m) is 0: row 0 is always live,
            # so m_new is a real score from the first page on
            pexp = jnp.exp(s_blk - m_new[None])
            l_new = l_prev * alpha + jnp.sum(pexp, axis=0)
            acc_ref[g] = acc_ref[g] * alpha + jnp.sum(pexp * vblk, axis=0)
            m_ref[g] = jnp.broadcast_to(m_new, m_ref.shape[1:])
            l_ref[g] = jnp.broadcast_to(l_new, l_ref.shape[1:])

    def walk(step, carry):
        half = lax.rem(half0 + step, 2)

        @pl.when(step + 1 < n_steps)
        def _():
            copies(s, hb, step + 1, 1 - half, start=True)

        @pl.when((step + 1 == n_steps) & (s_next < pl.num_programs(0)))
        def _():
            first_half[0] = 1 - half
            copies(s_next, hb_next, 0, 1 - half, start=True)

        copies(s, hb, step, half, start=False)
        first = step * pages_per_step

        def whole_page(i, carry):
            page(half, i, False)
            return carry

        def whole_pages(begin, end):
            """The whole pages ``[begin, end)`` of this half: a page at a
            time, or ``stack`` a product while that many are left and then
            the powers of two under it, each at most once."""
            if stack is None:
                lax.fori_loop(begin, end, whole_page, 0)
                return
            full = jnp.maximum(end - begin, 0) // stack

            def stacked(j, carry):
                flat_pages(half, begin + j * stack, stack)
                return carry

            lax.fori_loop(0, full, stacked, 0)
            begin, n = begin + full * stack, stack // 2
            while n:
                fits = end - begin >= n

                @pl.when(fits)
                def _(begin=begin, n=n):
                    flat_pages(half, begin, n)

                begin, n = begin + jnp.where(fits, n, 0), n // 2

        if window is None:
            whole_pages(0, jnp.minimum(n_full - first, pages_per_step))

            @pl.when((n_full < n_pages) & (n_full - first < pages_per_step))
            def _():
                page(half, n_full - first, True)
            return carry

        # the walk's first page, where the window starts inside it
        @pl.when((step == 0) & (head == 1))
        def _():
            page(half, 0, True, 0)

        whole_pages(jnp.clip(head - first, 0, pages_per_step),
                    jnp.minimum(n_full - first, pages_per_step))

        # the position's page, unless it was the first and is done
        @pl.when((n_full < n_pages) & (n_full - first >= 0)
                 & (n_full - first < pages_per_step)
                 & ((n_full > 0) | (head == 0)))
        def _():
            page(half, n_full - first, True, n_full)
        return carry

    lax.fori_loop(0, n_steps, walk, 0)
    o_ref[0] = (acc_ref[...] / jnp.maximum(l_ref[..., :1], 1e-30)
                ).astype(o_ref.dtype)


def paged_attention(q, k_arena, v_arena, block_tables, positions,
                    layer=0, scale=None, block_h=None, interpret=None,
                    selected=None, window=None, latent=None):
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

    ``selected=(tables [S, Hkv, K], counts [S, Hkv])``: each sequence and KV
    head walks ``tables[s, h, :counts[s, h]]`` (physical pages in ascending
    logical order, the position's page last) in place of its block-table
    row; ``k_arena`` is then head-major with fused rows, ``[num_pages + 1,
    num_layers * Hkv, page_size, 2 * D]``, and ``v_arena`` None.

    ``window=W`` (a positive int, the plain walk only): query ``s`` attends
    rows ``positions[s] - W < j <= positions[s]``, and walks from the page of
    the first of them.

    ``latent=(value_width, rotary_width)`` (the plain walk only, no window):
    the cache keeps ONE row a token and layer for all the heads, ``k_arena``
    is ``[num_pages + 1, num_layers, page_size, row]`` with ``row >=
    value_width + rotary_width`` (what lies past is padding) and ``v_arena``
    None. A row is a key whole and a value in its first ``value_width``
    columns: ``q`` is ``[S, Hq, value_width + rotary_width]`` (the absorbed
    query beside its rotary part), the scores are one dot with the row, and
    the result is ``[S, Hq, value_width]``, the weighted sum of the rows'
    value columns. A page is fetched once and serves both products.
    """
    if latent is not None:
        value_width, rotary_width = (int(n) for n in latent)  # noqa: PTA001 -- python ints of the configuration, never traced values
        if selected is not None or window is not None or v_arena is not None \
                or getattr(k_arena, "ndim", 0) != 4 \
                or q.shape[-1] != value_width + rotary_width \
                or k_arena.shape[-1] < q.shape[-1]:
            raise ValueError(
                "latent rows take the plain walk over ONE arena [P+1, L, "
                "page, row >= value + rotary], no second arena, window or "
                "selection, and queries of value + rotary columns")
        record_operand_dtype("paged_attn", jnp.bfloat16)  # _stacked_dot's
        return _paged_attention(
            q, k_arena[:, :, :, None, :], None,
            block_tables.astype(jnp.int32), positions.astype(jnp.int32),
            jnp.asarray(layer, jnp.int32).reshape(1),
            scale=1.0 / np.sqrt(q.shape[-1]) if scale is None else scale,
            block_h=1, interpret=resolve_interpret("paged_attn", interpret),
            value_width=value_width)
    if window is not None and (selected is not None or int(window) < 1):  # noqa: PTA001 -- a python int of the configuration, never a traced value
        raise ValueError(
            f"window must be a positive int on the plain walk, got "
            f"{window!r}" + (" with a selection" if selected is not None
                             else ""))
    if selected is not None:
        tables, counts = selected
        if v_arena is not None or k_arena.ndim != 4 \
                or k_arena.shape[-1] != 2 * q.shape[-1]:
            raise ValueError(
                "a selected walk reads a head-major arena of fused rows, "
                "[P+1, L * Hkv, page, 2 * D], and no second arena")
        if q.shape[1] % tables.shape[1] or k_arena.shape[1] % tables.shape[1]:
            raise ValueError(
                f"{q.shape[1]} query heads or {k_arena.shape[1]} arena rows "
                f"are not a multiple of the tables' {tables.shape[1]} KV "
                f"heads")
        return _selected_attention(
            q, k_arena, tables.astype(jnp.int32), counts.astype(jnp.int32),
            positions.astype(jnp.int32),
            jnp.asarray(layer, jnp.int32).reshape(1),
            scale=1.0 / np.sqrt(q.shape[-1]) if scale is None else scale,
            interpret=resolve_interpret("paged_attn", interpret))
    from ..tuner.space import (PAGED_BUFFER_BUDGET, paged_buffer_bytes,
                               paged_recurrence)

    if isinstance(k_arena, dict) or isinstance(v_arena, dict):
        raise ValueError(
            "paged_attention kernel reads dense arenas only — the int8 "
            "lane uses the gather implementation (dequantize in-graph)")
    num_heads, head_dim = k_arena.shape[3], q.shape[-1]        # KV heads
    if q.shape[1] % num_heads:
        raise ValueError(f"{q.shape[1]} query heads are not a multiple of "
                         f"the arena's {num_heads} KV heads")
    interpret = resolve_interpret("paged_attn", interpret)
    if v_arena is not None and head_dim % 128 and not interpret:
        # Mosaic copies whole lane tiles out of an arena in HBM: rows
        # under the lane width go side by side first (a copy of both
        # arenas, where the device already relaid each out for the call)
        k_arena = jnp.concatenate([k_arena, v_arena], axis=-1)
        v_arena = None
    arenas = 1 if v_arena is None else 2
    page_size, row = k_arena.shape[2], k_arena.shape[-1]
    itemsize = jnp.dtype(k_arena.dtype).itemsize
    if paged_recurrence(q.shape[1] // num_heads, num_heads, page_size, row,
                        itemsize, arenas) == "mxu":
        block_h = num_heads     # a page's flat rows hold all the KV heads
    if block_h is None:
        block_h = _tuned_block_h(num_heads, row, page_size, q.dtype)
    if block_h is None:
        # all the heads (a page's rows of one layer are then one contiguous
        # piece of the arena) where a page of them fits the buffers
        fits = paged_buffer_bytes(1, num_heads, page_size, row, itemsize,
                                  arenas) <= PAGED_BUFFER_BUDGET
        block_h = num_heads if fits else 8
    return _paged_attention(
        q, k_arena, v_arena, block_tables.astype(jnp.int32),
        positions.astype(jnp.int32),
        jnp.asarray(layer, jnp.int32).reshape(1),
        scale=1.0 / np.sqrt(head_dim) if scale is None else scale,
        block_h=_sanitize_block_h(block_h, num_heads, itemsize),
        interpret=interpret, window=None if window is None else int(window))  # noqa: PTA001 -- a python int of the configuration


@functools.partial(jax.jit, static_argnames=("scale", "block_h", "interpret",
                                             "window", "value_width"))
def _paged_attention(q, k_arena, v_arena, block_tables, positions, layer, *,
                     scale, block_h, interpret, window=None,
                     value_width=None):
    """The call itself, jitted on its own: a program's layers share one
    trace and one lowered function of it (the layer is an operand)."""
    import jax.experimental.pallas as pl
    from jax.experimental.pallas import tpu as pltpu
    from ..tuner.space import (paged_pages_per_step, paged_recurrence,
                               paged_stack_pages)

    fused = v_arena is None
    arenas = 1 if fused else 2
    if value_width is not None:     # zeros over the row's padding
        q = jnp.pad(q, ((0, 0), (0, 0), (0, k_arena.shape[-1] - q.shape[-1])))
    elif fused:     # zeros over the value's lanes
        q = jnp.pad(q, ((0, 0), (0, 0), (0, q.shape[-1])))
    s_n, q_heads, head_dim = q.shape
    num_heads, page_size = k_arena.shape[3], k_arena.shape[2]
    if num_heads % block_h:
        raise ValueError(f"block_h {block_h} does not divide the arena's "
                         f"{num_heads} KV heads")
    groups = q_heads // num_heads
    pages_per_seq = block_tables.shape[1]
    itemsize = jnp.dtype(k_arena.dtype).itemsize
    pages_per_step = paged_pages_per_step(
        block_h, page_size, head_dim, itemsize, arenas, pages_per_seq)
    mxu = block_h == num_heads and paged_recurrence(
        groups, num_heads, page_size, head_dim, itemsize, arenas) == "mxu"

    kernel = functools.partial(
        _paged_attn_kernel, scale=scale, page_size=page_size,
        pages_per_seq=pages_per_seq, pages_per_step=pages_per_step,
        block_h=block_h, head_blocks=num_heads // block_h, groups=groups,
        arenas=arenas, **({} if window is None else {"window": window}),
        **({"stack": paged_stack_pages(pages_per_step, q_heads,
                                       page_size * num_heads)}
           if mxu else {}),
        **({} if value_width is None else {"value_width": value_width}))
    out_dim = head_dim if value_width is None else value_width
    bt_flat = block_tables.reshape(-1)
    if mxu:
        # a page as its flat rows, the arena's own memory order (a bitcast),
        # under all the query heads in their own order
        page_block = (page_size * num_heads, head_dim)
        k_arena, v_arena = (a if a is None else a.reshape(
            a.shape[:2] + page_block) for a in (k_arena, v_arena))
        q_g, q_block = q, (1, q_heads, head_dim)
        o_shape, o_block = (s_n, q_heads, out_dim), (1, q_heads, out_dim)
        heads = (q_heads,)

        def _q_map(s, h, bt_ref, len_ref, layer_ref):
            return (s, 0, 0)
    else:
        page_block = (page_size, block_h, head_dim)
        # group-major: q_g[s, g, h] is query head h * groups + g
        q_g = jnp.swapaxes(q.reshape(s_n, num_heads, groups, head_dim), 1, 2)
        q_block = (1, groups, block_h, head_dim)
        o_shape, o_block = q_g.shape, q_block
        heads = (groups, block_h)

        def _q_map(s, h, bt_ref, len_ref, layer_ref):
            return (s, 0, h, 0)

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=3,
        grid=(s_n, num_heads // block_h),
        in_specs=[pl.BlockSpec(q_block, _q_map)]
        + [pl.BlockSpec(memory_space=pl.ANY)] * arenas,   # whole, in HBM
        out_specs=pl.BlockSpec(o_block, _q_map),
        scratch_shapes=[      # a double buffer of pages an arena
            pltpu.VMEM((2, pages_per_step) + page_block, k_arena.dtype)
        ] * arenas + [
            pltpu.SemaphoreType.DMA((2, arenas)),
            pltpu.SMEM((1,), jnp.int32),    # the half a step starts in
            pltpu.VMEM(heads + (out_dim,), jnp.float32),      # acc
            pltpu.VMEM(heads + (128,), jnp.float32),          # running max
            pltpu.VMEM(heads + (128,), jnp.float32),          # running sum
        ])
    out = pl.pallas_call(
        kernel,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct(o_shape, q.dtype),
        # in order: a step starts the next one's first copies
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary", "arbitrary")),
        interpret=interpret,
        name="paged_attn",
    )(bt_flat, positions, layer, q_g,
      *((k_arena,) if fused else (k_arena, v_arena)))
    if not mxu:
        out = jnp.swapaxes(out, 1, 2).reshape(s_n, q_heads, head_dim)
    if value_width is not None:
        return out
    return out[..., head_dim // 2:] if fused else out


# -- the walk over selected pages ----------------------------------------------

def _selected_attn_kernel(tab_ref, cnt_ref, len_ref, layer_ref, q_ref, hbm,
                          o_ref, buf, sem, first_half, acc_ref, m_ref, l_ref,
                          *, page_size, width, pages_per_step, kv_heads,
                          head_dim):
    """One sequence's KV head per grid step: the walk of
    ``_paged_attn_kernel`` over ``tables[s, h, :counts[s, h]]``. A page is a
    ``[page, 2 * D]`` slab of one head's fused rows; the head's ``G`` query
    heads ride the sublanes, so the scores of a page are one ``[G, D] x [D,
    page]`` product and its part of the result one ``[G, page] x [page,
    D]``."""
    import jax.experimental.pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    s, h = pl.program_id(0), pl.program_id(1)
    layer = layer_ref[0]
    pos = jnp.maximum(len_ref[s], 0)
    in_last = lax.rem(pos, page_size)        # the position's row in its page
    n_pages = jnp.clip(cnt_ref[s * kv_heads + h], 0, width)
    n_full = jnp.where(in_last + 1 == page_size, n_pages, n_pages - 1)
    n_steps = (n_pages + pages_per_step - 1) // pages_per_step

    def copies(seq, head, step, half, start):
        first = step * pages_per_step
        live = jnp.clip(cnt_ref[seq * kv_heads + head], 0, width)

        def one(i, carry):
            pid = tab_ref[(seq * kv_heads + head) * width + first + i]
            copy = pltpu.make_async_copy(
                hbm.at[pid, layer * kv_heads + head], buf.at[half, i],
                sem.at[half])
            (copy.start if start else copy.wait)()
            return carry

        lax.fori_loop(0, jnp.minimum(live - first, pages_per_step), one, 0)

    @pl.when((s == 0) & (h == 0))
    def _():
        first_half[0] = 0
        copies(s, h, 0, 0, start=True)

    half0 = first_half[0]
    last_h = h + 1 == kv_heads
    s_next, h_next = jnp.where(last_h, s + 1, s), jnp.where(last_h, 0, h + 1)

    acc_ref[...] = jnp.zeros_like(acc_ref)
    m_ref[...] = jnp.full_like(m_ref, _NEG_INF)
    l_ref[...] = jnp.zeros_like(l_ref)
    q = q_ref[0, 0].astype(jnp.float32)                 # [G, D], scaled

    def page(half, i, masked):
        kv = buf[half, i].astype(jnp.float32)           # [page, 2 D]
        k, v = kv[:, :head_dim], kv[:, head_dim:]
        scores = lax.dot_general(
            q, k, (((1,), (1,)), ((), ())), precision=lax.Precision.HIGHEST,
            preferred_element_type=jnp.float32)         # [G, page]
        if masked:    # rows past the end may hold anything: 0 * NaN is NaN
            scores = jnp.where(lax.broadcasted_iota(
                jnp.int32, scores.shape, 1) <= in_last, scores, _NEG_INF)
            v = jnp.where(lax.broadcasted_iota(
                jnp.int32, v.shape, 0) <= in_last, v, 0.0)
        m_prev, l_prev = m_ref[:, :1], l_ref[:, :1]
        m_new = jnp.maximum(m_prev, jnp.max(scores, axis=1, keepdims=True))
        alpha = jnp.exp(m_prev - m_new)
        pexp = jnp.exp(scores - m_new)
        l_new = l_prev * alpha + jnp.sum(pexp, axis=1, keepdims=True)
        acc_ref[...] = acc_ref[...] * alpha + lax.dot_general(
            pexp, v, (((1,), (0,)), ((), ())),
            precision=lax.Precision.HIGHEST,
            preferred_element_type=jnp.float32)
        m_ref[...] = jnp.broadcast_to(m_new, m_ref.shape)
        l_ref[...] = jnp.broadcast_to(l_new, l_ref.shape)

    def walk(step, carry):
        half = lax.rem(half0 + step, 2)

        @pl.when(step + 1 < n_steps)
        def _():
            copies(s, h, step + 1, 1 - half, start=True)

        @pl.when((step + 1 == n_steps) & (s_next < pl.num_programs(0)))
        def _():
            first_half[0] = 1 - half
            copies(s_next, h_next, 0, 1 - half, start=True)

        copies(s, h, step, half, start=False)
        first = step * pages_per_step

        def whole_page(i, carry):
            page(half, i, False)
            return carry

        lax.fori_loop(0, jnp.minimum(n_full - first, pages_per_step),
                      whole_page, 0)

        @pl.when((n_full < n_pages) & (n_full - first >= 0)
                 & (n_full - first < pages_per_step))
        def _():
            page(half, n_full - first, True)
        return carry

    lax.fori_loop(0, n_steps, walk, 0)

    # a step with nothing to walk (an empty slot) still hands the next
    # step its first copies
    @pl.when((n_steps == 0) & (s_next < pl.num_programs(0)))
    def _():
        copies(s_next, h_next, 0, half0, start=True)

    o_ref[0, 0] = (acc_ref[...] / jnp.maximum(l_ref[:, :1], 1e-30)
                   ).astype(o_ref.dtype)


@functools.partial(jax.jit, static_argnames=("scale", "interpret"))
def _selected_attention(q, arena, tables, counts, positions, layer, *, scale,
                        interpret):
    import jax.experimental.pallas as pl
    from jax.experimental.pallas import tpu as pltpu
    from ..tuner.space import paged_pages_per_step

    s_n, q_heads, head_dim = q.shape
    kv_heads, width = tables.shape[1], tables.shape[2]
    groups = q_heads // kv_heads
    page_size = arena.shape[2]
    pages_per_step = paged_pages_per_step(
        1, page_size, 2 * head_dim, jnp.dtype(arena.dtype).itemsize, 1,
        width)
    kernel = functools.partial(
        _selected_attn_kernel, page_size=page_size, width=width,
        pages_per_step=pages_per_step, kv_heads=kv_heads, head_dim=head_dim)
    # query head h * groups + g reads KV head h: already head-major
    q_g = (q * scale).reshape(s_n, kv_heads, groups, head_dim)

    def _q_map(s, h, *_):
        return (s, h, 0, 0)

    q_block = (1, 1, groups, head_dim)
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=4,
        grid=(s_n, kv_heads),
        in_specs=[pl.BlockSpec(q_block, _q_map),
                  pl.BlockSpec(memory_space=pl.ANY)],   # whole, in HBM
        out_specs=pl.BlockSpec(q_block, _q_map),
        scratch_shapes=[
            pltpu.VMEM((2, pages_per_step, page_size, 2 * head_dim),
                       arena.dtype),
            pltpu.SemaphoreType.DMA((2,)),
            pltpu.SMEM((1,), jnp.int32),    # the half a step starts in
            pltpu.VMEM((groups, head_dim), jnp.float32),      # acc
            pltpu.VMEM((groups, 128), jnp.float32),           # running max
            pltpu.VMEM((groups, 128), jnp.float32),           # running sum
        ])
    out = pl.pallas_call(
        kernel,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((s_n, kv_heads, groups, head_dim),
                                       q.dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary", "arbitrary")),
        interpret=interpret,
        name="paged_attn",
    )(tables.reshape(-1), counts.reshape(-1), positions, layer, q_g, arena)
    return out.reshape(s_n, q_heads, head_dim)
