"""Candidate spaces for the kernel autotuner, with VMEM-footprint pruning.

TVM's schedule-search insight applies at Pallas granularity: the right
block/grid shape is a function of (shape, dtype, platform), not a
constant. The spaces here are deliberately small — tens of candidates —
because each trial costs a Mosaic compile; VMEM pruning (the ~16 MiB/core
budget, pallas_guide.md) cuts the obviously-unbuildable ones before any
compile is attempted.
"""
from __future__ import annotations

from typing import Dict, Iterator, List, Optional, Tuple

#: per-core VMEM on current TPU generations (pallas_guide.md); trials
#: budget 80% of it so the compiler keeps headroom for spills/semaphores
VMEM_BYTES = 16 * 1024 * 1024
VMEM_BUDGET = int(VMEM_BYTES * 0.8)

#: sublane tile: block rows must stay multiples of 16 so both f32 (8) and
#: bf16 (16) layouts are legal (ops/pallas_attention.py convention)
SUBLANE = 16

#: candidate block edges for the flash-attention family
FLASH_BLOCKS = (16, 32, 64, 128, 256, 512)


def _ceil_to(n: int, m: int) -> int:
    return -(-n // m) * m


def flash_vmem_bytes(block_q: int, block_k: int, kv_len: int,
                     head_dim: int, itemsize: int = 4, bwd: bool = False,
                     q_len: Optional[int] = None) -> int:
    """VMEM-resident bytes of one flash-attention program instance, for
    the forward kernel or (``bwd``) the larger of the dQ and dK/dV ones
    (ops/pallas_attention.py).

    Two parts. **Pipeline buffers**: every BlockSpec operand and result
    is held twice (Pallas double-buffers a block even when its index
    repeats, as the whole-sequence K/V of the forward and dQ programs and
    the whole-sequence Q/dO of the dK/dV program do): a kernel whose
    resident pair alone reaches the budget builds at no block size.
    **In-kernel values**: the float32 score block (P overwrites S), in
    the backward a second one (dP, then dS), and the copy of P (and dS)
    that is the MXU's operand — bfloat16 when the inputs are (the dots
    take their operands in the input's dtype; 2-byte inputs are taken as
    bfloat16); float32 inputs cost the forward and dQ a block beside the
    next one, and dK/dV nothing: it computes its tile transposed, so P
    and dS are their dots' operands as they stand — plus what each
    program keeps in VMEM scratch between its loop's trips and no input
    dtype narrows: the float32 accumulators, and every row statistic as
    ``[rows, lanes]`` with the row's value in each lane (the forward's
    running max and sum, dQ's lse and delta turned into columns once a
    program; dK/dV reads its two as the lane rows they arrive as).

    Checked against the compiler for a described v5e (16 MiB scoped; the
    budget is 80% of it), PR 27 and again PR 37: every shape it refuses
    is over the budget here (K/V of 2 x 4 MiB, float32 1024x1024 backward
    blocks at head size 128, a 2048x2048 score block); 1024x1024 backward
    blocks at head size 64, which it builds (bfloat16 with nothing to
    spare; float32 only since dK/dV turns no tile), are over it too.
    """
    q_len = kv_len if q_len is None else q_len
    kv_pad = _ceil_to(kv_len, block_k)
    q_pad = _ceil_to(q_len, block_q)
    q_blk = block_q * head_dim * itemsize
    k_blk = block_k * head_dim * itemsize
    score = block_q * block_k * 4             # one f32 [bq, bk] block
    operand = block_q * block_k * itemsize    # P or dS as the dot takes it
    stat = block_q * 128 * 4                  # a lane-replicated column
    if not bwd:
        pipeline = 2 * (q_blk                       # q
                        + 2 * kv_pad * head_dim * itemsize   # K, V whole
                        + q_blk + block_q * 4)      # out, lse
        values = (score + operand
                  + block_q * head_dim * 4          # acc
                  + 2 * stat)                       # m, l
        return pipeline + values
    dq_prog = (2 * (2 * q_blk                       # q, dO
                    + 2 * kv_pad * head_dim * itemsize       # K, V whole
                    + 2 * block_q * 4               # lse, delta
                    + q_blk)                        # dQ
               + 2 * score + operand
               + block_q * head_dim * 4             # dQ accumulator
               + 2 * stat)                          # lse, delta as columns
    dkv_prog = (2 * (2 * q_pad * head_dim * itemsize         # Q, dO whole
                     + 2 * k_blk                    # k, v
                     + 2 * q_pad * 4                # lse, delta whole
                     + 2 * k_blk)                   # dK, dV
                + 2 * score + (2 * operand if itemsize < 4 else 0)
                + 2 * block_k * head_dim * 4)       # dK, dV accumulators
    return max(dq_prog, dkv_prog)


def blockspec_vmem_bytes(block_shapes, itemsize: int = 4) -> int:
    """Generic VMEM-resident bytes for a pallas_call's BlockSpec set: the
    sum of every block's element count times ``itemsize``. The family
    models above (:func:`flash_vmem_bytes`, :func:`paged_attn_vmem_bytes`)
    know their kernels' scratch/accumulator terms; this is the
    family-agnostic floor the static analyzer (PTA013) uses for arbitrary
    pallas_call sites — if the declared blocks alone bust the budget, no
    scratch accounting can save the kernel."""
    total = 0
    for shape in block_shapes:
        n = 1
        for d in shape:
            n *= int(d)
        total += n * itemsize
    return total


def flash_candidates(q_len: int, kv_len: int, head_dim: int,
                     itemsize: int = 4,
                     require_divides: bool = False,
                     bwd: bool = False) -> List[Tuple[int, int]]:
    """(block_q, block_k) candidates for a flash-attention shape, VMEM
    pruned by the forward's footprint or (``bwd``) the backward
    programs'. ``require_divides`` restricts to blocks that divide the
    16-rounded lengths exactly — the ring-flash path calls the kernel
    core without a padding wrapper, so only exact divisors are legal
    there."""
    q16 = max(SUBLANE, _ceil_to(q_len, SUBLANE))
    k16 = max(SUBLANE, _ceil_to(kv_len, SUBLANE))
    out: List[Tuple[int, int]] = []
    for bq in FLASH_BLOCKS:
        if bq > q16:
            continue
        if require_divides and q16 % bq:
            continue
        for bk in FLASH_BLOCKS:
            if bk > k16:
                continue
            if require_divides and k16 % bk:
                continue
            if flash_vmem_bytes(bq, bk, kv_len, head_dim, itemsize,
                                bwd=bwd, q_len=q_len) > VMEM_BUDGET:
                continue
            out.append((bq, bk))
    if not out:
        # tiniest legal block always fits; the caller's padding logic
        # clamps further
        out.append((SUBLANE, SUBLANE))
    return out


#: head-block candidates for the paged decode-attention family
#: (ops/paged_attention.py): how many heads share one grid step's page
#: DMA and compute block; the head count itself is always a candidate.
PAGED_BLOCK_H = (8, 16, 32)

#: sublane tile rows by itemsize (pallas_guide.md "Tiling Constraints")
SUBLANE_ROWS = {4: 8, 2: 16, 1: 32}


def paged_block_h_legal(block_h: int, num_heads: int,
                        itemsize: int = 4) -> bool:
    """The K/V block is ``(1, page_size, block_h, D)``: ``block_h`` must
    divide the head count (the grid is H // block_h) and, sitting
    second-to-last, be a multiple of the dtype's sublane tile or the
    whole head axis."""
    return (block_h > 0 and num_heads % block_h == 0
            and (block_h == num_heads
                 or block_h % SUBLANE_ROWS[itemsize] == 0))


#: VMEM the kernel gives its double-buffered page blocks; what is left of
#: the default 16 MiB scope holds the compute's own temporaries
PAGED_BUFFER_BUDGET = 4 * 1024 * 1024


def paged_buffer_bytes(pages: int, block_h: int, page_size: int,
                       head_dim: int, itemsize: int = 4,
                       arenas: int = 2) -> int:
    """Bytes of the kernel's double buffers at ``pages`` pages a loop step:
    two halves an arena, each ``[pages, page_size, block_h, head_dim]``.
    ``head_dim`` is the arena's row width (``2 * D`` for fused rows)."""
    return 2 * arenas * pages * page_size * block_h * head_dim * itemsize


def paged_pages_per_step(block_h: int, page_size: int, head_dim: int,
                         itemsize: int = 4, arenas: int = 2,
                         pages_per_seq: Optional[int] = None) -> int:
    """Pages the kernel fetches a loop step: the largest power of two whose
    double buffers (:func:`paged_buffer_bytes`) fit
    :data:`PAGED_BUFFER_BUDGET`, no more than a sequence has, and 1 where
    even one page is over the budget."""
    cap = PAGED_BUFFER_BUDGET // paged_buffer_bytes(
        1, block_h, page_size, head_dim, itemsize, arenas)
    if pages_per_seq is not None:
        cap = min(cap, pages_per_seq)
    pages = 1
    while 2 * pages <= cap:
        pages *= 2
    return pages


def paged_recurrence(groups: int, kv_heads: int, page_size: int,
                     head_dim: int, itemsize: int = 4,
                     arenas: int = 2) -> str:
    """Which online-softmax recurrence the plain walk of ``paged_attn``
    runs over a fetched page, from the call's shapes alone: ``"mxu"``
    where ``groups`` query heads share each KV head and a page of all the
    KV heads is whole sublane tiles that fit the buffers (the page's flat
    rows ``[page_size * kv_heads, head_dim]`` then go through the MXU once
    for all the query heads), ``"vpu"`` otherwise (a multiply and a
    reduction a query group in the arena's own layout: with one query head
    a KV head there is nothing for the MXU to amortize). ``head_dim`` is
    the arena's row width (``2 * D`` for fused rows). Latent rows are the
    shape ``(Hq, 1, page, row, itemsize, 1)``: every query head on the one
    row a token keeps, so ``"mxu"`` wherever a page is whole sublane
    tiles."""
    flat = page_size * kv_heads
    fits = paged_buffer_bytes(1, kv_heads, page_size, head_dim, itemsize,
                              arenas) <= PAGED_BUFFER_BUDGET
    if groups > 1 and fits and flat % SUBLANE_ROWS[itemsize] == 0:
        return "mxu"
    return "vpu"


#: the MXU recurrence's score block ``[Hq, stacked flat rows]`` in float32:
#: what the largest product measured held (LFM2: 32 heads x 32 pages of 128
#: flat rows), so that more heads stack fewer pages
PAGED_SCORE_BYTES = 512 * 1024


def paged_stack_pages(pages_per_step: int, q_heads: int,
                      flat_rows: int) -> int:
    """Whole pages the MXU recurrence puts through one product: the loop
    step's (a power of two), halved while the score block ``[q_heads,
    pages * flat_rows]`` is over :data:`PAGED_SCORE_BYTES`."""
    pages = pages_per_step
    while pages > 1 and q_heads * pages * flat_rows * 4 > PAGED_SCORE_BYTES:
        pages //= 2
    return pages


def paged_attn_vmem_bytes(block_h: int, page_size: int, head_dim: int,
                          itemsize: int = 4, arenas: int = 2,
                          groups: int = 1,
                          pages_per_seq: Optional[int] = None) -> int:
    """VMEM-resident bytes for one paged-attention program instance: the
    double-buffered page blocks of each arena (what
    :func:`paged_pages_per_step` sized), the q and out head blocks (held
    twice each by the pipeline), the f32 accumulator and the
    (block_h, 128)-padded running max/sum scratch. The MXU recurrence
    (:func:`paged_recurrence`; ``block_h`` is then all the KV heads) keeps
    the same scratch under other shapes (flat rows, ``[Hq, head_dim]``, a
    column a query head) and holds values besides: the scores of the pages
    stacked into a product, their ``exp``, and the other-heads bias of
    every product size (``stack``, ``stack / 2`` ... 1 pages).

    Latent rows (ONE KV head in ONE arena on the MXU recurrence; a fused-row
    call of one KV head holds less than is stated for it) build no bias and
    feed the MXU bfloat16 parts (``ops/paged_attention.py:_stacked_dot``):
    beside the scores and their ``exp`` a program holds the three parts of a
    product's stacked rows, each as converted and as re-tiled for the MXU,
    the float32 rows a part leaves, the stacked parts of the queries and of
    ``exp``, and the three partial results of either product (six blocks
    ``[Hq, rows]`` and six ``[Hq, row]``) before their sum. Checked against
    the compiler for a described v5e at ``(1, 64, 640, 4, 1, 16)``, PR 39:
    this states 8,704,000 bytes where the compiler's scratch, windows and
    1,448 spilled vector registers come to 8,704,000 (the parent's: 2,969,600
    stated, 11,694,080 built, the compiler's own split of ``HIGHEST``
    unstated), both under the 16 MiB scope."""
    pages = paged_pages_per_step(block_h, page_size, head_dim, itemsize,
                                 arenas, pages_per_seq)
    buffers = paged_buffer_bytes(pages, block_h, page_size, head_dim,
                                 itemsize, arenas)
    q_out = 2 * 2 * groups * block_h * head_dim * itemsize
    acc = groups * block_h * head_dim * 4
    stats = 2 * groups * block_h * 128 * 4
    scores = 0
    if paged_recurrence(groups, block_h, page_size, head_dim, itemsize,
                        arenas) == "mxu":
        q_heads, flat = groups * block_h, page_size * block_h
        stack = paged_stack_pages(pages, q_heads, flat)
        block = q_heads * stack * flat * 4
        if block_h == 1 and arenas == 1:
            rows = stack * flat * head_dim      # a product's stacked rows
            parts = 3 * rows * 2                # their bfloat16 parts
            stacked = 3 * q_heads * (head_dim + stack * flat) * 2   # q, exp
            partial = 6 * (block + q_heads * head_dim * 4)
            scores = 2 * block + 2 * parts + rows * 4 + stacked + partial
        else:
            scores = (4 * stack - 1) * q_heads * flat * 4
    return buffers + q_out + acc + stats + scores


def paged_attn_candidates(num_heads: int, head_dim: int, page_size: int,
                          itemsize: int = 4) -> List[Dict[str, int]]:
    """block_h candidates for a paged decode-attention shape: the legal
    head blocks only (:func:`paged_block_h_legal`), VMEM pruned (a head
    block so wide that one double-buffered page of it is over the
    budget)."""
    out = [{"block_h": b} for b in sorted({*PAGED_BLOCK_H, num_heads})
           if paged_block_h_legal(b, num_heads, itemsize)
           and paged_attn_vmem_bytes(b, page_size, head_dim,
                                     itemsize) <= VMEM_BUDGET]
    return out or [{"block_h": num_heads}]


#: candidate block sizes for the compressed-allreduce quantize stage.
#: Smaller blocks track outliers better (tighter scales) but pay more
#: scale-sidecar bytes; larger blocks amortize the sidecar but let one
#: outlier flatten a whole block's resolution.
COMPRESS_BLOCKS = (64, 128, 256, 512, 1024)


def compress_block_candidates(nelems: int) -> List[Dict[str, int]]:
    """Block-size candidates for one gradient-size family: a block larger
    than the payload only pads, so prune those."""
    out = [{"block": b} for b in COMPRESS_BLOCKS if b <= max(64, nelems)]
    return out or [{"block": COMPRESS_BLOCKS[0]}]


def nms_candidates(k: int) -> List[Dict[str, int]]:
    """Unroll factors for the greedy-NMS fori_loop (ops/custom.py): the
    loop body is tiny, so unrolling amortizes loop overhead until the
    unrolled body overflows instruction budget. Only exact divisors of
    the candidate count keep the trip arithmetic trivial. Mosaic lowers
    only ``unroll=1`` and ``unroll=k`` ("Only unroll=num_steps and
    unroll=1 supported"); the search drops the rest as unbuildable."""
    return [{"unroll": u} for u in (1, 2, 4, 8) if u <= max(1, k)]
