"""paddle_tpu.tuner: empirical Pallas-kernel autotuner.

TVM-style per-shape schedule search (PAPERS.md) scaled to this repo's
kernel families: instead of hand-picked 128x128 blocks everywhere, the
flash-attention kernels (ops/pallas_attention.py, the ring-flash chunk
kernel in distributed/fleet/sequence_parallel.py) and the ops/custom.py
Pallas kernels resolve their block/grid configuration per
``(shape, dtype, platform)`` key through this package:

1. **in-process memo** — after the first resolution a key costs one dict
   lookup on the kernel-call path (zero measurable overhead),
2. **on-disk winner cache** — only when ``PADDLE_TPU_TUNE_CACHE`` names
   a directory: versioned JSON written by ``tools/autotune.py`` (or by
   tune-on-miss), shared by every process that mounts it — replicas and
   restarts reuse each other's search,
3. **committed defaults** — ``default_winners.json`` ships winners for
   the bench-model shapes so CI and cold fleets never tune from scratch,
4. **heuristic fallback** — the historical hardcoded config, so an empty
   cache is never worse than the pre-tuner behavior.

Active search happens only in ``tools/autotune.py`` or when
``PADDLE_TPU_AUTOTUNE=1`` opts into tune-on-miss (a training step must
never block on a surprise search by default).
"""
from __future__ import annotations

import os
import threading
from typing import Any, Dict, Optional, Tuple

from . import runner, space, store
from .space import (compress_block_candidates, flash_candidates,
                    nms_candidates, paged_attn_candidates)
from .store import CACHE_VERSION, WinnerStore, cache_dir, store_for

__all__ = [
    "CACHE_VERSION", "WinnerStore", "cache_dir", "store_for",
    "flash_key", "nms_key", "compress_key", "paged_key",
    "get_flash_blocks", "get_nms_config", "get_compress_block",
    "get_paged_attn_config",
    "record_winner", "autotune_flash", "autotune_compress",
    "autotune_paged_attn",
    "tune_on_miss_enabled",
    "flash_candidates", "nms_candidates", "compress_block_candidates",
    "paged_attn_candidates",
    "clear_memo",
]

_ENV_AUTOTUNE = "PADDLE_TPU_AUTOTUNE"

#: resolved configs, keyed by canonical key string — the zero-overhead
#: tier consulted at kernel-call time
_MEMO: Dict[str, Optional[Dict[str, Any]]] = {}
_MEMO_LOCK = threading.Lock()


def clear_memo() -> None:
    with _MEMO_LOCK:
        _MEMO.clear()
    store._reset_for_tests()


def tune_on_miss_enabled() -> bool:
    return os.environ.get(_ENV_AUTOTUNE, "").strip() in ("1", "true", "on")


def _platform() -> str:
    import jax
    try:
        return jax.devices()[0].platform
    except Exception:
        return "cpu"


def _ceil16(n: int) -> int:
    return max(16, -(-int(n) // 16) * 16)


# -- canonical keys -----------------------------------------------------------

def flash_key(q_len: int, kv_len: int, head_dim: int, dtype: str,
              causal: bool, platform: Optional[str] = None,
              ring: bool = False, bwd: bool = False) -> str:
    """Key for the flash-attention family. Lengths are canonicalized to
    the 16-row sublane grid (4095 and 4096 share a winner); ``ring``
    marks the divisor-constrained ring-flash chunk variant; ``bwd``
    selects the backward-kernel family (the dQ/dKV recomputation programs
    have a different VMEM/compute balance than the forward, so they tune
    separately)."""
    p = platform or _platform()
    if bwd:
        fam = "ring_flash_bwd" if ring else "flash_bwd"
    else:
        fam = "ring_flash" if ring else "flash_fwd"
    try:                 # canonicalize: np.dtype / jnp scalar type / str
        import numpy as _np
        dtype = _np.dtype(dtype).name
    except TypeError:
        dtype = str(dtype)
    return (f"{fam}|{p}|{dtype}|d{int(head_dim)}|q{_ceil16(q_len)}"
            f"|k{_ceil16(kv_len)}|c{int(bool(causal))}")


def paged_key(num_heads: int, head_dim: int, page_size: int, dtype: str,
              platform: Optional[str] = None) -> str:
    """Key for the paged decode-attention family
    (``ops/paged_attention.py``). The query is always one token per
    sequence, so the shape family is (heads, head_dim, page_size) — the
    sequence count only scales the grid, not the per-step block."""
    p = platform or _platform()
    try:                 # canonicalize: np.dtype / jnp scalar type / str
        import numpy as _np
        dtype = _np.dtype(dtype).name
    except TypeError:
        dtype = str(dtype)
    return (f"paged_attn|{p}|{dtype}|h{int(num_heads)}|d{int(head_dim)}"  # noqa: PTA001 -- heads/head_dim/page_size are python shape ints at trace time
            f"|p{int(page_size)}")  # noqa: PTA001 -- see above


def nms_key(k: int, platform: Optional[str] = None) -> str:
    return f"nms|{platform or _platform()}|k{int(k)}"


def compress_key(nelems: int, wire_dtype: str = "int8",
                 platform: Optional[str] = None) -> str:
    """Key for the compressed-allreduce quantize-block family. Gradient
    sizes are bucketed to the next power of two (a 900k and a 1M gradient
    share a winner) with a 64-element floor."""
    n = max(64, int(nelems))  # noqa: PTA001 -- nelems is x.size, a host python int at trace time
    bucket = 1 << (n - 1).bit_length()
    return f"compress|{platform or _platform()}|{wire_dtype}|n{bucket}"


# -- lookup (the kernel-call path) -------------------------------------------

def _resolve(key: str) -> Optional[Dict[str, Any]]:
    with _MEMO_LOCK:
        if key in _MEMO:
            return _MEMO[key]
    cfg = store_for(key.split("|", 2)[1]).lookup(key)
    with _MEMO_LOCK:
        _MEMO[key] = cfg
    return cfg


def get_flash_blocks(q_len: int, kv_len: int, head_dim: int, dtype: str,
                     causal: bool, ring: bool = False, bwd: bool = False
                     ) -> Optional[Tuple[int, int]]:
    """The tuned (block_q, block_k) for a flash-attention shape, or None
    when no winner is known (caller applies its heuristic default)."""
    cfg = _resolve(flash_key(q_len, kv_len, head_dim, dtype, causal,
                             ring=ring, bwd=bwd))
    if not cfg:
        return None
    try:
        return int(cfg["block_q"]), int(cfg["block_k"])
    except (KeyError, TypeError, ValueError):
        return None


def get_spec_verify_blocks(k: int, kv_len: int, head_dim: int,
                           dtype: str = "float32"
                           ) -> Optional[Tuple[int, int]]:
    """Tuned (block_q, block_k) for a speculative *verify* step: k+1
    candidate queries attending causally over a full kv row. The shape is
    just a causal flash instance (q = k+1, canonicalised to the same
    16-multiple families `flash_key` uses), so verify reuses the flash
    winner memo instead of growing a new family."""
    return get_flash_blocks(k + 1, kv_len, head_dim, dtype, causal=True)


def get_paged_attn_config(num_heads: int, head_dim: int, page_size: int,
                          dtype: str) -> Optional[Dict[str, Any]]:
    """The tuned config (``{"block_h": ...}``) for a paged
    decode-attention shape, or None when no winner is known (the kernel
    applies its dividing heuristic)."""
    return _resolve(paged_key(num_heads, head_dim, page_size, dtype))


def get_nms_config(k: int) -> Optional[Dict[str, Any]]:
    return _resolve(nms_key(k))


def get_compress_block(nelems: int, wire_dtype: str = "int8"
                       ) -> Optional[int]:
    """The tuned quantize block for a gradient-size family, or None when
    no winner is known (collective.py applies its 256 default)."""
    cfg = _resolve(compress_key(nelems, wire_dtype))
    if not cfg:
        return None
    try:
        return int(cfg["block"])
    except (KeyError, TypeError, ValueError):
        return None


def record_winner(key: str, config: Dict[str, Any],
                  us: Optional[float] = None) -> None:
    """Write a winner to the disk cache and refresh the memo."""
    store_for(key.split("|", 2)[1]).record(key, config, us=us)
    with _MEMO_LOCK:
        _MEMO[key] = dict(config)


# -- active search ------------------------------------------------------------

def autotune_flash(batch_heads: int, q_len: int, kv_len: int,
                   head_dim: int, dtype: str = "float32",
                   causal: bool = False, ring: bool = False,
                   bwd: bool = False, trials: int = 5,
                   interpret: Optional[bool] = None,
                   record: bool = True) -> Dict[str, Any]:
    """Search (block_q, block_k) for one flash-attention shape by timing
    the real kernel, and (by default) persist the winner.

    Returns ``{"block_q", "block_k", "us", "results"}``. Runs the actual
    ``_fa_fwd_with_lse`` program (or, with ``bwd=True``, the
    ``_fa_bwd_with_lse`` recomputation program over residuals produced by
    an untimed forward) — candidate pruning is VMEM-based, the scoring is
    wall clock with median-of-``trials``.
    """
    import jax
    import jax.numpy as jnp
    from ..ops import pallas_attention as fa

    jdt = jnp.dtype(dtype)
    q16, k16 = _ceil16(q_len), _ceil16(kv_len)
    cands = flash_candidates(q_len, kv_len, head_dim,
                             itemsize=jdt.itemsize, require_divides=ring,
                             bwd=bwd)
    kq = jax.random.PRNGKey(0)
    qb = jax.random.normal(kq, (batch_heads, q16, head_dim), jdt)
    kb = jax.random.normal(kq, (batch_heads, k16, head_dim), jdt)
    vb = jax.random.normal(kq, (batch_heads, k16, head_dim), jdt)
    scale = 1.0 / float(head_dim) ** 0.5

    def _padded(bq, bk):
        if q16 % bq or k16 % bk:
            # pad to the candidate's grid exactly like flash_attention()
            qq = jnp.pad(qb, ((0, 0), (0, -(-q16 // bq) * bq - q16),
                              (0, 0)))
            kk = jnp.pad(kb, ((0, 0), (0, -(-k16 // bk) * bk - k16),
                              (0, 0)))
            vv = jnp.pad(vb, ((0, 0), (0, -(-k16 // bk) * bk - k16),
                              (0, 0)))
            return qq, kk, vv
        return qb, kb, vb

    def make_runner(cand):
        bq, bk = cand
        qq, kk, vv = _padded(bq, bk)
        if not bwd:
            fn = jax.jit(lambda a, b, c: fa._fa_fwd_with_lse(
                a, b, c, causal, scale, bq, bk, interpret, kv_len)[0])
            return lambda: fn(qq, kk, vv)
        # backward lane: residuals come from one untimed forward at the
        # same grid; only the dQ/dKV recomputation programs are timed
        out, lse = jax.jit(lambda a, b, c: fa._fa_fwd_with_lse(
            a, b, c, causal, scale, bq, bk, interpret, kv_len))(qq, kk, vv)
        do = jax.random.normal(kq, qq.shape, jdt)
        fn = jax.jit(lambda a, b, c, g, o, l: fa._fa_bwd_with_lse(
            a, b, c, g, o, l, causal, scale, bq, bk, interpret, kv_len))
        return lambda: fn(qq, kk, vv, do, out, lse)

    best, best_t, results = runner.search(cands, make_runner,
                                          trials=trials)
    if best is None:
        raise RuntimeError(
            f"autotune_flash: no candidate built for shape "
            f"(bh={batch_heads}, q={q_len}, kv={kv_len}, d={head_dim}, "
            f"{dtype})")
    cfg = {"block_q": int(best[0]), "block_k": int(best[1])}
    us = best_t * 1e6
    if record:
        record_winner(flash_key(q_len, kv_len, head_dim, dtype, causal,
                                ring=ring, bwd=bwd), cfg, us=us)
    return dict(cfg, us=us, results=results)


def autotune_paged_attn(num_seqs: int, num_heads: int, head_dim: int,
                        page_size: int, pages_per_seq: int = 8,
                        dtype: str = "float32", trials: int = 5,
                        interpret: Optional[bool] = None,
                        record: bool = True) -> Dict[str, Any]:
    """Search ``block_h`` for one paged decode-attention shape by timing
    the real kernel over a synthetic full arena (every sequence owns
    ``pages_per_seq`` disjoint pages, positions at the last row — the
    worst-case page walk), and (by default) persist the winner under
    :func:`paged_key`."""
    import jax
    import jax.numpy as jnp
    from ..ops.paged_attention import paged_attention

    jdt = jnp.dtype(dtype)
    num_pages = num_seqs * pages_per_seq
    kq = jax.random.PRNGKey(0)
    q = jax.random.normal(kq, (num_seqs, num_heads, head_dim), jdt)
    k_arena = jax.random.normal(           # a one-layer arena
        kq, (num_pages + 1, 1, page_size, num_heads, head_dim), jdt)
    v_arena = jax.random.normal(
        jax.random.PRNGKey(1), k_arena.shape, jdt)
    bt = jnp.arange(num_pages, dtype=jnp.int32).reshape(
        num_seqs, pages_per_seq)
    positions = jnp.full((num_seqs,), pages_per_seq * page_size - 1,
                         jnp.int32)
    cands = paged_attn_candidates(num_heads, head_dim, page_size,
                                  itemsize=jdt.itemsize)

    def make_runner(cand):
        bh = int(cand["block_h"])
        fn = jax.jit(lambda qq, kk, vv, b, p: paged_attention(  # noqa: PTA008 -- per-candidate kernels differ (block_h baked in); tuner intentionally compiles each once
            qq, kk, vv, b, p, block_h=bh, interpret=interpret))
        return lambda: fn(q, k_arena, v_arena, bt, positions)

    best, best_t, results = runner.search(cands, make_runner,
                                          trials=trials)
    if best is None:
        raise RuntimeError(
            f"autotune_paged_attn: no candidate built for shape "
            f"(s={num_seqs}, h={num_heads}, d={head_dim}, "
            f"page={page_size}, {dtype})")
    cfg = {"block_h": int(best["block_h"])}
    us = best_t * 1e6
    if record:
        record_winner(paged_key(num_heads, head_dim, page_size, dtype),
                      cfg, us=us)
    return dict(cfg, us=us, results=results)


def autotune_compress(nelems: int, wire_dtype: str = "int8",
                      trials: int = 5, record: bool = True
                      ) -> Dict[str, Any]:
    """Search the quantize block size for one gradient-size family by
    timing the jitted quantize→dequantize roundtrip (the stage whose cost
    the block size controls; the wire bytes per candidate are analytic
    and nearly flat past 64). Persists the winner under
    :func:`compress_key` so ``distributed.collective`` picks it up."""
    import jax
    import jax.numpy as jnp
    from ..distributed.collective import (_block_dequantize_int8,
                                          _block_quantize_int8)

    n = max(64, int(nelems))
    x = jax.random.normal(jax.random.PRNGKey(0), (n,), jnp.float32)
    cands = [c["block"] for c in compress_block_candidates(n)]

    def make_runner(blk):
        pad = -(-n // blk) * blk - n

        def roundtrip(v):
            blocks = jnp.pad(v, (0, pad)).reshape(-1, blk)
            if wire_dtype == "bf16":
                return blocks.astype(jnp.bfloat16).astype(
                    jnp.float32).reshape(-1)[:n]
            q, s = _block_quantize_int8(blocks)
            return _block_dequantize_int8(q, s).reshape(-1)[:n]
        fn = jax.jit(roundtrip)
        return lambda: fn(x)

    best, best_t, results = runner.search(cands, make_runner,
                                          trials=trials)
    if best is None:
        raise RuntimeError(
            f"autotune_compress: no candidate ran for nelems={nelems}")
    cfg = {"block": int(best)}
    us = best_t * 1e6
    if record:
        record_winner(compress_key(nelems, wire_dtype), cfg, us=us)
    return dict(cfg, us=us, results=results)
