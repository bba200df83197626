#!/usr/bin/env python
"""Pre-populate the Pallas autotuner winner cache for the bench shapes.

Searches block/grid configurations for the flash-attention family
(``paddle_tpu.ops.pallas_attention``, ring-flash chunks) and the
greedy-NMS kernel (``ops/custom.py``) by timing the real kernels, and
writes the winners into the on-disk cache (``PADDLE_TPU_TUNE_CACHE``;
unset, winners reach later processes only through ``--emit-defaults``)
that every kernel call consults — run it once per platform/fleet and the
searched configs are free forever after.

    python tools/autotune.py                 # tune this platform's lane
    python tools/autotune.py --quick         # small shapes (CPU/CI lane)
    python tools/autotune.py --trials 9      # steadier medians
    python tools/autotune.py --emit-defaults # refresh the committed table

On TPU the shape list is the bench-model lane (GPT-small S=4096 in bf16
and f32, the S=8192 headroom shape, the Tl=512 ring chunk, NMS k=128).
Off-TPU Pallas runs in interpret mode, so the default lane shrinks to
``--quick`` shapes automatically — interpret-mode timings still order
candidates by memory traffic, which is what the committed CPU entries
capture.

``--emit-defaults`` rewrites ``paddle_tpu/tuner/default_winners.json``:
existing curated entries (and their notes) are preserved; winners tuned
in this run are merged in, so the table accretes per-platform coverage
instead of being clobbered.
"""
from __future__ import annotations

import argparse
import json
import os
import sys
import time

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if REPO not in sys.path:
    sys.path.insert(0, REPO)

#: (q_len, kv_len, head_dim, dtype, causal, ring) — the bench lane
BENCH_FLASH_SHAPES = [
    (4096, 4096, 64, "bfloat16", True, False),   # GPT-small S=4096, amp
    (4096, 4096, 64, "float32", True, False),    # same, no autocast
    (8192, 8192, 64, "bfloat16", True, False),   # long-context headroom
    (512, 512, 64, "bfloat16", False, True),     # ring chunk Tl=512
]
BENCH_NMS_KS = [128]

#: backward-kernel block family (``--only flash-bwd``): the dQ/dKdV
#: recomputation grids are tuned independently of the forward — same
#: candidate space, different arithmetic intensity (5 matmuls vs 2)
BENCH_FLASH_BWD_SHAPES = [
    (4096, 4096, 64, "bfloat16", True, False),   # GPT-small S=4096, amp
    (4096, 4096, 64, "float32", True, False),    # same, no autocast
    (512, 512, 64, "bfloat16", False, True),     # ring chunk Tl=512
]

#: (num_seqs, num_heads, head_dim, page_size, dtype) — paged decode
#: attention (``ops/paged_attention.py``); the family key only uses
#: (heads, head_dim, page, dtype), num_seqs just sizes the search grid
BENCH_PAGED_SHAPES = [
    (48, 12, 64, 16, "bfloat16"),   # GPT-small paged serving lane, amp
    (48, 12, 64, 16, "float32"),    # same, no autocast
]

#: (nelems, wire_dtype) — gradient-size families for the compressed
#: allreduce quantize stage (pow2-bucketed by compress_key, so one entry
#: covers the whole bucket)
BENCH_COMPRESS_SIZES = [(1 << 20, "int8"), (1 << 24, "int8"),
                        (1 << 20, "bf16")]

#: small enough for interpret-mode Pallas (CPU/CI): seconds, not hours
QUICK_FLASH_SHAPES = [
    (128, 128, 32, "float32", True, False),
    (64, 64, 32, "float32", False, True),
]
QUICK_FLASH_BWD_SHAPES = [
    (128, 128, 32, "float32", True, False),
    (64, 64, 32, "float32", False, True),
]
QUICK_PAGED_SHAPES = [
    (4, 4, 8, 8, "float32"),        # tiny CI model geometry
]
QUICK_NMS_KS = [64]
QUICK_COMPRESS_SIZES = [(1 << 16, "int8")]


def tune_flash_lane(shapes, trials, batch_heads, bwd=False):
    from paddle_tpu import tuner

    results = {}
    for q, kv, d, dtype, causal, ring in shapes:
        key = tuner.flash_key(q, kv, d, dtype, causal, ring=ring, bwd=bwd)
        t0 = time.time()
        win = tuner.autotune_flash(batch_heads, q, kv, d, dtype=dtype,
                                   causal=causal, ring=ring, bwd=bwd,
                                   trials=trials)
        print(f"flash{'-bwd' if bwd else ''} {key}: "
              f"block_q={win['block_q']} "
              f"block_k={win['block_k']} ({win['us']:.0f}us, "
              f"{len(win['results'])} candidates, "
              f"{time.time() - t0:.1f}s search)")
        results[key] = {"block_q": win["block_q"],
                        "block_k": win["block_k"], "us": win["us"]}
    return results


def tune_nms_lane(ks, trials):
    import jax
    import jax.numpy as jnp
    from paddle_tpu import tuner
    from paddle_tpu.ops import custom as _custom
    from paddle_tpu.tuner import runner as _runner

    results = {}
    for k in ks:
        key = tuner.nms_key(k)
        rng = jax.random.PRNGKey(0)
        iou = jax.random.uniform(rng, (k, k), jnp.float32)
        iou = (iou + iou.T) / 2.0
        valid = jnp.ones((k,), jnp.int32)
        thr = jnp.asarray([0.5], jnp.float32)

        def make_runner(cand):
            # noqa-rationale: every candidate IS a distinct function
            # (unroll is baked into the kernel); the tuner times fresh
            # compiles on purpose and never reuses these traces.
            fn = jax.jit(lambda a, b, c, u=int(cand["unroll"]):  # noqa: PTA008 -- per-candidate kernels differ; tuner intentionally compiles each once
                         _custom.pallas_greedy_nms(a, b, c, unroll=u))
            return lambda: fn(iou, valid, thr)

        best, best_t, _ = _runner.search(tuner.nms_candidates(k),
                                         make_runner, trials=trials)
        if best is None:
            print(f"nms {key}: no candidate built, skipped")
            continue
        cfg = {"unroll": int(best["unroll"])}
        tuner.record_winner(key, cfg, us=best_t * 1e6)
        print(f"nms {key}: unroll={cfg['unroll']} ({best_t * 1e6:.0f}us)")
        results[key] = cfg
    return results


def tune_paged_lane(shapes, trials):
    from paddle_tpu import tuner

    results = {}
    for num_seqs, heads, d, page, dtype in shapes:
        key = tuner.paged_key(heads, d, page, dtype)
        win = tuner.autotune_paged_attn(num_seqs, heads, d, page,
                                        dtype=dtype, trials=trials)
        print(f"paged {key}: block_h={win['block_h']} "
              f"({win['us']:.0f}us, {len(win['results'])} candidates)")
        results[key] = {"block_h": win["block_h"]}
    return results


def tune_compress_lane(sizes, trials):
    from paddle_tpu import tuner

    results = {}
    for nelems, wire_dtype in sizes:
        key = tuner.compress_key(nelems, wire_dtype)
        win = tuner.autotune_compress(nelems, wire_dtype, trials=trials)
        print(f"compress {key}: block={win['block']} "
              f"({win['us']:.0f}us, {len(win['results'])} candidates)")
        results[key] = {"block": win["block"]}
    return results


def emit_defaults(tuned, path):
    """Merge this run's winners into the committed defaults table,
    preserving curated entries and notes for keys not retuned. A lane
    that reports its winner's time (``us``, beside the config's own
    keys) has it written into the entry."""
    try:
        with open(path) as f:
            table = json.load(f)
        entries = table.get("entries", {})
    except (OSError, ValueError):
        entries = {}
    for key, cfg in sorted(tuned.items()):
        prev = entries.get(key, {})
        cfg = dict(cfg)
        us = cfg.pop("us", None)
        entry = {"config": cfg}
        if us is not None:
            entry["us"] = round(float(us), 1)
        if "note" in prev:
            entry["note"] = prev["note"]
        entries[key] = entry
    payload = {"version": 1, "platform": "defaults",
               "entries": dict(sorted(entries.items()))}
    with open(path, "w") as f:
        json.dump(payload, f, indent=1)
        f.write("\n")
    print(f"defaults table updated: {path} ({len(entries)} entries)")


def main(argv=None):
    ap = argparse.ArgumentParser(
        description="pre-populate the Pallas autotuner winner cache")
    ap.add_argument("--quick", action="store_true",
                    help="small interpret-friendly shapes (CPU/CI lane); "
                         "automatic off-TPU")
    ap.add_argument("--full", action="store_true",
                    help="force the bench lane even off-TPU (interpret "
                         "mode: very slow)")
    ap.add_argument("--trials", type=int, default=5,
                    help="timed trials per candidate, median scored "
                         "(default %(default)s)")
    ap.add_argument("--batch-heads", type=int, default=8,
                    help="leading batch*heads dim for flash search "
                         "arrays (default %(default)s)")
    ap.add_argument("--only",
                    choices=["flash", "flash-bwd", "paged", "nms",
                             "compress"],
                    help="restrict to one kernel family")
    ap.add_argument("--emit-defaults", nargs="?", metavar="PATH",
                    const=os.path.join(REPO, "paddle_tpu", "tuner",
                                       "default_winners.json"),
                    help="merge this run's winners into the committed "
                         "default-winners table (default: the package "
                         "file)")
    args = ap.parse_args(argv)

    import jax
    platform = jax.devices()[0].platform
    on_tpu = platform == "tpu"
    quick = args.quick or (not on_tpu and not args.full)
    flash_shapes = QUICK_FLASH_SHAPES if quick else BENCH_FLASH_SHAPES
    flash_bwd_shapes = (QUICK_FLASH_BWD_SHAPES if quick
                        else BENCH_FLASH_BWD_SHAPES)
    paged_shapes = QUICK_PAGED_SHAPES if quick else BENCH_PAGED_SHAPES
    nms_ks = QUICK_NMS_KS if quick else BENCH_NMS_KS
    compress_sizes = (QUICK_COMPRESS_SIZES if quick
                      else BENCH_COMPRESS_SIZES)

    from paddle_tpu.tuner import cache_dir
    print(f"autotune: platform={platform} "
          f"lane={'quick' if quick else 'bench'} "
          f"trials={args.trials} cache={cache_dir()}")

    tuned = {}
    if args.only in (None, "flash"):
        tuned.update(tune_flash_lane(flash_shapes, args.trials,
                                     args.batch_heads))
    if args.only in (None, "flash-bwd"):
        tuned.update(tune_flash_lane(flash_bwd_shapes, args.trials,
                                     args.batch_heads, bwd=True))
    if args.only in (None, "paged"):
        tuned.update(tune_paged_lane(paged_shapes, args.trials))
    if args.only in (None, "nms"):
        tuned.update(tune_nms_lane(nms_ks, args.trials))
    if args.only in (None, "compress"):
        tuned.update(tune_compress_lane(compress_sizes, args.trials))

    if args.emit_defaults:
        emit_defaults(tuned, args.emit_defaults)
    print(f"done: {len(tuned)} winner(s) recorded")
    return 0


if __name__ == "__main__":
    sys.exit(main())
