"""Probe: ``paged_attention(latent=...)`` alone on the chip, at the shapes of
``serve-moonlight-longgen`` (48 sequences of 4,096 rows, 9 layers, pages of
64), for the row layouts a latent cache could take:

(a) rows of 576 padded to 640 (whole 128-lane tiles);
(b) a 512-wide arena of latents beside a second of rotary parts, two tokens'
    to a 128-lane row. Only its LOWER BOUND is timed: the walk over the
    512-wide arena alone (no second copy a page, no rotary product);
(c) rows of 576 as they are.

Prints one JSON line a layout: milliseconds a call (one layer's walk), the
bytes the walk reads as held, GB/s, and how far the kernel lies from the
gather oracle on the first sequences. A layout the compiler refuses prints
the refusal.

    python tools/probe_latent_rows.py [--seqs 48] [--rows 4096] [--reps 20]
"""
from __future__ import annotations

import argparse
import json
import sys
import time

import jax
import jax.numpy as jnp
import numpy as np

sys.path.insert(0, ".")
from paddle_tpu.ops.paged_attention import paged_attention  # noqa: E402
from paddle_tpu.serving.llm.paged.moonlight import \
    latent_gather_attention  # noqa: E402

LAYERS, PAGE, HEADS = 9, 64, 16
LAYOUTS = (("a_padded_640", 640, (512, 64)),
           ("b_lower_bound_512_only", 512, (512, 0)),
           ("c_rows_576", 576, (512, 64)))


def probe(name, row, latent, seqs, rows, reps, seed):
    pages_per_seq = rows // PAGE
    pages = seqs * pages_per_seq
    key = jax.random.PRNGKey(seed)
    arena = jax.random.normal(key, (pages + 1, LAYERS, PAGE, row),
                              jnp.float32)
    if row > sum(latent):       # what lies past a row is zeros
        arena = arena.at[..., sum(latent):].set(0.0)
    tables = jnp.asarray(np.random.default_rng(seed).permutation(pages)
                         .reshape(seqs, pages_per_seq), jnp.int32)
    # lengths spread over the last page, so that the masked page is walked
    pos = jnp.asarray(rows - 1 - np.arange(seqs) % PAGE, jnp.int32)
    q = jax.random.normal(jax.random.fold_in(key, 1),
                          (seqs, HEADS, sum(latent)), jnp.float32)
    scale = 192 ** -0.5

    @jax.jit
    def all_layers(q, arena):
        out = 0.0
        for layer in range(LAYERS):
            out = out + paged_attention(q, arena, None, tables, pos,
                                        layer=layer, scale=scale,
                                        latent=latent)
        return out

    all_layers(q, arena).block_until_ready()
    t = time.perf_counter()
    for _ in range(reps):
        out = all_layers(q, arena)
    out.block_until_ready()
    ms = (time.perf_counter() - t) * 1e3 / (reps * LAYERS)
    held = int(jnp.sum(pos + 1)) * row * 4
    few = slice(0, 4)
    want = latent_gather_attention(q[few], arena, tables[few], pos[few], 3,
                                   scale, latent)
    got = paged_attention(q[few], arena, None, tables[few], pos[few],
                          layer=3, scale=scale, latent=latent)
    return {"layout": name, "row": row, "ms_per_layer_call": ms,
            "bytes_read_as_held": held, "GB_per_s": held / ms / 1e6,
            "max_abs_diff_from_gather": float(jnp.max(jnp.abs(got - want)))}


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--seqs", type=int, default=48)
    ap.add_argument("--rows", type=int, default=4096)
    ap.add_argument("--reps", type=int, default=20)
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args()
    print(json.dumps({"device": jax.devices()[0].device_kind}), flush=True)
    for name, row, latent in LAYOUTS:
        try:
            print(json.dumps(probe(name, row, latent, args.seqs, args.rows,
                                   args.reps, args.seed)), flush=True)
        except Exception as e:  # noqa: BLE001 -- a refusal is the reading
            print(json.dumps({"layout": name, "row": row, "refused":
                              f"{type(e).__name__}: {str(e)[:300]}"}),
                  flush=True)


if __name__ == "__main__":
    main()
