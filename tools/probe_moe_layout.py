"""Probe: one expert layer alone on the chip at the chunk shapes of the three
cells that hold a share of their experts (``serve-qwen3next-longdoc`` 64 of
512, ``serve-trinity-mixedctx`` 16 of 128, ``serve-moonlight-longgen`` 8 of
64; 1,024 tokens, float32), under seeded routing, for the two row buffers
``ops/moe.py`` can lay the held pairs out in:

``whole``   the buffer for ALL the call's pairs in tiles of 128 rows
            (``group_layout`` without ``num_experts``: every call before
            PR 41, and still every tick and every holder of all experts);
``window``  tiles sized for what a held expert expects, in a window of the
            tiles twice the held share's expected pairs need
            (``window_sizes``), one pass.

Prints one JSON line a (shape, layout): milliseconds of the gather into the
buffer, of ``moe_experts_up``, of ``moe_experts_down`` and of the combine in
pair order (``y[dest]`` as one ``[T, k, h]`` gather, pairs elsewhere masked:
the whole buffer's; and a slot of the ``top_k`` at a time, ``[T, h]`` each:
the window's) and in row order (weights carried a row, summed into ``[T, h]``
by ``src``), each timed alone (a dispatch of its own: about 0.2 ms of floor
under every piece); then the whole layer as the program runs it (routing,
layout and the ``while`` included) beside the same layer over the whole
buffer, and how far the two results lie apart. ``--tiles`` times the two
kernels over the first so many tiles of the window's layout (what the spare
tiles' grid steps cost);
``--skew`` adds to the held experts' scores (about N(0, 1)), so that the held
share grows and the window is walked more than once; ``--toy`` divides the
widths by 16, for a rehearsal off the chip.

    python tools/probe_moe_layout.py [--reps 20] [--seed 0] [--skew 0.0]
        [--tiles 72,96,144] [--shapes qwen3next,trinity,moonlight] [--toy]
"""
from __future__ import annotations

import argparse
import json
import sys
import time

import jax
import jax.numpy as jnp

sys.path.insert(0, ".")
from paddle_tpu.core.pallas_mode import resolve_interpret  # noqa: E402
from paddle_tpu.ops import moe  # noqa: E402

TOKENS, HIDDEN = 1024, 2048
#: name: (expert width, held, of, top_k, route)
SHAPES = {"qwen3next": (512, 64, 512, 10, "softmax"),
          "trinity": (1024, 16, 128, 8, "sigmoid"),
          "moonlight": (1408, 8, 64, 6, "sigmoid")}


def timed(fn, *args, reps):
    """Milliseconds a call of the jitted ``fn`` (dispatched ``reps`` times,
    waited for once) and its last result."""
    out = fn(*args)
    jax.block_until_ready(out)
    t = time.perf_counter()
    for _ in range(reps):
        out = fn(*args)
    jax.block_until_ready(out)
    return (time.perf_counter() - t) * 1e3 / reps, out


def pieces(f, wts, w1, w3, w2, layout, tiles, reps):
    """The four steps over the first ``tiles`` tiles of ``layout``, each
    alone: ``{step: ms}``."""
    tm = layout["tm"]
    rows = tiles * tm
    src, te = layout["src"][:rows], layout["tile_expert"][:tiles]
    na = jnp.minimum(layout["n_active"], tiles)
    dest, valid = layout["dest"], layout["valid"]
    interpret = resolve_interpret("moe_experts", None)   # off the chip

    gather = jax.jit(lambda f, src: f[src])
    up = jax.jit(lambda x, w1, w3, te, na: moe._grouped_call(
        x, (w1, w3), te, na, tm, True, "moe_experts_up", interpret))
    down = jax.jit(lambda x, w2, te, na: moe._grouped_call(
        x, (w2,), te, na, tm, False, "moe_experts_down", interpret))

    @jax.jit
    def by_pair(y, dest, valid, wts):
        here = valid & (dest < rows)
        return jnp.sum(jnp.where(here[..., None], wts[..., None]
                                 * y[jnp.minimum(dest, rows - 1)], 0.0), 1)

    @jax.jit
    def by_slot(y, dest, valid, wts):
        here = valid & (dest < rows)
        at = jnp.minimum(dest, rows - 1)
        return sum(jnp.where(here[:, j, None], wts[:, j, None] * y[at[:, j]],
                             0.0) for j in range(dest.shape[1]))

    @jax.jit
    def by_row(y, src, dest, valid, wts, na):
        # a row's weight: its pair's, 0 for a row no pair has
        w_row = jnp.zeros((rows,), y.dtype).at[
            jnp.where(valid, dest, rows).reshape(-1)].set(
                wts.reshape(-1), mode="drop")
        used = (jnp.arange(rows) < na[0] * tm)[:, None]
        return jnp.zeros((TOKENS, y.shape[1]), y.dtype).at[src].add(
            jnp.where(used, w_row[:, None] * y, 0.0))

    out = {}
    out["gather"], x = timed(gather, f, src, reps=reps)
    out["up"], hid = timed(up, x, w1, w3, te, na, reps=reps)
    out["down"], y = timed(down, hid, w2, te, na, reps=reps)
    out["combine_by_pair"], a = timed(by_pair, y, dest, valid, wts, reps=reps)
    out["combine_by_slot"], b = timed(by_slot, y, dest, valid, wts, reps=reps)
    out["combine_by_row"], c = timed(by_row, y, src, dest, valid, wts, na,
                                     reps=reps)
    out["combines_apart"] = float(jnp.max(jnp.abs(jnp.stack([b, c]) - a)))
    return out


def probe(name, reps, seed, skew, tile_counts, hidden=HIDDEN, shrink=1):
    width, held, of, top_k, route = SHAPES[name]
    width //= shrink
    lo = held                           # the second holder's share
    key = jax.random.PRNGKey(seed)
    ks = jax.random.split(key, 6)
    f = jax.random.normal(ks[0], (TOKENS, hidden), jnp.float32)
    gate = jax.random.normal(ks[1], (hidden, of), jnp.float32) * hidden ** -.5
    # one constant feature: its row of the gate moves an expert's score for
    # every token alike (scores are about N(0, 1))
    f = f.at[:, 0].set(1.0)
    gate = gate.at[0, lo:lo + held].add(skew)
    bias = None if route == "softmax" else jnp.zeros((of,), jnp.float32)
    w1, w3 = (jax.random.normal(k, (held, hidden, width), jnp.float32)
              * hidden ** -.5 for k in ks[2:4])
    w2 = jax.random.normal(ks[4], (held, width, hidden),
                           jnp.float32) * width ** -.5

    def routed(f, gate):
        if route == "softmax":
            return moe.route_softmax_topk(f, gate, top_k)
        return moe.route_sigmoid_topk(f, gate, bias, top_k)

    idx, wts = routed(f, gate)
    layouts = {"whole": moe.group_layout(idx, held, lo),
               "window": moe.group_layout(idx, held, lo, num_experts=of)}
    kw = dict(top_k=top_k, expert_lo=lo, route=route, scope=name)

    @jax.jit
    def layer(f, gate, w1, w3, w2):     # as the program runs it
        return moe.moe_feed_forward(f, gate, bias, w1, w3, w2, **kw)

    @jax.jit
    def layer_whole(f, gate, w1, w3, w2):   # the same over the whole buffer
        idx, wts = routed(f, gate)
        lay = moe.group_layout(idx, held, lo)
        y = moe.moe_experts(f[lay["src"]], w1, w3, w2, lay)
        return jnp.sum(jnp.where(lay["valid"][..., None], wts[..., None]
                                 * y[lay["dest"]], 0.0), axis=1)

    ms_layer, (got, counts) = timed(layer, f, gate, w1, w3, w2, reps=reps)
    ms_whole, want = timed(layer_whole, f, gate, w1, w3, w2, reps=reps)
    passes = moe.window_passes(counts, TOKENS, top_k, of)
    head = {"shape": name, "held": f"{held} of {of}", "top_k": top_k,
            "pairs_held": int(counts.sum()),
            "pairs_expected": TOKENS * top_k * held // of,
            "rows_of_fullest_expert": int(counts.max())}
    lines = []
    for kind, lay in layouts.items():
        tm, window = lay["tm"], lay["window"]
        for tiles in ([window] if kind == "whole" else
                      [window] + [t for t in tile_counts if t < window]):
            if tiles < int(lay["n_active"][0]):
                continue                # the tiles in use do not fit
            lines.append(dict(
                head, layout=kind, tm=tm, tiles=tiles, rows=tiles * tm,
                tiles_in_use=int(lay["n_active"][0]),
                ms=pieces(f, wts, w1, w3, w2, lay, tiles, reps)))
    lines.append(dict(
        head, layout="layer", passes=None if passes is None else int(passes),
        ms_layer_as_run=ms_layer, ms_layer_whole_buffer=ms_whole,
        layers_apart=float(jnp.max(jnp.abs(got - want)))))
    return lines


def main(argv=None):
    ap = argparse.ArgumentParser()
    ap.add_argument("--reps", type=int, default=20)
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--skew", type=float, default=0.0)
    ap.add_argument("--tiles", default="")
    ap.add_argument("--shapes", default=",".join(SHAPES))
    ap.add_argument("--toy", action="store_true")
    args = ap.parse_args(argv)
    tile_counts = [int(t) for t in args.tiles.split(",") if t]
    shrink = 16 if args.toy else 1
    dev = jax.devices()[0]
    print(json.dumps({"device": dev.platform, "device_kind": dev.device_kind,
                      "tokens": TOKENS, "hidden": HIDDEN // shrink,
                      "reps": args.reps,
                      "seed": args.seed, "skew": args.skew}), flush=True)
    for name in args.shapes.split(","):
        for line in probe(name, args.reps, args.seed, args.skew, tile_counts,
                          HIDDEN // shrink, shrink):
            print(json.dumps(line), flush=True)


if __name__ == "__main__":
    main()
