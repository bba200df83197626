"""Sparse experts with no dropped tokens: routing, the grouped layout and
the grouped matrix product (Pallas kernel ``moe_experts``).

Routing is the caller's choice of two (``moe_feed_forward(route=...)``):
``"sigmoid"`` (:func:`route_sigmoid_topk`: independent sigmoid scores, chosen
by score plus a stored selection bias, weighted by score alone; the LFM2,
Trinity and Moonlight families) and ``"softmax"``
(:func:`route_softmax_topk`: the ``top_k`` of a softmax over ALL the experts,
renormalised over the chosen; the Qwen3-Next family). Either way every token
gets all ``top_k`` of its experts. The token-expert pairs are
laid out by expert in a row buffer in which each expert's rows start at a
multiple of the row tile ``tm``, so a tile of rows belongs to exactly one
expert and the product needs no mask:

    pairs [T*k] --by expert--> rows [tiles * tm, h]     (gather)
    rows @ W1[e], W3[e] -> silu(a) * b -> @ W2[e]       (one expert a tile)
    rows --back to pair order--> weigh, sum over k      (gather)

The layer is told which experts it holds: ``w1/w3/w2`` carry ``n`` experts
starting at ``expert_lo`` of the model's ``E``. Routing is always over all
``E``; pairs sent to an expert that lives elsewhere get no rows here and add
nothing, so the result is this holder's part of the sum (the parts of all
holders add up to the whole layer).

What the sizes follow. The kernels multiply float32 at ``HIGHEST``, six
bfloat16 passes, so a tile of ``tm`` rows costs 12 x ``tm`` operations for
each weight it streams (4 bytes): on a v5e (197 TFLOP/s over 819 GB/s) that
is ``tm`` x 0.0125 of the time the weights take to arrive. A tile of 128 rows
computes for 1.6 times its stream whether 128 of its rows are real or 20; a
tile of 32 for 0.4 of it. Hence two rules (:func:`window_sizes`):

- a call of at most ``MAX_ROW_TILE`` tokens, or one that holds every expert,
  takes tiles of all its tokens up to 128 (:func:`row_tile`) in a buffer for
  ALL its pairs (:func:`max_tiles`): one tile an expert at decode sizes, its
  weights streamed once;
- a longer call that holds ``n`` of ``E`` experts expects ``T*k/E`` rows an
  expert and ``T*k*n/E`` pairs in all. Its tiles are the smallest of 8 .. 128
  rows with half as much room again as an expert expects, and its row buffer
  is a WINDOW of the tiles twice the expected pairs need. The layout still
  places every held pair (worst case: all ``T*k`` of them, and nothing is
  ever dropped); gather, kernels and combine run over the window, in a
  ``lax.while_loop`` that walks ``ceil(tiles in use / window)`` windows:
  once, unless the routing sends this holder more than twice its share.
  :func:`window_passes` gives that trip count from ``counts``, for the
  engine's ``moe.window_passes`` over ``moe.window_calls``.

The product over that layout is the Pallas kernel ``moe_experts``
(interpreted off the TPU, like the other kernels; it has no backward). Grid
``(row tiles, K tiles)``; the tile's expert rides the scalar-prefetch
channel into the weights' index map, so each visited tile streams its
expert's matrix once, in contiguous ``[tk, N]`` chunks, and tiles past the
last one in use re-address the previous block and do nothing. Gate and up
projections share one call (``moe_experts_up``: two accumulators,
``silu(a) * b`` at the last K tile); the down projection is a second call
(``moe_experts_down``).

reference: none. The reference codebase's expert layer
(python/paddle/incubate/distributed/models/moe) dispatches by capacity over
an all-to-all and drops what overflows; a grouped product over ragged expert
batches is TPU-native here (tools/op_catalog.txt ``# native:``).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax import lax

from ..core.pallas_mode import resolve_interpret

__all__ = ["route_sigmoid_topk", "route_softmax_topk", "group_layout",
           "window_sizes", "window_passes", "moe_experts", "moe_feed_forward"]

ROUTES = ("sigmoid", "softmax")

#: rows of a tile at most (one MXU pass of rows); fewer when fewer tokens
MAX_ROW_TILE = 128
#: K rows of a weight chunk: [256, N] of float32 is 1.8-2 MB, contiguous
K_TILE = 256
#: rows a windowed call's tiles may have (whole sublane tiles)
WINDOW_ROW_TILES = (8, 16, 32, 64, 128)


def route_sigmoid_topk(f, gate_w, expert_bias, top_k: int,
                       norm_topk: bool = True, scale: float = 1.0,
                       eps: float = 1e-6):
    """Sigmoid scores, chosen by score plus bias, weighted by score alone.

    ``f``: ``[T, h]``; ``gate_w``: ``[h, E]``; ``expert_bias``: ``[E]``.
    Returns ``(idx [T, k] int32, weights [T, k])``: the ``top_k`` experts of
    ``sigmoid(f @ gate_w) + expert_bias`` and their scores (without the
    bias), divided by their sum + ``eps`` under ``norm_topk``, times
    ``scale``.
    """
    s = jax.nn.sigmoid(f @ gate_w)
    _, idx = lax.top_k(s + expert_bias, top_k)
    w = jnp.take_along_axis(s, idx, axis=-1)
    if norm_topk:
        w = w / (jnp.sum(w, axis=-1, keepdims=True) + eps)
    return idx.astype(jnp.int32), w * scale


def route_softmax_topk(f, gate_w, top_k: int, norm_topk: bool = True,
                       scale: float = 1.0):
    """The ``top_k`` of a softmax over ALL the experts, in float32.

    ``f``: ``[T, h]``; ``gate_w``: ``[h, E]``. Returns ``(idx [T, k] int32,
    weights [T, k])``: the ``top_k`` experts of ``softmax(f @ gate_w)`` and
    their probabilities, divided by their sum under ``norm_topk`` (no
    epsilon: a chosen probability is never zero), times ``scale``.
    """
    p = jax.nn.softmax((f @ gate_w).astype(jnp.float32), axis=-1)
    w, idx = lax.top_k(p, top_k)
    if norm_topk:
        w = w / jnp.sum(w, axis=-1, keepdims=True)
    return idx.astype(jnp.int32), (w * scale).astype(f.dtype)


def row_tile(num_tokens: int) -> int:
    """Rows of a tile for ``num_tokens`` tokens: all of them (rounded up to
    the sublane tile) up to ``MAX_ROW_TILE``. An expert gets a token at most
    once, so at decode sizes every expert fits one tile and its weights are
    streamed once. (A windowed call sizes its tiles by what an expert
    expects instead: :func:`window_sizes`.)"""
    return min(MAX_ROW_TILE, -(-num_tokens // 8) * 8)


def max_tiles(num_tokens: int, top_k: int, num_local: int, tm: int) -> int:
    """Static bound on the tiles in use when EVERY pair is held: every
    expert's last tile may be part empty, and no expert holds more than
    ``num_tokens`` rows. The whole row buffer of a call that is not windowed,
    and the worst case a windowed call's layout places."""
    return min(num_tokens * top_k // tm + num_local,
               num_local * -(-num_tokens // tm))


def window_sizes(num_tokens: int, top_k: int, num_local: int,
                 num_experts: int):
    """``(tm, window)`` of a call that holds ``num_local`` of ``num_experts``
    experts: the rows of a tile and the tiles of the row buffer, a window
    walked as often as the routing needs. None where the call takes the
    whole buffer of :func:`row_tile` tiles (at most ``MAX_ROW_TILE`` tokens,
    every expert held, or a window no smaller than that buffer).

    ``tm`` is the smallest of ``WINDOW_ROW_TILES`` with room for 1.5 times
    the ``T*k/E`` rows an expert expects: at ``HIGHEST`` a float32 tile
    computes for ``tm`` x 0.0125 of its weights' stream (module docstring),
    so rows a tile does not use are paid for above 80 of them, and an expert
    that spills a small tile costs one more stream. The window holds twice
    the ``T*k*n/E`` pairs the held share expects, and a part-empty last tile
    an expert."""
    pairs = num_tokens * top_k
    if num_tokens <= MAX_ROW_TILE or num_local >= num_experts:
        return None
    tm = next((rows for rows in WINDOW_ROW_TILES
               if 2 * rows * num_experts >= 3 * pairs), MAX_ROW_TILE)
    window = min(2 * pairs * num_local // (num_experts * tm) + num_local,
                 max_tiles(num_tokens, top_k, num_local, tm))
    whole = row_tile(num_tokens)
    if window * tm >= max_tiles(num_tokens, top_k, num_local, whole) * whole:
        return None
    return tm, window


def window_passes(counts, num_tokens: int, top_k: int, num_experts: int):
    """Windows the expert layer walked for ``counts`` ``[n]`` (the pairs each
    held expert received, as :func:`moe_feed_forward` returns them): an int32
    scalar, ``ceil(tiles in use / window)``, the trip count of the call's
    loop. None where a call of these sizes is not windowed."""
    sizes = window_sizes(num_tokens, top_k, counts.shape[0], num_experts)
    if sizes is None:
        return None
    tm, window = sizes
    return _passes(jnp.sum(-(-counts // tm)), window)


def _passes(tiles_in_use, window: int):
    return (-(-tiles_in_use // window)).astype(jnp.int32)


def group_layout(idx, num_local: int, expert_lo: int = 0, tm: int = None,
                 num_experts: int = None):
    """Where each token-expert pair's row lies, by expert, tile-aligned.

    ``idx``: ``[T, k]`` chosen experts (of all ``num_experts``; left out:
    every expert is held). Returns a dict: ``src [R]`` the token whose
    activations fill row ``r`` (0 for rows not in use), ``dest [T, k]`` the
    row of each pair, ``valid [T, k]`` whether the pair's expert is held
    here, ``tile_expert [tiles]`` the local expert of each tile,
    ``n_active [1]`` tiles in use, ``counts [n]`` pairs per held expert, and
    the static ``tm`` and ``window`` (tiles of the row buffer). ``R`` is
    ``window * tm`` unless the call is windowed (:func:`window_sizes`): then
    every held pair still has its row, in :func:`max_tiles` tiles rounded up
    to whole windows, and the buffer holds one window of them at a time."""
    t, k = idx.shape
    sizes = None if tm or num_experts is None else window_sizes(
        t, k, num_local, num_experts)
    if sizes is None:
        tm = tm or row_tile(t)
        tiles = window = max_tiles(t, k, num_local, tm)
    else:
        tm, window = sizes
        tiles = -(-max_tiles(t, k, num_local, tm) // window) * window
    le = idx.reshape(-1) - expert_lo
    valid = (le >= 0) & (le < num_local)
    le = jnp.where(valid, le, num_local)            # elsewhere: a last bin
    onehot = (le[:, None] == jnp.arange(num_local + 1)[None]).astype(jnp.int32)
    rank = jnp.take_along_axis(jnp.cumsum(onehot, axis=0), le[:, None],
                               axis=1)[:, 0] - 1     # place within expert
    counts = jnp.sum(onehot, axis=0)[:num_local]
    tiles_of = -(-counts // tm)
    tile_end = jnp.cumsum(tiles_of)
    row_start = (tile_end - tiles_of) * tm
    dest = jnp.where(valid, row_start[jnp.minimum(le, num_local - 1)] + rank,
                     tiles * tm)                     # out of range: dropped
    src = jnp.zeros((tiles * tm,), jnp.int32).at[dest].set(
        jnp.arange(t * k, dtype=jnp.int32) // k, mode="drop")
    tile_expert = jnp.minimum(
        jnp.searchsorted(tile_end, jnp.arange(tiles), side="right"),
        num_local - 1).astype(jnp.int32)
    return {"src": src, "dest": jnp.minimum(dest, tiles * tm - 1).reshape(t, k),
            "valid": valid.reshape(t, k), "tile_expert": tile_expert,
            "n_active": tile_end[-1:].astype(jnp.int32), "counts": counts,
            "tm": tm, "window": window}


# -- the grouped product -------------------------------------------------------

def _dot(x, w):
    return lax.dot_general(x, w, (((1,), (0,)), ((), ())),
                           precision=lax.Precision.HIGHEST,
                           preferred_element_type=jnp.float32)


def _grouped_kernel(te_ref, na_ref, x_ref, *refs, gated, k_tiles):
    """One K chunk of one row tile: ``acc += x[tm, tk] @ W[e][tk, N]`` for
    one weight stack or, ``gated``, for two whose results are combined as
    ``silu(a) * b`` when the last chunk is in. ``te_ref`` is read by the
    index maps only."""
    import jax.experimental.pallas as pl
    n_w = 2 if gated else 1
    w_refs, o_ref, accs = refs[:n_w], refs[n_w], refs[n_w + 1:]
    kk = pl.program_id(1)

    @pl.when(pl.program_id(0) < na_ref[0])
    def _tile_in_use():
        @pl.when(kk == 0)
        def _init():
            for acc in accs:
                acc[...] = jnp.zeros_like(acc)

        x = x_ref[...]
        for w_ref, acc in zip(w_refs, accs):
            acc[...] += _dot(x, w_ref[...])

        @pl.when(kk == k_tiles - 1)
        def _store():
            out = accs[0][...]
            if gated:
                out = jax.nn.silu(out) * accs[1][...]
            o_ref[...] = out.astype(o_ref.dtype)


def _grouped_call(rows, weights, tile_expert, n_active, tm, gated, name,
                  interpret):
    import jax.experimental.pallas as pl
    from jax.experimental.pallas import tpu as pltpu
    r, k = rows.shape
    n = weights[0].shape[2]
    tk = K_TILE if k % K_TILE == 0 else k
    k_tiles, tiles = k // tk, r // tm

    def _tile(t, na):       # past the last tile in use: stay on it
        return jnp.minimum(t, jnp.maximum(na[0] - 1, 0))

    def _chunk(t, kk, na):
        return jnp.where(t < na[0], kk, k_tiles - 1)

    def _x_map(t, kk, te, na):
        return (_tile(t, na), _chunk(t, kk, na))

    def _w_map(t, kk, te, na):
        return (te[_tile(t, na)], _chunk(t, kk, na), 0)

    def _o_map(t, kk, te, na):
        return (_tile(t, na), 0)

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2, grid=(tiles, k_tiles),
        in_specs=[pl.BlockSpec((tm, tk), _x_map)]
        + [pl.BlockSpec((None, tk, n), _w_map) for _ in weights],
        out_specs=pl.BlockSpec((tm, n), _o_map),
        scratch_shapes=[pltpu.VMEM((tm, n), jnp.float32) for _ in weights])
    return pl.pallas_call(
        functools.partial(_grouped_kernel, gated=gated, k_tiles=k_tiles),
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((r, n), rows.dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary", "arbitrary"),
            vmem_limit_bytes=64 << 20),
        interpret=interpret, name=name,
    )(tile_expert, n_active, rows, *weights)


def moe_experts(rows, w1, w3, w2, layout, interpret=None):
    """``(silu(rows @ W1[e]) * (rows @ W3[e])) @ W2[e]`` with ``e`` the
    expert of each row's tile. ``rows``: ``[tiles * tm, h]`` in
    :func:`group_layout`'s order; ``w1``/``w3``: ``[n, h, f]``; ``w2``:
    ``[n, f, h]``. Rows of tiles not in use come back undefined."""
    interpret = resolve_interpret("moe_experts", interpret)
    te, na, tm = layout["tile_expert"], layout["n_active"], layout["tm"]
    hid = _grouped_call(rows, (w1, w3), te, na, tm, True, "moe_experts_up",
                        interpret)
    return _grouped_call(hid, (w2,), te, na, tm, False, "moe_experts_down",
                         interpret)


def moe_feed_forward(f, gate_w, expert_bias, w1, w3, w2, *, top_k: int,
                     norm_topk: bool = True, scale: float = 1.0,
                     expert_lo: int = 0, interpret=None, eps: float = 1e-6,
                     scope: str = "lfm2", route: str = "sigmoid"):
    """The expert layer on ``f`` ``[T, h]``: ``(out [T, h], counts [n])``,
    ``counts`` the pairs each held expert received. ``route`` names the
    routing (:data:`ROUTES`; ``"softmax"`` has no selection bias and no
    epsilon: ``expert_bias`` is None). ``scope`` prefixes the two
    ``jax.named_scope``s (the calling family's name)."""
    if route not in ROUTES:
        raise ValueError(f"route must be one of {ROUTES}, got {route!r}")
    with jax.named_scope(f"{scope}/moe_route"):
        if route == "sigmoid":
            idx, wts = route_sigmoid_topk(f, gate_w, expert_bias, top_k,
                                          norm_topk, scale, eps)
        else:
            if expert_bias is not None:
                raise ValueError("softmax routing has no selection bias")
            idx, wts = route_softmax_topk(f, gate_w, top_k, norm_topk, scale)
        layout = group_layout(idx, w1.shape[0], expert_lo,
                              num_experts=gate_w.shape[1])
    with jax.named_scope(f"{scope}/moe_experts"):
        if layout["src"].shape[0] > layout["window"] * layout["tm"]:
            out = _walk_windows(f, wts, w1, w3, w2, layout, interpret)
        else:
            y = moe_experts(f[layout["src"]], w1, w3, w2, layout, interpret)
            pairs = y[layout["dest"]]                         # [T, k, h]
            out = jnp.sum(jnp.where(layout["valid"][..., None],
                                    wts[..., None] * pairs, 0.0), axis=1)
    return out, layout["counts"]


def _walk_windows(f, wts, w1, w3, w2, layout, interpret):
    """The held experts' part of the sum, a window of the layout's tiles at
    a time: gather the window's rows, multiply, and add to each token the
    weighted rows of its pairs that lie in this window. One pass unless more
    than ``window`` tiles are in use; none when no pair is held. The pairs
    are added a slot of the ``top_k`` at a time, ``[T, h]`` each: one gather
    of ``[T, k, h]`` pads ``k`` to whole sublane tiles and took 0.5 ms a
    layer longer at ``k = 10`` (PERF.md section 6, PR 41)."""
    tm, window = layout["tm"], layout["window"]
    rows = window * tm
    passes = _passes(layout["n_active"][0], window)

    def one_window(carry):
        p, out = carry
        part = {"tile_expert": lax.dynamic_slice_in_dim(
                    layout["tile_expert"], p * window, window),
                "n_active": jnp.clip(layout["n_active"] - p * window, 0,
                                     window), "tm": tm}
        src = lax.dynamic_slice_in_dim(layout["src"], p * rows, rows)
        y = moe_experts(f[src], w1, w3, w2, part, interpret)
        at = layout["dest"] - p * rows
        here = layout["valid"] & (at >= 0) & (at < rows)
        at = jnp.clip(at, 0, rows - 1)
        for j in range(at.shape[1]):
            out = out + jnp.where(here[:, j, None],
                                  wts[:, j, None] * y[at[:, j]], 0.0)
        return p + 1, out

    return lax.while_loop(lambda carry: carry[0] < passes, one_window,
                          (jnp.int32(0), jnp.zeros_like(f)))[1]
