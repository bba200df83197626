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
           "moe_experts", "moe_feed_forward"]

ROUTES = ("sigmoid", "softmax")

#: rows of a tile at most (one MXU pass of rows); fewer when fewer tokens
MAX_ROW_TILE = 128
#: K rows of a weight chunk: [256, N] of float32 is 1.8-2 MB, contiguous
K_TILE = 256


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
    streamed once."""
    return min(MAX_ROW_TILE, -(-num_tokens // 8) * 8)


def max_tiles(num_tokens: int, top_k: int, num_local: int, tm: int) -> int:
    """Static bound on the tiles in use: every expert's last tile may be
    part empty, and no expert holds more than ``num_tokens`` rows."""
    return min(num_tokens * top_k // tm + num_local,
               num_local * -(-num_tokens // tm))


def group_layout(idx, num_local: int, expert_lo: int = 0, tm: int = None):
    """Where each token-expert pair's row lies, by expert, tile-aligned.

    ``idx``: ``[T, k]`` chosen experts (of all ``E``). Returns a dict:
    ``src [R]`` the token whose activations fill row ``r`` (0 for rows not
    in use), ``dest [T, k]`` the row of each pair, ``valid [T, k]`` whether
    the pair's expert is held here, ``tile_expert [tiles]`` the local expert
    of each tile, ``n_active [1]`` tiles in use, ``counts [n]`` pairs per
    held expert, and the static ``tm``."""
    t, k = idx.shape
    tm = tm or row_tile(t)
    tiles = max_tiles(t, k, num_local, tm)
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
            "tm": tm}


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
        layout = group_layout(idx, w1.shape[0], expert_lo)
    with jax.named_scope(f"{scope}/moe_experts"):
        y = moe_experts(f[layout["src"]], w1, w3, w2, layout, interpret)
        pairs = y[layout["dest"]]                             # [T, k, h]
        out = jnp.sum(jnp.where(layout["valid"][..., None],
                                wts[..., None] * pairs, 0.0), axis=1)
    return out, layout["counts"]
