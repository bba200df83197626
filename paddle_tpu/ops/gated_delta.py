"""The gated delta rule (Gated DeltaNet, arXiv:2412.06464): linear attention
whose state decays by a data-dependent gate AND is corrected by a rank-one
delta a token. Forward only.

Per value head, with a ``[D_k, D_v]`` float32 state ``S``, a gate ``alpha_t =
exp(g_t)`` in (0, 1) (``g_t <= 0``) and a step size ``beta_t`` in (0, 1), one
token a step:

    S   <- alpha_t S
    r_t  = v_t - S^T k_t            what the state does not yet say of v_t
    S   <- S + beta_t k_t r_t^T
    o_t  = S^T q_t

(:func:`gated_delta_step`: a decode step's update.) Value head ``h`` reads key
head ``h // (H_v / H_k)``. Any scale or norm of ``q`` and ``k`` is the
caller's.

**The chunked form** (:func:`gated_delta_chunked`: a prefill chunk) cuts the
rows into chunks of ``CHUNK`` tokens. It is no masked product as the decayed
rule's is (``ops/linear_attention.py``): the correction ``r_t`` reads the
state that the chunk's earlier tokens wrote. Write ``u_t = beta_t r_t``, so
that ``S_t = alpha_t S_(t-1) + k_t u_t^T``; with ``gam_i = g_1 + .. + g_i``
inside a chunk, ``Gam_ij = exp(gam_i - gam_j)`` for ``i >= j`` and ``S_0`` the
state the chunk starts from, the ``u`` of a chunk solve a unit
lower-triangular system (the WY / UT transform):

    A  = strict_lower(diag(beta) (Gam * K K^T))
    T  = (I + A)^-1 diag(beta)
    W  = T (K * exp(gam)),  U = T V,  U' = U - W S_0
    O  = (Q * exp(gam)) S_0 + (Q K^T * Gam, causal with the diagonal) U'
    S_C = exp(gam_C) S_0 + (K * exp(gam_C - gam))^T U'

``T``, ``W`` and ``U`` do not read the state, so they are made for all the
chunks at once; only the last three lines run chunk after chunk
(``lax.scan``). ``(I + A)^-1`` is made by BLOCKS of ``BLOCK`` rows in exact
float32 arithmetic (:func:`_unit_lower_inverse`): the diagonal blocks by
forward substitution a row at a time (a multiply and a sum), the block rows
under them by products at ``HIGHEST`` against the inverse of the rows above;
no product form of the inverse, whose powers of ``A`` cancel badly. Why: a
row at a time over the whole system is ``CHUNK`` steps that each read the
inverse built so far, 8 MB at a served chunk's shapes (1,024 rows, 32 value
heads: 512 systems of 64 x 64), so 0.54 GB and 1.38 ms a layer, the HBM's
pace for rows of 64 in lanes of 128, and not one MXU operation. By blocks
the row steps run over a sixteenth of that with the systems in the lanes,
all the passes together read and write about 0.06 GB by their shapes, and
the inverse takes 0.21 ms (one TPU v5e, PR 42: the operator 1.70 -> 0.76 ms a
layer, of which 0.56 is what it does beside the inverse; blocks of 8 rows
read 0.77). Only exponents of non-positive differences are ever taken: no
power of a decay is divided by, so a fast head (``gam_C`` of -60 and less)
underflows to the zero it stands for where a form that scales ``k_j`` by
``exp(-gam_j)`` overflows.

``lens`` says how many of a row's ``T`` tokens are real (right padding), as in
``decayed_linear_attention``: padding is given ``g = 0`` and ``beta = 0``, so
it leaves the state as it found it and the state returned is the state after
token ``lens - 1``; the outputs of the padding are junk.

reference: none. The reference codebase has no linear-attention operator; the
parity target is the token-by-token recurrence above
(``benchmark/reference/qwen3next_ref.py:delta_layer``), NOT the chunked form.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

__all__ = ["gated_delta_chunked", "gated_delta_step", "CHUNK"]

#: rows of a chunk: the ``[CHUNK, CHUNK]`` system solved a chunk and head
CHUNK = 64
#: rows of the system's diagonal blocks, which are inverted a row at a time;
#: what lies under them is made by products
BLOCK = CHUNK // 4


def _expand_heads(x, value_heads: int):
    """Key heads ``[B, T, H_k, D]`` as the value heads read them ``[B, T,
    H_v, D]``: value head ``h`` reads key head ``h // (H_v / H_k)``."""
    heads = x.shape[2]
    if value_heads % heads:
        raise ValueError(f"{value_heads} value heads are no multiple of "
                         f"{heads} key heads")
    return x if heads == value_heads else jnp.repeat(
        x, value_heads // heads, axis=2)


def gated_delta_step(q, k, v, g, beta, state):
    """One token a row. ``q k`` ``[B, H_k, D_k]``, ``v`` ``[B, H_v, D_v]``,
    ``g beta`` ``[B, H_v]``, ``state`` ``[B, H_v, D_k, D_v]`` (float32) ->
    ``(o [B, H_v, D_v], state')``. Multiplies and sums in float32 on the
    state's own layout: the state is read and written once."""
    hv = v.shape[1]
    q, k = (_expand_heads(x[:, None], hv)[:, 0].astype(jnp.float32)
            for x in (q, k))
    state = state * jnp.exp(g.astype(jnp.float32))[..., None, None]
    r = v.astype(jnp.float32) - jnp.sum(state * k[..., None], axis=-2)
    state = state + (beta.astype(jnp.float32)[..., None] * k)[..., None] \
        * r[..., None, :]
    return jnp.sum(state * q[..., None], axis=-2).astype(v.dtype), state


def _inverse_by_rows(a):
    """``(I + a)^-1`` of strictly lower-triangular ``a`` ``[..., C, C]``, by
    forward substitution: row ``i`` of the inverse is ``e_i - sum_(j < i)
    a_ij row_j``, an exact float32 multiply and sum over the rows made so
    far. For blocks: the ``C`` steps unroll, and the systems lie along the
    LAST axis while they run, so that a step is one elementwise pass
    however small ``C`` is."""
    c = a.shape[-1]
    lanes = jnp.moveaxis(a.reshape((-1, c, c)), 0, -1)           # [C, C, M]
    eye = jnp.eye(c, dtype=a.dtype)[..., None]
    rows = [jnp.broadcast_to(eye[0], lanes.shape[1:])]
    for i in range(1, c):
        rows.append(eye[i] - jnp.sum(lanes[i, :i, None] * jnp.stack(rows),
                                     axis=0))
    return jnp.moveaxis(jnp.stack(rows), -1, 0).reshape(a.shape)


def _unit_lower_inverse(a):
    """``(I + a)^-1`` of strictly lower-triangular ``a`` ``[..., C, C]`` by
    blocks of ``BLOCK`` rows: the diagonal blocks are inverted a row at a
    time, all of them as one batch (``X_ii``); a block row under the diagonal
    is then ``-X_ii (a_i,<i X_<i,<i)``, two products against the inverse of
    the block rows above it (block forward substitution). ``a`` is padded
    with zero rows and columns to whole blocks, which leaves the leading ``C
    x C`` of the inverse as it is."""
    c = a.shape[-1]
    blocks = -(-c // BLOCK)
    batch = [(0, 0)] * (a.ndim - 2)
    a = jnp.pad(a, batch + [(0, blocks * BLOCK - c)] * 2)
    cuts = [slice(i * BLOCK, (i + 1) * BLOCK) for i in range(blocks)]
    diag = _inverse_by_rows(jnp.stack([a[..., cut, cut] for cut in cuts]))
    dot = functools.partial(jnp.matmul, precision=jax.lax.Precision.HIGHEST)
    inv = diag[0]
    for i in range(1, blocks):
        left = -dot(diag[i], dot(a[..., cuts[i], :i * BLOCK], inv))
        inv = jnp.concatenate([
            jnp.pad(inv, batch + [(0, 0), (0, BLOCK)]),
            jnp.concatenate([left, diag[i]], axis=-1)], axis=-2)
    return inv[..., :c, :c]


def gated_delta_chunked(q, k, v, g, beta, state, lens):
    """``q k`` ``[B, T, H_k, D_k]``, ``v`` ``[B, T, H_v, D_v]``, ``g beta``
    ``[B, T, H_v]`` (``g <= 0``), ``state`` ``[B, H_v, D_k, D_v]`` (float32),
    ``lens`` ``[B]`` int32 -> ``(o [B, T, H_v, D_v], state')``. ``T`` is at
    most ``CHUNK`` or a multiple of it."""
    b, t, hv, dv = v.shape
    c = min(t, CHUNK)
    if t % c:
        raise ValueError(f"{t} rows are neither at most {CHUNK} nor a "
                         f"multiple of it")
    n = t // c
    f32 = jnp.float32
    real = jnp.arange(t)[None] < jnp.clip(lens.astype(jnp.int32), 0,
                                          t)[:, None]            # [B, T]
    # padding neither decays nor writes: the state passes through it
    g = jnp.where(real[..., None], g.astype(f32), 0.0)
    beta = jnp.where(real[..., None], beta.astype(f32), 0.0)

    def cut(x):     # [B, T, H, ...] -> [B, H, N, C, ...]
        x = x.reshape((b, n, c) + x.shape[2:])
        return jnp.moveaxis(x, 3, 1)

    qc, kc = (cut(_expand_heads(x, hv).astype(f32)) for x in (q, k))
    vc, gc, bc = cut(v.astype(f32)), cut(g), cut(beta)   # g, beta [B,H,N,C]
    gam = jnp.cumsum(gc, axis=-1)
    diff = gam[..., :, None] - gam[..., None, :]                 # gam_i-gam_j
    tri = jnp.tril(jnp.ones((c, c), bool))
    decay = jnp.where(tri, jnp.exp(jnp.where(tri, diff, 0.0)), 0.0)
    kk = jnp.einsum("bhnid,bhnjd->bhnij", kc, kc)
    a = jnp.where(jnp.tril(jnp.ones((c, c), bool), -1),
                  bc[..., :, None] * decay * kk, 0.0)
    tmat = _unit_lower_inverse(a) * bc[..., None, :]
    into = jnp.exp(gam)[..., None]                               # exp(gam_i)
    w = jnp.einsum("bhnij,bhnjd->bhnid", tmat, kc * into)
    u = jnp.einsum("bhnij,bhnjd->bhnid", tmat, vc)
    qk = jnp.einsum("bhnid,bhnjd->bhnij", qc, kc) * decay
    q_in = qc * into
    to_end = jnp.exp(gam[..., -1:] - gam)[..., None]             # [.., C, 1]
    k_end = kc * to_end
    whole = jnp.exp(gam[..., -1])                                # [B, H, N]

    def step(s, xs):
        w_n, u_n, qk_n, q_n, k_n, whole_n = xs
        u_n = u_n - jnp.einsum("bhid,bhde->bhie", w_n, s)
        o_n = jnp.einsum("bhid,bhde->bhie", q_n, s) \
            + jnp.einsum("bhij,bhje->bhie", qk_n, u_n)
        s = s * whole_n[..., None, None] \
            + jnp.einsum("bhjd,bhje->bhde", k_n, u_n)
        return s, o_n

    def chunks(x):  # [B, H, N, ...] -> [N, B, H, ...]
        return jnp.moveaxis(x, 2, 0)

    state, o = jax.lax.scan(step, state.astype(f32), tuple(
        chunks(x) for x in (w, u, qk, q_in, k_end, whole)))
    # [N, B, H, C, D_v] -> [B, N, C, H, D_v]
    o = jnp.transpose(o, (1, 0, 3, 2, 4)).reshape(b, t, hv, dv)
    return o.astype(v.dtype), state
