"""The gated delta rule (``ops/gated_delta.py``) against the token-by-token
recurrence it stands for, at toy sizes on the CPU: the chunked form for whole
and ragged ``lens``, a state carried over two calls, the one-token step, and
decays that would overflow a form dividing by them.

Tolerances. The chunked form is the same sum in another order (a triangular
system a chunk in place of a correction a token): outputs of spread 0.1 and
states of spread one agree with a float32 recurrence to 2e-5 absolute; a
float64 recurrence lies as far from either.
"""
import re

import numpy as np
import pytest

import jax
import jax.numpy as jnp

import paddle_tpu  # noqa: F401 -- sets the package's matmul precision
from paddle_tpu.ops.gated_delta import (BLOCK, CHUNK, _unit_lower_inverse,
                                        gated_delta_chunked, gated_delta_step)

ATOL = 2e-5


def recurrence(q, k, v, g, beta, state, lens, dtype=np.float64):
    """The rule a token a step, in ``dtype``; rows at or past ``lens`` leave
    the state alone."""
    q, k, v, g, beta, state = (np.asarray(x, dtype)
                               for x in (q, k, v, g, beta, state))
    rep = v.shape[2] // q.shape[2]
    q, k = np.repeat(q, rep, 2), np.repeat(k, rep, 2)
    state, out = state.copy(), np.zeros(v.shape, dtype)
    for b in range(v.shape[0]):
        for t in range(v.shape[1]):
            s = state[b] * np.exp(g[b, t])[:, None, None]
            r = v[b, t] - np.einsum("hkv,hk->hv", s, k[b, t])
            s = s + beta[b, t][:, None, None] * k[b, t][:, :, None] \
                * r[:, None, :]
            out[b, t] = np.einsum("hkv,hk->hv", s, q[b, t])
            if t < lens[b]:
                state[b] = s
    return out, state


def inputs(seed, b, t, hk=2, hv=4, d=16, fastest=16.0):
    rng = np.random.default_rng(seed)
    q = rng.standard_normal((b, t, hk, d))
    k = rng.standard_normal((b, t, hk, d))
    q = q / np.linalg.norm(q, axis=-1, keepdims=True) / d ** 0.5
    k = k / np.linalg.norm(k, axis=-1, keepdims=True)
    v = rng.standard_normal((b, t, hv, d))
    rate = rng.uniform(0.0, fastest, (hv,))
    g = -rate * np.log1p(np.exp(rng.standard_normal((b, t, hv)) + 1.0))
    beta = 1.0 / (1.0 + np.exp(-rng.standard_normal((b, t, hv))))
    state = rng.standard_normal((b, hv, d, d))
    return tuple(jnp.asarray(x, jnp.float32)
                 for x in (q, k, v, g, beta, state))


@pytest.mark.parametrize("t,lens", [
    (CHUNK, [CHUNK, CHUNK]), (3 * CHUNK, [3 * CHUNK, 3 * CHUNK]),
    (3 * CHUNK, [2 * CHUNK + 5, 1]), (2 * CHUNK, [CHUNK, 0]),
    (24, [24, 7]), (1, [1, 1])])
def test_chunked_matches_the_recurrence(t, lens):
    args = inputs(t + lens[1], 2, t)
    lens = np.asarray(lens)
    got, state = gated_delta_chunked(*args, jnp.asarray(lens, jnp.int32))
    want, want_state = recurrence(*args, lens)
    real = (np.arange(t)[None] < lens[:, None])[..., None, None]
    assert np.abs(want[np.broadcast_to(real, want.shape)]).std() > 0.02
    np.testing.assert_allclose(np.where(real, got, 0),
                               np.where(real, want, 0), atol=ATOL, rtol=0)
    np.testing.assert_allclose(state, want_state, atol=ATOL, rtol=0)


def test_a_state_carried_over_two_calls_is_the_state_of_one():
    """A prompt's chunks: the second call starts from what the first left,
    the first ragged (its padding must leave the state alone)."""
    q, k, v, g, beta, state = inputs(3, 1, 3 * CHUNK)
    want, want_state = recurrence(q, k, v, g, beta, state, [2 * CHUNK + 9])
    cut, rest = CHUNK + 9, CHUNK        # the first call: CHUNK + 9 of 2 CHUNK
    first = [x[:, :2 * CHUNK] for x in (q, k, v, g, beta)]
    o1, mid = gated_delta_chunked(*first, state, jnp.asarray([cut]))
    second = [x[:, cut:cut + rest] for x in (q, k, v, g, beta)]
    o2, end = gated_delta_chunked(*second, mid, jnp.asarray([rest]))
    np.testing.assert_allclose(o1[:, :cut], want[:, :cut], atol=ATOL, rtol=0)
    np.testing.assert_allclose(o2, want[:, cut:cut + rest], atol=ATOL, rtol=0)
    np.testing.assert_allclose(end, want_state, atol=ATOL, rtol=0)


def test_one_token_is_one_step_of_the_recurrence():
    q, k, v, g, beta, state = inputs(5, 3, 1)
    want, want_state = recurrence(q, k, v, g, beta, state, [1, 1, 1])
    got, new = gated_delta_step(q[:, 0], k[:, 0], v[:, 0], g[:, 0],
                                beta[:, 0], state)
    np.testing.assert_allclose(got, want[:, 0], atol=1e-6, rtol=0)
    np.testing.assert_allclose(new, want_state, atol=1e-6, rtol=0)
    # g = 0 and beta = 0 leave a state EXACTLY as it was (a frozen slot)
    _, same = gated_delta_step(q[:, 0], k[:, 0], v[:, 0],
                               jnp.zeros_like(g[:, 0]),
                               jnp.zeros_like(beta[:, 0]), state)
    assert (np.asarray(same) == np.asarray(state)).all()


def test_decays_that_a_division_would_overflow_stay_finite():
    """Heads that forget ``exp(-40)`` a token: over a chunk the decay is
    ``exp(-2560)``, which underflows to the 0 it stands for; a form that
    scales ``k_j`` by ``exp(-gam_j)`` divides by it."""
    q, k, v, g, beta, state = inputs(9, 1, 2 * CHUNK)
    g = jnp.full_like(g, -40.0).at[..., 0].set(-1e-4)   # one head remembers
    got, new = gated_delta_chunked(q, k, v, g, beta, state,
                                   jnp.asarray([2 * CHUNK]))
    want, want_state = recurrence(q, k, v, g, beta, state, [2 * CHUNK])
    assert np.isfinite(got).all() and np.isfinite(new).all()
    assert float(np.exp(np.float32(-40.0 * CHUNK))) == 0.0
    np.testing.assert_allclose(got, want, atol=ATOL, rtol=0)
    np.testing.assert_allclose(new, want_state, atol=ATOL, rtol=0)


def test_value_heads_read_their_key_head_and_bad_shapes_are_refused():
    q, k, v, g, beta, state = inputs(11, 1, 8, hk=2, hv=4)
    got, _ = gated_delta_chunked(q, k, v, g, beta, state, jnp.asarray([8]))
    # with every value head given its key head's rows outright: the same
    wide, _ = gated_delta_chunked(jnp.repeat(q, 2, 2), jnp.repeat(k, 2, 2), v,
                                  g, beta, state, jnp.asarray([8]))
    np.testing.assert_array_equal(got, wide)
    with pytest.raises(ValueError, match="multiple"):
        gated_delta_chunked(*inputs(1, 1, CHUNK + 8)[:5],
                            jnp.zeros((1, 4, 16, 16)), jnp.asarray([8]))
    with pytest.raises(ValueError, match="key heads"):
        gated_delta_chunked(q[:, :, :1].repeat(3, 2), k[:, :, :1].repeat(3, 2),
                            v, g, beta, state, jnp.asarray([8]))


def inverse_a_row_at_a_time(a):
    """What ``gated_delta_chunked`` ran before it solved by blocks: ``C``
    steps, each a multiply and a sum over the WHOLE inverse built so far. The
    oracle of the block solve, and the loop the lowered program must not
    hold."""
    c = a.shape[-1]

    def row(i, inv):
        a_i = jax.lax.dynamic_index_in_dim(a, i, axis=-2, keepdims=False)
        new = -jnp.sum(a_i[..., :, None] * inv, axis=-2)
        new = new + (jnp.arange(c) == i).astype(a.dtype)
        return jax.lax.dynamic_update_index_in_dim(inv, new, i, axis=-2)

    return jax.lax.fori_loop(0, c, row, jnp.zeros_like(a))


def systems(seed, c, g_token, lead=(2, 3), d=128):
    """``A`` as the operator builds it (module docstring), float64, at the
    cell's size of entry: keys of norm 1 that lean on one direction (entries
    up to 0.9, where independent keys of 128 give 0.1), ``beta`` near 1,
    ``g = g_token`` a token."""
    rng = np.random.default_rng(seed)
    k = rng.standard_normal(lead + (c, d))
    k = k / np.linalg.norm(k, axis=-1, keepdims=True)
    k = 0.6 * k + 0.4 * k[..., :1, :] * rng.choice([-1.0, 1.0], lead + (c, 1))
    k = k / np.linalg.norm(k, axis=-1, keepdims=True)
    beta = 1.0 / (1.0 + np.exp(-rng.standard_normal(lead + (c,)) - 3.0))
    gam = np.cumsum(np.full(lead + (c,), g_token), axis=-1)
    below = np.tril(np.ones((c, c), bool), -1)
    decay = np.exp(np.where(below, gam[..., :, None] - gam[..., None, :], 0.0))
    return np.where(below, beta[..., :, None] * decay
                    * (k @ np.swapaxes(k, -1, -2)), 0.0)


@pytest.mark.parametrize("g_token", [0.0, -0.05, -40.0])
@pytest.mark.parametrize("c", [1, 5, BLOCK, BLOCK + 1, 3 * BLOCK, CHUNK])
def test_the_block_solve_is_the_inverse_and_agrees_with_the_row_loop(
        c, g_token):
    """``g = 0`` keeps every entry of ``A`` (up to 0.6 and of either sign);
    -40 a token is the overflow test's decay, under which the first
    subdiagonal is ``exp(-40)`` of itself and what lies under it less."""
    a64 = systems(7 * c, c, g_token)
    a = jnp.asarray(a64, jnp.float32)
    got = np.asarray(jax.jit(_unit_lower_inverse)(a), np.float64)
    rows = np.asarray(jax.jit(inverse_a_row_at_a_time)(a), np.float64)
    assert got.shape == a.shape
    assert (got[..., ~np.tril(np.ones((c, c), bool))] == 0).all()
    assert (np.diagonal(got, axis1=-2, axis2=-1) == 1).all()
    eye = np.broadcast_to(np.eye(c), a.shape)
    if g_token == 0.0 and c >= BLOCK:
        assert np.abs(a64).max() > 0.5
    # float32 rounding: 2^-24 a sum, sums as long as a row, entries up to 1
    tol = c * 2.0 ** -24
    assert np.abs(rows).max() == 1.0
    np.testing.assert_allclose((eye + np.asarray(a, np.float64)) @ got, eye,
                               atol=tol, rtol=0)
    np.testing.assert_allclose(got, rows, atol=tol, rtol=0)


def loops(lowered):
    """``(trips, carried types)`` of each ``while`` of a lowered program's
    text whose bound is a constant, as ``fori_loop`` and ``scan`` make
    them."""
    return [(int(m[2]), m[1]) for m in re.finditer(
        r"stablehlo\.while\(.*?\) : (.*?)\n\s*cond \{\n\s*%\S+ = "
        r"stablehlo\.constant dense<(\d+)> : tensor<i32>", lowered)]


def test_no_loop_of_a_chunks_rows_over_a_whole_system_is_lowered():
    """A 1,024-row call: the scan over its 16 chunks is there (the rows of a
    diagonal block unroll); ``CHUNK`` trips round a ``[.., CHUNK, CHUNK]``
    carry (8 MB at the served shapes, read a trip) are not. The reader finds
    that loop in the oracle, so it would find it here."""
    whole = f"x{CHUNK}x{CHUNK}xf32>"
    q, k, v, g, beta, state = inputs(2, 1, 16 * CHUNK)
    held = loops(jax.jit(inverse_a_row_at_a_time).lower(
        jnp.zeros((1, 4, 16, CHUNK, CHUNK))).as_text())
    assert [(n, whole in kinds) for n, kinds in held] == [(CHUNK, True)]
    found = loops(jax.jit(gated_delta_chunked).lower(
        q, k, v, g, beta, state, jnp.asarray([16 * CHUNK])).as_text())
    assert (16, True) in [(n, whole in kinds) for n, kinds in found]  # scan
    assert not [(n, kinds) for n, kinds in found
                if n >= CHUNK and whole in kinds]
