"""``ops.linear_attention.decayed_linear_attention``: the chunked form against
the token-by-token recurrence, for decays whose powers underflow, across
sub-chunk and chunk boundaries, and under right padding."""
import numpy as np
import pytest

import jax.numpy as jnp

from paddle_tpu.ops.linear_attention import SUB, decayed_linear_attention


def _recurrence(q, k, v, rate, state, n):
    """``S_t = lam S_{t-1} + k_t v_t^T``, ``y_t = S_t^T q_t`` over the first
    ``n`` rows, in float64."""
    q, k, v = (np.asarray(x, np.float64) for x in (q, k, v))
    state = np.array(state, np.float64)
    lam = np.exp(-np.asarray(rate, np.float64))[:, None, None]
    ys = np.zeros(v.shape, np.float64)
    for t in range(n):
        state = lam * state + k[t][:, :, None] * v[t][:, None, :]
        ys[t] = np.einsum("hde,hd->he", state, q[t])
    return ys, state


def _inputs(t, heads=4, d=8, seed=0):
    rng = np.random.default_rng(seed)
    q, k, v = (rng.normal(size=(1, t, heads, d)).astype(np.float32)
               for _ in range(3))
    state = rng.normal(size=(1, heads, d, d)).astype(np.float32)
    return q, k, v, state


#: the configuration's fastest head decays by exp(-0.84) a token: over a
#: sub-chunk that is exp(-108), under float32's smallest normal; 5.0 makes
#: even two rows underflow a form that divides by powers of lam
RATES = {"published": [2.0 ** (-8.0 * (h + 1) / 4) for h in range(4)],
         "underflow": [0.84, 5.0, 40.0, 1e-4]}


@pytest.mark.parametrize("rates", sorted(RATES))
@pytest.mark.parametrize("t,n", [(1, 1), (8, 8), (8, 5), (SUB, SUB),
                                 (2 * SUB, 2 * SUB), (3 * SUB, SUB + 7),
                                 (2 * SUB, 0)])
def test_chunked_form_is_the_recurrence(rates, t, n):
    q, k, v, state = _inputs(t)
    rate = np.asarray(RATES[rates], np.float32)
    y, new = decayed_linear_attention(
        jnp.asarray(q), jnp.asarray(k), jnp.asarray(v), jnp.asarray(rate),
        jnp.asarray(state), jnp.asarray([n], jnp.int32))
    want_y, want_state = _recurrence(q[0], k[0], v[0], rate, state[0], n)
    assert np.all(np.isfinite(np.asarray(y[0, :n])))
    # float32 sums of up to SUB products of unit normals in another order
    np.testing.assert_allclose(np.asarray(y[0, :n]), want_y[:n], atol=2e-4,
                               rtol=2e-5)
    np.testing.assert_allclose(np.asarray(new[0]), want_state, atol=2e-4,
                               rtol=2e-5)


@pytest.mark.parametrize("cuts", [(8, 8, 8), (16, 3, 5), (SUB, 2 * SUB, 1)])
def test_the_state_carries_a_sequence_across_calls(cuts):
    """A prompt cut into chunks, each call starting from the state the last
    one left (the last rows of a chunk may be padding), is the recurrence
    over the whole."""
    total = sum(cuts)
    q, k, v, _ = _inputs(total, seed=3)
    rate = np.asarray(RATES["underflow"], np.float32)
    state = jnp.zeros((1, 4, 8, 8), jnp.float32)
    ys, at = [], 0
    for n in cuts:
        # a short chunk carries a few rows of padding, a long one fills up
        # to the next sub-chunk
        width = n + 3 if n + 3 <= SUB else -(-n // SUB) * SUB
        pad = [(0, 0), (0, width - n), (0, 0), (0, 0)]
        parts = [jnp.asarray(np.pad(x[:, at:at + n], pad)) for x in (q, k, v)]
        y, state = decayed_linear_attention(
            *parts, jnp.asarray(rate), state, jnp.asarray([n], jnp.int32))
        ys.append(np.asarray(y[0, :n]))
        at += n
    want_y, want_state = _recurrence(q[0], k[0], v[0], rate,
                                     np.zeros((4, 8, 8)), total)
    np.testing.assert_allclose(np.concatenate(ys), want_y, atol=2e-4,
                               rtol=2e-5)
    np.testing.assert_allclose(np.asarray(state[0]), want_state, atol=2e-4,
                               rtol=2e-5)


def test_a_length_between_sub_chunks_is_refused():
    q, k, v, state = _inputs(SUB + 8)
    with pytest.raises(ValueError, match="multiple"):
        decayed_linear_attention(
            jnp.asarray(q), jnp.asarray(k), jnp.asarray(v),
            jnp.ones((4,), jnp.float32), jnp.asarray(state),
            jnp.asarray([3], jnp.int32))
