"""Expert parallelism: Switch-style MoE over the "ep" mesh axis with
all_to_all token dispatch (parity-plus; the reference snapshot has no MoE).
Forward checked exactly against a per-token dense reference."""
import numpy as np
import jax
import jax.numpy as jnp
import pytest

import paddle_tpu as paddle
import paddle_tpu.distributed as dist
from paddle_tpu.distributed.fleet import moe_ffn, MoELayer


@pytest.fixture
def ep_mesh():
    dist.set_mesh(dist.build_mesh({"ep": 8}))
    yield dist.get_mesh()
    dist.set_mesh(None)


def _params(seed=0, D=16, F=32, E=8):
    rng = np.random.RandomState(seed)
    wg = rng.randn(D, E).astype(np.float32) * 0.5
    w1 = rng.randn(E, D, F).astype(np.float32) * 0.1
    w2 = rng.randn(E, F, D).astype(np.float32) * 0.1
    return wg, w1, w2


def _dense_ref(x, wg, w1, w2):
    B, T, D = x.shape
    xt = x.reshape(-1, D)
    logits = xt @ wg
    p = np.exp(logits - logits.max(-1, keepdims=True))
    p /= p.sum(-1, keepdims=True)
    e = p.argmax(-1)
    gp = p.max(-1)
    y = np.zeros_like(xt)
    for i in range(xt.shape[0]):
        h = xt[i] @ w1[e[i]]
        h = 0.5 * h * (1 + np.tanh(np.sqrt(2 / np.pi)
                                   * (h + 0.044715 * h ** 3)))
        y[i] = gp[i] * (h @ w2[e[i]])
    return y.reshape(B, T, D)


class TestMoE:
    def test_forward_matches_dense(self, ep_mesh):
        rng = np.random.RandomState(0)
        x = rng.randn(8, 4, 16).astype(np.float32)
        wg, w1, w2 = _params()
        out, aux = moe_ffn(jnp.asarray(x), jnp.asarray(wg),
                           jnp.asarray(w1), jnp.asarray(w2),
                           mesh=ep_mesh, capacity_factor=8.0)
        np.testing.assert_allclose(np.asarray(out),
                                   _dense_ref(x, wg, w1, w2),
                                   rtol=2e-3, atol=2e-4)
        assert float(aux) > 0

    @pytest.mark.slow
    def test_capacity_drops_overflow(self, ep_mesh):
        # gate forced to expert 0: with tiny capacity most tokens drop
        rng = np.random.RandomState(1)
        # positive inputs so the linear gate really sends EVERY token to
        # expert 0 (zero-mean inputs would flip sign per token)
        x = (np.abs(rng.randn(8, 4, 16)) + 0.1).astype(np.float32)
        wg = np.zeros((16, 8), np.float32)
        wg[:, 0] = 10.0 / 16
        _, w1, w2 = _params(1)
        out, _ = moe_ffn(jnp.asarray(x), jnp.asarray(wg * 100),
                         jnp.asarray(w1), jnp.asarray(w2),
                         mesh=ep_mesh, capacity_factor=0.25)
        dropped = np.asarray(out).reshape(-1, 16)
        # capacity = ceil(4 * 0.25 / 8 * ... ) = 1 per expert per rank:
        # exactly 1 token per rank routed, the other 3 zeroed
        zero_rows = (np.abs(dropped).sum(-1) < 1e-7).sum()
        assert zero_rows == 8 * 4 - 8

    @pytest.mark.slow
    def test_training_decreases_loss(self, ep_mesh):
        rng = np.random.RandomState(2)
        x = rng.randn(8, 4, 16).astype(np.float32)
        y = rng.randn(8, 4, 16).astype(np.float32)
        wg, w1, w2 = _params(2)

        def loss_fn(params):
            o, aux = moe_ffn(jnp.asarray(x), *params, mesh=ep_mesh,
                             capacity_factor=8.0)
            return jnp.mean((o - jnp.asarray(y)) ** 2) + 0.01 * aux

        params = tuple(jnp.asarray(a) for a in (wg, w1, w2))
        l1, g = jax.value_and_grad(loss_fn)(params)
        assert all(np.abs(np.asarray(gi)).sum() > 0 for gi in g)
        params = jax.tree_util.tree_map(lambda p, gg: p - 0.5 * gg,
                                        params, g)
        l2 = loss_fn(params)
        assert float(l2) < float(l1)

    @pytest.mark.slow
    def test_layer_wrapper_tape(self, ep_mesh):
        rng = np.random.RandomState(3)
        x = paddle.to_tensor(rng.randn(8, 4, 16).astype(np.float32),
                             stop_gradient=False)
        wg, w1, w2 = _params(3)
        layer = MoELayer(mesh=ep_mesh, capacity_factor=8.0)
        out, aux = layer(x, paddle.to_tensor(wg, stop_gradient=False),
                         paddle.to_tensor(w1, stop_gradient=False),
                         paddle.to_tensor(w2, stop_gradient=False))
        (out * out).sum().backward()
        assert x.grad is not None and np.abs(x.grad.numpy()).sum() > 0


class TestSharedExpertBesideAHeldShare:
    """``nn.MoEFeedForward``: the held experts' part of the routed sum, a
    shared expert every holder computes, the renormalisation's epsilon."""

    def _layer(self, held, shared, eps=1e-6, seed=3):
        from paddle_tpu.nn import MoEFeedForward
        paddle.seed(seed)
        layer = MoEFeedForward(16, 8, 8, 2, True, 1.5, held=held,
                               shared=shared, eps=eps, scope="trinity")
        rng = np.random.RandomState(seed)
        whole = {m: rng.randn(8, *shape).astype(np.float32) * 0.3
                 for m, shape in (("w1", (16, 8)), ("w3", (16, 8)),
                                  ("w2", (8, 16)))}
        lo, n = held or (0, 8)
        layer.gate.weight.set_value(rng.randn(16, 8).astype(np.float32))
        layer.expert_bias.set_value(
            rng.randn(8).astype(np.float32) * 0.02)
        for m, w in whole.items():
            getattr(layer.experts, m).set_value(w[lo:lo + n])
        if shared:
            for m, shape in (("w1", (1, 16, 8 * shared)),
                             ("w3", (1, 16, 8 * shared)),
                             ("w2", (1, 8 * shared, 16))):
                getattr(layer.shared_experts, m).set_value(
                    rng.randn(*shape).astype(np.float32) * 0.3)
        return layer

    @pytest.mark.parametrize("shared", [0, 1, 2])
    @pytest.mark.parametrize("cuts", [((0, 8),), ((0, 4), (4, 4)),
                                      ((0, 1), (1, 5), (6, 2))])
    def test_shares_add_up_with_the_shared_expert_counted_once(self, cuts,
                                                               shared):
        x = np.random.RandomState(0).randn(12, 16).astype(np.float32)
        with paddle.no_grad():
            whole = self._layer(None, shared)(paddle.to_tensor(x)).numpy()
            routed = self._layer(None, 0)(paddle.to_tensor(x)).numpy()
            parts = [self._layer(cut, shared)(paddle.to_tensor(x)).numpy()
                     for cut in cuts]
        once = whole - routed                   # the shared expert's part
        total = sum(p - once for p in parts) + once
        np.testing.assert_allclose(total, whole, atol=2e-5, rtol=0)
        if shared:
            assert np.abs(once).max() > 1e-2
            layer = self._layer(cuts[0], shared)
            assert layer.shared_experts.w1.shape == [1, 16, 8 * shared]

    def test_eps_is_a_keyword_and_its_default_is_lfm2s(self):
        from paddle_tpu.nn import MoEFeedForward
        assert MoEFeedForward(16, 8, 8, 2).eps == 1e-6
        assert MoEFeedForward(16, 8, 8, 2).scope == "lfm2"
        assert not hasattr(MoEFeedForward(16, 8, 8, 2), "shared_experts")
        x = np.full((3, 16), 1e-9, np.float32)
        with paddle.no_grad():
            a = self._layer(None, 0, eps=1e-6)(paddle.to_tensor(x)).numpy()
            b = self._layer(None, 0, eps=1e-20)(paddle.to_tensor(x)).numpy()
        np.testing.assert_allclose(a, b, rtol=1e-4, atol=1e-9)


class TestASecondConfigurationOnTheHeldPath:
    """``MoEFeedForward(2048, 1408, 64, 6, scale=2.446, held=(0, 8),
    shared=2, eps=1e-20, scope="moonlight")`` at toy widths: 6 of 64 chosen
    with 8 held and TWO shared experts (one SwiGLU of twice the width), the
    shares adding up as Trinity's 8 of 128 with 16 held do."""

    def _layer(self, experts, top_k, held, shared, seed=5):
        from paddle_tpu.nn import MoEFeedForward
        paddle.seed(seed)
        layer = MoEFeedForward(16, 8, experts, top_k, True, 2.446, held=held,
                               shared=shared, eps=1e-20, scope="moonlight")
        rng = np.random.RandomState(seed)
        whole = {m: rng.randn(experts, *shape).astype(np.float32) * 0.3
                 for m, shape in (("w1", (16, 8)), ("w3", (16, 8)),
                                  ("w2", (8, 16)))}
        lo, n = held or (0, experts)
        layer.gate.weight.set_value(
            rng.randn(16, experts).astype(np.float32))
        layer.expert_bias.set_value(
            rng.randn(experts).astype(np.float32) * 0.02)
        for m, w in whole.items():
            getattr(layer.experts, m).set_value(w[lo:lo + n])
        for m, shape in (("w1", (1, 16, 8 * shared)),
                         ("w3", (1, 16, 8 * shared)),
                         ("w2", (1, 8 * shared, 16))):
            getattr(layer.shared_experts, m).set_value(
                rng.randn(*shape).astype(np.float32) * 0.3)
        return layer

    @pytest.mark.parametrize("experts,top_k,n_held", [
        (64, 6, 8), (16, 6, 8), (128, 8, 16)])
    def test_every_share_of_the_layer_adds_up(self, experts, top_k, n_held):
        x = np.random.RandomState(1).randn(20, 16).astype(np.float32)
        with paddle.no_grad():
            whole = self._layer(experts, top_k, None, 2)(
                paddle.to_tensor(x)).numpy()
            parts = [self._layer(experts, top_k, (lo, n_held), 2)(
                paddle.to_tensor(x)).numpy()
                for lo in range(0, experts, n_held)]
            lone = self._layer(experts, top_k, (0, n_held), 2)
            shared = lone.shared_experts
            from paddle_tpu.models.lfm2 import swiglu
            once = np.asarray(swiglu(x, shared.w1._data[0],
                                     shared.w3._data[0], shared.w2._data[0]))
        assert lone.scope == "moonlight" and lone.eps == 1e-20
        assert lone.experts.w1.shape == [n_held, 16, 8]
        assert shared.w1.shape == [1, 16, 16]       # two shared: one SwiGLU
        total = sum(p - once for p in parts) + once
        np.testing.assert_allclose(total, whole, atol=3e-5, rtol=0)
        # a holder far from the chosen experts still computes the shared ones
        assert all(np.abs(p).max() > 1e-2 for p in parts)
