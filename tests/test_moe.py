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


class TestSoftmaxRoutingAndAGatedSharedExpert:
    """``MoEFeedForward(2048, 512, 512, 10, held=(0, 64), shared=1,
    scope="qwen3next", route="softmax", shared_gate=True)`` at toy widths:
    the top of a softmax over ALL the experts, renormalised, no selection
    bias and no epsilon; a shared expert weighted by one sigmoid a token; the
    sigmoid families' code as it was."""

    def _layer(self, experts, top_k, held, seed=7, **kw):
        from paddle_tpu.nn import MoEFeedForward
        paddle.seed(seed)
        kw = dict(dict(shared=1, scope="qwen3next", route="softmax",
                       shared_gate=True), **kw)
        layer = MoEFeedForward(16, 8, experts, top_k, True, held=held, **kw)
        rng = np.random.RandomState(seed)
        whole = {m: rng.randn(experts, *shape).astype(np.float32) * 0.3
                 for m, shape in (("w1", (16, 8)), ("w3", (16, 8)),
                                  ("w2", (8, 16)))}
        lo, n = held or (0, experts)
        layer.gate.weight.set_value(
            rng.randn(16, experts).astype(np.float32))
        for m, w in whole.items():
            getattr(layer.experts, m).set_value(w[lo:lo + n])
        for m, shape in (("w1", (1, 16, 8)), ("w3", (1, 16, 8)),
                         ("w2", (1, 8, 16))):
            value = rng.randn(*shape).astype(np.float32) * 0.3
            if hasattr(layer, "shared_experts"):
                getattr(layer.shared_experts, m).set_value(value)
        if hasattr(layer, "shared_expert_gate"):
            layer.shared_expert_gate.weight.set_value(
                rng.randn(16, 1).astype(np.float32))
        return layer, whole

    def test_softmax_route_is_the_top_of_a_softmax_renormalised(self):
        from paddle_tpu.ops.moe import route_softmax_topk
        rng = np.random.RandomState(0)
        f = rng.randn(12, 16).astype(np.float32)
        gate = rng.randn(16, 32).astype(np.float32)
        idx, w = route_softmax_topk(f, gate, 5)
        logits = f @ gate
        p = np.exp(logits - logits.max(-1, keepdims=True))
        p /= p.sum(-1, keepdims=True)
        order = np.argsort(-p, axis=-1)[:, :5]
        np.testing.assert_array_equal(np.sort(np.asarray(idx), -1),
                                      np.sort(order, -1))
        want = np.take_along_axis(p, np.asarray(idx), -1)
        np.testing.assert_allclose(w, want / want.sum(-1, keepdims=True),
                                   rtol=1e-5)
        np.testing.assert_allclose(np.asarray(w).sum(-1), 1.0, rtol=1e-6)
        # unnormalised: the probabilities themselves, times the scale
        _, raw = route_softmax_topk(f, gate, 5, norm_topk=False, scale=2.0)
        np.testing.assert_allclose(raw, 2.0 * want, rtol=1e-5)

    @pytest.mark.parametrize("experts,top_k,n_held", [
        (64, 10, 8), (16, 4, 2), (32, 10, 32)])
    def test_every_share_adds_up_with_the_gated_shared_expert_once(
            self, experts, top_k, n_held):
        from paddle_tpu.models.lfm2 import swiglu
        x = np.random.RandomState(1).randn(20, 16).astype(np.float32)
        with paddle.no_grad():
            whole_layer, w = self._layer(experts, top_k, None)
            whole = whole_layer(paddle.to_tensor(x)).numpy()
            parts = [self._layer(experts, top_k, (lo, n_held))[0](
                paddle.to_tensor(x)).numpy()
                for lo in range(0, experts, n_held)]
        sh, sg = whole_layer.shared_experts, whole_layer.shared_expert_gate
        gate = 1.0 / (1.0 + np.exp(-(x @ np.asarray(sg.weight._data))))
        once = gate * np.asarray(swiglu(x, sh.w1._data[0], sh.w3._data[0],
                                        sh.w2._data[0]))
        # one number a token, and not the same number for every token
        assert gate.shape == (20, 1) and gate.min() < 0.4 < 0.6 < gate.max()
        total = sum(p - once for p in parts) + once
        np.testing.assert_allclose(total, whole, atol=3e-5, rtol=0)
        # against the layer written out: every expert on every row
        logits = x @ np.asarray(whole_layer.gate.weight._data)
        p = np.exp(logits - logits.max(-1, keepdims=True))
        p /= p.sum(-1, keepdims=True)
        kth = np.sort(p, -1)[:, -top_k][:, None]
        wts = np.where(p >= kth, p, 0.0)
        wts /= wts.sum(-1, keepdims=True)
        dense = once + sum(
            wts[:, e:e + 1] * np.asarray(swiglu(x, w["w1"][e], w["w3"][e],
                                                w["w2"][e]))
            for e in range(experts))
        np.testing.assert_allclose(whole, dense, atol=3e-5, rtol=0)

    def test_the_gate_weighs_the_shared_expert_and_nothing_else(self):
        x = np.random.RandomState(2).randn(9, 16).astype(np.float32)
        with paddle.no_grad():
            gated, _ = self._layer(16, 4, (0, 4))
            plain, _ = self._layer(16, 4, (0, 4), shared_gate=False)
            bare, _ = self._layer(16, 4, (0, 4), shared=0, shared_gate=False)
            g, p, b = (layer(paddle.to_tensor(x)).numpy()
                       for layer in (gated, plain, bare))
        gate = 1.0 / (1.0 + np.exp(-(x @ np.asarray(
            gated.shared_expert_gate.weight._data))))
        np.testing.assert_allclose(g - b, gate * (p - b), atol=1e-5)
        assert not hasattr(plain, "shared_expert_gate")
        assert not hasattr(gated, "expert_bias")    # softmax: no bias

    def test_what_does_not_fit_is_refused(self):
        from paddle_tpu.nn import MoEFeedForward
        from paddle_tpu.ops.moe import moe_feed_forward
        with pytest.raises(ValueError, match="route"):
            MoEFeedForward(16, 8, 8, 2, route="top1")
        with pytest.raises(ValueError, match="shared_gate"):
            MoEFeedForward(16, 8, 8, 2, shared_gate=True)
        for held in ((6, 4), (0, 0), (-1, 2)):      # both routings alike
            for route in ("sigmoid", "softmax"):
                with pytest.raises(ValueError, match="held"):
                    MoEFeedForward(16, 8, 8, 2, held=held, route=route)
        with pytest.raises(ValueError, match="top_k"):
            MoEFeedForward(16, 8, 4, 6, route="softmax")
        f = np.zeros((4, 16), np.float32)
        w = np.zeros((2, 16, 8), np.float32)
        with pytest.raises(ValueError, match="selection bias"):
            moe_feed_forward(f, np.zeros((16, 4), np.float32),
                             np.zeros((4,), np.float32), w, w,
                             np.zeros((2, 8, 16), np.float32), top_k=2,
                             route="softmax")
        with pytest.raises(ValueError, match="route"):
            moe_feed_forward(f, np.zeros((16, 4), np.float32), None, w, w,
                             np.zeros((2, 8, 16), np.float32), top_k=2,
                             route="argmax")

    def test_the_sigmoid_families_run_the_code_they_ran(self):
        """The default is the sigmoid routing with its bias and epsilon: a
        layer built as LFM2, Trinity and Moonlight build theirs compiles to
        the same program whether ``route`` is named or left out ('route'
        reaches the trace as a Python branch and leaves nothing behind; PR 40
        read the text equal to the parent tree's for all three families),
        and to the layer written out."""
        import jax
        from paddle_tpu.ops.moe import moe_feed_forward, route_sigmoid_topk
        rng = np.random.RandomState(3)
        f = rng.randn(6, 16).astype(np.float32)
        gate = rng.randn(16, 8).astype(np.float32)
        bias = (rng.randn(8) * 0.02).astype(np.float32)
        w1, w3 = (0.3 * rng.randn(8, 16, 8).astype(np.float32)
                  for _ in range(2))
        w2 = 0.3 * rng.randn(8, 8, 16).astype(np.float32)
        kw = dict(top_k=2, norm_topk=True, scale=2.446, eps=1e-20,
                  scope="moonlight")

        def default(*a):
            return moe_feed_forward(*a, **kw)

        def named(*a):
            return moe_feed_forward(*a, route="sigmoid", **kw)

        args = (f, gate, bias, w1, w3, w2)
        a, b = (jax.jit(fn).lower(*args).as_text(debug_info=False)
                for fn in (default, named))
        assert a.replace("named", "default") == b.replace("named", "default")
        out, counts = default(*args)
        idx, wts = route_sigmoid_topk(f, gate, bias, 2, True, 2.446, 1e-20)
        assert int(np.asarray(counts).sum()) == 12
        from paddle_tpu.models.lfm2 import swiglu
        dense = sum(np.asarray(wts)[:, j:j + 1] * np.stack([
            np.asarray(swiglu(f[t], w1[e], w3[e], w2[e]))
            for t, e in enumerate(np.asarray(idx)[:, j])]) for j in range(2))
        np.testing.assert_allclose(out, dense, atol=2e-5, rtol=0)


class TestTheRowBufferIsAWindowSizedForTheHeldShare:
    """``ops/moe.py:window_sizes``: a call of more than 128 tokens that holds
    a share of the router's experts lays its pairs out in tiles sized for
    what a held expert expects and walks a window of them as often as the
    routing needs; every other call takes the whole buffer as it always
    did."""

    #: (cell, tokens, top_k, held, of, rows of the buffer, rows of a tile)
    CHUNKS = [("serve-qwen3next-longdoc", 1024, 10, 64, 512, 4608, 32),
              ("serve-trinity-mixedctx", 1024, 8, 16, 128, 4096, 128),
              ("serve-moonlight-longgen", 1024, 6, 8, 64, 2560, 128)]
    #: (cell, tokens, top_k, held, of): the whole buffer, no window
    WHOLE = [("serve-qwen3next-longdoc", 32, 10, 64, 512),
             ("serve-trinity-mixedctx", 32, 8, 16, 128),
             ("serve-moonlight-longgen", 48, 6, 8, 64),
             ("serve-lfm2moe-decode", 32, 4, 32, 32),
             ("serve-lfm2moe-decode", 128, 4, 32, 32),
             ("serve-lfm2moe-decode", 256, 4, 32, 32),
             ("serve-lfm2moe-decode", 512, 4, 32, 32),
             ("nn.MoE", 1024, 10, 512, 512)]

    @staticmethod
    def _tick_rows(cell):
        """What ``benchmark/costs_lfm2.py:decode_moe`` finds a tick's
        kernels by."""
        from benchmark import costs_lfm2, costs_moonlight, spec
        c = spec.load_cell(spec.load_benchmark(), cell)
        if "n_routed_experts" in c["config_data"]:  # as its readers do
            c = costs_moonlight.as_lfm2({"cell": c})["cell"]
        return costs_lfm2.decode_rows(
            c["config_data"], c["traffic_data"]["engine"]["num_slots"])

    @staticmethod
    def _layout_shapes(t, k, n, e, lo=0):
        """``group_layout``'s arrays as shapes, beside its two statics."""
        from paddle_tpu.ops import moe
        static = {}

        def arrays(idx):
            lay = moe.group_layout(idx, n, lo, num_experts=e)
            static.update(tm=lay.pop("tm"), window=lay.pop("window"))
            return lay
        return dict(jax.eval_shape(
            arrays, jax.ShapeDtypeStruct((t, k), jnp.int32)), **static)

    @staticmethod
    def _whiles(fn, *args):
        """Loops of ``fn`` lowered as the chip's compiler is given it (off
        the chip an interpreted kernel is loops of its own)."""
        return jax.jit(fn).trace(*args).lower(
            lowering_platforms=("tpu",)).as_text().count("stablehlo.while")

    @staticmethod
    def _weights(rng, n, h=16, f=8):
        w1, w3 = (0.3 * rng.randn(n, h, f).astype(np.float32)
                  for _ in range(2))
        return w1, w3, 0.3 * rng.randn(n, f, h).astype(np.float32)

    @staticmethod
    def _dense(f, idx, wts, w1, w3, w2, lo):
        """Every held expert on every row, weighted where it was chosen."""
        from paddle_tpu.models.lfm2 import swiglu
        idx, wts = np.asarray(idx), np.asarray(wts)
        return sum(
            (wts * (idx == lo + e)).sum(-1, keepdims=True)
            * np.asarray(swiglu(f, w1[e], w3[e], w2[e]))
            for e in range(w1.shape[0]))

    @pytest.mark.parametrize("cell,t,k,n,e,rows,tm", CHUNKS)
    def test_a_chunks_buffer_follows_the_held_share(self, cell, t, k, n, e,
                                                    rows, tm):
        from paddle_tpu.ops import moe
        assert moe.window_sizes(t, k, n, e) == (tm, rows // tm)
        lay = self._layout_shapes(t, k, n, e, lo=n)
        assert (lay["tm"], lay["window"]) == (tm, rows // tm)
        # every held pair has a row, whatever the routing: the parent's
        # bound at this tile, in whole windows
        worst = moe.max_tiles(t, k, n, tm)
        assert lay["src"].shape[0] // tm >= worst > lay["window"]
        assert lay["src"].shape[0] % rows == 0
        # under the buffer for all the pairs in tiles of 128, and never a
        # tick's row count (the benchmark finds a tick's kernels by it)
        assert rows < moe.max_tiles(t, k, n, 128) * 128
        assert rows != self._tick_rows(cell)
        # one loop more than the layout's own: the walk over the windows
        s, h, f = jax.ShapeDtypeStruct, 16, 8
        assert self._whiles(
            lambda x, gate, w1, w3, w2: moe.moe_feed_forward(
                x, gate, None, w1, w3, w2, top_k=k, route="softmax",
                interpret=False),
            s((t, h), jnp.float32), s((h, e), jnp.float32),
            s((n, h, f), jnp.float32), s((n, h, f), jnp.float32),
            s((n, f, h), jnp.float32)) == 2

    @pytest.mark.parametrize("cell,t,k,n,e", WHOLE)
    def test_every_other_call_takes_the_whole_buffer_and_no_loop(
            self, cell, t, k, n, e):
        from paddle_tpu.ops import moe
        assert moe.window_sizes(t, k, n, e) is None
        s = jax.ShapeDtypeStruct
        lay = self._layout_shapes(t, k, n, e)
        tm = moe.row_tile(t)
        tiles = min(t * k // tm + n, n * -(-t // tm))   # the parent's bound
        assert (lay["tm"], lay["window"]) == (tm, tiles)
        assert lay["src"].shape == (tiles * tm,)
        if t <= 48:     # a tick: the rows its kernels are found by
            assert tiles * tm == self._tick_rows(cell)
        h, f = 16, 8
        args = (s((t, h), jnp.float32), s((h, e), jnp.float32),
                s((n, h, f), jnp.float32), s((n, h, f), jnp.float32),
                s((n, f, h), jnp.float32))

        def layer(x, gate, w1, w3, w2):
            return moe.moe_feed_forward(x, gate, None, w1, w3, w2, top_k=k,
                                        route="softmax", interpret=False)

        def layout(x, gate):
            idx, _ = moe.route_softmax_topk(x, gate, k)
            return moe.group_layout(idx, n, 0)

        # the one loop is the layout's own (``searchsorted``), the parent's
        assert self._whiles(layer, *args) == self._whiles(
            layout, *args[:2]) == 1

    @pytest.mark.parametrize("route", ["sigmoid", "softmax"])
    @pytest.mark.parametrize("held", [(8, 8), (16, 32), (0, 64)])
    def test_a_chunk_equals_the_dense_sum_over_the_held(self, held, route):
        """An eighth (a window, tiles of 64), a half (tiles of 64, the window
        is the whole of them) and all the experts (today's path)."""
        from paddle_tpu.ops import moe
        lo, n = held
        rng = np.random.RandomState(n)
        f = rng.randn(256, 16).astype(np.float32)
        gate = rng.randn(16, 64).astype(np.float32)
        bias = (0.1 * rng.randn(64).astype(np.float32)
                if route == "sigmoid" else None)
        w1, w3, w2 = self._weights(rng, n)
        sizes = moe.window_sizes(256, 10, n, 64)
        assert sizes == {8: (64, 18), 32: (64, 72), 64: None}[n]
        out, counts = jax.jit(lambda *a: moe.moe_feed_forward(
            *a, top_k=10, expert_lo=lo, route=route))(f, gate, bias, w1, w3,
                                                      w2)
        if route == "sigmoid":
            idx, wts = moe.route_sigmoid_topk(f, gate, bias, 10)
        else:
            idx, wts = moe.route_softmax_topk(f, gate, 10)
        held_pairs = int(((np.asarray(idx) >= lo)
                          & (np.asarray(idx) < lo + n)).sum())
        assert int(counts.sum()) == held_pairs
        np.testing.assert_allclose(
            out, self._dense(f, idx, wts, w1, w3, w2, lo), atol=3e-5, rtol=0)
        passes = moe.window_passes(counts, 256, 10, 64)
        assert (passes is None) == (n == 64)
        assert n == 64 or int(passes) == 1

    @pytest.mark.parametrize("rig", ["every_pair_held", "one_hot_expert",
                                     "no_pair_held"])
    def test_an_overflow_costs_a_pass_and_never_a_row(self, rig):
        """512 tokens, 2 of 64 experts each, 8 held: 16 rows an expert are
        expected, so tiles of 32 in a window of 16 tiles. A router that
        sends EVERY pair to the held experts fills 32-40; an expert that
        takes every token fills 16 alone, across two windows."""
        from paddle_tpu.ops import moe
        rng = np.random.RandomState(11)
        lo, n, t, k, e = 16, 8, 512, 2, 64
        f = rng.randn(t, 16).astype(np.float32)
        f[:, 0] = 1.0       # its row of the gate moves a score for all
        gate = rng.randn(16, e).astype(np.float32)
        if rig == "every_pair_held":
            gate[0, lo:lo + n] += 40.0
        elif rig == "one_hot_expert":
            gate[0, lo + 3] += 40.0
        else:
            gate[0, lo:lo + n] -= 40.0
        w1, w3, w2 = self._weights(rng, n)
        assert moe.window_sizes(t, k, n, e) == (32, 16)
        out, counts = jax.jit(lambda *a: moe.moe_feed_forward(
            *a, top_k=k, expert_lo=lo, route="softmax"))(f, gate, None, w1,
                                                         w3, w2)
        idx, wts = moe.route_softmax_topk(f, gate, k)
        counts = np.asarray(counts)
        passes = int(moe.window_passes(jnp.asarray(counts), t, k, e))
        if rig == "every_pair_held":
            assert counts.sum() == t * k and passes >= 2
        elif rig == "one_hot_expert":
            assert counts[3] == t and passes == 2
        else:
            assert counts.sum() == 0 and passes == 0
            assert not np.asarray(out).any()
        np.testing.assert_allclose(
            out, self._dense(f, idx, wts, w1, w3, w2, lo), atol=3e-5, rtol=0)
