"""Math/elementwise/reduction op tests with numpy references
(pattern: reference unittests/test_*_op.py via the OpTest harness)."""
import numpy as np
import pytest

import paddle_tpu as paddle
import paddle_tpu.ops as ops
from op_test import check_output, check_grad


class TestElementwise:
    def test_add_broadcast(self):
        check_output(paddle.add, np.add,
                     [np.random.rand(3, 4).astype(np.float32),
                      np.random.rand(4).astype(np.float32)])

    def test_binary_family(self):
        a = np.random.rand(2, 3).astype(np.float32) + 0.5
        b = np.random.rand(2, 3).astype(np.float32) + 0.5
        for pfn, nfn in [(paddle.add, np.add), (paddle.subtract, np.subtract),
                         (paddle.multiply, np.multiply), (paddle.divide, np.divide),
                         (paddle.maximum, np.maximum), (paddle.minimum, np.minimum),
                         (paddle.pow, np.power), (paddle.atan2, np.arctan2)]:
            check_output(pfn, nfn, [a, b])

    def test_scalar_ops(self):
        x = paddle.to_tensor([1.0, 2.0, 3.0])
        np.testing.assert_allclose((x + 1).numpy(), [2, 3, 4])
        np.testing.assert_allclose((2 * x).numpy(), [2, 4, 6])
        np.testing.assert_allclose((1 - x).numpy(), [0, -1, -2])
        np.testing.assert_allclose((x / 2).numpy(), [0.5, 1, 1.5])
        np.testing.assert_allclose((x ** 2).numpy(), [1, 4, 9])

    def test_unary_family(self):
        x = np.random.rand(3, 4).astype(np.float32) + 0.1
        for pfn, nfn in [(paddle.exp, np.exp), (paddle.log, np.log),
                         (paddle.sqrt, np.sqrt), (paddle.abs, np.abs),
                         (paddle.tanh, np.tanh), (paddle.sin, np.sin),
                         (paddle.cos, np.cos), (paddle.floor, np.floor),
                         (paddle.ceil, np.ceil), (paddle.square, np.square)]:
            check_output(pfn, nfn, [x], atol=1e-4, rtol=1e-3)

    def test_comparisons(self):
        a = np.array([1.0, 2.0, 3.0], np.float32)
        b = np.array([2.0, 2.0, 2.0], np.float32)
        check_output(paddle.greater_than, np.greater, [a, b])
        check_output(paddle.equal, np.equal, [a, b])
        check_output(paddle.less_equal, np.less_equal, [a, b])

    def test_clip(self):
        x = np.array([-2.0, 0.5, 3.0], np.float32)
        check_output(lambda t: paddle.clip(t, 0.0, 1.0),
                     lambda a: np.clip(a, 0.0, 1.0), [x])


class TestMatmul:
    def test_matmul(self):
        check_output(paddle.matmul, np.matmul,
                     [np.random.rand(3, 4).astype(np.float32),
                      np.random.rand(4, 5).astype(np.float32)])

    def test_matmul_transpose(self):
        a = np.random.rand(4, 3).astype(np.float32)
        b = np.random.rand(5, 4).astype(np.float32)
        check_output(lambda x, y: paddle.matmul(x, y, transpose_x=True, transpose_y=True),
                     lambda x, y: x.T @ y.T, [a, b])

    def test_batched(self):
        check_output(paddle.bmm, np.matmul,
                     [np.random.rand(2, 3, 4).astype(np.float32),
                      np.random.rand(2, 4, 5).astype(np.float32)])

    def test_matmul_grad(self):
        check_grad(paddle.matmul,
                   [np.random.rand(3, 4), np.random.rand(4, 2)], grad_idx=0)
        check_grad(paddle.matmul,
                   [np.random.rand(3, 4), np.random.rand(4, 2)], grad_idx=1)


class TestReduce:
    def test_sum_axes(self):
        x = np.random.rand(2, 3, 4).astype(np.float32)
        check_output(lambda t: paddle.sum(t), lambda a: np.sum(a).reshape(()), [x])
        check_output(lambda t: paddle.sum(t, axis=1), lambda a: a.sum(1), [x])
        check_output(lambda t: paddle.sum(t, axis=[0, 2], keepdim=True),
                     lambda a: a.sum((0, 2), keepdims=True), [x])

    def test_mean_max_min_prod(self):
        x = np.random.rand(3, 5).astype(np.float32)
        check_output(lambda t: paddle.mean(t, axis=0), lambda a: a.mean(0), [x])
        check_output(lambda t: paddle.max(t, axis=1), lambda a: a.max(1), [x])
        check_output(lambda t: paddle.min(t, axis=1), lambda a: a.min(1), [x])
        check_output(lambda t: paddle.prod(t, axis=0), lambda a: a.prod(0), [x])

    def test_std_var(self):
        x = np.random.rand(4, 6).astype(np.float32)
        check_output(lambda t: paddle.std(t, axis=1),
                     lambda a: a.std(1, ddof=1), [x], atol=1e-4)
        check_output(lambda t: paddle.var(t, axis=1, unbiased=False),
                     lambda a: a.var(1), [x], atol=1e-4)

    def test_logsumexp(self):
        x = np.random.rand(3, 4).astype(np.float32)
        from scipy.special import logsumexp as np_lse
        check_output(lambda t: paddle.logsumexp(t, axis=1),
                     lambda a: np_lse(a, axis=1), [x])

    def test_cumsum(self):
        x = np.random.rand(3, 4).astype(np.float32)
        check_output(lambda t: paddle.cumsum(t, axis=1), lambda a: a.cumsum(1), [x])

    def test_mean_grad(self):
        check_grad(lambda t: paddle.mean(t, axis=1), [np.random.rand(3, 4)])


class TestSearchSort:
    def test_argmax_argsort(self):
        x = np.random.rand(4, 5).astype(np.float32)
        check_output(lambda t: paddle.argmax(t, axis=1), lambda a: a.argmax(1), [x])
        check_output(lambda t: paddle.argsort(t, axis=1), lambda a: a.argsort(1), [x])

    def test_topk(self):
        x = np.array([[1.0, 9.0, 3.0, 7.0]], np.float32)
        v, i = paddle.topk(paddle.to_tensor(x), 2)
        np.testing.assert_allclose(v.numpy(), [[9.0, 7.0]])
        np.testing.assert_array_equal(i.numpy(), [[1, 3]])

    def test_where(self):
        c = np.array([True, False, True])
        a = np.array([1.0, 2.0, 3.0], np.float32)
        b = np.array([9.0, 8.0, 7.0], np.float32)
        check_output(paddle.where, np.where, [c, a, b])

    def test_gather_scatter(self):
        x = np.random.rand(5, 3).astype(np.float32)
        idx = np.array([0, 2, 4])
        check_output(lambda t, i: paddle.gather(t, i, axis=0),
                     lambda a, i: a[i], [x, idx])
        got = paddle.scatter(paddle.to_tensor(np.zeros((4, 2), np.float32)),
                             paddle.to_tensor(np.array([1, 3])),
                             paddle.to_tensor(np.ones((2, 2), np.float32)))
        expected = np.zeros((4, 2), np.float32)
        expected[[1, 3]] = 1
        np.testing.assert_allclose(got.numpy(), expected)

    def test_gather_nd(self):
        x = np.random.rand(3, 4, 5).astype(np.float32)
        idx = np.array([[0, 1], [2, 3]])
        check_output(paddle.gather_nd, lambda a, i: a[tuple(i.T)], [x, idx])

    def test_index_select(self):
        x = np.random.rand(4, 6).astype(np.float32)
        check_output(lambda t, i: paddle.index_select(t, i, axis=1),
                     lambda a, i: a[:, i], [x, np.array([0, 5, 2])])

    def test_unique(self):
        x = np.array([3, 1, 2, 1, 3])
        out = paddle.unique(paddle.to_tensor(x))
        np.testing.assert_array_equal(out.numpy(), [1, 2, 3])

    def test_nonzero_masked_select(self):
        x = paddle.to_tensor(np.array([0.0, 1.5, 0.0, 2.0], np.float32))
        nz = paddle.nonzero(x)
        np.testing.assert_array_equal(nz.numpy(), [[1], [3]])
        ms = paddle.masked_select(x, x > 0)
        np.testing.assert_allclose(ms.numpy(), [1.5, 2.0])


class TestManipulation:
    def test_reshape_transpose(self):
        x = np.arange(24).reshape(2, 3, 4).astype(np.float32)
        check_output(lambda t: paddle.reshape(t, [4, 6]), lambda a: a.reshape(4, 6), [x])
        check_output(lambda t: paddle.transpose(t, [2, 0, 1]),
                     lambda a: a.transpose(2, 0, 1), [x])
        check_output(lambda t: paddle.flatten(t, 1, 2), lambda a: a.reshape(2, 12), [x])

    def test_concat_stack_split(self):
        a = np.random.rand(2, 3).astype(np.float32)
        b = np.random.rand(2, 3).astype(np.float32)
        out = paddle.concat([paddle.to_tensor(a), paddle.to_tensor(b)], axis=1)
        np.testing.assert_allclose(out.numpy(), np.concatenate([a, b], 1))
        out = paddle.stack([paddle.to_tensor(a), paddle.to_tensor(b)], axis=0)
        np.testing.assert_allclose(out.numpy(), np.stack([a, b], 0))
        parts = paddle.split(paddle.to_tensor(a), [1, 2], axis=1)
        np.testing.assert_allclose(parts[0].numpy(), a[:, :1])
        np.testing.assert_allclose(parts[1].numpy(), a[:, 1:])

    def test_squeeze_unsqueeze_tile_expand(self):
        x = np.random.rand(1, 3, 1).astype(np.float32)
        check_output(lambda t: paddle.squeeze(t, axis=0), lambda a: a.squeeze(0), [x])
        check_output(lambda t: paddle.unsqueeze(t, [0]), lambda a: a[None], [x])
        check_output(lambda t: paddle.tile(t, [2, 1, 4]), lambda a: np.tile(a, (2, 1, 4)), [x])
        check_output(lambda t: paddle.expand(t, [5, 3, 2]),
                     lambda a: np.broadcast_to(a, (5, 3, 2)), [x])

    def test_pad(self):
        x = np.random.rand(1, 2, 3, 3).astype(np.float32)
        check_output(lambda t: paddle.pad(t, [1, 1, 2, 2]),
                     lambda a: np.pad(a, [(0, 0), (0, 0), (2, 2), (1, 1)]), [x])

    def test_flip_roll(self):
        x = np.arange(6).reshape(2, 3).astype(np.float32)
        check_output(lambda t: paddle.flip(t, axis=1), lambda a: a[:, ::-1], [x])
        check_output(lambda t: paddle.roll(t, 1, axis=0), lambda a: np.roll(a, 1, 0), [x])

    def test_concat_grad(self):
        a = paddle.to_tensor(np.random.rand(2, 2).astype(np.float32))
        b = paddle.to_tensor(np.random.rand(2, 2).astype(np.float32))
        a.stop_gradient = False
        b.stop_gradient = False
        out = paddle.concat([a, b], axis=0)
        (out * 2).sum().backward()
        np.testing.assert_allclose(a.grad.numpy(), np.full((2, 2), 2.0))
        np.testing.assert_allclose(b.grad.numpy(), np.full((2, 2), 2.0))

    def test_setitem_getitem(self):
        x = paddle.zeros([3, 3])
        x[1] = 5.0
        assert x.numpy()[1].tolist() == [5.0, 5.0, 5.0]
        y = x[0:2]
        assert y.shape == [2, 3]


class TestLinalg:
    def test_cholesky_inverse_det(self):
        a = np.random.rand(3, 3).astype(np.float32)
        spd = a @ a.T + 3 * np.eye(3, dtype=np.float32)
        check_output(paddle.linalg.cholesky, np.linalg.cholesky, [spd], atol=1e-4)
        check_output(paddle.linalg.inv, np.linalg.inv, [spd], atol=1e-4)
        check_output(lambda t: paddle.linalg.det(t),
                     lambda x: np.asarray(np.linalg.det(x)), [spd], atol=1e-3)

    def test_solve(self):
        a = np.random.rand(3, 3).astype(np.float32) + 3 * np.eye(3, dtype=np.float32)
        b = np.random.rand(3, 2).astype(np.float32)
        check_output(paddle.linalg.solve, np.linalg.solve, [a, b], atol=1e-4)


class TestCreation:
    def test_basics(self):
        assert paddle.zeros([2, 3]).shape == [2, 3]
        assert paddle.ones([2], "int32").dtype == np.dtype("int32")
        np.testing.assert_allclose(paddle.full([2], 7.0).numpy(), [7, 7])
        np.testing.assert_array_equal(paddle.arange(5).numpy(), np.arange(5))
        np.testing.assert_allclose(paddle.linspace(0, 1, 5).numpy(),
                                   np.linspace(0, 1, 5), rtol=1e-6)
        assert paddle.eye(3).numpy().trace() == 3.0
        np.testing.assert_array_equal(paddle.tril(paddle.ones([3, 3])).numpy(),
                                      np.tril(np.ones((3, 3))))

    def test_random_reproducible(self):
        paddle.seed(7)
        a = paddle.rand([4])
        paddle.seed(7)
        b = paddle.rand([4])
        np.testing.assert_allclose(a.numpy(), b.numpy())

    def test_randint_randperm(self):
        r = paddle.randint(0, 10, [100])
        assert r.numpy().min() >= 0 and r.numpy().max() < 10
        p = paddle.randperm(10)
        assert sorted(p.numpy().tolist()) == list(range(10))

    def test_one_hot(self):
        oh = paddle.one_hot(paddle.to_tensor([0, 2]), 3)
        np.testing.assert_allclose(oh.numpy(), [[1, 0, 0], [0, 0, 1]])


class TestRound3MathTail:
    """Numpy checks for the round-3 math additions (reference: logit_op,
    cum_op cummin/logcumsumexp, renorm_op, cos_sim_op, shard_index_op,
    paddle.take/index_add/bucketize/diff/cov)."""

    def test_logit(self):
        x = np.array([0.2, 0.5, 0.8], np.float32)
        out = ops.logit(paddle.to_tensor(x)).numpy()
        np.testing.assert_allclose(out, np.log(x / (1 - x)), rtol=1e-6)

    def test_rad2deg_deg2rad_roundtrip(self):
        x = np.array([0.0, np.pi / 2, -np.pi], np.float32)
        deg = ops.rad2deg(paddle.to_tensor(x)).numpy()
        np.testing.assert_allclose(deg, [0, 90, -180], atol=1e-4)
        back = ops.deg2rad(paddle.to_tensor(deg)).numpy()
        np.testing.assert_allclose(back, x, rtol=1e-6)

    def test_cummin_values_and_indices(self):
        x = np.array([3.0, 1.0, 2.0, 0.5], np.float32)
        vals, idx = ops.cummin(paddle.to_tensor(x))
        np.testing.assert_allclose(vals.numpy(), [3, 1, 1, 0.5])
        np.testing.assert_array_equal(idx.numpy(), [0, 1, 1, 3])
        # ties: the EARLIEST index wins
        vals2, idx2 = ops.cummin(paddle.to_tensor(
            np.array([2.0, 1.0, 1.0, 3.0], np.float32)))
        np.testing.assert_allclose(vals2.numpy(), [2, 1, 1, 1])
        np.testing.assert_array_equal(idx2.numpy(), [0, 1, 1, 1])

    def test_logcumsumexp(self):
        x = np.array([0.1, -2.0, 1.5], np.float32)
        out = ops.logcumsumexp(paddle.to_tensor(x)).numpy()
        ref = np.log(np.cumsum(np.exp(x)))
        np.testing.assert_allclose(out, ref, rtol=1e-5)

    def test_diff_with_prepend(self):
        x = np.array([1.0, 4.0, 9.0], np.float32)
        out = ops.diff(paddle.to_tensor(x),
                       prepend=paddle.to_tensor(
                           np.array([0.0], np.float32))).numpy()
        np.testing.assert_allclose(out, [1, 3, 5])

    def test_take_modes(self):
        x = np.arange(6.0, dtype=np.float32).reshape(2, 3)
        idx = np.array([0, 5, -1], np.int32)
        out = ops.take(paddle.to_tensor(x), paddle.to_tensor(idx)).numpy()
        np.testing.assert_allclose(out, [0, 5, 5])
        wrap = ops.take(paddle.to_tensor(x),
                        paddle.to_tensor(np.array([7], np.int32)),
                        mode="wrap").numpy()
        np.testing.assert_allclose(wrap, [1.0])

    def test_index_add(self):
        x = np.zeros((3, 2), np.float32)
        v = np.ones((2, 2), np.float32)
        out = ops.index_add(paddle.to_tensor(x),
                            paddle.to_tensor(np.array([0, 2], np.int32)),
                            0, paddle.to_tensor(v)).numpy()
        np.testing.assert_allclose(out, [[1, 1], [0, 0], [1, 1]])

    def test_renorm_clamps_norms(self):
        x = np.array([[3.0, 4.0], [0.3, 0.4]], np.float32)
        out = ops.renorm(paddle.to_tensor(x), p=2.0, axis=0,
                         max_norm=1.0).numpy()
        np.testing.assert_allclose(np.linalg.norm(out[0]), 1.0, rtol=1e-4)
        np.testing.assert_allclose(out[1], x[1], rtol=1e-5)  # under limit

    def test_cos_sim(self):
        a = np.array([[1.0, 0.0], [1.0, 1.0]], np.float32)
        b = np.array([[1.0, 0.0], [1.0, 0.0]], np.float32)
        out = ops.cos_sim(paddle.to_tensor(a), paddle.to_tensor(b)).numpy()
        np.testing.assert_allclose(out.ravel(), [1.0, 1 / np.sqrt(2)],
                                   rtol=1e-5)

    def test_bucketize(self):
        edges = np.array([1.0, 3.0, 5.0], np.float32)
        x = np.array([0.5, 1.0, 4.0, 6.0], np.float32)
        # searchsorted-left semantics (paddle.bucketize is 1-D
        # searchsorted): equal values insert BEFORE the edge
        out = ops.bucketize(paddle.to_tensor(x),
                            paddle.to_tensor(edges)).numpy()
        np.testing.assert_array_equal(out, [0, 0, 2, 3])
        out_r = ops.bucketize(paddle.to_tensor(x),
                              paddle.to_tensor(edges), right=True).numpy()
        np.testing.assert_array_equal(out_r, [0, 1, 2, 3])

    def test_shard_index_ceiling_convention(self):
        # reference shard_index_op: shard_size = ceil(index_num/nshards)
        x = np.array([1, 6, 12, 19], np.int64)
        out = ops.shard_index(paddle.to_tensor(x), index_num=20, nshards=3,
                              shard_id=0).numpy()
        np.testing.assert_array_equal(out, [1, 6, -1, -1])
        out1 = ops.shard_index(paddle.to_tensor(x), index_num=20, nshards=3,
                               shard_id=1).numpy()
        np.testing.assert_array_equal(out1, [-1, -1, 5, -1])


def test_linalg_toplevel_and_tensor_namespace():
    """paddle.cholesky/inverse/matrix_power + paddle.rank +
    paddle.tensor.* import path (reference: python/paddle/tensor/)."""
    import numpy as np
    import paddle_tpu as paddle

    a = np.array([[4.0, 2.0], [2.0, 3.0]], np.float32)
    c = paddle.cholesky(paddle.to_tensor(a)).numpy()
    np.testing.assert_allclose(c @ c.T, a, rtol=1e-5)
    inv = paddle.inverse(paddle.to_tensor(a)).numpy()
    np.testing.assert_allclose(inv @ a, np.eye(2), atol=1e-5)
    mp = paddle.matrix_power(paddle.to_tensor(a), 3).numpy()
    np.testing.assert_allclose(mp, a @ a @ a, rtol=1e-4)
    assert int(paddle.rank(paddle.to_tensor(a)).numpy()) == 2
    assert paddle.tensor.cholesky is paddle.cholesky
    np.testing.assert_allclose(
        paddle.tensor.rank(paddle.to_tensor(a)).numpy(), 2)


def test_tensor_method_parity_vs_reference(reference_paddle):
    """Every method-shaped name in the reference's tensor/__init__.py
    resolves on Tensor (free creation functions excluded — they live at
    the paddle top level and are covered by the top-level parity test)."""
    import re
    import numpy as np
    import paddle_tpu as paddle

    src = open(f"{reference_paddle}/tensor/__init__.py").read()
    names = []
    for m in re.finditer(r"from \.\w+ import ([\w,\s]+)", src):
        for n in m.group(1).split(","):
            n = n.strip()
            if " as " in n:          # `import flip as reverse`
                n = n.split(" as ")[-1].strip()
            if n:
                names.append(n)
    names += re.findall(r"^\s+'(\w+)',?\s*$", src, re.M)
    free = {"arange", "array_length", "array_read", "array_write",
            "create_array", "empty", "empty_like", "eye", "full",
            "full_like", "linspace", "meshgrid", "ones", "ones_like",
            "rand", "randint", "randn", "randperm", "set_printoptions",
            "to_tensor", "zeros", "zeros_like", "normal", "uniform",
            "standard_normal", "add_n", "diag", "is_tensor", "multiplex",
            "concat", "stack", "broadcast_shape", "shard_index",
            "scatter_nd", "increment", "is_empty"}
    t = paddle.to_tensor(np.ones((2, 2), np.float32))
    missing = sorted(set(n for n in names if not n.startswith("_")
                         and n not in free and not hasattr(t, n)))
    assert not missing, missing


def test_tensor_method_tail_semantics():
    import numpy as np
    import paddle_tpu as paddle

    x = paddle.to_tensor(np.array([[1.0, 2.0], [3.0, 4.0]], np.float32))
    np.testing.assert_allclose(x.t().numpy(), x.numpy().T)
    assert int(x.numel().numpy()) == 4
    assert int(x.rank().numpy()) == 2
    np.testing.assert_allclose(x.tril().numpy(), np.tril(x.numpy()))
    np.testing.assert_allclose(
        x.mul(paddle.to_tensor(np.float32(2.0))).numpy(), x.numpy() * 2)
    np.testing.assert_allclose(x.reverse(axis=[0]).numpy(),
                               x.numpy()[::-1])
    import pytest
    with pytest.raises(ValueError, match="t\\(\\) expects"):
        paddle.to_tensor(np.ones((2, 2, 2), np.float32)).t()
    # inplace variants stay on the tape
    y = paddle.to_tensor(np.array([0.5, 1.5], np.float32),
                         stop_gradient=False)
    z = y * 2.0
    z.sqrt_()
    z.sum().backward()
    ref = 2.0 * 0.5 / np.sqrt(np.array([1.0, 3.0]))
    np.testing.assert_allclose(y.grad.numpy(), ref, rtol=1e-5)
    w = paddle.to_tensor(np.array([1.0, -2.0], np.float32))
    w.clip_(min=0.0)
    np.testing.assert_allclose(w.numpy(), [1.0, 0.0])
