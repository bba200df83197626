"""paddle.batch + paddle.reader combinators (reference: batch.py,
reader/decorator.py — same semantics, pure python)."""
import numpy as np

import paddle_tpu as paddle
from paddle_tpu import reader as R


def _r10():
    def r():
        yield from range(10)
    return r


def test_batch_semantics():
    out = list(paddle.batch(_r10(), 3)())
    assert out == [[0, 1, 2], [3, 4, 5], [6, 7, 8], [9]]
    out = list(paddle.batch(_r10(), 3, drop_last=True)())
    assert out == [[0, 1, 2], [3, 4, 5], [6, 7, 8]]


def test_combinators():
    assert list(R.firstn(_r10(), 4)()) == [0, 1, 2, 3]
    assert list(R.chain(_r10(), _r10())()) == list(range(10)) * 2
    assert list(R.map_readers(lambda a, b: a + b, _r10(), _r10())()) == \
        [2 * i for i in range(10)]
    assert sorted(R.shuffle(_r10(), 5)()) == list(range(10))
    assert list(R.buffered(_r10(), 2)()) == list(range(10))
    got = list(R.compose(_r10(), R.map_readers(lambda x: x * 10,
                                               _r10()))())
    assert got == [(i, i * 10) for i in range(10)]
    c = R.cache(_r10())
    assert list(c()) == list(range(10)) and list(c()) == list(range(10))
    got = list(R.xmap_readers(lambda x: x + 1, _r10(), 3, 4, order=True)())
    assert got == [i + 1 for i in range(10)]
    got = sorted(R.xmap_readers(lambda x: x + 1, _r10(), 3, 4)())
    assert got == [i + 1 for i in range(10)]


def test_callbacks_and_sysconfig_surface():
    import os
    assert hasattr(paddle.callbacks, "Callback") or \
        hasattr(paddle.callbacks, "EarlyStopping") or \
        len(dir(paddle.callbacks)) > 3
    assert os.path.isdir(paddle.sysconfig.get_include())
    assert os.path.exists(os.path.join(paddle.sysconfig.get_include(),
                                       "paddle_tpu_capi.h"))


def test_compose_alignment_raises():
    from paddle_tpu.reader import ComposeNotAligned

    def r7():
        yield from range(7)
    import pytest
    with pytest.raises(ComposeNotAligned):
        list(R.compose(_r10(), r7)())
    # check_alignment=False truncates at the shortest, quietly
    assert len(list(R.compose(_r10(), r7, check_alignment=False)())) == 7


def test_reader_errors_surface_not_truncate():
    import pytest

    def bad():
        yield 1
        raise IOError("decode failed")
    with pytest.raises(IOError, match="decode failed"):
        list(R.buffered(bad, 4)())
    with pytest.raises(IOError):
        list(R.xmap_readers(lambda x: x, bad, 2, 4)())

    def bad_map(x):
        if x == 5:
            raise ValueError("corrupt item")
        return x
    with pytest.raises(ValueError, match="corrupt"):
        list(R.xmap_readers(bad_map, _r10(), 2, 4, order=True)())


def test_cache_partial_pass_not_committed():
    calls = []

    def flaky():
        calls.append(1)
        yield 0
        yield 1
        if len(calls) == 1:
            raise IOError("transient")
        yield 2
    c = R.cache(flaky)
    import pytest
    with pytest.raises(IOError):
        list(c())
    assert list(c()) == [0, 1, 2]      # no duplicated prefix


def test_top_level_export_parity_vs_reference(reference_paddle):
    """Every name the reference's paddle/__init__.py __all__ exports must
    resolve here (backend-specific ones as documented stubs)."""
    import re
    import paddle_tpu as p
    src = open(f"{reference_paddle}/__init__.py").read()
    names = re.findall(r"^\s+'([A-Za-z_0-9]+)',\s*$", src, re.M)
    missing = sorted(set(n for n in names if not hasattr(p, n)))
    assert not missing, missing


def test_namespace_export_parity_vs_reference(reference_paddle):
    """Same check for every public sub-namespace the reference ships."""
    import re
    import importlib
    pairs = [("static", "paddle_tpu.static"), ("jit", "paddle_tpu.jit"),
             ("utils", "paddle_tpu.utils"),
             ("autograd", "paddle_tpu.autograd"),
             ("distributed", "paddle_tpu.distributed"),
             ("distributed/fleet", "paddle_tpu.distributed.fleet"),
             ("metric", "paddle_tpu.metric"),
             ("optimizer", "paddle_tpu.optimizer"),
             ("io", "paddle_tpu.io"), ("text", "paddle_tpu.text"),
             ("amp", "paddle_tpu.amp"),
             ("vision/transforms", "paddle_tpu.vision.transforms"),
             ("vision/datasets", "paddle_tpu.vision.datasets"),
             ("incubate", "paddle_tpu.incubate")]
    bad = {}
    for ref, ourmod in pairs:
        rsrc = open(f"{reference_paddle}/{ref}/__init__.py").read()
        names = re.findall(r"from [\w.]+ import (\w+)", rsrc)
        names += re.findall(r"^\s+'(\w+)',?\s*$", rsrc, re.M)
        ours = importlib.import_module(ourmod)
        missing = sorted(set(n for n in names if not n.startswith("_")
                             and not hasattr(ours, n)))
        if missing:
            bad[ref] = missing
    assert not bad, bad


def test_inplace_aliases_keep_gradients():
    """tanh_/scatter_ must stay on the tape (round-5 review: direct
    _data assignment silently dropped the op from backward)."""
    import paddle_tpu as paddle
    x = paddle.to_tensor(np.array([0.5, 2.0], np.float32),
                         stop_gradient=False)
    y = x * 2.0
    paddle.tanh_(y)
    y.sum().backward()
    # d/dx sum(tanh(2x)) = 2 * (1 - tanh^2(2x))
    ref = 2.0 * (1.0 - np.tanh(np.array([1.0, 4.0])) ** 2)
    np.testing.assert_allclose(x.grad.numpy(), ref, rtol=1e-3,
                               atol=1e-6)


def test_add_n_never_aliases():
    import paddle_tpu as paddle
    x = paddle.to_tensor(np.zeros(3, np.float32))
    y = paddle.add_n(x)
    assert y is not x
    paddle.tanh_(y)          # mutating y must not touch x
    np.testing.assert_allclose(x.numpy(), 0.0)
    z = paddle.add_n([x])
    assert z is not x


def test_lookahead_and_model_average():
    import paddle_tpu as paddle
    import paddle_tpu.optimizer as optim
    from paddle_tpu.incubate.optimizer import LookAhead, ModelAverage
    paddle.seed(0)
    net = paddle.nn.Linear(4, 2)
    inner = optim.SGD(learning_rate=0.5, parameters=net.parameters())
    la = LookAhead(inner, alpha=0.5, k=2)
    x = paddle.to_tensor(np.ones((3, 4), np.float32))
    w0 = net.weight.numpy().copy()
    for _ in range(2):
        net(x).sum().backward()
        la.step()
        la.clear_grad()
    g = np.ones_like(w0) * 3.0
    expect = w0 + 0.5 * ((w0 - g) - w0)   # slow <- slow+0.5(fast2-slow)
    np.testing.assert_allclose(net.weight.numpy(), expect, rtol=1e-5)

    ma = ModelAverage(0.5, parameters=net.parameters(),
                      min_average_window=2, max_average_window=4)
    vals = []
    for _ in range(3):
        net.weight._data = net.weight._data + 1.0
        ma.step()
        vals.append(net.weight.numpy().copy())
    cur = net.weight.numpy().copy()
    with ma.apply():
        np.testing.assert_allclose(net.weight.numpy(),
                                   np.mean(vals, axis=0), rtol=1e-5)
    np.testing.assert_allclose(net.weight.numpy(), cur)
