"""Round-5 nn-surface additions: export parity vs the reference's
nn/functional __all__, BiRNN vs torch's bidirectional GRU,
BeamSearchDecoder+dynamic_decode vs brute-force enumeration,
HSigmoidLoss/PairwiseDistance layers, inplace functional aliases."""
import re

import numpy as np
import pytest

import paddle_tpu as paddle
import paddle_tpu.nn as nn
import paddle_tpu.nn.functional as F


def test_export_parity_nn_and_functional(reference_paddle):
    for path, ours in [
            (f"{reference_paddle}/nn/__init__.py", nn),
            (f"{reference_paddle}/nn/functional/__init__.py", F)]:
        src = open(path).read()
        names = re.findall(r"from \.[\w.]+ import (\w+)", src)
        names += re.findall(r"^\s+'(\w+)',?\s*$", src, re.M)
        missing = sorted(set(n for n in names
                             if not n.startswith("_")
                             and not hasattr(ours, n)))
        assert not missing, (path, missing)


def test_birnn_matches_torch():
    import torch
    paddle.seed(0)
    cf, cb = nn.GRUCell(3, 4), nn.GRUCell(3, 4)
    bi = nn.BiRNN(cf, cb)
    tg = torch.nn.GRU(3, 4, batch_first=True, bidirectional=True)
    for ours, pre in [(cf, ""), (cb, "_reverse")]:
        getattr(tg, "weight_ih_l0" + pre).data = \
            torch.from_numpy(ours.weight_ih.numpy().copy())
        getattr(tg, "weight_hh_l0" + pre).data = \
            torch.from_numpy(ours.weight_hh.numpy().copy())
        getattr(tg, "bias_ih_l0" + pre).data = \
            torch.from_numpy(ours.bias_ih.numpy().copy())
        getattr(tg, "bias_hh_l0" + pre).data = \
            torch.from_numpy(ours.bias_hh.numpy().copy())
    x = np.random.RandomState(0).randn(2, 5, 3).astype(np.float32)
    out, _ = bi(paddle.to_tensor(x))
    ref, _ = tg(torch.from_numpy(x))
    np.testing.assert_allclose(out.numpy(), ref.detach().numpy(),
                               rtol=1e-4, atol=1e-5)


class _TableCell(nn.Layer):
    """Deterministic 'cell': logits depend only on the input token —
    makes exact brute-force enumeration of sequence scores possible."""

    def __init__(self, table):
        super().__init__()
        self._table = paddle.to_tensor(table)

    def forward(self, ids, states):
        from paddle_tpu import ops
        logits = ops.gather(self._table, ids)
        return logits, states


def test_beam_search_decoder_matches_bruteforce():
    rng = np.random.RandomState(3)
    V, T, K = 5, 3, 3
    table = rng.randn(V, V).astype(np.float32) * 2.0
    cell = _TableCell(table)
    dec = nn.BeamSearchDecoder(cell, start_token=0, end_token=V - 1,
                               beam_size=K)
    h0 = paddle.to_tensor(np.zeros((1, 2), np.float32))
    ids, scores = nn.dynamic_decode(dec, inits=h0, max_step_num=T)
    assert tuple(ids.shape) == (1, T, K)

    # brute force: enumerate all V^T sequences, score with log-softmax
    # chain + end-token absorption
    import itertools
    logp = np.log(np.exp(table) / np.exp(table).sum(-1, keepdims=True))
    best = []
    for seq in itertools.product(range(V), repeat=T):
        s, prev, done = 0.0, 0, False
        for tok in seq:
            if done:
                if tok != V - 1:
                    s = -np.inf
                continue
            s += logp[prev, tok]
            prev = tok
            if tok == V - 1:
                done = True
        best.append((s, seq))
    best.sort(key=lambda t: -t[0])
    got_scores = scores.numpy()[0]
    exp_scores = np.array([b[0] for b in best[:K]])
    np.testing.assert_allclose(np.sort(got_scores)[::-1], exp_scores,
                               rtol=1e-4)
    # the top beam's token sequence matches the argmax enumeration
    top_k_col = int(np.argmax(got_scores))
    np.testing.assert_array_equal(ids.numpy()[0, :, top_k_col],
                                  list(best[0][1]))


def test_hsigmoid_layer_and_pairwise_distance():
    paddle.seed(1)
    lay = nn.HSigmoidLoss(8, 6)
    x = np.random.RandomState(0).randn(4, 8).astype(np.float32)
    lab = np.random.RandomState(1).randint(0, 6, (4,)).astype(np.int64)
    out = lay(paddle.to_tensor(x), paddle.to_tensor(lab))
    ref = F.hsigmoid_loss(paddle.to_tensor(x), paddle.to_tensor(lab), 6,
                          lay.weight, lay.bias)
    np.testing.assert_allclose(out.numpy(), ref.numpy(), rtol=1e-6)

    pd = nn.PairwiseDistance(p=2.0)
    a = np.random.RandomState(2).randn(3, 4).astype(np.float32)
    b = np.random.RandomState(3).randn(3, 4).astype(np.float32)
    got = pd(paddle.to_tensor(a), paddle.to_tensor(b)).numpy()
    ref = np.linalg.norm(a - b + 1e-6, axis=-1)
    np.testing.assert_allclose(got, ref, rtol=1e-5)


def test_functional_inplace_aliases_on_tape():
    x = paddle.to_tensor(np.array([0.2, -0.4], np.float32),
                         stop_gradient=False)
    y = x * 3.0
    F.tanh_(y)
    y.sum().backward()
    ref = 3.0 * (1 - np.tanh(np.array([0.6, -1.2])) ** 2)
    np.testing.assert_allclose(x.grad.numpy(), ref, rtol=1e-3, atol=1e-6)
    z = paddle.to_tensor(np.array([1.0, 2.0], np.float32))
    F.softmax_(z)
    np.testing.assert_allclose(z.numpy().sum(), 1.0, rtol=1e-6)
    w = paddle.to_tensor(np.array([-1.0, 1.0], np.float32))
    F.elu_(w)
    np.testing.assert_allclose(w.numpy()[1], 1.0)


def test_spectral_norm_functional_alias():
    # paddle.nn.spectral_norm (fluid-style functional; alias of
    # utils_weight_norm.spectral_norm_fn)
    paddle.seed(2)
    w = np.random.RandomState(4).randn(6, 4).astype(np.float32)
    got = nn.spectral_norm(paddle.to_tensor(w), power_iters=50)
    sigma = np.linalg.svd(w, compute_uv=False)[0]
    np.testing.assert_allclose(got.numpy(), w / sigma, rtol=1e-3,
                               atol=1e-4)


def test_rnn_sequence_length_masks_padded_rows():
    """RNN/BiRNN with sequence_length: outputs past a row's length are
    zero, final states freeze at the row's end, backward direction reads
    only the valid prefix (verified vs torch packed sequences for the
    BiRNN in the drive; here the single-direction invariants)."""
    paddle.seed(3)
    cell = nn.GRUCell(3, 4)
    layer = nn.RNN(cell)
    x = np.random.RandomState(5).randn(2, 5, 3).astype(np.float32)
    lens = paddle.to_tensor(np.array([5, 2]))
    out, last = layer(paddle.to_tensor(x), sequence_length=lens)
    o = out.numpy()
    assert np.abs(o[1, 2:]).max() == 0.0          # masked tail
    np.testing.assert_allclose(last.numpy()[1], o[1, 1], rtol=1e-5)
    # row 0 (full length) identical to the unmasked run
    out_full, _ = layer(paddle.to_tensor(x))
    np.testing.assert_allclose(o[0], out_full.numpy()[0], rtol=1e-5)


def test_spectral_norm_functional_deterministic():
    w = np.random.RandomState(6).randn(6, 4).astype(np.float32)
    a = nn.spectral_norm(paddle.to_tensor(w)).numpy()
    b = nn.spectral_norm(paddle.to_tensor(w)).numpy()
    np.testing.assert_array_equal(a, b)           # deterministic
    sigma = np.linalg.svd(w, compute_uv=False)[0]
    np.testing.assert_allclose(a, w / sigma, rtol=1e-3, atol=1e-4)


def test_dynamic_decode_rejects_unknown_kwargs():
    cell = _TableCell(np.eye(4, dtype=np.float32))
    dec = nn.BeamSearchDecoder(cell, 0, 3, 2)
    h0 = paddle.to_tensor(np.zeros((1, 2), np.float32))
    with pytest.raises(TypeError, match="impute_finished|unsupported"):
        nn.dynamic_decode(dec, inits=h0, max_step_num=2,
                          impute_finished=True)
    # output_time_major works
    ids, _ = nn.dynamic_decode(dec, inits=h0, max_step_num=2,
                               output_time_major=True)
    assert tuple(ids.shape) == (2, 1, 2)
