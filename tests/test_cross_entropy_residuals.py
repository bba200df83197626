"""What the hard-label softmax cross entropy keeps between forward and
backward (PR 48): the logits as it was given them and one float32 ``lse`` a
row, where JAX's rule for ``log_softmax`` keeps a float32 table of the
logits' shape. The plain ``log_softmax`` form lives on here as the
reference, and the forms the new function does not cover must still take
the plain lines."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

import paddle_tpu as paddle
import paddle_tpu.nn.functional as F
from paddle_tpu.jit.functionalize import build_pure

IGNORE = -100
ROWS = {2: (12,), 3: (3, 4)}
V = 40


def _plain(logits, lab, reduction):
    """``cross_entropy`` as it stood: the label's column of ``log_softmax``
    over the logits cast to float32, ignored rows zeroed, mean over the
    rows that count."""
    logp = jax.nn.log_softmax(logits.astype(jnp.float32), axis=-1)
    valid = lab != IGNORE
    safe = jnp.where(valid, lab, 0)
    loss = -jnp.take_along_axis(logp, safe[..., None], -1)[..., 0]
    loss = jnp.where(valid, loss, 0.0)
    if reduction == "mean":
        return loss.sum() / jnp.maximum(valid.sum(), 1)
    return loss.sum() if reduction == "sum" else loss


def _case(dtype, ndim, seed=0):
    rng = np.random.default_rng(seed)
    rows = ROWS[ndim]
    logits = jnp.asarray(3 * rng.standard_normal(rows + (V,)), dtype)
    lab = rng.integers(0, V, rows)
    lab.reshape(-1)[[1, 7]] = IGNORE
    cot = jnp.asarray(rng.uniform(0.5, 1.5, rows), jnp.float32)
    return logits, jnp.asarray(lab, jnp.int32), cot


def _eager(logits, lab, cot, reduction):
    x = paddle.to_tensor(logits, stop_gradient=False)
    loss = F.cross_entropy(x, paddle.to_tensor(lab), reduction=reduction,
                           ignore_index=IGNORE)
    weighed = loss * paddle.to_tensor(cot) if reduction == "none" else loss
    weighed.sum().backward()
    return loss._data, x.grad._data


def _jitted(logits, lab, cot, reduction):
    x = paddle.to_tensor(logits)
    pure, _ = build_pure(
        lambda y: F.cross_entropy(x, y, reduction=reduction,
                                  ignore_index=IGNORE), [x])

    def scalar(raw):
        loss, = pure([raw], [lab], jax.random.PRNGKey(0), None)
        return ((loss * cot) if reduction == "none" else loss).sum(), loss
    (_, loss), grad = jax.jit(jax.value_and_grad(scalar, has_aux=True))(logits)
    return loss, grad


@pytest.mark.parametrize("mode", ["eager", "jit"])
@pytest.mark.parametrize("reduction", ["none", "sum", "mean"])
@pytest.mark.parametrize("trailing_one", [False, True],
                         ids=["labels", "labels_1"])
@pytest.mark.parametrize("ndim", [2, 3], ids=["2d", "3d"])
@pytest.mark.parametrize("dtype", ["float32", "bfloat16"])
def test_loss_and_gradient_equal_the_log_softmax_forms(
        dtype, ndim, trailing_one, reduction, mode):
    """Two rows of every case carry ``ignore_index``. The loss is float32
    for either dtype (the sums are taken in float32 from the logits as
    given); the gradient comes back in the logits' dtype."""
    logits, lab, cot = _case(dtype, ndim)
    lab_in = lab[..., None] if trailing_one else lab
    loss, grad = (_eager if mode == "eager" else _jitted)(
        logits, lab_in, cot, reduction)

    def scalar(x):
        out = _plain(x, lab, reduction)
        return ((out * cot) if reduction == "none" else out).sum()
    want_grad = jax.grad(scalar)(logits)
    assert loss.dtype == jnp.float32 and grad.dtype == logits.dtype
    np.testing.assert_allclose(loss, _plain(logits, lab, reduction),
                               rtol=2e-6, atol=2e-6)
    if reduction == "none":
        assert not np.asarray(loss).reshape(-1)[[1, 7]].any()
    assert not np.asarray(grad, np.float32).reshape(-1, V)[[1, 7]].any()
    # bfloat16: both sides round a float32 gradient once, one ulp apart
    tol = 1e-6 if dtype == "float32" else 2 ** -7
    np.testing.assert_allclose(np.asarray(grad, np.float32),
                               np.asarray(want_grad, np.float32),
                               rtol=tol, atol=tol * 1e-2)


def _gpt_fwd_loss():
    """The hapi train step's loss function over a GPT of the benchmark's
    rehearsal widths (benchmark/configs/gpt2-small.json, ``rehearsal``),
    traced under bf16 autocast as the train cell runs it."""
    from paddle_tpu.core import generator
    from paddle_tpu.models import (GPTConfig, GPTForCausalLM,
                                   GPTPretrainingCriterion)
    paddle.seed(5)
    batch, seq, vocab = 2, 64, 512
    net = GPTForCausalLM(GPTConfig(
        vocab_size=vocab, hidden_size=64, num_layers=2, num_heads=2,
        intermediate_size=256, max_position_embeddings=seq,
        hidden_dropout_prob=0.0, attention_dropout_prob=0.0))
    model = paddle.Model(net)
    model.prepare(paddle.optimizer.AdamW(parameters=net.parameters()),
                  GPTPretrainingCriterion())
    ids = jnp.asarray(np.random.default_rng(5).integers(0, vocab, (batch, seq)),
                      jnp.int32)
    ts = model._get_train_step(((((batch, seq), "int32"),) * 2, False))
    train = [p._data for p in ts["trainable"]]
    fixed = [ts["state"][i]._data for i in ts["fixed_pos"]]
    key = generator.next_key()

    def loss(train_raws):
        with paddle.amp.auto_cast(enable=True, dtype="bfloat16"):
            return ts["fwd_loss"](train_raws, fixed, [ids], [ids], key)[0]
    return loss, train, (batch * seq, vocab)


def test_the_gpt_step_keeps_the_logits_and_lse_and_no_float32_table(capsys):
    """Parent: ``f32[128,512] output of jitted function 'log_softmax'``.
    Now the product's own bf16 output, ``lse`` a row, and no float32 array
    of the logits' shape, merged or not."""
    from jax.ad_checkpoint import print_saved_residuals
    loss, train, (n, v) = _gpt_fwd_loss()
    print_saved_residuals(loss, train)
    saved = capsys.readouterr().out.strip().splitlines()
    kept = [line.split()[0] for line in saved]
    assert f"f32[{n},{v}]" not in kept and f"f32[2,{n // 2},{v}]" not in kept
    assert f"bf16[{n},{v}]" in kept or f"bf16[2,{n // 2},{v}]" in kept
    assert [line for line in saved if line.startswith(f"f32[{n}] ")
            and "_lse_less_pick_fwd" in line]
    assert np.isfinite(float(jax.jit(loss)(train)))


@pytest.mark.parametrize("level", ["O1", "O2"])
def test_autocast_hands_the_logits_over_as_they_are(level):
    """The function is float32 inside whatever it is given, so bf16
    autocast casts nothing round it (a cast up outside would be what is
    kept): bf16 logits bring back a bf16 gradient and float32 logits a
    float32 one, the loss float32 either way."""
    for dtype in ("bfloat16", "float32"):
        logits, lab, _ = _case(dtype, 2)
        x = paddle.to_tensor(logits, stop_gradient=False)
        with paddle.amp.auto_cast(enable=True, dtype="bfloat16", level=level):
            loss = F.cross_entropy(x, paddle.to_tensor(lab))
        loss.backward()
        assert loss._data.dtype == jnp.float32
        assert x.grad._data.dtype == logits.dtype
        np.testing.assert_allclose(loss._data, _plain(logits, lab, "mean"),
                                   rtol=2e-6)


def _np_log_softmax(x, axis):
    x = x - x.max(axis, keepdims=True)
    return x - np.log(np.exp(x).sum(axis, keepdims=True))


def _plain_forms():
    rng = np.random.default_rng(1)
    x = rng.standard_normal((6, 5)).astype(np.float32)
    lab = rng.integers(0, 5, (6,))
    soft = rng.dirichlet(np.ones(5), 6).astype(np.float32)
    w = rng.uniform(0.5, 2.0, 5).astype(np.float32)
    probs = np.exp(_np_log_softmax(x, -1))
    x4 = rng.standard_normal((3, 5, 4)).astype(np.float32)
    lab4 = rng.integers(0, 5, (3, 4))
    logp4 = _np_log_softmax(x4, 1)
    picked4 = np.take_along_axis(logp4, lab4[:, None, :], 1)[:, 0]
    logp = _np_log_softmax(x, -1)
    return {
        "soft_label": ((x, soft), dict(soft_label=True),
                       -(soft * logp).sum(-1).mean()),
        "weight": ((x, lab), dict(weight=paddle.to_tensor(w)),
                   -(w[lab] * logp[np.arange(6), lab]).sum() / w[lab].sum()),
        "use_softmax_false": ((probs, lab), dict(use_softmax=False),
                              -np.log(probs[np.arange(6), lab]).mean()),
        "axis_1": ((x4, lab4), dict(axis=1), -picked4.mean()),
        "hard_labels_last_axis": ((x, lab), {},
                                  -logp[np.arange(6), lab].mean()),
    }


@pytest.mark.parametrize("form", ["soft_label", "weight", "use_softmax_false",
                                  "axis_1", "hard_labels_last_axis"])
def test_every_other_form_takes_the_plain_lines(form):
    """Soft labels, class weights, probabilities for logits and an axis that
    is not the last are other mathematics: no custom rule in their trace,
    ``log_softmax``'s (or ``log``'s) own, and the values they gave. The
    last case is the control: hard labels over the last axis do trace the
    custom rule."""
    (x, lab), kwargs, want = _plain_forms()[form]
    xt = paddle.to_tensor(x, stop_gradient=False)
    got = F.cross_entropy(xt, paddle.to_tensor(lab), **kwargs)
    np.testing.assert_allclose(float(got), want, rtol=1e-5)
    got.backward()
    assert np.isfinite(np.asarray(xt.grad._data)).all()

    pure, _ = build_pure(
        lambda y: F.cross_entropy(xt, y, **kwargs), [xt])
    text = str(jax.make_jaxpr(
        lambda raw: pure([raw], [jnp.asarray(lab)], jax.random.PRNGKey(0),
                         None))(jnp.asarray(x)))
    assert ("custom_vjp_call" in text) == (form == "hard_labels_last_axis")


def test_the_backward_rule_can_be_differentiated_again():
    """``paddle.grad(create_graph=True)`` replays an operation's VJP as a
    recorded one: the custom rule's backward is ``jax.numpy``, so the
    gradient of a gradient's square is ``log_softmax``'s."""
    logits, lab, _ = _case("float32", 2)
    x = paddle.to_tensor(logits, stop_gradient=False)
    grad, = paddle.grad(F.cross_entropy(x, paddle.to_tensor(lab)), [x],
                        create_graph=True)
    (grad * grad).sum().backward()
    want = jax.grad(lambda raw: (jax.grad(
        lambda r: _plain(r, lab, "mean"))(raw) ** 2).sum())(logits)
    np.testing.assert_allclose(x.grad._data, want, rtol=1e-5, atol=1e-8)
