"""Loss functionals.

Parity targets (reference: paddle/fluid/operators/): softmax_with_cross_entropy,
cross_entropy2, bce_loss, sigmoid_cross_entropy_with_logits, nll_loss,
kldiv_loss, smooth_l1_loss, huber_loss, hinge_loss, log_loss, mse (via ops),
margin_rank_loss, cos_sim, ctc/warpctc (deferred), sigmoid_focal_loss,
square_error_cost, npair/triplet-era losses.
"""
from __future__ import annotations

import numpy as np
import jax
import jax.numpy as jnp

from ...ops.dispatch import apply
from ...core.tensor import Tensor


def _reduce(out, reduction):
    if reduction == "mean":
        return jnp.mean(out)
    if reduction == "sum":
        return jnp.sum(out)
    return out


@jax.custom_vjp
def _lse_less_pick(logits, safe):
    """``logsumexp(logits) - logits[safe]`` over the last axis, a float32 a
    row, with sums and pick taken in float32 from the logits cast up in the
    pass that reads them. Kept for the backward pass: the logits in the
    dtype they came in, one float32 ``lse`` a row and the labels. JAX's own
    rule for ``log_softmax`` keeps its float32 result, an array of the
    logits' shape."""
    return _lse_less_pick_fwd(logits, safe)[0]


def _lse_less_pick_fwd(logits, safe):
    lse = jax.nn.logsumexp(logits.astype(jnp.float32), axis=-1)
    picked = jnp.take_along_axis(logits, safe[..., None], -1)[..., 0]
    return lse - picked.astype(jnp.float32), (logits, lse, safe)


def _lse_less_pick_bwd(kept, g):
    logits, lse, safe = kept
    p = jnp.exp(logits.astype(jnp.float32) - lse[..., None])
    onehot = safe[..., None] == jnp.arange(logits.shape[-1])
    # rounded to the logits' dtype: the transpose of the cast up in forward
    return (g[..., None] * (p - onehot)).astype(logits.dtype), None


_lse_less_pick.defvjp(_lse_less_pick_fwd, _lse_less_pick_bwd)


def cross_entropy(input, label, weight=None, ignore_index=-100, reduction="mean",
                  soft_label=False, axis=-1, use_softmax=True, name=None):
    """reference: operators/softmax_with_cross_entropy_op.cc +
    python/paddle/nn/functional/loss.py cross_entropy.

    Hard labels over the last axis with a softmax and no class weights (the
    form every language-model head takes) go through ``_lse_less_pick``,
    which keeps for the backward pass the logits as they were given and one
    float32 ``lse`` a row: at 16,384 rows of 50,304 columns the float32
    table of log-probabilities that ``log_softmax`` would keep is 3.07 GiB
    and a pass of its own to write. Its loss is float32 whatever the logits'
    dtype, so autocast leaves its logits as they are (``amp.F32_INSIDE_OPS``).
    Every other form is different mathematics and keeps ``log_softmax``."""
    lean = (use_softmax and not soft_label and weight is None
            and axis in (-1, len(input.shape) - 1))

    def impl(logits, lab, *w):
        if not lean:
            logp = jax.nn.log_softmax(logits, axis=axis) if use_softmax \
                else jnp.log(jnp.maximum(logits, 1e-30))
        if soft_label:
            loss = -jnp.sum(lab * logp, axis=axis)
        else:
            lab_i = lab.astype(jnp.int32)
            if lab_i.ndim == logits.ndim:  # [N,...,1] hard labels
                lab_i = jnp.squeeze(lab_i, axis)
            valid = lab_i != ignore_index
            safe = jnp.where(valid, lab_i, 0)
            if lean:
                loss = _lse_less_pick(logits, safe)
            else:
                picked = jnp.take_along_axis(logp, jnp.expand_dims(safe, axis),
                                             axis)
                loss = -jnp.squeeze(picked, axis)
            if w:
                cw = jnp.take(w[0], safe)
                loss = loss * cw
            loss = jnp.where(valid, loss, 0.0)
            if reduction == "mean":
                if w:
                    denom = jnp.sum(jnp.where(valid, jnp.take(w[0], safe), 0.0))
                else:
                    denom = jnp.maximum(jnp.sum(valid.astype(loss.dtype)), 1.0)
                return jnp.sum(loss) / denom
        return _reduce(loss, reduction)
    args = [input, label] + ([weight] if weight is not None else [])
    return apply("softmax_cross_entropy_rows" if lean
                 else "softmax_with_cross_entropy", impl, *args)


def softmax_with_cross_entropy(logits, label, soft_label=False,
                               ignore_index=-100, numeric_stable_mode=True,
                               return_softmax=False, axis=-1):
    out = cross_entropy(logits, label, soft_label=soft_label,
                        ignore_index=ignore_index, reduction="none", axis=axis)
    out = out.unsqueeze(axis)
    if return_softmax:
        from .activation import softmax as _softmax
        return out, _softmax(logits, axis=axis)
    return out


def nll_loss(input, label, weight=None, ignore_index=-100, reduction="mean", name=None):
    """reference: operators/nll_loss_op.cc (input is log-probabilities)."""
    return _nll(input, label, weight, ignore_index, reduction)


def _nll(input, label, weight, ignore_index, reduction):
    def impl(logp, lab, *w):
        lab_i = lab.astype(jnp.int32)
        valid = lab_i != ignore_index
        safe = jnp.where(valid, lab_i, 0)
        picked = jnp.take_along_axis(logp, jnp.expand_dims(safe, 1), 1)
        loss = -jnp.squeeze(picked, 1)
        cw = jnp.take(w[0], safe) if w else jnp.ones_like(loss)
        loss = jnp.where(valid, loss * cw, 0.0)
        if reduction == "mean":
            return jnp.sum(loss) / jnp.maximum(jnp.sum(jnp.where(valid, cw, 0.0)), 1e-12)
        return _reduce(loss, reduction)
    args = [input, label] + ([weight] if weight is not None else [])
    return apply("nll_loss", impl, *args)


def mse_loss(input, label, reduction="mean", name=None):
    return apply("mse_loss",
                 lambda a, b: _reduce((a - b) ** 2, reduction), input, label)


def l1_loss(input, label, reduction="mean", name=None):
    return apply("l1_loss",
                 lambda a, b: _reduce(jnp.abs(a - b), reduction), input, label)


def square_error_cost(input, label):
    """reference: operators/squared_l2_distance_op / fluid.layers.square_error_cost."""
    return apply("square_error_cost", lambda a, b: (a - b) ** 2, input, label)


def binary_cross_entropy(input, label, weight=None, reduction="mean", name=None):
    def impl(p, y, *w):
        p = jnp.clip(p, 1e-12, 1.0 - 1e-12)
        loss = -(y * jnp.log(p) + (1 - y) * jnp.log(1 - p))
        if w:
            loss = loss * w[0]
        return _reduce(loss, reduction)
    args = [input, label] + ([weight] if weight is not None else [])
    return apply("bce_loss", impl, *args)


def binary_cross_entropy_with_logits(logit, label, weight=None, reduction="mean",
                                     pos_weight=None, name=None):
    """reference: operators/sigmoid_cross_entropy_with_logits_op.cc."""
    def impl(z, y, *extra):
        it = iter(extra)
        w = next(it) if weight is not None else None
        pw = next(it) if pos_weight is not None else None
        # stable: max(z,0) - z*y + log(1+exp(-|z|)) with pos_weight variant
        if pw is not None:
            log_w = (pw - 1) * y + 1
            loss = (1 - y) * z + log_w * (jnp.logaddexp(0.0, -jnp.abs(z))
                                          + jnp.maximum(-z, 0.0))
        else:
            loss = jnp.maximum(z, 0.0) - z * y + jnp.logaddexp(0.0, -jnp.abs(z))
        if w is not None:
            loss = loss * w
        return _reduce(loss, reduction)
    args = [logit, label] + [t for t in (weight, pos_weight) if t is not None]
    return apply("sigmoid_cross_entropy_with_logits", impl, *args)


def sigmoid_focal_loss(logit, label, normalizer=None, alpha=0.25, gamma=2.0,
                       reduction="sum", name=None):
    """reference: operators/sigmoid_focal_loss_op.cc."""
    def impl(z, y, *n):
        p = jax.nn.sigmoid(z)
        ce = jnp.maximum(z, 0.0) - z * y + jnp.logaddexp(0.0, -jnp.abs(z))
        p_t = p * y + (1 - p) * (1 - y)
        a_t = alpha * y + (1 - alpha) * (1 - y)
        loss = a_t * jnp.power(1 - p_t, gamma) * ce
        if n:
            loss = loss / n[0]
        return _reduce(loss, reduction)
    args = [logit, label] + ([normalizer] if normalizer is not None else [])
    return apply("sigmoid_focal_loss", impl, *args)


def kl_div(input, label, reduction="mean", name=None):
    """reference: operators/kldiv_loss_op.cc (input is log-prob)."""
    def impl(logp, y):
        loss = jnp.where(y > 0, y * (jnp.log(jnp.maximum(y, 1e-30)) - logp), 0.0)
        if reduction == "batchmean":
            return jnp.sum(loss) / logp.shape[0]
        return _reduce(loss, reduction)
    return apply("kldiv_loss", impl, input, label)


def smooth_l1_loss(input, label, reduction="mean", delta=1.0, name=None):
    """reference: operators/smooth_l1_loss_op.cc / huber semantics."""
    def impl(a, b):
        d = jnp.abs(a - b)
        loss = jnp.where(d < delta, 0.5 * d * d / delta, d - 0.5 * delta)
        return _reduce(loss, reduction)
    return apply("smooth_l1_loss", impl, input, label)


def huber_loss(input, label, delta=1.0):
    def impl(a, b):
        d = jnp.abs(a - b)
        return jnp.where(d <= delta, 0.5 * d * d, delta * (d - 0.5 * delta))
    return apply("huber_loss", impl, input, label)


def hinge_loss(input, label):
    return apply("hinge_loss",
                 lambda a, y: jnp.maximum(0.0, 1.0 - (2.0 * y - 1.0) * a), input, label)


def log_loss(input, label, epsilon=1e-4, name=None):
    return apply("log_loss",
                 lambda p, y: -y * jnp.log(p + epsilon)
                 - (1 - y) * jnp.log(1 - p + epsilon), input, label)


def margin_ranking_loss(input, other, label, margin=0.0, reduction="mean", name=None):
    return apply("margin_rank_loss",
                 lambda a, b, y: _reduce(jnp.maximum(0.0, -y * (a - b) + margin),
                                         reduction), input, other, label)


def cosine_similarity(x1, x2, axis=1, eps=1e-8):
    def impl(a, b):
        num = jnp.sum(a * b, axis=axis)
        den = jnp.sqrt(jnp.sum(a * a, axis=axis)) * jnp.sqrt(jnp.sum(b * b, axis=axis))
        return num / jnp.maximum(den, eps)
    return apply("cos_sim", impl, x1, x2)


def cosine_embedding_loss(input1, input2, label, margin=0.0, reduction="mean", name=None):
    def impl(a, b, y):
        cos = jnp.sum(a * b, axis=1) / jnp.maximum(
            jnp.linalg.norm(a, axis=1) * jnp.linalg.norm(b, axis=1), 1e-12)
        loss = jnp.where(y == 1, 1 - cos, jnp.maximum(0.0, cos - margin))
        return _reduce(loss, reduction)
    return apply("cosine_embedding_loss", impl, input1, input2, label)


def triplet_margin_loss(input, positive, negative, margin=1.0, p=2.0,
                        epsilon=1e-6, swap=False, reduction="mean", name=None):
    def impl(a, pos, neg):
        dp = jnp.power(jnp.sum(jnp.power(jnp.abs(a - pos), p), -1) + epsilon, 1 / p)
        dn = jnp.power(jnp.sum(jnp.power(jnp.abs(a - neg), p), -1) + epsilon, 1 / p)
        if swap:
            dsn = jnp.power(jnp.sum(jnp.power(jnp.abs(pos - neg), p), -1) + epsilon, 1 / p)
            dn = jnp.minimum(dn, dsn)
        return _reduce(jnp.maximum(dp - dn + margin, 0.0), reduction)
    return apply("triplet_margin_loss", impl, input, positive, negative)


def label_smooth(label, prior_dist=None, epsilon=0.1, name=None):
    """reference: operators/label_smooth_op.cc."""
    def impl(y, *pd):
        k = y.shape[-1]
        if pd:
            return (1 - epsilon) * y + epsilon * pd[0]
        return (1 - epsilon) * y + epsilon / k
    args = [label] + ([prior_dist] if prior_dist is not None else [])
    return apply("label_smooth", impl, *args)


def ctc_loss(log_probs, labels, input_lengths, label_lengths, blank=0,
             reduction="mean", norm_by_times=False):
    """CTC via dynamic-programming in log space (reference: warpctc op).
    log_probs: [T, N, C] (paddle layout), labels: [N, S]."""
    def impl(lp, lab, in_len, lab_len):
        T, N, C = lp.shape
        S = lab.shape[1]
        # extended label seq with blanks: length 2S+1
        ext = jnp.full((N, 2 * S + 1), blank, jnp.int32)
        ext = ext.at[:, 1::2].set(lab.astype(jnp.int32))
        ext_len = 2 * lab_len.astype(jnp.int32) + 1
        neg_inf = jnp.asarray(-1e30, lp.dtype)

        lp = jax.nn.log_softmax(lp, axis=-1)

        def emit(t):
            # [N, 2S+1] log prob of each extended symbol at time t
            return jnp.take_along_axis(lp[t], ext, axis=1)

        alpha0 = jnp.full((N, 2 * S + 1), neg_inf)
        alpha0 = alpha0.at[:, 0].set(emit(0)[:, 0])
        alpha0 = alpha0.at[:, 1].set(jnp.where(ext_len > 1, emit(0)[:, 1], neg_inf))

        same = jnp.concatenate(
            [jnp.zeros((N, 2), bool),
             ext[:, 2:] == ext[:, :-2]], axis=1)

        def step(alpha, t):
            shift1 = jnp.concatenate([jnp.full((N, 1), neg_inf), alpha[:, :-1]], 1)
            shift2 = jnp.concatenate([jnp.full((N, 2), neg_inf), alpha[:, :-2]], 1)
            shift2 = jnp.where(same, neg_inf, shift2)
            merged = jnp.logaddexp(jnp.logaddexp(alpha, shift1), shift2)
            new = merged + emit(t)
            keep = (t >= in_len)[:, None]
            return jnp.where(keep, alpha, new), None

        alphaT, _ = jax.lax.scan(step, alpha0, jnp.arange(1, T))
        idx_last = ext_len - 1
        ll_last = jnp.take_along_axis(alphaT, idx_last[:, None], 1)[:, 0]
        ll_prev = jnp.take_along_axis(alphaT, jnp.maximum(idx_last - 1, 0)[:, None], 1)[:, 0]
        loss = -jnp.logaddexp(ll_last, ll_prev)
        if norm_by_times:
            loss = loss / in_len.astype(loss.dtype)
        if reduction == "mean":
            return jnp.mean(loss / lab_len.astype(loss.dtype))
        return _reduce(loss, reduction)
    return apply("warpctc", impl, log_probs, labels, input_lengths, label_lengths)


def warpctc(input, label, blank=0, norm_by_times=False, input_length=None,
            label_length=None):
    """Fluid-era alias of ctc_loss (reference: operators/warpctc_op.cc;
    per-sequence losses, the op's raw output). Lengths default to the
    full padded extents."""
    import numpy as _np
    from ...core.tensor import Tensor as _T
    T_len = input.shape[0]
    S_len = label.shape[1]
    N = input.shape[1]
    if input_length is None:
        input_length = _T(_np.full((N,), T_len, _np.int64))
    if label_length is None:
        label_length = _T(_np.full((N,), S_len, _np.int64))
    return ctc_loss(input, label, input_length, label_length, blank=blank,
                    reduction="none", norm_by_times=norm_by_times)


def hinge_embedding_loss(input, label, margin=1.0, reduction="mean",
                         name=None):
    """reference: paddle.nn.functional.hinge_embedding_loss — label in
    {1, -1}: loss = x if y==1 else max(0, margin - x)."""
    def impl(x, y):
        val = jnp.where(y > 0, x, jnp.maximum(0.0, margin - x))
        return _reduce(val, reduction)
    return apply("hinge_embedding_loss", impl, input, label)


def rank_loss(label, left, right, name=None):
    """reference: operators/rank_loss_op.cc — pairwise RankNet loss:
    C = log(1 + exp(o)) - o * label with o = left - right."""
    def impl(lab, l, r):
        o = l - r
        return jnp.log1p(jnp.exp(-jnp.abs(o))) + jnp.maximum(o, 0.0) \
            - o * lab
    return apply("rank_loss", impl, label, left, right)


def dice_loss(input, label, epsilon=1e-5, name=None):
    """reference: python/paddle/fluid/layers/nn.py dice_loss — 1 - 2|X∩Y| /
    (|X|+|Y|); input [N, ..., C] probabilities, label [N, ..., 1] ids."""
    def impl(x, y):
        num_classes = x.shape[-1]
        oh = jax.nn.one_hot(y.squeeze(-1), num_classes, dtype=x.dtype)
        x_flat = x.reshape(x.shape[0], -1)
        y_flat = oh.reshape(x.shape[0], -1)
        inter = jnp.sum(x_flat * y_flat, axis=1)
        union = jnp.sum(x_flat, axis=1) + jnp.sum(y_flat, axis=1)
        # epsilon on the denominator ONLY — fluid layers.nn dice_loss
        return jnp.mean(1.0 - (2.0 * inter) / (union + epsilon))
    return apply("dice_loss", impl, input, label)


def ctc_greedy_decoder(input, blank=None, input_length=None, padding_value=0):
    """reference: operators/ctc_align_op.cc + fluid layers
    ctc_greedy_decoder — argmax per step then collapse repeats/blanks.
    input: [T, N, C] log-probs (paddle warpctc layout), or [N, T, C]
    when ``input_length`` is given (the padded+lengths convention);
    returns (decoded [N, T], lengths)."""
    from ...ops import beam as _beam

    batch_major = input_length is not None

    def impl(lp):
        ids = jnp.argmax(lp, axis=-1)      # [T, N] or [N, T]
        return ids if batch_major else ids.T
    ids = apply("ctc_argmax", impl, input)
    b = blank if blank is not None else 0
    return _beam.ctc_align(ids, blank=b, merge_repeated=True,
                           padding_value=padding_value,
                           lengths=input_length)
