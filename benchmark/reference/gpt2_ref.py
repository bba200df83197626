"""Plain reference of the GPT-2 architecture: ``jax.numpy``, float32, matrix
multiplications at precision ``highest``, no kernel, no cache, no batching.

Follows Radford et al. 2019 (GPT-2) as the configurations here state it:
learned positions, pre-norm blocks, full multi-head causal attention,
LayerNorm, a GELU feed-forward and an output head tied to the token
embedding. Departure noted in the configuration files: the GELU is the exact
(erf) form for every configuration. It imports nothing of the program.

``mode`` lowers the precision of every matrix multiplication, for the
controls that must come out as not correct: ``highest`` (the reference),
``high`` (three bf16 passes), ``bfloat16`` (operands rounded to bf16) and
``fp8`` (operands rounded to float8_e4m3fn).
"""
from __future__ import annotations

import functools
from typing import NamedTuple

import jax
import jax.numpy as jnp

MODES = ("highest", "high", "bfloat16", "fp8")


def _lower(x, mode):
    if mode == "bfloat16":
        return x.astype(jnp.bfloat16).astype(jnp.float32)
    if mode == "fp8":
        return x.astype(jnp.float8_e4m3fn).astype(jnp.float32)
    return x


def _ein(eq, a, b, mode):
    prec = jax.lax.Precision.HIGH if mode == "high" \
        else jax.lax.Precision.HIGHEST
    return jnp.einsum(eq, _lower(a, mode), _lower(b, mode), precision=prec)


def _layer_norm(x, w, b, eps):
    mu = jnp.mean(x, axis=-1, keepdims=True)
    var = jnp.mean((x - mu) ** 2, axis=-1, keepdims=True)
    return (x - mu) / jnp.sqrt(var + eps) * w + b


def _block(w, p, x, heads, eps, mode):
    s, h = x.shape
    d = h // heads
    a = _layer_norm(x, w[p + "ln1_w"], w[p + "ln1_b"], eps)
    q = (_ein("sh,hk->sk", a, w[p + "q_w"], mode) + w[p + "q_b"])
    k = (_ein("sh,hk->sk", a, w[p + "k_w"], mode) + w[p + "k_b"])
    v = (_ein("sh,hk->sk", a, w[p + "v_w"], mode) + w[p + "v_b"])
    q, k, v = (t.reshape(s, heads, d) for t in (q, k, v))
    scores = _ein("qnd,knd->nqk", q, k, mode) / jnp.sqrt(jnp.float32(d))
    causal = jnp.tril(jnp.ones((s, s), bool))
    probs = jax.nn.softmax(jnp.where(causal, scores, -jnp.inf), axis=-1)
    ctx = _ein("nqk,knd->qnd", probs, v, mode).reshape(s, h)
    x = x + _ein("sh,hk->sk", ctx, w[p + "o_w"], mode) + w[p + "o_b"]
    a = _layer_norm(x, w[p + "ln2_w"], w[p + "ln2_b"], eps)
    a = _ein("sh,hf->sf", a, w[p + "fc_w"], mode) + w[p + "fc_b"]
    a = jax.nn.gelu(a, approximate=False)
    return x + _ein("sf,fh->sh", a, w[p + "proj_w"], mode) + w[p + "proj_b"]


class Arch(NamedTuple):
    """What the forward pass reads from a configuration (hashable, so a
    jitted function can take it as a static argument)."""
    n_layer: int
    n_head: int
    eps: float


def arch_of(cfg: dict) -> Arch:
    return Arch(cfg["n_layer"], cfg["n_head"], cfg["layer_norm_epsilon"])


def hidden_states(w, arch, tokens, mode="highest", remat=False):
    """Final-norm hidden states ``[S, h]`` of one token row ``[S]``."""
    s = tokens.shape[0]
    x = w["wte"][tokens] + w["wpe"][:s]
    blk = functools.partial(_block, heads=arch.n_head, eps=arch.eps,
                            mode=mode)
    if remat:   # save memory in the backward pass; the arithmetic is the same
        blk = jax.checkpoint(blk, static_argnums=(1,))
    for i in range(arch.n_layer):
        x = blk(w, f"h{i}.", x)
    return _layer_norm(x, w["lnf_w"], w["lnf_b"], arch.eps)


def logits_of(w, hidden, mode="highest"):
    return _ein("sh,vh->sv", hidden, w["wte"], mode)


def row_loss_sum(w, arch, tokens, mode="highest"):
    """Sum over positions 0..S-2 of the cross entropy of the next token."""
    logits = logits_of(w, hidden_states(w, arch, tokens, mode, remat=True),
                       mode)
    logp = jax.nn.log_softmax(logits[:-1], axis=-1)
    return -jnp.sum(jnp.take_along_axis(logp, tokens[1:, None], axis=-1))


_row_value_and_grad = jax.jit(jax.value_and_grad(row_loss_sum),
                              static_argnums=(1, 3))


def batch_loss_and_grads(w, arch, batch, mode="highest"):
    """Mean next-token loss over a ``[B, S]`` batch and its gradients, one
    row at a time so that a long sequence fits."""
    total, grads = 0.0, None
    for row in batch:
        v, g = _row_value_and_grad(w, arch, jnp.asarray(row), mode)
        total = total + v
        grads = g if grads is None else jax.tree.map(jnp.add, grads, g)
    n = batch.shape[0] * (batch.shape[1] - 1)
    return total / n, jax.tree.map(lambda g: g / n, grads)


@jax.jit
def adamw_step(w, grads, m, v, step, lr, beta1, beta2, eps, decay):
    """Decoupled weight decay (Loshchilov & Hutter), bias-corrected Adam."""
    def one(p, g, m_, v_):
        p = p * (1.0 - lr * decay)
        m2 = beta1 * m_ + (1.0 - beta1) * g
        v2 = beta2 * v_ + (1.0 - beta2) * g * g
        mhat = m2 / (1.0 - beta1 ** step)
        vhat = v2 / (1.0 - beta2 ** step)
        return p - lr * mhat / (jnp.sqrt(vhat) + eps), m2, v2
    out = {k: one(w[k], grads[k], m[k], v[k]) for k in w}
    return ({k: o[0] for k, o in out.items()},
            {k: o[1] for k, o in out.items()},
            {k: o[2] for k, o in out.items()})
