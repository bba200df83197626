"""The comparisons that decide ``correct``: what the timed path produced,
against the plain reference, each number beside its limit.

Serving: the program hands back tokens only, so for a sample of the requests
the window finished the reference runs once over prompt + served tokens and
the number compared is the widest gap by which a served (greedy) token's
reference logit lies below the reference's best at that position. Training:
the losses of the first three steps, the first gradient as the optimizer got
it and the parameters' change after three steps, the last two by the worst
leaf.
"""
from __future__ import annotations

import functools
import statistics

import jax
import jax.numpy as jnp
import numpy as np

from .reference import gpt2_ref as ref
from .traffic import rng_for


# -- serving -------------------------------------------------------------------

@functools.partial(jax.jit, static_argnums=(1,))
def _served_gaps(w, arch, seq, rows, served):
    """Per served position, how far the served token's reference logit lies
    below the reference's best. ``seq`` is prompt + served tokens padded to
    a fixed length; ``rows`` are the positions whose logits predict the
    served tokens (padded with 0)."""
    logits = ref.logits_of(w, ref.hidden_states(w, arch, seq)[rows])
    best = jnp.max(logits, axis=-1)
    return best - jnp.take_along_axis(logits, served[:, None], -1)[:, 0]


@functools.partial(jax.jit, static_argnums=(1, 4))
def _first_tokens(w, arch, seq, rows, mode):
    """The token a pass in the lower precision ``mode`` puts first at each
    of ``rows``: what a program computing in that precision would serve."""
    low = ref.logits_of(w, ref.hidden_states(w, arch, seq, mode)[rows], mode)
    return jnp.argmax(low, axis=-1).astype(jnp.int32)


def sample_finished(records, seed, count):
    """``count`` finished requests drawn by the seed, the longest included."""
    done = [r for r in records if r["finished"] and r["tokens"]]
    if not done:
        return []
    longest = max(done, key=lambda r: len(r["prompt"]) + len(r["tokens"]))
    rest = [r for r in done if r is not longest]
    order = rng_for(seed, "check").permutation(len(rest))
    return [longest] + [rest[i] for i in order[:max(count - 1, 0)]]


def serve_gaps(w, cfg, sample, pad_len, max_new, control_modes=()):
    """Widest served-token gap over the sample, how many tokens were
    compared and, for each control mode, the widest gap of the tokens that
    precision puts first. One compiled program serves every request."""
    arch = ref.arch_of(cfg)
    out = {"served_token_gap": 0.0, "tokens_compared": 0}
    out.update({f"control_{m}_token_gap": 0.0 for m in control_modes})
    for r in sample:
        plen, n = len(r["prompt"]), len(r["tokens"])
        seq = np.zeros(pad_len, np.int32)
        seq[:plen] = r["prompt"]
        seq[plen:plen + n - 1] = r["tokens"][:-1]
        rows = np.zeros(max_new, np.int32)
        rows[:n] = plen - 1 + np.arange(n)
        served = np.zeros(max_new, np.int32)
        served[:n] = r["tokens"]
        seq, rows = jnp.asarray(seq), jnp.asarray(rows)
        gaps = {"served_token_gap":
                _served_gaps(w, arch, seq, rows, jnp.asarray(served))}
        for m in control_modes:
            gaps[f"control_{m}_token_gap"] = _served_gaps(
                w, arch, seq, rows, _first_tokens(w, arch, seq, rows, m))
        for k, g in gaps.items():
            out[k] = max(out[k], float(jnp.max(g[:n])))
        out["tokens_compared"] += n
    return out


# -- training ------------------------------------------------------------------

def leaf_norms(tree: dict) -> dict:
    """``{name: L2 norm}`` of a dict of arrays, fetched to the host."""
    names = sorted(tree)
    norms = jax.jit(lambda t: [jnp.sqrt(jnp.sum(jnp.square(
        t[k].astype(jnp.float32)))) for k in names])(tree)
    return dict(zip(names, (float(x) for x in jax.device_get(norms))))


def worst_leaf_gap(got: dict, want: dict, what: str) -> float:
    """Largest ``|got - want|`` over the leaves, each against the reference's
    norm of that leaf or of the median leaf, whichever is larger (some
    gradients are all but zero)."""
    floor = statistics.median(want.values())
    gap, leaf = max((abs(got[k] - want[k]) / max(want[k], floor), k)
                    for k in want)
    print(f"worst leaf of {what}: {leaf}, program {got[leaf]:.6g} against "
          f"reference {want[leaf]:.6g} (median leaf {floor:.6g})", flush=True)
    return gap


def reference_train_numbers(w, cfg, batches, opt, mode="highest"):
    """The reference's three steps: losses, first-gradient norms by leaf and
    the norms of the parameters' change after the last step."""
    arch = ref.arch_of(cfg)
    w0 = w
    m = jax.tree.map(jnp.zeros_like, w)
    v = jax.tree.map(jnp.zeros_like, w)
    losses, grad_norms = [], None
    for step, batch in enumerate(batches, start=1):
        loss, grads = ref.batch_loss_and_grads(w, arch, batch, mode)
        if grad_norms is None:
            grad_norms = leaf_norms(grads)
        losses.append(float(loss))
        w, m, v = ref.adamw_step(w, grads, m, v, float(step), opt["lr"],
                                 opt["beta1"], opt["beta2"], opt["epsilon"],
                                 opt["weight_decay"])
    delta = leaf_norms({k: w[k] - w0[k] for k in w})
    return {"losses": losses, "grad_norms": grad_norms, "delta_norms": delta}


#: a leaf whose first gradient is below this share of the median leaf's is
#: left out of the parameters' change: its gradient is zero in exact
#: arithmetic (a key bias shifts every score of a row alike, and softmax does
#: not see it), so Adam scales pure rounding noise up to full-size updates
ZERO_GRADIENT_SHARE = 1e-3


def train_gaps(got: dict, want: dict) -> dict:
    floor = ZERO_GRADIENT_SHARE * statistics.median(
        want["grad_norms"].values())
    live = [k for k, g in want["grad_norms"].items() if g >= floor]
    print(f"parameters' change compared on {len(live)} of "
          f"{len(want['grad_norms'])} leaves (the rest have no gradient "
          f"but rounding noise)", flush=True)
    return {
        "loss_gap": max(abs(a - b) / abs(b)
                        for a, b in zip(got["losses"], want["losses"])),
        "first_grad_gap": worst_leaf_gap(got["grad_norms"],
                                         want["grad_norms"], "first gradient"),
        "param_change_gap": worst_leaf_gap(
            {k: got["delta_norms"][k] for k in live},
            {k: want["delta_norms"][k] for k in live}, "parameters' change"),
    }


# -- the verdict ---------------------------------------------------------------

def judge(numbers: dict, limits: dict) -> bool:
    """Print each number compared beside its limit; all must hold. A number
    the limits file does not name is an error, not a pass."""
    ok = True
    for name, limit in limits.items():
        value = numbers[name]
        holds = bool(np.isfinite(value)) and value <= limit
        print(f"check: {name} = {value:.6g} (limit {limit:.6g}) "
              f"{'ok' if holds else 'FAILED'}", flush=True)
        ok = ok and holds
    return ok
