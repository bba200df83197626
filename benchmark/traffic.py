"""The one traffic generator: a traffic file declares distributions, this
draws a run's requests from them and the seed.

The draw is stratified. For ``n`` values of a distribution, value ``i`` is
the distribution's quantile at ``(i + u_i) / n`` with ``u_i`` uniform from
the seed, and the values are then shuffled by the seed. Every run therefore
has the same marginal distribution (to within one stratum) and a different
order and content: run-to-run differences come from the system, not from
one run having drawn longer prompts than another.

Distributions (``{"dist": ..., ...}``), those the traffic files use:
``constant`` (value), ``uniform_int`` (lo, hi inclusive), ``exponential``
(mean). A later ``benchmark`` PR whose mix needs another shape adds its
inverse CDF to ``quantile``.
"""
from __future__ import annotations

import numpy as np

SEED_MASK = (1 << 63) - 1


def rng_for(seed: int, stream: str) -> np.random.Generator:
    """An independent generator per (seed, purpose); any whole-number seed."""
    tag = int.from_bytes(stream.encode()[:8].ljust(8, b"\0"), "little")
    return np.random.default_rng([int(seed) & SEED_MASK, tag])


def quantile(spec: dict, q: np.ndarray) -> np.ndarray:
    """Inverse CDF of the declared distribution at ``q`` in [0, 1)."""
    kind = spec["dist"]
    q = np.asarray(q, np.float64)
    if kind == "constant":
        return np.full(q.shape, spec["value"], np.float64)
    if kind == "uniform_int":
        lo, hi = int(spec["lo"]), int(spec["hi"])
        return np.minimum(lo + np.floor(q * (hi - lo + 1)), hi)
    if kind == "exponential":
        return -spec["mean"] * np.log1p(-q)
    raise ValueError(f"unknown distribution {kind!r}")


def stratified(spec: dict, n: int, rng: np.random.Generator) -> np.ndarray:
    """``n`` values, one per stratum of the distribution, shuffled."""
    q = (np.arange(n) + rng.random(n)) / n
    v = quantile(spec, q)
    rng.shuffle(v)
    return v


def _tokens(rng, n, vocab):
    return rng.integers(0, vocab, size=int(n), dtype=np.int32)


def open_requests(traffic: dict, seed: int, seconds: float, vocab: int):
    """The requests of an open-loop run: ``[{"due_s", "prompt",
    "max_new_tokens"}]`` in due order. ``n = rate * seconds`` requests;
    prompt lengths, output lengths and inter-arrival gaps are each
    stratified. A gap's distribution is declared with mean 1: only its shape
    matters, the rate sets the scale."""
    n = max(1, int(round(traffic["rate_per_s"] * seconds)))
    plens = stratified(traffic["prompt_len"], n, rng_for(seed, "plen"))
    olens = stratified(traffic["output_len"], n, rng_for(seed, "olen"))
    gaps = stratified(traffic["arrival_gap"], n, rng_for(seed, "gap"))
    # scaled so that the gaps fill the window exactly: every seed then has
    # all n requests due inside it (the strata's jitter moves the sum by a
    # few percent, which would cut the last requests of some seeds)
    due = np.cumsum(gaps) * (seconds / gaps.sum())
    due -= due[0]
    rng = rng_for(seed, "tokens")
    return [{"due_s": float(d), "prompt": _tokens(rng, int(p), vocab),
             "max_new_tokens": int(o)}
            for d, p, o in zip(due, plens, olens)]


def closed_requests(traffic: dict, seed: int, vocab: int, count: int):
    """Per client, a list of ``count`` requests for a closed loop. Lengths
    are stratified over each block of ``traffic["block"]`` consecutive
    requests of one client, so whatever prefix of its list a client gets
    through, it has seen nearly the declared distribution."""
    clients, block = int(traffic["clients"]), int(traffic["block"])
    rng = rng_for(seed, "tokens")
    out = []
    for c in range(clients):
        rp, ro = rng_for(seed, f"p{c}"), rng_for(seed, f"o{c}")
        plens = np.concatenate([stratified(traffic["prompt_len"], block, rp)
                                for _ in range(-(-count // block))])
        olens = np.concatenate([stratified(traffic["output_len"], block, ro)
                                for _ in range(-(-count // block))])
        out.append([{"prompt": _tokens(rng, int(p), vocab),
                     "max_new_tokens": int(o)}
                    for p, o in zip(plens[:count], olens[:count])])
    return out


def train_rows(traffic: dict, seed: int, vocab: int):
    """An endless stream of distinct token rows of ``seq_len`` tokens."""
    rng = rng_for(seed, "rows")
    while True:
        yield _tokens(rng, traffic["seq_len"], vocab)
