"""The cache-view contract of ``models.gpt.gpt_block``: the same tokens fed
through any view give the hidden states of the whole sequence with no past.

One block, five views (``FullSequence``; ``SlotRows`` and ``PagedRows`` for
new tokens per slot; ``TailRows`` over a slice or a gather). Each case
feeds a toy prompt through one view on one schedule and compares every
position's final-norm hidden state with ``gpt_hidden`` over ``FullSequence``;
the paged gather lane must also equal the slot plane bit for bit on the same
schedule (same shapes, same reduction order), which is the contract the
engine-level parity tests stand on.
"""
import numpy as np
import pytest

import jax
import jax.numpy as jnp

from paddle_tpu.models.gpt import (FullSequence, GPTDecodeSpec, gpt_hidden,
                                   stack_kv)
from paddle_tpu.serving.llm.kvcache import (SlotRows, TailRows, valid_mask,
                                            write_prompt_kv)
from paddle_tpu.serving.llm.paged.pool import (PagedRows, paged_gather_rows,
                                               paged_write_prompts)

SPEC = GPTDecodeSpec(vocab_size=48, hidden_size=32, num_layers=2,
                     num_heads=4, max_position_embeddings=32)
B, L, MAX_SEQ, PAGE = 2, 12, 16, 4
PREFIX = 8                       # a page multiple: the tail starts there
PP = MAX_SEQ // PAGE


def _params(seed=0):
    """Wide weights, not the model's N(0, 0.02) initialisation: attention
    is then far from uniform, so a row read or masked wrongly moves the
    hidden states by much more than the tolerance."""
    rng = np.random.default_rng(seed)
    e, v = SPEC.hidden_size, SPEC.vocab_size

    def arr(*shape, scale=0.3, shift=0.0):
        return jnp.asarray(rng.standard_normal(shape) * scale + shift,
                           jnp.float32)

    layers = tuple(
        {"qw": arr(e, e), "qb": arr(e), "kw": arr(e, e), "kb": arr(e),
         "vw": arr(e, e), "vb": arr(e), "ow": arr(e, e), "ob": arr(e),
         "w1": arr(e, 4 * e), "b1": arr(4 * e), "w2": arr(4 * e, e),
         "b2": arr(e), "n1w": arr(e, scale=0.1, shift=1.0),
         "n1b": arr(e, scale=0.1), "n2w": arr(e, scale=0.1, shift=1.0),
         "n2b": arr(e, scale=0.1)} for _ in range(SPEC.num_layers))
    return {"tok": arr(v, e, scale=1.0),
            "pos": arr(SPEC.max_position_embeddings, e, scale=0.5),
            "fnw": arr(e, scale=0.1, shift=1.0), "fnb": arr(e, scale=0.1),
            "layers": layers}


PARAMS = _params()
TOKENS = jnp.asarray(np.random.default_rng(5).integers(0, 48, (B, L)),
                     jnp.int32)
# slots 0 and 1 own pages in an interleaved order, so that a gather which
# ignored the block table would read another slot's rows
BT = jnp.asarray(np.arange(B * PP).reshape(PP, B).T, jnp.int32)


def _buffers(plane, int8):
    lead = ((B, SPEC.num_layers, MAX_SEQ) if plane == "slot"
            else (B * PP + 1, SPEC.num_layers, PAGE))
    shape = lead + (SPEC.num_heads, SPEC.head_dim)

    def one():
        if int8:
            return {"q": jnp.zeros(shape, jnp.int8),
                    "s": jnp.zeros(shape[:3], jnp.float32)}
        return jnp.zeros(shape, jnp.float32)

    return one(), one()


def _whole(tokens):
    view = FullSequence()
    pos = jnp.arange(tokens.shape[1], dtype=jnp.int32)[None]
    return gpt_hidden(SPEC, PARAMS, tokens, pos, view), view


def _rows_view(plane, kbuf, vbuf, pos):
    if plane == "slot":
        return SlotRows(kbuf, vbuf, pos, jnp.float32)
    lane = "kernel" if plane == "paged-kernel" else "gather"
    return PagedRows(kbuf, vbuf, BT, pos, PAGE, lane, jnp.float32)


def _feed_windows(plane, int8, t):
    """``t`` new tokens per slot at a time from an empty cache: 3 is a
    verify window of k = 2; 1 is the decode tick, which has no T axis
    (``[S]`` tokens and positions, ``[S, E]`` hidden states)."""
    kbuf, vbuf = _buffers(plane, int8)
    out = []
    for start in range(0, L, t):
        pos = jnp.broadcast_to(jnp.arange(start, start + t,
                                          dtype=jnp.int32), (B, t))
        tokens = TOKENS[:, start:start + t]
        if t == 1:
            pos, tokens = pos[:, 0], tokens[:, 0]
        view = _rows_view(plane, kbuf, vbuf, pos)
        h = gpt_hidden(SPEC, PARAMS, tokens, pos, view)
        out.append(h[:, None] if t == 1 else h)
        kbuf, vbuf = (view.buffers() if plane == "slot"
                      else (view.kbuf, view.vbuf))
    return jnp.concatenate(out, axis=1)


def _feed_prefix_then_tail(plane):
    """The first PREFIX tokens as a whole prompt stored in the cache, the
    rest through the tail view at ``starts = PREFIX``."""
    kbuf, vbuf = _buffers(plane, False)
    head, whole = _whole(TOKENS[:, :PREFIX])
    slot_ids = jnp.arange(B, dtype=jnp.int32)
    starts = jnp.full((B,), PREFIX, jnp.int32)
    if plane == "slot":
        kbuf, vbuf = write_prompt_kv(kbuf, vbuf, *stack_kv(whole.kv, 1),
                                     slot_ids)

        def rows(buf, li):
            return buf[slot_ids, li]
    else:
        zeros = jnp.zeros((B,), jnp.int32)
        k_new, v_new = stack_kv(whole.kv, 2)
        kbuf = paged_write_prompts(kbuf, k_new, BT, slot_ids, zeros, starts,
                                   PAGE)
        vbuf = paged_write_prompts(vbuf, v_new, BT, slot_ids, zeros, starts,
                                   PAGE)

        def rows(buf, li):
            return paged_gather_rows(buf, BT[slot_ids], li)
    pos = starts[:, None] + jnp.arange(L - PREFIX, dtype=jnp.int32)[None]
    view = TailRows(rows, kbuf, vbuf, starts,
                    valid_mask(pos, MAX_SEQ, jnp.float32))
    tail = gpt_hidden(SPEC, PARAMS, TOKENS[:, PREFIX:], pos, view)
    assert len(view.kv) == SPEC.num_layers
    return jnp.concatenate([head, tail], axis=1)


SCHEDULES = {"step": lambda plane, int8: _feed_windows(plane, int8, 1),
             "verify": lambda plane, int8: _feed_windows(plane, int8, 3),
             "tail": lambda plane, int8: _feed_prefix_then_tail(plane)}

CASES = [(plane, schedule, int8)
         for plane in ("slot", "paged-gather")
         for schedule, int8 in (("step", False), ("step", True),
                                ("verify", False), ("verify", True),
                                ("tail", False))]
CASES.append(("paged-kernel", "step", False))


@pytest.mark.parametrize(
    "plane,schedule,int8", CASES,
    ids=[f"{p}-{s}-{'int8' if q else 'f32'}" for p, s, q in CASES])
def test_every_view_gives_the_whole_sequences_hidden_states(plane, schedule,
                                                            int8):
    want, _ = _whole(TOKENS)
    got = SCHEDULES[schedule](plane, int8)
    assert got.shape == want.shape == (B, L, SPEC.hidden_size)
    # float32 rows: the same sums in another order; int8 rows: each row
    # rounded to 1/127 of its largest element on the way into the cache
    tol = 0.15 if int8 else 2e-5
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), rtol=tol,
                               atol=tol)
    if plane == "paged-gather":
        slot = SCHEDULES[schedule]("slot", int8)
        assert np.asarray(got).tobytes() == np.asarray(slot).tobytes()


def test_int8_rows_are_not_silently_dense():
    """The int8 cases above compare against a loose tolerance; make sure
    they do run quantised rows (the hidden states differ from float32's)."""
    dense = _feed_windows("slot", False, 1)
    quant = _feed_windows("slot", True, 1)
    assert float(jnp.max(jnp.abs(dense - quant))) > 1e-4


def test_full_sequence_records_what_a_cache_keeps():
    _, view = _whole(TOKENS)
    assert len(view.kv) == SPEC.num_layers
    k, v = view.kv[0]
    assert k.shape == v.shape == (B, L, SPEC.num_heads, SPEC.head_dim)
    ks, _ = stack_kv(view.kv, 1)
    assert ks.shape == (B, SPEC.num_layers, L, SPEC.num_heads, SPEC.head_dim)


def test_views_trace_under_jit():
    """A view is built and consumed inside one traced program."""
    @jax.jit
    def step(kbuf, vbuf, tokens, lengths):
        view = SlotRows(kbuf, vbuf, lengths, jnp.float32)
        return gpt_hidden(SPEC, PARAMS, tokens, lengths, view), view.buffers()

    kbuf, vbuf = _buffers("slot", False)
    h, (kbuf, vbuf) = step(kbuf, vbuf, TOKENS[:, 0],
                           jnp.zeros((B,), jnp.int32))
    want, _ = _whole(TOKENS[:, :1])
    np.testing.assert_allclose(np.asarray(h), np.asarray(want[:, 0]),
                               rtol=2e-5, atol=2e-5)
    assert float(jnp.abs(kbuf[:, :, 0]).sum()) > 0
    assert float(jnp.abs(kbuf[:, :, 1:]).sum()) == 0
