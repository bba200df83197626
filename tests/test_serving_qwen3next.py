"""Qwen3-Next on the paged engine: prompts prefilled whole, in chunks of 16
and in chunks of 8 (``gated_delta_chunked`` from the slot's carried state, the
full layers through the chunk lane's page-tile walk), then decoded through the
pages and the states (``gated_delta_step``), against the plain reference's
full forward pass (``benchmark/reference/qwen3next_ref.py``: the recurrence a
token a step), at toy width on the CPU; slots reused, so that a stale state
would show; the counters and gauges the family adds; what is refused.

Tolerances. Logits agree to float32 reassociation, 1e-3 absolute on logits of
spread one (``tests/test_qwen3next.py`` says where the linear layers' share of
it comes from). Whole, chunks of 16 and chunks of 8 are held to the same bound
against the one reference. Served tokens are compared as the benchmark
compares them: the served token's reference logit may lie below the
reference's best by at most ``GAP`` = 1e-3, three times the closest pair of
logits the seeded head makes (a pair is swapped where the logits differ by
more than its gap; a token outside its pair lies 0.1 and more below).
Pages hold 8 rows: prompts of 1-61 end inside a page (5, 13, 27), on a page's
boundary (16, 40) and mid-chunk.
"""
import time

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from paddle_tpu.core.monitor import StatRegistry
from paddle_tpu.models.qwen3next import qwen3next_hidden
from paddle_tpu.serving.llm import LLMEngine, LLMEngineConfig
from paddle_tpu.serving.llm.paged import (Qwen3NextPagedDecoder,
                                          paged_decoder_class)
from paddle_tpu.serving.llm.paged.qwen3next import PagedChunk, PagedStep
from tests.test_qwen3next import reference_logits, seeded  # noqa: F401

pytestmark = pytest.mark.timeout_s(900)
ATOL, GAP, PAGE, MAX_SEQ = 1e-3, 1e-3, 8, 96


def _engine(net, impl="gather", chunk=16, **over):
    kw = dict(kv_layout="paged", num_slots=2, max_seq=MAX_SEQ,
              page_size=PAGE, num_pages=26, prefill_buckets=[16, 32, 64],
              max_top_k=4, paged_attn_impl=impl, prefill_chunk=chunk)
    kw.update(over)
    return LLMEngine(net, LLMEngineConfig(**kw), registry=StatRegistry())


def _served_gap(cfg, prompt, tokens):
    """The benchmark's comparison of one request."""
    seq = np.concatenate([prompt, tokens[:-1]]).astype(np.int32)
    logits = reference_logits(cfg, seq)[0][len(prompt) - 1:]
    return float((logits.max(-1)
                  - logits[np.arange(len(tokens)), tokens]).max())


def _stats(eng):
    pre = eng.config.stat_prefix + "."
    st = eng.stats()
    return {k[len(pre):]: v for k, v in st["stats"].items()}, st


# -- through the engine's normal entry ---------------------------------------------

@pytest.mark.parametrize("chunk,impl", [(None, "gather"), (16, "gather"),
                                        (8, "kernel")])
def test_engine_serves_what_the_reference_puts_first(seeded, chunk, impl):
    """Seven prompts one after another through two slots: every request but
    the first two inherits a slot whose states another request left."""
    cfg, net = seeded
    rng = np.random.default_rng(0)
    eng = _engine(net, impl, chunk)
    try:
        assert isinstance(eng.decoder, Qwen3NextPagedDecoder)
        assert paged_decoder_class(net) is Qwen3NextPagedDecoder
        _, st = _stats(eng)
        assert st["paged_attn_impl"] == impl
        # 2 query heads on a KV head's own fused rows, a head a call: the MXU's
        assert st["paged_attn_recurrence"] == (
            "mxu" if impl == "kernel" else None)
        for plen in (1, 5, 13, 16, 27, 40, 61):
            prompt = rng.integers(0, cfg["vocab_size"], plen).astype(np.int32)
            got = eng.generate(prompt, max_new_tokens=12)
            assert got["finish_reason"] == "length"
            assert _served_gap(cfg, prompt, np.asarray(got["tokens"])) <= GAP
        assert eng._batcher.kv.pool.pages_in_use == 0
    finally:
        eng.drain(timeout=30)


def test_prompts_enter_together_and_the_counters_say_what_the_cache_holds(
        seeded):
    """Two slots: one prompt's chunks run between the other's decode ticks.
    Every new counter and gauge is read here."""
    cfg, net = seeded
    rng = np.random.default_rng(1)
    eng = _engine(net, "gather", 8)
    try:
        counters, _ = _stats(eng)
        kv = eng._batcher.kv
        # pages for the ONE full layer of the toy's four, head-major: 26 + 1
        # pages, 2 KV heads, 8 rows of [K 32 | V 32]; no second arena
        assert kv.k.shape == (27, 2, PAGE, 64) and kv.v.shape == (0,)
        assert 2 * kv.row_nbytes() == 2 * 64 * 4 == counters["kv_row_bytes"]
        # a slot and linear layer: 4 value heads of [16, 16], and 3 rows of
        # the 128 convolved channels
        assert sorted(kv.state) == sorted(
            f"{kind}{li}" for kind in ("gdn", "conv") for li in range(3))
        assert kv.state["gdn2"].shape == (2, 4, 16, 16)
        assert kv.state["conv0"].shape == (2, 3, 128)
        assert counters["gdn_state_bytes"] == kv.state_bytes() \
            == 2 * 3 * (4 * 16 * 16 + 3 * 128) * 4
        prompts = [rng.integers(0, cfg["vocab_size"], n).astype(np.int32)
                   for n in (44, 13, 57, 35)]
        reqs = [eng.submit(p, max_new_tokens=10) for p in prompts]
        for p, r in zip(prompts, reqs):
            tokens = np.asarray(r.result(timeout=300)["tokens"])
            assert _served_gap(cfg, p, tokens) <= GAP
        counters, _ = _stats(eng)
        assert counters["prefill_chunks"] == sum(-(-len(p) // 8)
                                                 for p in prompts)
        assert counters["prefills"] == 4
        # every prompt token passed the 3 linear layers' scan, every decoded
        # token their step
        assert counters["gdn.chunk_rows"] == 3 * sum(len(p) for p in prompts)
        decoded = counters["tokens_generated"] - counters["prefills"]
        assert counters["gdn.step_rows"] == 3 * decoded == 3 * 4 * 9
        # the held experts' counters: 4 of 8 held in all 4 layers
        assert counters["moe_experts_active"] > 0
        assert counters["moe_load_max"] > 0
        assert counters["moe_pairs_routed"] == 2 * 4 * decoded
    finally:
        eng.drain(timeout=30)


def test_the_cache_gives_everything_back_and_a_slots_next_tenant_starts_afresh(
        seeded):
    """After finish, after a deadline in the middle of a prompt, every page
    is free again; and the same prompt served again in a slot that another
    request's states were left in gives the same tokens."""
    cfg, net = seeded
    rng = np.random.default_rng(2)
    eng = _engine(net, "gather", 8)
    try:
        kv = eng._batcher.kv
        prompt = rng.integers(0, cfg["vocab_size"], 50).astype(np.int32)
        first = eng.generate(prompt, max_new_tokens=6)["tokens"]
        assert kv.pool.pages_in_use == 0 and kv.free_slots == 2
        assert float(jnp.abs(kv.state["gdn0"]).max()) > 0   # left behind
        # a deadline that passes while the prompt is still entering
        req = eng.submit(rng.integers(0, cfg["vocab_size"], 90), deadline=0.0,
                         max_new_tokens=4)
        with pytest.raises(Exception):
            req.result(timeout=60)
        deadline = time.time() + 30
        while eng._batcher.active and time.time() < deadline:
            time.sleep(0.01)
        assert kv.pool.pages_in_use == 0 and kv.free_slots == 2
        other = rng.integers(0, cfg["vocab_size"], 33).astype(np.int32)
        eng.generate(other, max_new_tokens=6)
        assert eng.generate(prompt, max_new_tokens=6)["tokens"] == first
        assert kv.pool.total_allocs == kv.pool.total_releases
    finally:
        eng.drain(timeout=30)


# -- at program level: logits of every row -----------------------------------------

def _prefill_in_chunks(dec, kv, params, row, plen, chunk, slot):
    """Logits of every prompt row, the prompt entering ``chunk`` tokens at
    a time."""
    @jax.jit
    def run(kvbuf, state, tables, tokens, start, n):
        view = PagedChunk(dec.spec, kvbuf, state, tables, jnp.asarray(slot),
                          start, n)
        pos = (start + jnp.arange(tokens.shape[1]))[None]
        h, _ = qwen3next_hidden(dec.spec, params, tokens, pos, view)
        return h[0] @ params["head"], view.kvbuf, view.state

    logits = []
    for start in range(0, plen, chunk):
        n = min(chunk, plen - start)
        padded = np.zeros((1, chunk), np.int32)
        padded[0, :n] = row[start:start + n]
        kv.ensure_pages(slot, start + n)
        out, kvbuf, state = run(kv.k, kv.state, kv.block_tables,
                                jnp.asarray(padded), jnp.asarray(start),
                                jnp.asarray(n))
        kv.swap(kvbuf, kv.v, kv.lengths, state)
        logits.append(np.asarray(out)[:n])
    return np.concatenate(logits)


@pytest.mark.parametrize("plen,chunk,impl", [
    (43, 8, "gather"), (48, 16, "kernel"), (21, 64, "gather"),
    (43, 16, "kernel"), (70, 64, "gather")])
def test_chunks_then_decode_logits_match_the_full_forward(seeded, chunk,
                                                          impl, plen):
    """A prompt in slot 1 (in a page, on its boundary, mid-chunk; a chunk of
    64 is the rule's own chunk and 70 rows two of them), then paged decode to
    76 rows: the logits of every row against the reference's. The slot's
    states start as junk another tenant left: a first chunk must not read
    them."""
    cfg, net = seeded
    dec = Qwen3NextPagedDecoder(net, page_size=PAGE, num_pages=24,
                                attn_impl=impl)
    kv = dec.new_kv(2, MAX_SEQ)
    kv.state = {k: jnp.full_like(v, 3.0) for k, v in kv.state.items()}
    params = dec.params()
    row = np.random.default_rng(7).integers(0, cfg["vocab_size"],
                                            76).astype(np.int32)
    want, _ = reference_logits(cfg, row)
    kv.alloc()
    slot = kv.alloc()
    got = _prefill_in_chunks(dec, kv, params, row, plen, chunk, slot)
    np.testing.assert_allclose(got, want[:plen], atol=ATOL, rtol=0)
    # slot 0, which nobody serves, kept what it had
    assert float(kv.state["gdn1"][0].min()) == 3.0

    @jax.jit
    def step(kvbuf, state, tables, lengths, tokens):
        view = PagedStep(dec.spec, kvbuf, state, tables, lengths,
                         jnp.asarray([True, False]), impl)
        h, counts = qwen3next_hidden(dec.spec, params, tokens[:, None],
                                     lengths[:, None], view)
        return (h[:, 0] @ params["head"], view.kvbuf, view.state,
                lengths + 1, jnp.stack(counts))

    lengths = jnp.asarray([0, plen], jnp.int32)
    for t in range(plen, 76):
        kv.ensure_pages(slot, t + 1)
        logits, kvbuf, state, lengths, counts = step(
            kv.k, kv.state, kv.block_tables, lengths,
            jnp.asarray([0, row[t]], jnp.int32))
        kv.swap(kvbuf, kv.v, kv.lengths, state)
        np.testing.assert_allclose(logits[1], want[t], atol=ATOL, rtol=0,
                                   err_msg=f"position {t}")
    # two tokens' pairs over the held half of 8 experts, 4 layers
    assert counts.shape == (4, 4) and int(counts.sum()) <= 2 * 2 * 4
    # the frozen slot's states are EXACTLY what they were
    assert float(kv.state["gdn2"][0].min()) == 3.0 \
        == float(kv.state["conv2"][0].max())


def test_the_programs_carry_the_scopes_the_readers_ask_for(seeded):
    """``gdn_ms_per_chunk``, ``gdn_scan_ms_per_chunk`` and
    ``gdn_step_ms_per_tick`` ask ``benchmark/scope_time.py`` for these scope
    paths of ``jit__step`` and ``jit__chunk``; ``opscope`` reads them from
    the compiled programs' ``op_name``s."""
    import re
    from paddle_tpu.observability.opscope import scope_of
    from paddle_tpu.serving.llm.paged.qwen3next import (
        build_qwen3next_paged_chunk_fn, build_qwen3next_paged_decode_step)
    _, net = seeded
    dec = Qwen3NextPagedDecoder(net, max_top_k=4, page_size=PAGE,
                                num_pages=24, attn_impl="gather")
    kv = dec.new_kv(2, MAX_SEQ)
    params, fin = dec.params(), jnp.zeros((2,), bool)
    samp = (jnp.ones((2,)), jnp.zeros((2,), jnp.int32), fin,
            jnp.full((2,), -1, jnp.int32))
    key = jax.random.PRNGKey(0)

    def scopes(fn, *args):
        text = jax.jit(fn).lower(*args).compile().as_text()
        return {scope_of(op)[0]
                for op in set(re.findall(r'op_name="([^"]+)"', text))}

    step = scopes(build_qwen3next_paged_decode_step(dec.spec, 4, "gather"),
                  params, kv.k, kv.state, kv.block_tables, kv.lengths, fin,
                  jnp.zeros((2,), jnp.int32), *samp, key)
    for name in ("proj", "conv", "gdn_step", "gate_norm", "out"):
        assert any(s.endswith("qwen3next/gdn/" + name) for s in step), name
    assert {"qwen3next/moe_route", "qwen3next/moe_experts",
            "qwen3next/shared_expert", "qwen3next/norm"} <= step
    assert any(s.startswith("qwen3next/attn") for s in step)
    assert not any("gdn_scan" in s or "chunk_walk" in s for s in step)
    chunk = scopes(build_qwen3next_paged_chunk_fn(dec.spec, 4), params,
                   jnp.zeros((1, 16), jnp.int32), jnp.asarray(16),
                   jnp.asarray(16), jnp.asarray(True), kv.k, kv.state,
                   kv.block_tables, kv.lengths, fin, jnp.asarray(0),
                   *(x[:1] for x in samp), key)
    for name in ("proj", "conv", "gdn_scan", "gate_norm", "out"):
        assert any(s.endswith("qwen3next/gdn/" + name) for s in chunk), name
    assert any(s.endswith("qwen3next/attn/chunk_walk") for s in chunk)
    assert not any(s.endswith("/gdn_step") for s in chunk)


# -- what is refused -----------------------------------------------------------------

@pytest.mark.parametrize("option", [
    {"kv_layout": "slot"}, {"prefix_cache": True}, {"spec_k": 2},
    {"weight_dtype": "int8"}, {"kv_dtype": "int8"}])
def test_unsupported_option_raises_at_construction(seeded, option):
    _, net = seeded
    kw = dict(kv_layout="paged", num_slots=1, max_seq=32, page_size=PAGE,
              prefill_buckets=[16], warmup=False)
    kw.update(option)
    with pytest.raises(NotImplementedError):
        LLMEngine(net, LLMEngineConfig(**kw), draft_model=net)


def test_a_mesh_export_and_a_ragged_chunk_raise(seeded):
    _, net = seeded
    with pytest.raises(NotImplementedError, match="mesh"):
        Qwen3NextPagedDecoder(net, mesh=object())
    with pytest.raises(ValueError, match="multiple of the page"):
        _engine(net, chunk=12)
    with pytest.raises(ValueError, match="positions"):
        _engine(net, max_seq=512, num_pages=80, warmup=False)
    dec = Qwen3NextPagedDecoder(net, page_size=PAGE, attn_impl="gather")
    with pytest.raises(ValueError, match="gated delta rule's chunk"):
        dec.chunk_fn(72)
    with pytest.raises(NotImplementedError, match="snapshot"):
        dec.check_config(LLMEngineConfig(kv_layout="paged", spec_k=2,
                                         page_size=PAGE, max_seq=32,
                                         warmup=False))
    eng = _engine(net, warmup=False)
    try:
        assert not eng.supports_migration
        with pytest.raises(NotImplementedError):
            eng.export_sequences()
    finally:
        eng.drain(timeout=30)
