"""MiniCPM-SALA on the paged engine: prompts prefilled whole, in chunks of 16
and in chunks of 8, then decoded through the pages, against the plain
reference's full forward pass (``benchmark/reference/sala_ref.py``), at toy
width on the CPU; what the cache holds and gives back; what is refused.

Tolerances. Logits agree to float32 reassociation, 5e-5 absolute on logits of
spread one: a chunk sums the linear layers by sub-chunks from a carried state
where the reference runs the recurrence, the sparse layers read pages in
tiles under an online softmax where the reference takes one softmax, and the
compressed keys are means summed in another order. Whole, chunks of 16 and
chunks of 8 are held to the same bound against the one reference (so they
agree with one another to twice it). Served tokens are compared as the
benchmark compares them: the served token's reference logit may lie below
the reference's best by at most ``GAP`` = 1e-4, a third of the closest pair
of logits the seeded head makes. The toy sizes make every context past 32
tokens select 4 of its blocks of 8; prompts of 27-61 and 12 decoded tokens
cross that line mid-chunk and mid-decode.
"""
import json
import os
import time

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from benchmark import sala_adapter, sala_weights, spec as bench_spec
from benchmark.reference import sala_ref as ref
from paddle_tpu.core.monitor import StatRegistry
from paddle_tpu.models import GPTConfig, GPTForCausalLM
from paddle_tpu.models.sala import sala_hidden
from paddle_tpu.observability import tracer
from paddle_tpu.serving.llm import LLMEngine, LLMEngineConfig
from paddle_tpu.serving.llm.decode import SamplingParams, pack_sampling
from paddle_tpu.serving.llm.paged import (PagedBatcher, PagesExhausted,
                                          SALAPagedDecoder,
                                          paged_decoder_class)
from paddle_tpu.serving.llm.scheduler import GenerationRequest
from paddle_tpu.serving.llm.paged.sala import PagedChunk, PagedStep

pytestmark = pytest.mark.timeout_s(900)
GAP, SEED, PAGE, MAX_SEQ = 1e-4, 11, 8, 96


def toy_config():
    with open(os.path.join(bench_spec.HERE, "configs",
                           "minicpm-sala.json")) as f:
        cfg = json.load(f)
    return bench_spec._merged(cfg, cfg["rehearsal"])


@pytest.fixture(scope="module")
def seeded():
    cfg = toy_config()
    net = sala_adapter.build_net(cfg)
    sala_adapter.load_weights(net, cfg, SEED)
    net.eval()
    return cfg, net


def reference_logits(cfg, tokens):
    pad = np.zeros(MAX_SEQ, np.int32)
    pad[:len(tokens)] = tokens
    top = sala_weights.make_top(cfg, SEED)
    hid, _ = ref.hidden_states(
        top, lambda i: sala_weights.make_layer(cfg, SEED, i),
        ref.arch_of(cfg), jnp.asarray(pad))
    return np.asarray(ref.logits_of(top, hid))[:len(tokens)]


def _engine(net, impl="gather", chunk=16, **over):
    kw = dict(kv_layout="paged", num_slots=2, max_seq=MAX_SEQ,
              page_size=PAGE, num_pages=26, prefill_buckets=[16, 32, 64],
              max_top_k=4, paged_attn_impl=impl, prefill_chunk=chunk)
    kw.update(over)
    return LLMEngine(net, LLMEngineConfig(**kw), registry=StatRegistry())


def _served_gap(cfg, prompt, tokens):
    """The benchmark's comparison of one request."""
    seq = np.concatenate([prompt, tokens[:-1]]).astype(np.int32)
    logits = reference_logits(cfg, seq)[len(prompt) - 1:]
    return float((logits.max(-1)
                  - logits[np.arange(len(tokens)), tokens]).max())


def _stats(eng):
    pre = eng.config.stat_prefix + "."
    st = eng.stats()
    return ({k[len(pre):]: v for k, v in st["stats"].items()},
            {k[len(pre):]: v for k, v in st["histograms"].items()}, st)


# -- through the engine's normal entry ---------------------------------------------

@pytest.mark.parametrize("chunk,impl", [(None, "gather"), (16, "gather"),
                                        (8, "kernel"), (16, "kernel")])
def test_engine_serves_what_the_reference_puts_first(seeded, chunk, impl):
    """Prompts of one token, under a page, across ``dense_len`` (32) inside a
    chunk, and past it; 12 decoded tokens carry 27 across it mid-decode."""
    cfg, net = seeded
    rng = np.random.default_rng(0)
    eng = _engine(net, impl, chunk)
    try:
        assert isinstance(eng.decoder, SALAPagedDecoder)
        assert eng.stats()["paged_attn_impl"] == impl
        for plen in (1, 5, 27, 40, 41, 61):   # 40: the prompt ends a page
            prompt = rng.integers(0, cfg["vocab_size"], plen).astype(np.int32)
            got = eng.generate(prompt, max_new_tokens=12)
            assert got["finish_reason"] == "length"
            assert _served_gap(cfg, prompt, np.asarray(got["tokens"])) <= GAP
    finally:
        eng.drain(timeout=30)


def test_two_prompts_enter_together_and_decode_beside_each_other(seeded):
    """Two slots: the second prompt's chunks run between the first one's
    decode ticks, and neither disturbs the other's pages or states."""
    cfg, net = seeded
    rng = np.random.default_rng(1)
    eng = _engine(net, "gather", 8)
    try:
        prompts = [rng.integers(0, cfg["vocab_size"], n).astype(np.int32)
                   for n in (44, 13, 57, 35)]
        reqs = [eng.submit(p, max_new_tokens=10) for p in prompts]
        for p, r in zip(prompts, reqs):
            tokens = np.asarray(r.result(timeout=300)["tokens"])
            assert _served_gap(cfg, p, tokens) <= GAP
        counters, hists, st = _stats(eng)
        assert counters["prefill_chunks"] == sum(-(-len(p) // 8)
                                                 for p in prompts)
        assert hists["prefill_chunk_ms"]["count"] == counters[
            "prefill_chunks"]
        assert counters["worker.prefill_chunk_s"] > 0
        assert counters["prefills"] == 4
        # every context past 32 tokens reads 4 of its 5-9 pages
        assert 0.4 < st["sparse_attn_selected_share"] < 1.0
        assert counters["sparse_attn.pages_selected"] \
            < counters["sparse_attn.pages_live"]
        assert counters["sparse_prefill.blocks_selected"] \
            < counters["sparse_prefill.blocks_computed"]
    finally:
        eng.drain(timeout=30)


def test_the_cache_gives_back_pages_keys_and_state(seeded):
    """After finish, after a deadline in the middle of a prompt and after an
    eviction for pages, every page is free again, and the next tenant of a
    slot starts from its own prompt."""
    cfg, net = seeded
    rng = np.random.default_rng(2)
    eng = _engine(net, "gather", 8)
    try:
        kv = eng._batcher.kv
        counters, _, _ = _stats(eng)
        assert counters["linear_state_bytes"] == 2 * 3 * 4 * 16 * 16 * 4
        assert counters["ckey_bytes"] == 27 * 1 * 4 * 2 * 16 * 4
        assert kv.k.shape == (27, 1 * 2, PAGE, 32) and kv.v.size == 0
        prompt = rng.integers(0, cfg["vocab_size"], 50).astype(np.int32)
        first = eng.generate(prompt, max_new_tokens=6)["tokens"]
        assert kv.pool.pages_in_use == 0 and kv.free_slots == 2
        # a deadline that passes while the prompt is still entering
        req = eng.submit(rng.integers(0, cfg["vocab_size"], 90), deadline=0.0,
                         max_new_tokens=4)
        with pytest.raises(Exception):
            req.result(timeout=60)
        deadline = time.time() + 30
        while eng._batcher.active and time.time() < deadline:
            time.sleep(0.01)
        assert kv.pool.pages_in_use == 0 and kv.free_slots == 2
        # the same prompt again, in the slot the dead request held: the
        # linear states and compressed keys are its own
        assert eng.generate(prompt, max_new_tokens=6)["tokens"] == first
        assert kv.pool.total_allocs == kv.pool.total_releases
    finally:
        eng.drain(timeout=30)


def test_a_prompt_being_prefilled_is_evicted_for_pages(seeded):
    """The batcher driven by hand (no worker thread, so the ticks are the
    test's): 18 pages, an old request that decodes and a young one whose 11
    chunks are still entering when the old one needs a page the pool no
    longer has: the young one gives back its pages and its slot."""
    cfg, net = seeded
    rng = np.random.default_rng(3)
    dec = SALAPagedDecoder(net, max_top_k=4, page_size=PAGE, num_pages=18,
                           attn_impl="gather")
    b = PagedBatcher(dec, LLMEngineConfig(
        kv_layout="paged", num_slots=2, max_seq=MAX_SEQ, page_size=PAGE,
        num_pages=18, prefill_chunk=8, max_top_k=4, warmup=False),
        StatRegistry())
    old = GenerationRequest(rng.integers(0, cfg["vocab_size"], 40),
                            SamplingParams(max_new_tokens=40))
    b.admit(old)
    while len(old.tokens) < 8:          # 5 chunks, then it decodes
        b.tick()
    assert b.kv.pool.pages_in_use == 6 and not b._prefilling
    young = GenerationRequest(rng.integers(0, cfg["vocab_size"], 88),
                              SamplingParams(max_new_tokens=4))
    b.admit(young)
    assert list(b._prefilling) == [1] and b.kv.pool.free_pages == 1
    assert b.active == 2 and b.free_slots == 0
    while not young.future.done():
        b.tick()
    with pytest.raises(PagesExhausted, match="youngest"):
        young.result(timeout=0)
    assert not young.tokens and not b._prefilling
    assert b.kv.free_slots == 1
    assert b.kv.pool.pages_in_use == b.kv.mapped_pages(0) == 8
    while not old.future.done():
        b.tick()
    assert len(old.result(timeout=0)["tokens"]) == 40
    assert _served_gap(cfg, old.prompt, np.asarray(old.tokens)) <= GAP
    assert b.kv.pool.pages_in_use == 0 and b.kv.free_slots == 2
    # a forced shutdown in the middle of a prompt gives everything back too
    late = GenerationRequest(rng.integers(0, cfg["vocab_size"], 30),
                             SamplingParams(max_new_tokens=4))
    b.admit(late)
    b.tick()
    b.abort_all(lambda req: RuntimeError("stopped"))
    with pytest.raises(RuntimeError, match="stopped"):
        late.result(timeout=0)
    assert b.kv.pool.pages_in_use == 0 and b.kv.free_slots == 2 \
        and b.active == 0


def test_a_chunk_is_a_span_with_its_request_offset_and_length(seeded):
    cfg, net = seeded
    eng = _engine(net, "gather", 16)
    tracer.default_tracer().clear()
    tracer.enable()
    try:
        eng.generate(np.arange(1, 41), max_new_tokens=3)
    finally:
        tracer.disable()
        eng.drain(timeout=30)
    spans = [s for s in tracer.default_tracer().spans()
             if s["name"] == "serving.llm/prefill_chunk"]
    tracer.default_tracer().clear()
    assert [(s["attrs"]["start"], s["attrs"]["n"]) for s in spans] \
        == [(0, 16), (16, 16), (32, 8)]
    assert len({s["attrs"]["req"] for s in spans}) == 1


# -- what is refused ----------------------------------------------------------------

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


def test_a_mesh_export_and_other_sizes_raise(seeded):
    _, net = seeded
    with pytest.raises(NotImplementedError):
        SALAPagedDecoder(net, mesh=object(), page_size=PAGE)
    with pytest.raises(ValueError, match="selection block"):
        SALAPagedDecoder(net, page_size=16)
    with pytest.raises(ValueError, match="multiple of the page"):
        _engine(net, chunk=12, warmup=False)
    assert paged_decoder_class(net) is SALAPagedDecoder
    eng = _engine(net, warmup=False)
    try:
        with pytest.raises(NotImplementedError):
            eng.export_sequences()
        assert eng.import_sequence(object()) is False
    finally:
        eng.drain(timeout=10)


def test_chunked_prefill_is_refused_where_no_decoder_offers_it():
    gpt = GPTForCausalLM(GPTConfig(
        vocab_size=64, hidden_size=32, num_layers=1, num_heads=4,
        max_position_embeddings=64, hidden_dropout_prob=0.0,
        attention_dropout_prob=0.0))
    gpt.eval()
    with pytest.raises(NotImplementedError, match="chunked"):
        LLMEngine(gpt, LLMEngineConfig(kv_layout="paged", max_seq=32,
                                       page_size=8, prefill_chunk=16,
                                       warmup=False))
    with pytest.raises(NotImplementedError, match="paged"):
        LLMEngineConfig(kv_layout="slot", max_seq=32, prefill_chunk=16)


# -- the decoder's programs, directly -----------------------------------------------

def _prefill_in_chunks(dec, kv, params, row, plen, chunk, slot):
    """Logits of the prompt's rows from the chunk view, a chunk at a time."""
    @jax.jit
    def run(k, state, tokens, start, n):
        view = PagedChunk(dec.spec, k, state, kv.block_tables,
                          jnp.asarray(slot), start, n)
        pos = (start + jnp.arange(tokens.shape[1]))[None]
        h = sala_hidden(dec.spec, params, tokens, pos, view)
        return h[0] @ params["head"], view.kvbuf, view.state

    logits = []
    for start in range(0, plen, chunk):
        n = min(chunk, plen - start)
        padded = np.zeros((1, chunk), np.int32)
        padded[0, :n] = row[start:start + n]
        out, k, state = run(kv.k, kv.state, jnp.asarray(padded),
                            jnp.asarray(start), jnp.asarray(n))
        kv.swap(k, kv.v, kv.lengths, state)
        logits.append(np.asarray(out)[:n])
    return np.concatenate(logits)


@pytest.mark.parametrize("chunk", [8, 16, 64])
@pytest.mark.parametrize("impl", ["gather", "kernel"])
def test_chunks_then_decode_logits_match_the_full_forward(seeded, chunk,
                                                          impl):
    """A prompt of 43 in slot 1 (whole: one chunk of 64), then paged decode
    to 60 tokens: the logits of every row against the reference's."""
    cfg, net = seeded
    dec = SALAPagedDecoder(net, page_size=PAGE, num_pages=20,
                           attn_impl=impl)
    kv = dec.new_kv(2, MAX_SEQ)
    params = dec.params()
    row = np.random.default_rng(7).integers(0, cfg["vocab_size"],
                                            60).astype(np.int32)
    want = reference_logits(cfg, row)
    kv.alloc()
    slot = kv.alloc()
    plen = 43
    kv.ensure_pages(slot, 60)
    got = _prefill_in_chunks(dec, kv, params, row, plen, chunk, slot)
    np.testing.assert_allclose(got, want[:plen], atol=5e-5, rtol=0)

    @jax.jit
    def step(k, state, lengths, tokens):
        view = PagedStep(dec.spec, k, state, kv.block_tables, lengths,
                         jnp.asarray([True, False]), impl)
        h = sala_hidden(dec.spec, params, tokens[:, None], lengths[:, None],
                        view)
        return h[:, 0] @ params["head"], view.kvbuf, view.state, lengths + 1

    k, state = kv.k, kv.state
    lengths = jnp.asarray([0, plen], jnp.int32)
    idle = np.asarray(state["lin"][0])
    for t in range(plen, 60):
        logits, k, state, lengths = step(
            k, state, lengths, jnp.asarray([0, row[t]], jnp.int32))
        np.testing.assert_allclose(logits[1], want[t], atol=5e-5, rtol=0,
                                   err_msg=f"position {t}")
    # the frozen slot's state was left alone
    np.testing.assert_array_equal(np.asarray(state["lin"][0]), idle)


def test_compressed_keys_lie_with_their_pages_across_a_boundary(seeded):
    """Kernel ``j`` covers rows ``[2 j, 2 j + 4)`` and is stored with the page
    of its first row: the fourth kernel of a page reaches two rows into the
    next page, in a prefill chunk and in decode alike."""
    cfg, net = seeded
    dec = SALAPagedDecoder(net, page_size=PAGE, num_pages=20,
                           attn_impl="gather")
    kv = dec.new_kv(1, MAX_SEQ)
    slot = kv.alloc()
    kv.ensure_pages(slot, 40)
    row = np.random.default_rng(8).integers(0, cfg["vocab_size"],
                                            40).astype(np.int32)
    samp = pack_sampling([SamplingParams()])
    fin = jnp.zeros((1,), bool)
    padded = np.zeros((1, 16), np.int32)
    for start in (0, 16):
        padded[0] = row[start:start + 16]
        _, fin = dec.chunk_prefill(kv, dec.params(), jnp.asarray(padded),
                                   start, 16, start == 16, slot, fin, samp,
                                   jax.random.PRNGKey(0))
    for t in range(32, 40):
        _, fin = dec.decode_step(kv, dec.params(), fin, jnp.asarray(
            row[t:t + 1]), samp, jax.random.PRNGKey(t))
    pages = kv.slot_page_ids(slot)
    d = cfg["head_dim"]
    keys = np.concatenate([np.asarray(kv.k)[p, :, :, :d] for p in pages], 1)
    ck = np.asarray(kv.state["ckey"])
    for j in range(19):     # the kernels whole inside 40 rows
        want = keys[:, 2 * j:2 * j + 4].mean(1)                 # [Hkv, D]
        np.testing.assert_allclose(ck[pages[j // 4], 0, j % 4], want,
                                   atol=1e-6, err_msg=f"kernel {j}")
