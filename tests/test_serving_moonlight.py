"""Moonlight on the paged engine: prompts prefilled whole, in chunks of 16 and
in chunks of 8 (the EXPANDED order over the cached latent rows), then decoded
through the pages (the ABSORBED order), against the plain reference's full
forward pass (``benchmark/reference/moonlight_ref.py``), at toy width on the
CPU; the ONE arena the cache allocates and what it gives back; the counters
and gauges the family adds; what is refused.

Tolerances. Logits agree to float32 reassociation, 5e-5 absolute on logits of
spread one: a chunk expands the prefix's rows a tile at a time under an online
softmax where the reference takes one softmax over a whole masked row, the
decode step multiplies the same numbers in the absorbed order
(``tests/test_moonlight.py`` says what that moves) and its kernel walks pages
under its own online softmax. Whole, chunks of 16 and chunks of 8 are held to
the same bound against the one reference (so they agree with one another to
twice it). Served tokens are compared as the benchmark compares them: the
served token's reference logit may lie below the reference's best by at most
``GAP`` = 1e-4, a third of the closest pair of logits the seeded head makes.
Pages hold 8 rows: prompts of 1-61 end inside a page (5, 13, 27), on a page's
boundary (16, 40) and mid-chunk.
"""
import time

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from paddle_tpu.core.monitor import StatRegistry
from paddle_tpu.models.moonlight import moonlight_hidden
from paddle_tpu.serving.llm import LLMEngine, LLMEngineConfig
from paddle_tpu.serving.llm.decode import SamplingParams
from paddle_tpu.serving.llm.paged import (MoonlightPagedDecoder, PagedBatcher,
                                          PagesExhausted,
                                          paged_decoder_class)
from paddle_tpu.serving.llm.paged.moonlight import (PagedChunk, PagedStep,
                                                    latent_row_width,
                                                    tiles_expanded)
from paddle_tpu.serving.llm.scheduler import GenerationRequest
from tests.test_moonlight import reference_logits, seeded  # noqa: F401

pytestmark = pytest.mark.timeout_s(900)
GAP, PAGE, MAX_SEQ = 1e-4, 8, 96
#: a toy row: 32 + 8 numbers, padded to one whole lane tile
ROW = 128


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
                                        (8, "kernel"), (16, "kernel")])
def test_engine_serves_what_the_reference_puts_first(seeded, chunk, impl):
    cfg, net = seeded
    rng = np.random.default_rng(0)
    eng = _engine(net, impl, chunk)
    try:
        assert isinstance(eng.decoder, MoonlightPagedDecoder)
        assert paged_decoder_class(net) is MoonlightPagedDecoder
        assert eng.stats()["paged_attn_impl"] == impl
        # 4 query heads on the one row a token keeps: on the kernel lane the
        # plain walk's recurrence is the MXU's; the gather lane has no walk
        counters, st = _stats(eng)
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
        counters, st = _stats(eng)
        kv = eng._batcher.kv
        # ONE arena of 26 + 1 pages, 4 layers, 8 rows of 128 floats; no
        # second arena is allocated
        assert kv.k.shape == (27, 4, PAGE, ROW) and kv.v.shape == (0,)
        assert len(kv.groups) == 1 and not kv.has_window and kv.state is None
        assert kv.kv_bytes() == 27 * 4 * PAGE * ROW * 4 == kv.k.nbytes
        assert kv.page_nbytes() == 4 * PAGE * ROW * 4
        assert kv.row_nbytes() == ROW * 4 == counters["kv_row_bytes"]
        # 4 heads of (16 + 8) + 16 floats
        assert counters["kv_row_bytes_expanded"] == 4 * 40 * 4
        assert st["latent_cache_row_share"] == ROW / 160
        prompts = [rng.integers(0, cfg["vocab_size"], n).astype(np.int32)
                   for n in (44, 13, 57, 35)]
        reqs = [eng.submit(p, max_new_tokens=10) for p in prompts]
        for p, r in zip(prompts, reqs):
            tokens = np.asarray(r.result(timeout=300)["tokens"])
            assert _served_gap(cfg, p, tokens) <= GAP
        counters, st = _stats(eng)
        assert counters["prefill_chunks"] == sum(-(-len(p) // 8)
                                                 for p in prompts)
        assert counters["prefills"] == 4
        # every tick's walks read each live row of each sequence in each of
        # the 4 layers: 9 ticks a request over contexts of plen + 1 ...
        rows = sum(sum(len(p) + j for j in range(1, 10)) for p in prompts)
        assert counters["latent_attn.rows_live"] == 4 * rows
        # a chunk behind `start` rows expands the tiles that hold them: the
        # 12 pages of a slot walk in tiles of 12 pages (96 rows)
        assert tiles_expanded(0, 12, PAGE) == 0
        assert tiles_expanded(8, 12, PAGE) == 96 == tiles_expanded(
            96, 12, PAGE)
        chunks_behind = sum(-(-len(p) // 8) - 1 for p in prompts)
        assert counters["latent_prefill.rows_expanded"] \
            == 4 * 96 * chunks_behind
        # the held experts' counters: 4 of 8 held in 3 expert layers
        assert counters["moe_experts_active"] > 0
        assert counters["moe_load_max"] > 0
        assert counters["moe_pairs_routed"] == 2 * 3 * (
            counters["tokens_generated"] - counters["prefills"])
    finally:
        eng.drain(timeout=30)


def test_the_cache_gives_everything_back(seeded):
    """After finish, after a deadline in the middle of a prompt, every page
    is free again, and the next tenant of a slot starts from its own
    prompt."""
    cfg, net = seeded
    rng = np.random.default_rng(2)
    eng = _engine(net, "gather", 8)
    try:
        kv = eng._batcher.kv
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
        assert eng.generate(prompt, max_new_tokens=6)["tokens"] == first
        assert kv.pool.total_allocs == kv.pool.total_releases
    finally:
        eng.drain(timeout=30)


def test_a_prompt_being_prefilled_is_evicted_and_frees_its_pages(seeded):
    """18 pages: an old request that decodes and a young one whose chunks are
    still entering when the old one needs a page the pool no longer has: the
    young one gives its pages back."""
    cfg, net = seeded
    rng = np.random.default_rng(3)
    dec = MoonlightPagedDecoder(net, max_top_k=4, page_size=PAGE,
                                num_pages=18, attn_impl="gather")
    config = LLMEngineConfig(
        kv_layout="paged", num_slots=2, max_seq=MAX_SEQ, page_size=PAGE,
        num_pages=18, prefill_chunk=8, max_top_k=4, warmup=False)
    dec.check_config(config)
    b = PagedBatcher(dec, config, StatRegistry())
    pool = b.kv.pool
    old = GenerationRequest(rng.integers(0, cfg["vocab_size"], 40),
                            SamplingParams(max_new_tokens=40))
    b.admit(old)
    while len(old.tokens) < 8:
        b.tick()
    young = GenerationRequest(rng.integers(0, cfg["vocab_size"], 88),
                              SamplingParams(max_new_tokens=4))
    b.admit(young)
    assert list(b._prefilling) == [1] and pool.free_pages == 1
    while not young.future.done():
        b.tick()
    with pytest.raises(PagesExhausted, match="youngest"):
        young.result(timeout=0)
    assert not b.kv.slot_page_ids(1) and b.kv.free_slots == 1
    while not old.future.done():
        b.tick()
    assert _served_gap(cfg, old.prompt, np.asarray(old.tokens)) <= GAP
    assert pool.pages_in_use == 0
    # a cancel in the middle of a prompt gives everything back too
    late = GenerationRequest(rng.integers(0, cfg["vocab_size"], 30),
                             SamplingParams(max_new_tokens=4))
    b.admit(late)
    b.tick()
    assert pool.pages_in_use > 0
    b.abort_all(lambda req: RuntimeError("stopped"))
    with pytest.raises(RuntimeError, match="stopped"):
        late.result(timeout=0)
    assert pool.pages_in_use == 0 and b.kv.free_slots == 2 and b.active == 0


# -- at program level: logits of every row -----------------------------------------

def _prefill_in_chunks(dec, kv, params, row, plen, chunk, slot):
    """Logits of every prompt row, the prompt entering ``chunk`` tokens at
    a time."""
    @jax.jit
    def run(arena, tables, tokens, start, n):
        view = PagedChunk(dec.spec, arena, tables, jnp.asarray(slot), start,
                          n)
        pos = (start + jnp.arange(tokens.shape[1]))[None]
        h, _ = moonlight_hidden(dec.spec, params, tokens, pos, view)
        return h[0] @ params["head"], view.arena

    logits = []
    for start in range(0, plen, chunk):
        n = min(chunk, plen - start)
        padded = np.zeros((1, chunk), np.int32)
        padded[0, :n] = row[start:start + n]
        kv.ensure_pages(slot, start + n)
        out, arena = run(kv.k, kv.block_tables, jnp.asarray(padded),
                         jnp.asarray(start), jnp.asarray(n))
        kv.swap(arena, kv.v, kv.lengths)
        logits.append(np.asarray(out)[:n])
    return np.concatenate(logits)


@pytest.mark.parametrize("plen", [43, 48, 21])   # in a page, on its boundary,
@pytest.mark.parametrize("chunk", [8, 16, 64])   # mid-chunk
@pytest.mark.parametrize("impl", ["gather", "kernel"])
def test_chunks_then_decode_logits_match_the_full_forward(seeded, chunk,
                                                          impl, plen):
    """A prompt in slot 1 (whole: one chunk of 64), then paged decode to 70
    rows: the logits of every row against the reference's."""
    cfg, net = seeded
    dec = MoonlightPagedDecoder(net, page_size=PAGE, num_pages=24,
                                attn_impl=impl)
    kv = dec.new_kv(2, MAX_SEQ)
    params = dec.params()
    row = np.random.default_rng(7).integers(0, cfg["vocab_size"],
                                            70).astype(np.int32)
    want, _ = reference_logits(cfg, row)
    kv.alloc()
    slot = kv.alloc()
    with jax.default_matmul_precision("highest"):
        got = _prefill_in_chunks(dec, kv, params, row, plen, chunk, slot)
    np.testing.assert_allclose(got, want[:plen], atol=5e-5, rtol=0)

    @jax.jit
    def step(arena, tables, lengths, tokens):
        view = PagedStep(dec.spec, arena, tables, lengths,
                         jnp.asarray([True, False]), impl)
        h, counts = moonlight_hidden(dec.spec, params, tokens[:, None],
                                     lengths[:, None], view)
        return (h[:, 0] @ params["head"], view.arena, lengths + 1,
                jnp.stack(counts))

    lengths = jnp.asarray([0, plen], jnp.int32)
    with jax.default_matmul_precision("highest"):
        for t in range(plen, 70):
            kv.ensure_pages(slot, t + 1)
            logits, arena, lengths, counts = step(
                kv.k, kv.block_tables, lengths,
                jnp.asarray([0, row[t]], jnp.int32))
            kv.swap(arena, kv.v, kv.lengths)
            np.testing.assert_allclose(logits[1], want[t], atol=5e-5, rtol=0,
                                       err_msg=f"position {t}")
    # two tokens' pairs over the held half of 8 experts, 3 expert layers
    assert counts.shape == (3, 4) and int(counts.sum()) <= 2 * 2 * 3
    # no expanded key or value outlives a program: the cache is the arena
    assert kv.k.shape == (25, 4, PAGE, latent_row_width(dec.spec))


def test_the_programs_carry_the_scopes_the_readers_ask_for(seeded):
    """``mla_ms_per_tick``, ``mla_absorb_ms_per_tick`` and
    ``mla_expand_ms_per_chunk`` ask ``benchmark/scope_time.py`` for these
    scope paths of ``jit__step`` and ``jit__chunk``; ``opscope`` reads them
    from the compiled programs' ``op_name``s."""
    from paddle_tpu.observability.opscope import scope_of
    from paddle_tpu.serving.llm.paged.moonlight import (
        build_moonlight_paged_chunk_fn, build_moonlight_paged_decode_step)
    _, net = seeded
    dec = MoonlightPagedDecoder(net, max_top_k=4, page_size=PAGE,
                                num_pages=24, attn_impl="gather")
    kv = dec.new_kv(2, MAX_SEQ)
    params, fin = dec.params(), jnp.zeros((2,), bool)
    samp = (jnp.ones((2,)), jnp.zeros((2,), jnp.int32), fin,
            jnp.full((2,), -1, jnp.int32))
    key = jax.random.PRNGKey(0)

    def scopes(fn, *args):
        text = jax.jit(fn).lower(*args).compile().as_text()
        found = set()
        for op in set(__import__("re").findall(r'op_name="([^"]+)"', text)):
            found.add(scope_of(op)[0])
        return found

    step = scopes(build_moonlight_paged_decode_step(dec.spec, 4, "gather"),
                  params, kv.k, kv.block_tables, kv.lengths, fin,
                  jnp.zeros((2,), jnp.int32), *samp, key)
    for name in ("q", "latent", "absorb", "attn", "out"):
        assert any(s.endswith("moonlight/mla/" + name) for s in step), name
    assert {"moonlight/moe_route", "moonlight/moe_experts",
            "moonlight/shared_expert", "moonlight/ffn",
            "moonlight/norm"} <= step
    assert not any("expand" in s or "chunk_walk" in s for s in step)
    chunk = scopes(build_moonlight_paged_chunk_fn(dec.spec, 4), params,
                   jnp.zeros((1, 16), jnp.int32), jnp.asarray(16),
                   jnp.asarray(16), jnp.asarray(True), kv.k,
                   kv.block_tables, kv.lengths, fin, jnp.asarray(0),
                   *(x[:1] for x in samp), key)
    for name in ("q", "latent", "expand", "chunk_walk", "out"):
        assert any(s.endswith("moonlight/mla/" + name) for s in chunk), name
    assert not any(s.endswith("/absorb") for s in chunk)


def test_one_arena_of_the_stated_row_bytes_and_its_pages_move():
    """The latent layout of ``PagedKVCache``: one arena, and the page export
    and import of a cache of one arena."""
    from paddle_tpu.serving.llm.paged import PagedKVCache
    kv = PagedKVCache(2, 3, 32, 1, 40, page_size=8, fused_kv=True,
                      row_shape=(40,))
    assert kv.k.shape == (9, 3, 8, 40) and kv.v.shape == (0,)
    assert kv.kv_bytes() == 9 * 3 * 8 * 40 * 4
    assert kv.row_nbytes() == 160 and kv.page_nbytes() == 3 * 8 * 160
    slot = kv.alloc()
    kv.ensure_pages(slot, 16)
    pids = kv.slot_page_ids(slot)
    kv.k = kv.k.at[pids[0]].set(1.5)
    pages, none = kv.read_pages(pids)
    assert none is None and pages.shape == (2, 3, 8, 40)
    kv.write_page(pids[1], pages[0], None)
    assert float(kv.k[pids[1]].min()) == 1.5 and kv.v.shape == (0,)


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
        MoonlightPagedDecoder(net, mesh=object())
    with pytest.raises(ValueError, match="multiple of the page"):
        _engine(net, chunk=12)
    with pytest.raises(ValueError, match="positions"):
        _engine(net, max_seq=512, num_pages=80, warmup=False)
    eng = _engine(net, warmup=False)
    try:
        assert not eng.supports_migration
        with pytest.raises(NotImplementedError):
            eng.export_sequences()
    finally:
        eng.drain(timeout=30)
