"""Trinity on the paged engine: prompts prefilled whole, in chunks of 16 and
in chunks of 8, then decoded through the two page groups, against the plain
reference's full forward pass (``benchmark/reference/trinity_ref.py``), at toy
width on the CPU; what the window group holds, gives back and frees; the
counters and gauges the family adds; what is refused.

Tolerances. Logits agree to float32 reassociation, 5e-5 absolute on logits of
spread one: a chunk reads pages in tiles under an online softmax where the
reference takes one softmax over a whole masked row, the decode kernel walks
pages under its own online softmax, and the expert product sums the chosen in
pair order. Whole, chunks of 16 and chunks of 8 are held to the same bound
against the one reference (so they agree with one another to twice it).
Served tokens are compared as the benchmark compares them: the served
token's reference logit may lie below the reference's best by at most ``GAP``
= 1e-4, a third of the closest pair of logits the seeded head makes. The toy
window is 16 rows (two pages of 8): prompts of 5-61 and 12 decoded tokens
cross it mid-chunk and mid-decode.
"""
import time

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from paddle_tpu.core.monitor import StatRegistry
from paddle_tpu.models.trinity import trinity_hidden
from paddle_tpu.serving.llm import LLMEngine, LLMEngineConfig
from paddle_tpu.serving.llm.decode import SamplingParams
from paddle_tpu.serving.llm.paged import (PagedBatcher, PagedKVCache,
                                          PageGroup, PagesExhausted,
                                          TrinityPagedDecoder,
                                          paged_decoder_class)
from paddle_tpu.serving.llm.paged.pool import window_page_bound
from paddle_tpu.serving.llm.paged.trinity import PagedChunk, PagedStep
from paddle_tpu.serving.llm.scheduler import GenerationRequest
from tests.test_trinity import (SEED, reference_logits, seeded,  # noqa: F401
                                toy_config)

pytestmark = pytest.mark.timeout_s(900)
GAP, PAGE, MAX_SEQ, WINDOW = 1e-4, 8, 96, 16


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
    """Prompts inside the window, across it inside a chunk, and several
    windows long; 12 decoded tokens carry 5 and 13 across it mid-decode."""
    cfg, net = seeded
    rng = np.random.default_rng(0)
    eng = _engine(net, impl, chunk)
    try:
        assert isinstance(eng.decoder, TrinityPagedDecoder)
        assert paged_decoder_class(net) is TrinityPagedDecoder
        assert eng.stats()["paged_attn_impl"] == impl
        # 2 KV heads under 4 query heads, pages of 8 rows: on the kernel
        # lane the plain walk's recurrence is the MXU's; the gather lane
        # has no walk to name
        counters, st = _stats(eng)
        assert st["paged_attn_recurrence"] == (
            "mxu" if impl == "kernel" else None)
        assert counters.get("paged_attn.recurrence_mxu") == (
            1 if impl == "kernel" else None)
        for plen in (1, 5, 13, 16, 27, 40, 61):   # 16: a window; 40: a page
            prompt = rng.integers(0, cfg["vocab_size"], plen).astype(np.int32)
            got = eng.generate(prompt, max_new_tokens=12)
            assert got["finish_reason"] == "length"
            assert _served_gap(cfg, prompt, np.asarray(got["tokens"])) <= GAP
        kv = eng._batcher.kv
        assert all(g.pool.pages_in_use == 0 for g in kv.groups)
    finally:
        eng.drain(timeout=30)


def test_prompts_enter_together_and_the_counters_say_what_the_window_spared(
        seeded):
    """Two slots: one prompt's chunks run between the other's decode ticks.
    Every new counter and gauge is read here."""
    cfg, net = seeded
    rng = np.random.default_rng(1)
    eng = _engine(net, "gather", 8)
    try:
        counters, _ = _stats(eng)
        # the full group: 1 layer, 26 + 1 pages; the window group: 4 layers,
        # 2 slots x (ceil((16 + 8) / 8) + 2 = 5, and a spare)
        row = 2 * PAGE * 2 * 16 * 4
        assert counters["kv_group_bytes.full"] == 27 * 1 * row
        assert counters["kv_group_bytes.window"] == 13 * 4 * row
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
        # contexts of 13-67 rows under a window of 16: the walks read 3 pages
        # of 2-9, the group holds 3-4 where one group would hold them all
        assert 0 < counters["window_attn.pages_walked"] \
            < counters["window_attn.pages_live"]
        assert 0 < counters["kv_pages.window_held"] \
            < counters["kv_pages.window_unbounded"]
        assert counters["kv_pages.window_released"] > 0
        assert 0.3 < st["window_attn_walked_share"] < 0.8
        assert 0.3 < st["window_held_page_share"] < 0.8
        # the held experts' counters: 4 of 8 held in 4 expert layers
        assert counters["moe_experts_active"] > 0
        assert counters["moe_load_max"] > 0
        assert counters["moe_pairs_routed"] > 0
    finally:
        eng.drain(timeout=30)


def test_a_chunk_past_its_window_of_expert_tiles_walks_it_twice_and_counts_it():
    """2 of 8 experts held and chunks of 144 tokens: 36 rows an expert are
    expected, so tiles of 64 in a window of 4 (``ops/moe.py:window_sizes``).
    A selection bias that sends EVERY pair to the two held experts fills 6:
    each chunk's four expert layers walk their window twice, the chunk
    program says so beside its token, the tick after it counts it, and the
    tokens are those of chunks of 16, which have no window."""
    from benchmark import trinity_adapter
    from paddle_tpu.ops import moe
    cfg = toy_config(num_experts=2)
    cfg["share"] = dict(cfg["share"], experts_held=[0, 2])
    net = trinity_adapter.build_net(cfg)
    trinity_adapter.load_weights(net, cfg, SEED)
    for name, p in net.named_parameters():
        if name.endswith("expert_bias"):
            p.set_value(np.where(np.arange(8) < 2, 10.0, 0.0).astype(
                np.float32))
    net.eval()
    assert moe.window_sizes(144, 2, 2, 8) == (64, 4)
    assert moe.window_sizes(16, 2, 2, 8) is None
    rng = np.random.default_rng(4)
    prompt = rng.integers(0, cfg["vocab_size"], 150).astype(np.int32)
    served = {}
    for chunk in (144, 16):
        eng = _engine(net, "gather", chunk, max_seq=192, num_pages=52)
        try:
            served[chunk] = eng.generate(prompt, max_new_tokens=8)["tokens"]
            counters, _ = _stats(eng)
        finally:
            eng.drain(timeout=30)
        if chunk == 16:     # a call of at most 128 tokens has no window
            assert not counters.get("moe.window_calls")
            continue
        # two chunks (144 and 6 of 144) and the warm-up's one, of four
        # expert layers each; the tick after the last has read them all
        assert counters["prefill_chunks"] == 2
        assert counters["moe.window_calls"] == 12
        assert counters["moe.window_passes"] == 24
    assert served[144] == served[16]


def test_both_groups_give_everything_back(seeded):
    """After finish, after a deadline in the middle of a prompt, every page
    of both groups is free again, and the next tenant of a slot starts from
    its own prompt."""
    cfg, net = seeded
    rng = np.random.default_rng(2)
    eng = _engine(net, "gather", 8)
    try:
        kv = eng._batcher.kv
        full, window = kv.groups
        assert (full.window, window.window) == (None, WINDOW)
        assert full.k.shape == (27, 1, PAGE, 2, 16) == full.v.shape
        assert window.k.shape == (13, 4, PAGE, 2, 16)
        assert kv.k is full.k and kv.pool is full.pool
        prompt = rng.integers(0, cfg["vocab_size"], 50).astype(np.int32)
        first = eng.generate(prompt, max_new_tokens=6)["tokens"]
        assert not any(g.pool.pages_in_use for g in kv.groups)
        assert kv.free_slots == 2
        # a deadline that passes while the prompt is still entering
        req = eng.submit(rng.integers(0, cfg["vocab_size"], 90), deadline=0.0,
                         max_new_tokens=4)
        with pytest.raises(Exception):
            req.result(timeout=60)
        deadline = time.time() + 30
        while eng._batcher.active and time.time() < deadline:
            time.sleep(0.01)
        assert not any(g.pool.pages_in_use for g in kv.groups)
        assert kv.free_slots == 2
        assert eng.generate(prompt, max_new_tokens=6)["tokens"] == first
        for g in kv.groups:
            assert g.pool.total_allocs == g.pool.total_releases
    finally:
        eng.drain(timeout=30)


def test_a_window_group_stays_under_its_bound_to_twenty_windows(seeded):
    """The batcher driven by hand: a prompt of 200 rows in chunks of 16,
    then decode to 336 rows, 21 windows of 16. At every moment the window
    group maps at most ``ceil((W + chunk) / page) + 2`` pages for the slot,
    the full group all of them; what the window group gave back another
    slot is given."""
    cfg, net = seeded
    rng = np.random.default_rng(4)
    max_seq, chunk = 352, 16
    dec = TrinityPagedDecoder(net, max_top_k=4, page_size=PAGE,
                              num_pages=2 * 44, attn_impl="gather")
    config = LLMEngineConfig(
        kv_layout="paged", num_slots=2, max_seq=max_seq, page_size=PAGE,
        num_pages=2 * 44, prefill_chunk=chunk, max_top_k=4, warmup=False)
    dec.check_config(config)
    b = PagedBatcher(dec, config, StatRegistry())
    full, window = b.kv.groups
    bound = window_page_bound(WINDOW, chunk, PAGE)
    assert bound == 6 and window.num_pages == 2 * (bound + 1)
    req = GenerationRequest(rng.integers(0, cfg["vocab_size"], 200),
                            SamplingParams(max_new_tokens=136))
    b.admit(req)
    assert len(full.slot_pages[0]) == 25 and not window.slot_pages[0]
    seen, held_most, mapped = set(), 0, []
    ensure = b.kv.ensure_pages

    def ensure_and_note(slot, n_tokens, **kw):
        """The most a slot holds is just after its pages are mapped, before
        the chunk or the step has run and what it left behind is released."""
        out = ensure(slot, n_tokens, **kw)
        mapped.append(len(window.slot_pages[slot]))
        return out

    b.kv.ensure_pages = ensure_and_note
    while not req.future.done():
        b.tick()
        held_most = max([held_most, *mapped])
        assert held_most <= bound
        seen.update(window.slot_pages[0])
        rows = req.prompt_len + len(req.tokens)
        if req.tokens and not req.future.done():
            assert len(full.slot_pages[0]) == -(-(rows - 1) // PAGE)
            # the last query was at row rows - 2: no page before its window
            assert window.first[0] == max(0, rows - 2 - WINDOW + 1) // PAGE
    assert held_most >= 4 and len(req.tokens) == 136
    assert b.kv.window_released >= 38           # 42 pages, 3 held at the end
    # every page the window pool has was used by this one slot in turn
    assert len(seen) <= window.num_pages and window.pool.pages_in_use == 0
    assert full.pool.pages_in_use == 0
    # pages the first tenant released are allocated again by another slot
    other = GenerationRequest(rng.integers(0, cfg["vocab_size"], 30),
                              SamplingParams(max_new_tokens=3))
    b.admit(other)
    while not other.future.done():
        b.tick()
        assert set(window.slot_pages[0]) | set(window.slot_pages[1]) <= set(
            range(window.num_pages))
    assert window.pool.total_allocs == window.pool.total_releases > 42


def test_a_prompt_being_prefilled_is_evicted_and_frees_both_groups(seeded):
    """18 pages in the full group: an old request that decodes and a young
    one whose chunks are still entering when the old one needs a page the
    pool no longer has: the young one gives back both groups' pages."""
    cfg, net = seeded
    rng = np.random.default_rng(3)
    dec = TrinityPagedDecoder(net, max_top_k=4, page_size=PAGE, num_pages=18,
                              attn_impl="gather")
    config = LLMEngineConfig(
        kv_layout="paged", num_slots=2, max_seq=MAX_SEQ, page_size=PAGE,
        num_pages=18, prefill_chunk=8, max_top_k=4, warmup=False)
    dec.check_config(config)
    b = PagedBatcher(dec, config, StatRegistry())
    full, window = b.kv.groups
    old = GenerationRequest(rng.integers(0, cfg["vocab_size"], 40),
                            SamplingParams(max_new_tokens=40))
    b.admit(old)
    while len(old.tokens) < 8:
        b.tick()
    young = GenerationRequest(rng.integers(0, cfg["vocab_size"], 88),
                              SamplingParams(max_new_tokens=4))
    b.admit(young)
    assert list(b._prefilling) == [1] and full.pool.free_pages == 1
    while not young.future.done():
        b.tick()
    with pytest.raises(PagesExhausted, match="youngest"):
        young.result(timeout=0)
    assert not window.slot_pages[1] and not full.slot_pages[1]
    assert window.first[1] == 0 and b.kv.free_slots == 1
    while not old.future.done():
        b.tick()
    assert _served_gap(cfg, old.prompt, np.asarray(old.tokens)) <= GAP
    assert not any(g.pool.pages_in_use for g in b.kv.groups)
    # a forced shutdown in the middle of a prompt gives everything back too
    late = GenerationRequest(rng.integers(0, cfg["vocab_size"], 30),
                             SamplingParams(max_new_tokens=4))
    b.admit(late)
    b.tick()
    assert window.pool.pages_in_use > 0
    b.abort_all(lambda req: RuntimeError("stopped"))
    with pytest.raises(RuntimeError, match="stopped"):
        late.result(timeout=0)
    assert not any(g.pool.pages_in_use for g in b.kv.groups) \
        and b.kv.free_slots == 2 and b.active == 0


def test_admission_asks_the_window_group_for_its_bounded_need(seeded):
    """A window pool that holds one slot's bound and no more: the second
    prompt waits until the first has ended, though the full group has room
    for both."""
    cfg, net = seeded
    rng = np.random.default_rng(6)
    dec = TrinityPagedDecoder(net, max_top_k=4, page_size=PAGE, num_pages=24,
                              attn_impl="gather")
    config = LLMEngineConfig(
        kv_layout="paged", num_slots=2, max_seq=MAX_SEQ, page_size=PAGE,
        num_pages=24, prefill_chunk=8, max_top_k=4, warmup=False)
    dec.check_config(config)
    dec.window_pages = lambda slots, max_seq: 7       # one slot's 5, and 2
    b = PagedBatcher(dec, config, StatRegistry())
    first = GenerationRequest(rng.integers(0, cfg["vocab_size"], 30),
                              SamplingParams(max_new_tokens=5))
    second = GenerationRequest(rng.integers(0, cfg["vocab_size"], 30),
                               SamplingParams(max_new_tokens=5))
    b.admit(first)
    b.admit(second)             # 5 + a lookahead page of one running > 7
    assert list(b._pending) == [second] and b.free_slots == 0
    while not second.future.done():
        b.tick()
    for r in (first, second):
        assert _served_gap(cfg, r.prompt, np.asarray(r.tokens)) <= GAP


# -- at program level: chunks, then decode, against the full forward ---------------

def _prefill_in_chunks(dec, kv, params, row, plen, chunk, slot):
    """Logits of every prompt row, the prompt entering ``chunk`` tokens at
    a time as the batcher maps and releases its pages."""
    @jax.jit
    def run(ks, vs, tables, tokens, start, n):
        view = PagedChunk(dec.spec, ks, vs, tables, jnp.asarray(slot), start,
                          n)
        pos = (start + jnp.arange(tokens.shape[1]))[None]
        h, _ = trinity_hidden(dec.spec, params, tokens, pos, view)
        return h[0] @ params["head"], tuple(view.ks), tuple(view.vs)

    logits = []
    for start in range(0, plen, chunk):
        n = min(chunk, plen - start)
        padded = np.zeros((1, chunk), np.int32)
        padded[0, :n] = row[start:start + n]
        kv.ensure_pages(slot, start + n)
        ks, vs, tables = dec._arenas(kv)
        out, ks, vs = run(ks, vs, tables, jnp.asarray(padded),
                          jnp.asarray(start), jnp.asarray(n))
        kv.swap_groups(ks, vs, kv.lengths)
        kv.release_behind(slot, start + n)
        logits.append(np.asarray(out)[:n])
    return np.concatenate(logits)


@pytest.mark.parametrize("chunk", [8, 16, 64])
@pytest.mark.parametrize("impl", ["gather", "kernel"])
def test_chunks_then_decode_logits_match_the_full_forward(seeded, chunk,
                                                          impl):
    """A prompt of 43 in slot 1 (whole: one chunk of 64), then paged decode
    to 70 rows, pages mapped and released as the batcher does: the logits of
    every row against the reference's."""
    cfg, net = seeded
    dec = TrinityPagedDecoder(net, page_size=PAGE, num_pages=24,
                              attn_impl=impl)
    dec.span = chunk
    kv = dec.new_kv(2, MAX_SEQ)
    params = dec.params()
    row = np.random.default_rng(7).integers(0, cfg["vocab_size"],
                                            70).astype(np.int32)
    want, _ = reference_logits(cfg, row)
    kv.alloc()
    slot = kv.alloc()
    plen = 43
    got = _prefill_in_chunks(dec, kv, params, row, plen, chunk, slot)
    np.testing.assert_allclose(got, want[:plen], atol=5e-5, rtol=0)
    bound = window_page_bound(WINDOW, chunk, PAGE)
    assert len(kv.groups[1].slot_pages[slot]) <= min(bound, 6)

    @jax.jit
    def step(ks, vs, tables, lengths, tokens):
        view = PagedStep(dec.spec, ks, vs, tables, lengths,
                         jnp.asarray([True, False]), impl)
        h, counts = trinity_hidden(dec.spec, params, tokens[:, None],
                                   lengths[:, None], view)
        return (h[:, 0] @ params["head"], tuple(view.ks), tuple(view.vs),
                lengths + 1, jnp.stack(counts))

    lengths = jnp.asarray([0, plen], jnp.int32)
    for t in range(plen, 70):
        kv.release_behind(slot, t)
        kv.ensure_pages(slot, t + 1)
        ks, vs, tables = dec._arenas(kv)
        logits, ks, vs, lengths, counts = step(
            ks, vs, tables, lengths, jnp.asarray([0, row[t]], jnp.int32))
        kv.swap_groups(ks, vs, kv.lengths)
        np.testing.assert_allclose(logits[1], want[t], atol=5e-5, rtol=0,
                                   err_msg=f"position {t}")
        assert len(kv.groups[1].slot_pages[slot]) <= 3
    # two tokens' pairs over the held half of 8 experts, 4 expert layers
    assert counts.shape == (4, 4) and int(counts.sum()) <= 2 * 2 * 4


# -- one group with no window is the cache the other families have -----------------

def test_one_group_is_the_cache_of_today():
    plain = PagedKVCache(2, 3, 32, 2, 16, page_size=8, num_pages=6)
    one = PagedKVCache(2, 3, 32, 2, 16, page_size=8,
                       groups=[PageGroup((0, 1, 2), None, 6)])
    for kv in (plain, one):
        assert len(kv.groups) == 1 and not kv.has_window
        assert kv.k.shape == kv.v.shape == (7, 3, 8, 2, 16)
        assert kv.num_pages == 6 and kv.trash == 6
        slot = kv.alloc()
        assert kv.ensure_pages(slot, 20) == 3
        assert kv.ensure_pages(slot, 20, windowed=False) == 0
        assert kv.release_behind(slot, 19) == 0 and kv.window_released == 0
        assert kv.mapped_pages(slot) == 3 and kv.slot_page_ids(slot) == (
            0, 1, 2)
        assert not kv.groups_short(1000, 1000, 50)
        assert kv.window_pages() == (0, 0)
        assert np.asarray(kv.block_tables)[slot].tolist() == [0, 1, 2, 6]
        with pytest.raises(PagesExhausted):
            other = kv.alloc()
            kv.ensure_pages(other, 32)
        assert kv.mapped_pages(other) == 0
        kv.free(slot)
        assert kv.pool.pages_in_use == 0
        assert kv.kv_bytes() == 2 * 7 * 3 * 8 * 2 * 16 * 4
        assert kv.group_bytes(windowed=True) == 0


@pytest.mark.parametrize("groups,message", [
    ([PageGroup((0, 1), None, 8)], "each once"),
    ([PageGroup((0, 1), None, 8), PageGroup((1, 2), 8, 8)], "each once"),
    ([PageGroup((0, 1, 2), 0, 8)], "window"),
    ([PageGroup((0, 1), None, 8), PageGroup((2,), 8, 1)], "cannot hold")])
def test_groups_that_do_not_fit_are_refused(groups, message):
    with pytest.raises(ValueError, match=message):
        PagedKVCache(2, 3, 32, 2, 16, page_size=8, groups=groups)
    with pytest.raises(ValueError, match="its own num_pages"):
        PagedKVCache(2, 3, 32, 2, 16, page_size=8, num_pages=8,
                     groups=[PageGroup((0, 1, 2), None, 8)])


def test_a_cache_of_two_groups_is_atomic_across_them():
    kv = PagedKVCache(2, 3, 64, 2, 16, page_size=8, groups=[
        PageGroup((2,), None, 16), PageGroup((0, 1), 16, 4)])
    slot = kv.alloc()
    with pytest.raises(PagesExhausted):     # 5 pages: the window pool has 4
        kv.ensure_pages(slot, 40)
    assert not any(g.slot_pages[slot] for g in kv.groups)
    assert kv.ensure_pages(slot, 40, windowed=False) == 5
    # a newcomer's need against the 4 free less the 4 this slot may claim
    assert kv.groups_short(8, 8, 0) is True
    kv._active.discard(slot)                         # were it alone:
    assert kv.groups_short(24, 8, 0) is False        # 3 pages of 4
    assert kv.groups_short(64, 8, 0) is True         # ceil(24 / 8) + 2 = 5
    kv._active.add(slot)
    assert kv.ensure_pages(slot, 24) == 3            # the window group's
    assert kv.release_behind(slot, 24) == 1          # rows 0-7 are behind
    assert kv.groups[1].first[slot] == 1
    assert kv.window_pages() == (2, 3)
    assert np.asarray(kv.groups[1].block_tables)[slot, :4].tolist() \
        == [4, 1, 2, 4]


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


def test_a_mesh_export_and_a_ragged_chunk_raise(seeded):
    _, net = seeded
    with pytest.raises(NotImplementedError, match="mesh"):
        TrinityPagedDecoder(net, mesh=object())
    with pytest.raises(ValueError, match="multiple of the page"):
        _engine(net, chunk=12)
    eng = _engine(net, warmup=False)
    try:
        assert not eng.supports_migration
        with pytest.raises(NotImplementedError):
            eng.export_sequences()
    finally:
        eng.drain(timeout=30)
