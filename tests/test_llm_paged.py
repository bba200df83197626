"""Paged KV cache (serving/llm/paged/): page pool + block tables, the
paged decode/prefill/spec programs, COW prefix sharing, page-granular
admission — and the contracts the slot path must keep (double-free
hardening, bitwise decode parity)."""
import numpy as np
import pytest

import jax
import jax.numpy as jnp

import paddle_tpu as paddle
from paddle_tpu.core.monitor import StatRegistry
from paddle_tpu.models import GPTConfig, GPTForCausalLM
from paddle_tpu.serving.llm import LLMEngine, LLMEngineConfig, StaticKVCache
from paddle_tpu.serving.llm.decode import (_AUDIT_SPEC, _audit_params,
                                           build_decode_step,
                                           build_prefill_fn)
from paddle_tpu.serving.llm.paged import (GPTPagedDecoder, PagedKVCache,
                                          PagePool, PagesExhausted,
                                          build_paged_decode_step,
                                          build_paged_prefill_fn,
                                          paged_gather_rows,
                                          pages_for_tokens)
from paddle_tpu.serving.llm.paged.prefix import PagedPrefixStore
from paddle_tpu.ops.paged_attention import paged_attention


def _tiny_model(seed=0, vocab=64, hidden=32, layers=2, heads=4,
                max_pos=128):
    paddle.seed(seed)
    cfg = GPTConfig(vocab_size=vocab, hidden_size=hidden,
                    num_layers=layers, num_heads=heads,
                    max_position_embeddings=max_pos,
                    hidden_dropout_prob=0.0, attention_dropout_prob=0.0)
    net = GPTForCausalLM(cfg)
    net.eval()
    return net


@pytest.fixture(scope="module")
def model():
    return _tiny_model()


def _engine(model, **kw):
    cfg = dict(num_slots=4, max_seq=64, prefill_buckets=(8, 16, 40),
               warmup=True, seed=3)
    cfg.update(kw)
    return LLMEngine(model, LLMEngineConfig(**cfg),
                     registry=StatRegistry())


class TestPagePool:
    def test_alloc_release_refcount(self):
        pool = PagePool(4)
        a, b = pool.alloc(), pool.alloc()
        assert pool.pages_in_use == 2 and pool.free_pages == 2
        pool.retain(a)
        assert pool.refcount(a) == 2
        assert pool.release(a) is False      # still referenced
        assert pool.release(a) is True       # back on the free list
        assert pool.release(b) is True
        assert pool.pages_in_use == 0

    def test_release_double_free_raises(self):
        pool = PagePool(2)
        p = pool.alloc()
        pool.release(p)
        with pytest.raises(ValueError, match="double-free"):
            pool.release(p)

    def test_retain_free_page_raises(self):
        pool = PagePool(2)
        with pytest.raises(ValueError):
            pool.retain(0)

    def test_alloc_many_atomic(self):
        pool = PagePool(3)
        pool.alloc()
        with pytest.raises(PagesExhausted):
            pool.alloc_many(3)
        # the failed alloc must not have leaked any page
        assert pool.pages_in_use == 1
        assert len(pool.alloc_many(2)) == 2

    def test_lowest_page_first(self):
        pool = PagePool(4)
        a = pool.alloc()
        b = pool.alloc()
        pool.release(a)
        assert pool.alloc() == a             # heap reuses the lowest id
        assert b == 1

    def test_pages_for_tokens(self):
        assert pages_for_tokens(0, 8) == 0
        assert pages_for_tokens(1, 8) == 1
        assert pages_for_tokens(8, 8) == 1
        assert pages_for_tokens(9, 8) == 2


class TestPagedKVCache:
    def _kv(self, **kw):
        cfg = dict(num_slots=2, num_layers=1, max_seq=16, num_heads=2,
                   head_dim=4, page_size=4, num_pages=8)
        cfg.update(kw)
        return PagedKVCache(**cfg)

    def test_constructor_validation(self):
        with pytest.raises(ValueError, match="page_size"):
            self._kv(page_size=5)
        with pytest.raises(ValueError, match="num_pages"):
            self._kv(num_pages=3)            # < pages_per_seq

    def test_block_tables_start_at_trash(self):
        kv = self._kv()
        assert kv.trash == 8
        assert (np.asarray(kv.block_tables) == kv.trash).all()

    def test_slot_lifecycle_and_double_free(self):
        kv = self._kv()
        slot = kv.alloc()
        kv.ensure_pages(slot, 6)             # 2 pages
        assert kv.mapped_pages(slot) == 2
        assert kv.pool.pages_in_use == 2
        kv.free(slot)
        assert kv.pool.pages_in_use == 0
        assert (np.asarray(kv.block_tables[slot]) == kv.trash).all()
        with pytest.raises(ValueError, match="double free"):
            kv.free(slot)

    def test_ensure_pages_atomic_on_exhaustion(self):
        kv = self._kv(num_pages=4)
        s0, s1 = kv.alloc(), kv.alloc()
        kv.ensure_pages(s0, 12)              # 3 of 4 pages
        with pytest.raises(PagesExhausted):
            kv.ensure_pages(s1, 8)           # needs 2, only 1 left
        assert kv.mapped_pages(s1) == 0      # nothing partially mapped
        assert kv.pool.pages_in_use == 3

    def test_adopt_shared_and_copied(self):
        kv = self._kv(num_slots=3, num_pages=12)
        owner = kv.alloc()
        kv.ensure_pages(owner, 4)
        pid = kv.slot_page_ids(owner)[0]
        kv.pool.retain(pid)                  # the store's reference
        other = kv.alloc()
        kv.adopt_shared_page(other, pid)
        assert kv.pool.refcount(pid) == 3
        assert kv.slot_page_ids(other)[0] == pid
        third = kv.alloc()
        new_pid = kv.adopt_copied_page(third, pid)
        assert new_pid != pid and kv.cow_splits == 1
        assert kv.pool.refcount(pid) == 3    # copy took no reference
        # the copy is bitwise-identical arena content
        assert (np.asarray(kv.k[new_pid]) == np.asarray(kv.k[pid])).all()
        for s in (owner, other, third):
            kv.free(s)
        kv.pool.release(pid)
        assert kv.pool.pages_in_use == 0


class TestStaticKVCacheDoubleFree:
    """Satellite regression: free() must reject a stale slot id instead
    of corrupting the free list (a double-freed slot handed to two
    sequences interleaves their KV rows)."""

    def test_double_free_raises(self):
        kv = StaticKVCache(num_slots=2, num_layers=1, max_seq=8,
                           num_heads=2, head_dim=4)
        slot = kv.alloc()
        kv.free(slot)
        with pytest.raises(ValueError, match="double free"):
            kv.free(slot)

    def test_out_of_range_raises(self):
        kv = StaticKVCache(num_slots=2, num_layers=1, max_seq=8,
                           num_heads=2, head_dim=4)
        with pytest.raises(ValueError):
            kv.free(7)
        with pytest.raises(ValueError):
            kv.free(-1)


class TestStepParity:
    """Slot-vs-paged bitwise parity of the raw decode programs: same
    shapes, same reduction order, so greedy AND seeded top-k sampling
    must produce identical tokens (the paged gather lane's contract)."""

    def _run(self, mode):
        spec = _AUDIT_SPEC
        rng = np.random.default_rng(0)
        params = _audit_params(rng)
        S, max_seq, page = 2, 16, 4
        L = spec.num_layers
        H, D = spec.num_heads, spec.head_dim
        slot_step = build_decode_step(spec, 4)
        paged_step = build_paged_decode_step(spec, 4, page, "gather")
        slot_pre = build_prefill_fn(spec, 4)
        paged_pre = build_paged_prefill_fn(spec, 4, page)
        kb_s = jnp.zeros((S, L, max_seq, H, D), jnp.float32)
        vb_s = jnp.zeros_like(kb_s)
        kb_p = jnp.zeros((9, L, page, H, D), jnp.float32)
        vb_p = jnp.zeros_like(kb_p)
        bt = jnp.asarray([[0, 1, 2, 3], [4, 5, 6, 7]], jnp.int32)
        lengths = jnp.zeros((S,), jnp.int32)
        finished = jnp.zeros((S,), bool)
        tokens = jnp.asarray(rng.integers(0, spec.vocab_size, (S, 8)),
                             jnp.int32)
        true_lens = jnp.asarray([5, 3], jnp.int32)
        slot_ids = jnp.asarray([0, 1], jnp.int32)
        temp, topk, dos = ((1.0, 0, False) if mode == "greedy"
                           else (0.9, 3, True))
        samp = (jnp.full((S,), temp, jnp.float32),
                jnp.full((S,), topk, jnp.int32),
                jnp.full((S,), dos, bool),
                jnp.full((S,), -1, jnp.int32))
        key = jax.random.PRNGKey(7)
        ks, vs, ls, fs, last_s = jax.jit(slot_pre)(
            params, tokens, true_lens, kb_s, vb_s, lengths, finished,
            slot_ids, *samp, key)
        kp, vp, lp, fp, last_p = jax.jit(paged_pre)(
            params, tokens, true_lens, kb_p, vb_p, bt, lengths, finished,
            slot_ids, *samp, key)
        assert (np.asarray(last_s) == np.asarray(last_p)).all()
        for i in range(6):
            key = jax.random.PRNGKey(100 + i)
            ks, vs, ls, fs, last_s = jax.jit(slot_step)(  # noqa: PTA008 -- same fn object each pass: pjit cache hit, parity test wants the jitted lane
                params, ks, vs, ls, fs, last_s, *samp, key)
            kp, vp, lp, fp, last_p = jax.jit(paged_step)(  # noqa: PTA008 -- same fn object each pass: pjit cache hit, parity test wants the jitted lane
                params, kp, vp, bt, lp, fp, last_p, *samp, key)
            assert (np.asarray(last_s) == np.asarray(last_p)).all(), \
                (mode, i)
            assert (np.asarray(ls) == np.asarray(lp)).all()
        # the gathered valid rows are the slot rows, bitwise
        g = paged_gather_rows(kp, bt, 0)
        sl = ks[:, 0]
        for si, ln in enumerate(np.asarray(ls)):
            assert (np.asarray(g[si, :ln])
                    == np.asarray(sl[si, :ln])).all()

    def test_greedy_bitwise(self):
        self._run("greedy")

    def test_seeded_topk_bitwise(self):
        self._run("topk")


class TestPagedAttentionKernel:
    def test_matches_gather_reference(self):
        rng = np.random.default_rng(3)
        S, H, D, page, pp = 3, 4, 8, 4, 3
        num_pages = S * pp
        q = jnp.asarray(rng.standard_normal((S, H, D)), jnp.float32)
        ka = jnp.asarray(rng.standard_normal(
            (num_pages + 1, 1, page, H, D)), jnp.float32)
        va = jnp.asarray(rng.standard_normal(ka.shape), jnp.float32)
        bt = jnp.arange(num_pages, dtype=jnp.int32).reshape(S, pp)
        positions = jnp.asarray([2, 7, 11], jnp.int32)
        out = paged_attention(q, ka, va, bt, positions, interpret=True)
        # reference: gather the pages dense, mask, softmax
        kg = paged_gather_rows(ka, bt, 0)        # [S, pp*page, H, D]
        vg = paged_gather_rows(va, bt, 0)
        scale = 1.0 / np.sqrt(D)
        mask = (jnp.arange(pp * page)[None, :]
                <= positions[:, None])           # [S, T]
        logits = jnp.einsum("shd,sthd->sht", q * scale, kg)
        logits = jnp.where(mask[:, None, :], logits, -1e30)
        w = jax.nn.softmax(logits, axis=-1)
        ref = jnp.einsum("sht,sthd->shd", w, vg)
        np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                                   rtol=2e-5, atol=2e-5)

    def test_rejects_int8_arena(self):
        q = jnp.zeros((1, 2, 4), jnp.float32)
        arena = {"q": jnp.zeros((3, 1, 4, 2, 4), jnp.int8),
                 "s": jnp.zeros((3, 1, 4), jnp.float32)}
        bt = jnp.zeros((1, 2), jnp.int32)
        pos = jnp.zeros((1,), jnp.int32)
        with pytest.raises(ValueError, match="dense"):
            paged_attention(q, arena, arena, bt, pos)


class TestEngineParity:
    """End-to-end greedy decode through the engine: the paged layout
    must be invisible in the tokens."""

    PROMPTS = [(5,), (11,), (20,), (33,)]

    def _prompts(self, vocab=64):
        rng = np.random.default_rng(5)
        return [list(rng.integers(0, vocab, n)) for (n,) in self.PROMPTS]

    def test_greedy_bitwise_and_leak_free(self, model):
        prompts = self._prompts()
        slot_eng = _engine(model)
        slot_out = [slot_eng.generate(p, max_new_tokens=6)["tokens"]
                    for p in prompts]
        slot_eng.drain(timeout=120)
        paged_eng = _engine(model, kv_layout="paged", page_size=8)
        paged_out = [paged_eng.generate(p, max_new_tokens=6)["tokens"]
                     for p in prompts]
        st = paged_eng.stats()
        assert slot_out == paged_out
        assert st["kv_layout"] == "paged"
        assert st["pages"]["total"] == 4 * 64 // 8
        kv = paged_eng._batcher.kv
        paged_eng.drain(timeout=120)
        assert kv.pool.pages_in_use == 0     # every exit path released
        assert kv.pool.total_allocs == kv.pool.total_releases

    def test_spec_decode_composed_parity(self, model):
        draft = _tiny_model(seed=1, layers=1)
        prompts = self._prompts()[:3]
        plain = _engine(model)
        plain_out = [plain.generate(p, max_new_tokens=6)["tokens"]
                     for p in prompts]
        plain.drain(timeout=120)
        paged = LLMEngine(model, LLMEngineConfig(
            num_slots=4, max_seq=64, prefill_buckets=(8, 16, 40),
            warmup=True, seed=3, spec_k=2, kv_layout="paged",
            page_size=8), registry=StatRegistry(), draft_model=draft)
        paged_out = [paged.generate(p, max_new_tokens=6)["tokens"]
                     for p in prompts]
        kv = paged._batcher.kv
        paged.drain(timeout=120)
        assert plain_out == paged_out        # spec decode is lossless
        assert kv.pool.pages_in_use == 0

    @pytest.mark.slow      # ~10s of int8 executable compiles; the fast
    # int8 contract (dict-arena kernel rejection + step-level parity)
    # stays in tier-1 via TestPagedAttentionKernel/TestStepParity
    def test_int8_page_parity(self, model):
        prompts = self._prompts()[:3]
        slot8 = _engine(model, kv_dtype="int8")
        slot_out = [slot8.generate(p, max_new_tokens=6)["tokens"]
                    for p in prompts]
        slot8.drain(timeout=120)
        paged8 = _engine(model, kv_dtype="int8", kv_layout="paged",
                         page_size=8)
        paged_out = [paged8.generate(p, max_new_tokens=6)["tokens"]
                     for p in prompts]
        kv = paged8._batcher.kv
        assert kv.quantized
        paged8.drain(timeout=120)
        assert slot_out == paged_out
        assert kv.pool.pages_in_use == 0


class TestPrefixSharing:
    def test_aligned_hit_is_zero_copy(self, model):
        rng = np.random.default_rng(11)
        sysp = list(rng.integers(0, 64, 24))     # 3 pages, page_size 8
        eng = _engine(model, kv_layout="paged", page_size=8,
                      prefix_cache=True)
        r1 = eng.generate(sysp + [1, 2, 3], max_new_tokens=4)["tokens"]
        r2 = eng.generate(sysp + [1, 2, 3], max_new_tokens=4)["tokens"]
        r3 = eng.generate(sysp + [9, 9], max_new_tokens=4)["tokens"]
        ps = eng.prefix_store.stats()
        assert r1 == r2
        assert ps["hits"] == 2 and ps["misses"] == 1
        # 27-token prompts align to a 24-token (3-page) head: every hit
        # splices those pages by refcount — zero bytes copied
        page_nbytes = eng._batcher.kv.page_nbytes()
        assert ps["bytes_copied"] == 0
        assert ps["bytes_shared"] == 2 * 3 * page_nbytes
        reg = eng.registry
        assert reg.get("serving.llm.pages_cow_splits") == 0
        assert reg.get("serving.llm.pages_free") > 0
        # correctness of the divergent third request vs an unshared run
        ref = _engine(model, kv_layout="paged", page_size=8)
        assert r1 == ref.generate(sysp + [1, 2, 3],
                                  max_new_tokens=4)["tokens"]
        assert r3 == ref.generate(sysp + [9, 9],
                                  max_new_tokens=4)["tokens"]
        ref.drain(timeout=120)
        kv = eng._batcher.kv
        eng.drain(timeout=120)
        eng.prefix_store.clear()
        assert kv.pool.pages_in_use == 0

    def test_cow_split_on_partial_page_divergence(self, model):
        rng = np.random.default_rng(13)
        p1 = list(rng.integers(0, 64, 32))       # 4 pages, aligned
        d = (p1[30] + 1) % 64
        p2 = p1[:30] + [d]                       # diverges inside page 3
        eng = _engine(model, kv_layout="paged", page_size=8,
                      prefix_cache=True)
        r1 = eng.generate(p1, max_new_tokens=4)["tokens"]
        r2 = eng.generate(p2, max_new_tokens=4)["tokens"]
        r1b = eng.generate(p1, max_new_tokens=4)["tokens"]
        kv = eng._batcher.kv
        ps = eng.prefix_store.stats()
        # p2 shares 3 full pages, then COWs the partial 4th: rows 24..29
        # reuse the copy, row 30 (the divergent token) writes into it
        assert kv.cow_splits >= 1
        assert ps["bytes_copied"] >= kv.page_nbytes()
        assert eng.registry.get("serving.llm.pages_cow_splits") >= 1
        # shared pages stayed immutable: both sequences decode exactly
        # like unshared engines
        ref = _engine(model, kv_layout="paged", page_size=8)
        assert r1 == ref.generate(p1, max_new_tokens=4)["tokens"]
        assert r2 == ref.generate(p2, max_new_tokens=4)["tokens"]
        assert r1b == r1
        ref.drain(timeout=120)
        eng.drain(timeout=120)
        eng.prefix_store.clear()
        assert kv.pool.pages_in_use == 0

    def test_store_evict_unpinned_releases_pages(self):
        kv = PagedKVCache(num_slots=2, num_layers=1, max_seq=16,
                          num_heads=2, head_dim=4, page_size=4,
                          num_pages=8)
        store = PagedPrefixStore(kv, capacity_pages=8,
                                 registry=StatRegistry())
        slot = kv.alloc()
        kv.ensure_pages(slot, 8)
        toks = np.arange(8, dtype=np.int32)
        sig = (1, 2, 4, "float32", 4)
        entry = store.insert(toks, kv.slot_page_ids(slot), sig)
        kv.free(slot)                        # store refs keep pages live
        assert kv.pool.pages_in_use == 2
        store.unpin(entry)
        assert store.evict_unpinned(2) == 2
        assert kv.pool.pages_in_use == 0


class TestAdmissionAndEviction:
    @pytest.mark.slow      # page-starved drain takes ~5s; admission +
    # reclamation stay covered fast by test_midstream_eviction below
    def test_pending_burst_drains_without_deadlock(self, model):
        # more requests than slots AND pages: everything must complete
        eng = _engine(model, kv_layout="paged", page_size=8,
                      num_pages=16, num_slots=2)
        rng = np.random.default_rng(17)
        reqs = [eng.submit(list(rng.integers(0, 64, 12)),
                           max_new_tokens=4) for _ in range(6)]
        outs = [r.result()["tokens"] for r in reqs]
        assert all(len(t) == 4 for t in outs)
        kv = eng._batcher.kv
        eng.drain(timeout=120)
        assert kv.pool.pages_in_use == 0

    def test_midstream_eviction_reclaims_pages(self, model):
        # two sequences whose combined growth outruns an 8-page pool:
        # the younger is evicted mid-stream, its pages return, and the
        # survivor finishes at full length
        eng = _engine(model, kv_layout="paged", page_size=8,
                      num_pages=8, num_slots=2)
        rng = np.random.default_rng(19)
        r1 = eng.submit(list(rng.integers(0, 64, 20)), max_new_tokens=30)
        r2 = eng.submit(list(rng.integers(0, 64, 20)), max_new_tokens=30)
        results, errors = [], []
        for r in (r1, r2):
            try:
                results.append(r.result()["tokens"])
            except Exception as e:           # noqa: BLE001 -- the evicted lane's error type is the assertion
                errors.append(e)
        assert len(errors) == 1 and "page" in str(errors[0]).lower()
        assert len(results) == 1 and len(results[0]) == 30
        assert eng.registry.get(
            "serving.llm.pages_evicted_midstream") >= 1
        kv = eng._batcher.kv
        eng.drain(timeout=120)
        assert kv.pool.pages_in_use == 0


class TestSchedulerConfig:
    def test_kv_layout_validation(self):
        with pytest.raises(ValueError, match="kv_layout"):
            LLMEngineConfig(kv_layout="fancy")
        with pytest.raises(ValueError, match="page_size"):
            LLMEngineConfig(kv_layout="paged", max_seq=64, page_size=7)
        with pytest.raises(ValueError, match="num_pages"):
            LLMEngineConfig(kv_layout="paged", max_seq=64, page_size=8,
                            num_pages=4)
        with pytest.raises(ValueError, match="paged_attn_impl"):
            LLMEngineConfig(kv_layout="paged", paged_attn_impl="magic")

    def test_decoder_requires_paged_types(self, model):
        dec = GPTPagedDecoder(model, page_size=8)
        assert dec.kv_layout == "paged"
        kv = dec.new_kv(num_slots=2, max_seq=32)
        assert isinstance(kv, PagedKVCache)
        with pytest.raises(NotImplementedError):
            dec.insert_prefix(kv, 0, None, None)


class TestTunerFamily:
    def test_candidates_are_divisors(self):
        from paddle_tpu.tuner import paged_attn_candidates
        cands = [c["block_h"] for c in paged_attn_candidates(12, 64, 16)]
        assert cands and all(12 % b == 0 for b in cands)

    def test_key_and_committed_default(self):
        from paddle_tpu import tuner
        key = tuner.paged_key(4, 8, 8, "float32", platform="cpu")
        assert key == "paged_attn|cpu|float32|h4|d8|p8"
        cfg = tuner._resolve(key)
        assert cfg and cfg["block_h"] == 4   # committed default winner


class TestAuditEntrypoint:
    def test_paged_decode_step_registered(self):
        from paddle_tpu.core.audit import load_default_entrypoints
        eps = load_default_entrypoints()
        assert "llm_paged_decode_step" in eps
