"""Paged KV cache (serving/llm/paged/): page pool + block tables, the
paged decode/prefill/spec programs, COW prefix sharing, page-granular
admission — and the contracts the slot path must keep (double-free
hardening, bitwise decode parity)."""
import functools

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
                                          PageGroup,
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


class TestPageGroups:
    """Two kinds of layer keep different pages of one sequence."""
    PAGE, WINDOW = 4, 8

    def _kv(self, window_pages=10, slots=2, max_seq=176):
        return PagedKVCache(
            num_slots=slots, num_layers=3, max_seq=max_seq, num_heads=2,
            head_dim=4, page_size=self.PAGE, groups=[
                PageGroup((1,), None, slots * max_seq // self.PAGE),
                PageGroup((0, 2), self.WINDOW, window_pages)])

    def test_each_group_has_its_own_arena_pool_and_tables(self):
        kv = self._kv()
        full, window = kv.groups
        assert full.k.shape == (89, 1, 4, 2, 4)
        assert window.k.shape == (11, 2, 4, 2, 4) == window.v.shape
        assert (full.trash, window.trash) == (88, 10)
        assert kv.k is full.k and kv.block_tables is full.block_tables
        assert (np.asarray(window.block_tables) == 10).all()
        assert kv.has_window and kv.kv_bytes() == 2 * (89 + 2 * 11) * 128
        assert kv.group_bytes(windowed=True) == 2 * 2 * 11 * 128

    @pytest.mark.parametrize("span", [1, 4, 8, 16])
    def test_a_window_group_never_holds_more_than_its_bound(self, span):
        """A sequence grows to 20 windows and 160 rows, ``span`` rows at a
        time (decode steps, chunks up to two windows long): mapped before
        the rows are written, released behind the window after."""
        from paddle_tpu.serving.llm.paged.pool import window_page_bound
        kv = self._kv()
        full, window = kv.groups
        bound = window_page_bound(self.WINDOW, span, self.PAGE)
        assert bound == -(-(self.WINDOW + span) // self.PAGE) + 2
        slot = kv.alloc()
        for n in range(span, 20 * self.WINDOW + 1, span):
            kv.ensure_pages(slot, n)
            assert len(window.slot_pages[slot]) <= bound
            assert len(full.slot_pages[slot]) == -(-n // self.PAGE)
            kv.release_behind(slot, n)
            first = max(0, n - self.WINDOW + 1) // self.PAGE
            assert window.first[slot] == first
            table = np.asarray(window.block_tables[slot])
            assert (table[:first] == window.trash).all()
            assert (table[first:-(-n // self.PAGE)] != window.trash).all()
        assert kv.window_released == window.first[slot] >= 37
        assert kv.window_pages() == (len(window.slot_pages[slot]), 40)

    def test_released_pages_serve_another_slot_and_free_walks_the_groups(
            self):
        kv = self._kv(window_pages=4)
        full, window = kv.groups
        a, b = kv.alloc(), kv.alloc()
        kv.ensure_pages(a, 16)               # all 4 of the window pool
        with pytest.raises(PagesExhausted):
            kv.ensure_pages(b, 4)
        assert not full.slot_pages[b]        # atomic across the groups
        assert kv.release_behind(a, 16) == 2     # rows 0-7 are behind
        kv.ensure_pages(b, 8)
        assert sorted(window.slot_pages[b]) == [0, 1]    # a's first two
        kv.free(a)
        assert window.pool.pages_in_use == 2 and full.pool.pages_in_use == 2
        assert window.first[a] == 0
        assert (np.asarray(window.block_tables[a]) == window.trash).all()
        kv.reset()
        assert not window.pool.pages_in_use and not full.pool.pages_in_use
        assert kv.free_slots == 2


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


def _gather_lane(q, ka, va, bt, positions, layer=0, window=None):
    """The reference: the pages gathered dense, masked, softmaxed. Query
    head ``j`` reads KV head ``j // groups``; ``va=None``: fused rows;
    ``window``: rows behind it are masked too."""
    if va is None:
        kg, vg = jnp.split(paged_gather_rows(ka, bt, layer), 2, axis=-1)
    else:
        kg = paged_gather_rows(ka, bt, layer)    # [S, pp*page, Hkv, D]
        vg = paged_gather_rows(va, bt, layer)
    groups = q.shape[1] // kg.shape[2]
    kg, vg = jnp.repeat(kg, groups, axis=2), jnp.repeat(vg, groups, axis=2)
    logits = jnp.einsum("shd,sthd->sht", q / np.sqrt(q.shape[-1]), kg)
    at = jnp.arange(kg.shape[1])[None, :]
    # a walk ends with its table, whatever the position says
    ends = jnp.minimum(positions, kg.shape[1] - 1)[:, None]
    mask = at <= positions[:, None]
    if window is not None:
        mask &= at > ends - window
    w = jax.nn.softmax(jnp.where(mask[:, None, :], logits, -1e30), axis=-1)
    return jnp.einsum("sht,sthd->shd", w, vg)


class TestPagedAttentionKernel:
    # 6 pages of 4 rows a sequence; tiny rows, so the buffers' budget
    # allows the whole table and the kernel takes the largest power of
    # two in it, 4 pages a loop step: the table is a step and a half
    PAGE, PP, STEP = 4, 6, 4
    EDGE = STEP * PAGE            # first row of the second loop step
    POSITIONS = {
        "first_rows": [0, PAGE - 1, PAGE],
        "step_edge": [EDGE - 1, EDGE, EDGE - 2],
        # the table's last row, a dead slot (its table all trash), a
        # short and a long sequence side by side
        "mixed_and_dead": [PP * PAGE - 1, 0, 5, EDGE + 1],
        # a free slot's length keeps counting ticks, past its table: the
        # walk ends with the table (the last slot's has nothing behind it)
        "past_the_table": [3, PP * PAGE, PP * PAGE + 1000],
    }

    def _arenas(self, rng, S, hkv, D, fused, fill=None):
        num_pages = S * self.PP
        shape = (num_pages + 1, 2, self.PAGE, hkv, 2 * D if fused else D)
        make = (lambda: jnp.full(shape, fill, jnp.float32)) if fill \
            is not None else (lambda: jnp.asarray(
                rng.standard_normal(shape), jnp.float32))
        bt = rng.permutation(num_pages).reshape(S, self.PP)
        return make(), None if fused else make(), bt, num_pages

    def test_these_shapes_take_four_pages_a_step(self):
        from paddle_tpu.tuner.space import paged_pages_per_step
        for row, arenas in ((8, 2), (16, 1)):      # two arenas, fused rows
            assert paged_pages_per_step(2, self.PAGE, row, 4, arenas,
                                        self.PP) == self.STEP

    @pytest.mark.parametrize("fused", [False, True],
                             ids=["two_arenas", "fused_rows"])
    @pytest.mark.parametrize("groups", [1, 4])
    @pytest.mark.parametrize("case", sorted(POSITIONS))
    def test_matches_gather_reference(self, case, groups, fused):
        rng = np.random.default_rng(3)
        pos = self.POSITIONS[case]
        S, hkv, D = len(pos), 2, 8
        ka, va, bt, trash = self._arenas(rng, S, hkv, D, fused)
        if case == "mixed_and_dead":
            bt[1] = trash
        q = jnp.asarray(rng.standard_normal((S, hkv * groups, D)),
                        jnp.float32)
        bt, pos = jnp.asarray(bt, jnp.int32), jnp.asarray(pos, jnp.int32)
        out = paged_attention(q, ka, va, bt, pos, layer=1, interpret=True)
        ref = _gather_lane(q, ka, va, bt, pos, layer=1)
        np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                                   rtol=2e-5, atol=2e-5)

    @pytest.mark.parametrize("fused", [False, True],
                             ids=["two_arenas", "fused_rows"])
    @pytest.mark.parametrize("window", [1, 3, 4, 9, 24])
    @pytest.mark.parametrize("case", sorted(POSITIONS))
    def test_windowed_walk_matches_gather_reference(self, case, window,
                                                    fused):
        """Windows of a row, under a page, of a page, over two and of the
        whole table, at the same positions: the walk begins at the
        window's first page and masks the rows before it there."""
        rng = np.random.default_rng(6)
        pos = self.POSITIONS[case]
        S, hkv, D, groups = len(pos), 2, 8, 2
        ka, va, bt, trash = self._arenas(rng, S, hkv, D, fused)
        if case == "mixed_and_dead":
            bt[1] = trash
        q = jnp.asarray(rng.standard_normal((S, hkv * groups, D)),
                        jnp.float32)
        bt, pos = jnp.asarray(bt, jnp.int32), jnp.asarray(pos, jnp.int32)
        out = paged_attention(q, ka, va, bt, pos, layer=1, window=window,
                              interpret=True)
        ref = _gather_lane(q, ka, va, bt, pos, layer=1, window=window)
        np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                                   rtol=2e-5, atol=2e-5)

    @pytest.mark.parametrize("window", [2, 4, 7])
    def test_nothing_behind_the_window_reaches_the_result(self, window):
        """The walk begins at the window: every page wholly behind it is
        given back (its table entry is the trash page's) and holds NaN, as
        do the rows before the window in its first page and past the
        position in its last, and the result is the reference's over the
        window's rows. A step for such a page, or a copy of it, would put
        NaN in the result."""
        rng = np.random.default_rng(7)
        pos = [0, self.PAGE - 1, self.PAGE, self.EDGE - 1, self.EDGE,
               self.PP * self.PAGE - 2]
        S, hkv, D, groups = len(pos), 2, 8, 2
        ka, va, bt, trash = self._arenas(rng, S, hkv, D, False, fill=np.nan)
        live = np.zeros(ka.shape[:3], bool)           # [pages, L, row]
        clean = bt.copy()
        for s, p in enumerate(pos):
            first = max(0, p - window + 1)
            for j in range(first, p + 1):
                live[bt[s, j // self.PAGE], 1, j % self.PAGE] = True
            bt[s, :first // self.PAGE] = trash
            bt[s, p // self.PAGE + 1:] = trash
        rows = jnp.asarray(rng.standard_normal(ka.shape), jnp.float32)
        ka = jnp.where(live[..., None, None], rows, ka)
        va = jnp.where(live[..., None, None], rows[::-1], va)
        q = jnp.asarray(rng.standard_normal((S, hkv * groups, D)),
                        jnp.float32)
        pos = jnp.asarray(pos, jnp.int32)
        out = np.asarray(paged_attention(q, ka, va, jnp.asarray(bt), pos,
                                         layer=1, window=window,
                                         interpret=True))
        assert np.isfinite(out).all()
        ref = _gather_lane(q, jnp.nan_to_num(ka), jnp.nan_to_num(va),
                           jnp.asarray(clean), pos, layer=1, window=window)
        np.testing.assert_allclose(out, np.asarray(ref), rtol=2e-5,
                                   atol=2e-5)

    def test_head_blocks_share_the_walk(self):
        # two head blocks a sequence: each step starts the next one's
        # first copies, across head blocks and across sequences
        rng = np.random.default_rng(4)
        pos = [self.EDGE, 2, self.PP * self.PAGE - 1]
        ka, va, bt, _ = self._arenas(rng, len(pos), 16, 8, False)
        q = jnp.asarray(rng.standard_normal((len(pos), 16, 8)), jnp.float32)
        bt, pos = jnp.asarray(bt, jnp.int32), jnp.asarray(pos, jnp.int32)
        out = paged_attention(q, ka, va, bt, pos, block_h=8,
                              interpret=True)
        np.testing.assert_allclose(
            np.asarray(out), np.asarray(_gather_lane(q, ka, va, bt, pos)),
            rtol=2e-5, atol=2e-5)

    @pytest.mark.parametrize("fused", [False, True],
                             ids=["two_arenas", "fused_rows"])
    def test_nothing_past_the_end_reaches_the_result(self, fused):
        """The walk stops at the sequence's end: every page a sequence
        does not own (the trash page and the other layer among them) and
        every row past its position is NaN, and the result is the
        reference's over the live rows."""
        rng = np.random.default_rng(5)
        pos = [0, self.PAGE - 1, self.PAGE, self.EDGE - 1, self.EDGE,
               self.PP * self.PAGE - 2]
        S, hkv, D, groups = len(pos), 2, 8, 2
        ka, va, bt, trash = self._arenas(rng, S, hkv, D, fused,
                                         fill=np.nan)
        live = np.zeros(ka.shape[:3], bool)           # [pages, L, row]
        for s, p in enumerate(pos):
            bt[s, p // self.PAGE + 1:] = trash
            for j in range(p + 1):
                live[bt[s, j // self.PAGE], 1, j % self.PAGE] = True
        rows = jnp.asarray(rng.standard_normal(ka.shape), jnp.float32)
        ka = jnp.where(live[..., None, None], rows, ka)
        if not fused:
            va = jnp.where(live[..., None, None], rows[::-1], va)
        q = jnp.asarray(rng.standard_normal((S, hkv * groups, D)),
                        jnp.float32)
        bt, pos = jnp.asarray(bt, jnp.int32), jnp.asarray(pos, jnp.int32)
        out = np.asarray(paged_attention(q, ka, va, bt, pos, layer=1,
                                         interpret=True))
        assert np.isfinite(out).all()
        ref = _gather_lane(q, jnp.nan_to_num(ka),
                           None if fused else jnp.nan_to_num(va), bt, pos,
                           layer=1)
        np.testing.assert_allclose(out, np.asarray(ref), rtol=2e-5,
                                   atol=2e-5)

    def test_rejects_int8_arena(self):
        q = jnp.zeros((1, 2, 4), jnp.float32)
        arena = {"q": jnp.zeros((3, 1, 4, 2, 4), jnp.int8),
                 "s": jnp.zeros((3, 1, 4), jnp.float32)}
        bt = jnp.zeros((1, 2), jnp.int32)
        pos = jnp.zeros((1,), jnp.int32)
        with pytest.raises(ValueError, match="dense"):
            paged_attention(q, arena, arena, bt, pos)


def _eqns(jaxpr):
    """Every equation of ``jaxpr`` and of the jaxprs its equations hold."""
    for eqn in jaxpr.eqns:
        yield eqn
        for sub in jax.core.jaxprs_in_params(eqn.params):
            yield from _eqns(sub)


class TestMXURecurrence:
    """Where query heads share a KV head the plain walk puts a page's flat
    rows through the MXU once for all of them, whole pages stacked a
    product (``tuner.space.paged_recurrence``); each case here is such a
    shape, held to the gather lane."""
    PP = 6

    def _positions(self, page):
        # a frozen slot at row 0 (its table all trash), a row of the first
        # page, a page's last row and the next page's first, the second
        # loop step, the table's last row, and a slot past its table
        return [0, 5, page - 1, page, 4 * page + 3, self.PP * page - 1,
                self.PP * page + 1000]

    def _case(self, rng, groups, hkv, fused, page, fill=None):
        from paddle_tpu.tuner.space import (paged_pages_per_step,
                                            paged_recurrence)
        D = 8
        row, arenas = (2 * D, 1) if fused else (D, 2)
        assert paged_recurrence(groups, hkv, page, row, 4, arenas) == "mxu"
        # a loop step of four pages: the table is a step and a half, so a
        # product takes 4, 2 and 1 whole pages in turn
        assert paged_pages_per_step(hkv, page, row, 4, arenas, self.PP) == 4
        pos = self._positions(page)
        S = len(pos)
        num_pages = S * self.PP
        shape = (num_pages + 1, 2, page, hkv, row)
        make = (lambda: jnp.full(shape, fill, jnp.float32)) if fill \
            is not None else (lambda: jnp.asarray(
                rng.standard_normal(shape), jnp.float32))
        bt = rng.permutation(num_pages).reshape(S, self.PP)
        bt[0] = num_pages                       # the trash page
        q = jnp.asarray(rng.standard_normal((S, hkv * groups, D)),
                        jnp.float32)
        return q, make(), None if fused else make(), bt, pos, num_pages

    @pytest.mark.parametrize("window", [None, 21],
                             ids=["to_the_position", "window_21"])
    @pytest.mark.parametrize("page", [16, 64])
    @pytest.mark.parametrize("fused", [False, True],
                             ids=["two_arenas", "fused_rows"])
    @pytest.mark.parametrize("hkv", [2, 4, 8])
    @pytest.mark.parametrize("groups", [2, 4, 8])
    def test_matches_gather_reference(self, groups, hkv, fused, page,
                                      window):
        """A window of 21 rows starts inside a page at every position but
        the frozen slot's and is under a page and over one (pages of 16)."""
        rng = np.random.default_rng(35)
        q, ka, va, bt, pos, _ = self._case(rng, groups, hkv, fused, page)
        bt, pos = jnp.asarray(bt, jnp.int32), jnp.asarray(pos, jnp.int32)
        out = paged_attention(q, ka, va, bt, pos, layer=1, window=window,
                              interpret=True)
        ref = _gather_lane(q, ka, va, bt, pos, layer=1, window=window)
        np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                                   rtol=2e-5, atol=2e-5)

    @pytest.mark.parametrize("window", [None, 21],
                             ids=["to_the_position", "window_21"])
    @pytest.mark.parametrize("fused", [False, True],
                             ids=["two_arenas", "fused_rows"])
    @pytest.mark.parametrize("groups,hkv,page", [(8, 4, 64), (4, 8, 16),
                                                 (2, 2, 16)])
    def test_nothing_outside_the_attended_rows_reaches_the_result(
            self, groups, hkv, page, fused, window):
        """Every row past a position or behind a window, every page a walk
        does not read and the other layer hold NaN: a masked entry that kept
        a weight, or a row of another page stacked into a product, would
        put NaN in the result."""
        rng = np.random.default_rng(36)
        q, ka, va, bt, pos, trash = self._case(rng, groups, hkv, fused,
                                               page, fill=np.nan)
        pos = pos[:-1]              # past its table a walk reads it whole
        q, bt = q[:len(pos)], bt[:len(pos)]
        bt[0] = rng.permutation(trash)[:self.PP]
        live = np.zeros(ka.shape[:3], bool)            # [pages, L, row]
        clean = bt.copy()
        for s, p in enumerate(pos):
            first = 0 if window is None else max(0, p - window + 1)
            for j in range(first, p + 1):
                live[bt[s, j // page], 1, j % page] = True
            bt[s, :first // page] = trash
            bt[s, p // page + 1:] = trash
        rows = jnp.asarray(rng.standard_normal(ka.shape), jnp.float32)
        ka = jnp.where(live[..., None, None], rows, ka)
        if not fused:
            va = jnp.where(live[..., None, None], rows[::-1], va)
        pos = jnp.asarray(pos, jnp.int32)
        out = np.asarray(paged_attention(q, ka, va, jnp.asarray(bt), pos,
                                         layer=1, window=window,
                                         interpret=True))
        assert np.isfinite(out).all()
        ref = _gather_lane(q, jnp.nan_to_num(ka),
                           None if fused else jnp.nan_to_num(va),
                           jnp.asarray(clean), pos, layer=1, window=window)
        np.testing.assert_allclose(out, np.asarray(ref), rtol=2e-5,
                                   atol=2e-5)

    @pytest.mark.parametrize("shape,found", [
        # (groups, KV heads, page, row width, itemsize, arenas)
        ((1, 16, 16, 128, 4, 2), "vpu"),    # both 1.3B cells
        ((1, 12, 16, 128, 4, 1), "vpu"),    # chip_smoke.py, side by side
        ((4, 8, 16, 128, 4, 1), "mxu"),     # serve-lfm2moe-decode, fused
        ((8, 4, 64, 128, 4, 2), "mxu"),     # serve-trinity-mixedctx
        ((16, 1, 64, 640, 4, 1), "mxu"),    # serve-moonlight-longgen, latent
        ((16, 1, 64, 576, 4, 1), "mxu"),    # its rows without the padding
        ((2, 3, 2, 8, 4, 2), "vpu"),        # 6 flat rows: no sublane tile
        ((2, 4, 4, 128, 2, 2), "mxu"),      # bfloat16: a tile is 16 rows
        ((2, 2, 4, 128, 2, 2), "vpu"),
        ((8, 32, 256, 128, 4, 2), "vpu"),   # a page is over the buffers
    ])
    def test_the_rule_reads_shapes_alone(self, shape, found):
        from paddle_tpu.tuner import space
        assert space.paged_recurrence(*shape) == found
        # nothing but its arguments: no tuner entry, no device, no option
        assert space.paged_recurrence.__code__.co_names == (
            "paged_buffer_bytes", "PAGED_BUFFER_BUDGET", "SUBLANE_ROWS")

    def test_a_head_block_the_flat_rows_cannot_take_is_sanitized(self):
        """The tuner's block_h candidates reach the call: where the rule
        sends the shape to the MXU a page's flat rows hold all the KV
        heads, whatever block was asked for."""
        rng = np.random.default_rng(37)
        q, ka, va, bt, pos, _ = self._case(rng, 2, 16, False, 16)
        bt, pos = jnp.asarray(bt, jnp.int32), jnp.asarray(pos, jnp.int32)
        out = paged_attention(q, ka, va, bt, pos, block_h=8, interpret=True)
        np.testing.assert_allclose(
            np.asarray(out), np.asarray(_gather_lane(q, ka, va, bt, pos)),
            rtol=2e-5, atol=2e-5)

    @pytest.mark.parametrize("steps,q_heads,flat,pages", [
        (8, 32, 256, 8),        # serve-trinity-mixedctx: the whole step
        (8, 16, 64, 8),         # serve-moonlight-longgen: the whole step
        (32, 32, 128, 32),      # serve-lfm2moe-decode: the whole step
        (32, 128, 128, 8),      # more heads stack fewer pages
        (4, 4, 8, 4), (1, 512, 1024, 1)])
    def test_pages_a_product_follow_the_step_and_the_score_block(
            self, steps, q_heads, flat, pages):
        from paddle_tpu.tuner import space
        assert space.paged_stack_pages(steps, q_heads, flat) == pages

    def test_one_query_head_a_kv_head_is_the_vpu_program(self, monkeypatch):
        """G = 1 never takes the new body: its lowered call is the same
        text whether the rule is consulted or stands fixed at "vpu", and
        that text holds no product."""
        from paddle_tpu.ops import paged_attention as pa
        from paddle_tpu.tuner import space
        arena = jnp.zeros((9, 2, 16, 16, 128), jnp.float32)
        args = (jnp.zeros((2, 16, 128), jnp.float32), arena, arena,
                jnp.zeros((2, 4), jnp.int32), jnp.zeros((2,), jnp.int32))

        def lowered():
            pa._paged_attention.clear_cache()
            return jax.jit(lambda *a: paged_attention(
                *a, layer=1, interpret=False)).trace(*args).lower(
                    lowering_platforms=("tpu",)).as_text()

        asked = []
        rule = space.paged_recurrence

        def watched(*shape):
            asked.append(shape)
            return rule(*shape)

        texts = []
        # one call site: the lowered text carries its callers' lines
        for stand_in in (watched, lambda *shape: "vpu"):
            monkeypatch.setattr(space, "paged_recurrence", stand_in)
            texts.append(lowered())
        assert asked and all(shape[0] == 1 for shape in asked)
        assert texts[0] == texts[1]
        pa._paged_attention.clear_cache()
        kernel, = [e for e in jax.make_jaxpr(lambda *a: paged_attention(
            *a, layer=1, interpret=False))(*args).eqns[-1].params[
                "jaxpr"].eqns if e.primitive.name == "pallas_call"]
        assert "dot_general" not in str(kernel.params["jaxpr"])


class TestLatentRows:
    """``paged_attention(latent=...)``: one row a token for all the query
    heads, a key whole and a value in its first columns; the walk is the
    plain one (``tests/test_moonlight.py`` holds it to an expanded walk)."""

    @pytest.mark.parametrize("row", [24, 128])
    @pytest.mark.parametrize("heads,page,pp", [(4, 8, 5), (16, 16, 3),
                                               (2, 8, 17)])
    def test_what_lies_past_the_end_never_reaches_the_result(self, row,
                                                             heads, page, pp):
        """NaN in every row past a sequence's position, in the trash page
        and behind the row's own columns' padding of other pages: the result
        is finite and the gather lane's."""
        from paddle_tpu.serving.llm.paged.moonlight import \
            latent_gather_attention
        rng = np.random.default_rng(heads + page + row)
        value, rotary, seqs = 16, 8, 4
        pages = seqs * pp
        arena = rng.standard_normal((pages + 1, 2, page, row)).astype(
            np.float32)
        arena[..., value + rotary:] = 0.0
        bt = rng.permutation(pages).reshape(seqs, pp).astype(np.int32)
        pos = np.array([0, page - 1, page, pp * page - 2])[:seqs]
        clean = arena.copy()
        arena[-1] = np.nan
        for s_, p_ in enumerate(pos):       # the rows past each position
            at = np.arange(pp * page)
            dead = at[at > p_]
            arena[bt[s_, dead // page], :, dead % page] = np.nan
        q = jnp.asarray(rng.standard_normal((seqs, heads, value + rotary)),
                        jnp.float32)
        out = np.asarray(paged_attention(
            q, jnp.asarray(arena), None, jnp.asarray(bt),
            jnp.asarray(pos, jnp.int32), layer=1, scale=0.3,
            latent=(value, rotary), interpret=True))
        assert out.shape == (seqs, heads, value) and np.isfinite(out).all()
        want = latent_gather_attention(
            q, jnp.asarray(clean), jnp.asarray(bt),
            jnp.asarray(pos, jnp.int32), 1, 0.3, (value, rotary))
        np.testing.assert_allclose(out, np.asarray(want), rtol=2e-5,
                                   atol=2e-5)

    def test_the_other_walks_calls_are_what_they_were(self):
        """``latent=None``: the kernel's parameters of a fused and of a
        two-arena call hold no ``value_width`` (the compiled kernels of the
        accepted cells are keyed by them)."""
        for fused in (False, True):
            arena = jnp.zeros((9, 2, 16, 4, 256 if fused else 128))
            args = (jnp.zeros((2, 16, 128)), arena,
                    None if fused else arena, jnp.zeros((2, 4), jnp.int32),
                    jnp.zeros((2,), jnp.int32))
            *_, jitted = jax.make_jaxpr(
                lambda *a: paged_attention(*a, interpret=True))(*args).eqns
            assert "value_width" not in jitted.params or \
                jitted.params.get("value_width") is None
            call, = [e for e in jitted.params["jaxpr"].eqns
                     if e.primitive.name == "pallas_call"]
            assert call.params["out_avals"][0].shape[-1] == (
                256 if fused else 128)


    #: the accepted cells' calls: query heads and width, the arena (a second
    #: one of the same shape or fused rows), pages a sequence, options, and
    #: the products the traced kernel holds (none on the VPU recurrence; two
    #: a product size and masked page on the MXU one)
    SERVED = {
        "gpt1p3b": ((16, 128), (49, 2, 16, 16, 128), True, 48, {}, 0),
        "lfm2": ((32, 64), (97, 2, 16, 8, 128), False, 96, {}, 14),
        "trinity_window": ((32, 128), (40, 2, 64, 4, 128), True, 34,
                           {"window": 2048}, 12),
        "trinity_full": ((32, 128), (40, 2, 64, 4, 128), True, 34, {}, 10),
    }

    @staticmethod
    def _kernel_dots(q, arena, second, pp, **options):
        """The ``dot_general`` equations of the traced kernel of one call
        over four sequences, as (operand dtypes, precision, result dtype)."""
        shape = jax.ShapeDtypeStruct
        args = [shape((4,) + q, jnp.float32), shape(arena, jnp.float32),
                shape(arena, jnp.float32) if second else None,
                shape((4, pp), jnp.int32), shape((4,), jnp.int32)]
        traced = jax.make_jaxpr(lambda *a: paged_attention(
            *a, layer=1, interpret=True, **options))(*args)
        return [(tuple(v.aval.dtype.name for v in e.invars),
                 e.params["precision"], e.outvars[0].aval.dtype.name)
                for e in _eqns(traced.jaxpr)
                if e.primitive.name == "dot_general"]

    @pytest.mark.parametrize("cell", list(SERVED))
    def test_the_split_is_not_in_the_other_cells_kernels(self, cell,
                                                         monkeypatch):
        """At the accepted cells' shapes the kernel's products are float32
        operands at ``highest``, as many as before the latent walk split its
        own, and the call records no operand dtype."""
        from jax import lax
        from paddle_tpu.core import pallas_mode
        monkeypatch.setattr(pallas_mode, "_OPERANDS", {})
        *call, options, products = self.SERVED[cell]
        dots = self._kernel_dots(*call, **options)
        assert len(dots) == products
        assert all(dot == (("float32", "float32"), (lax.Precision.HIGHEST,) * 2,
                           "float32") for dot in dots)
        assert "paged_attn" not in pallas_mode.chosen_operand_dtypes()

    def test_the_latent_walk_feeds_the_mxu_bfloat16_parts(self, monkeypatch):
        """Every product of the latent kernel takes bfloat16 operands at
        ``DEFAULT`` into float32, three (a part of the fetched rows each)
        where there was one, and the call says so at trace time."""
        from jax import lax
        from paddle_tpu.core import pallas_mode
        monkeypatch.setattr(pallas_mode, "_OPERANDS", {})
        dots = self._kernel_dots((16, 576), (120, 2, 64, 640), False, 112,
                                 latent=(512, 64))
        # two products a size (8, 4, 2, 1 pages) and the masked page
        assert len(dots) == 3 * 10
        assert all(dot == (("bfloat16", "bfloat16"), (lax.Precision.DEFAULT,) * 2,
                           "float32") for dot in dots)
        assert pallas_mode.chosen_operand_dtypes()["paged_attn"] == (
            "bfloat16",)


_SCORES, _VALUES = (((1,), (1,)), ((), ())), (((1,), (0,)), ((), ()))
#: the six bfloat16 products of a float32 product at ``highest``, by the
#: parts (0 high, 1 middle, 2 low) of the streamed and the standing operand
_SIX = ((0, 0), (0, 1), (1, 0), (0, 2), (2, 0), (1, 1))


class TestStackedDot:
    """The latent walk's products (``ops/paged_attention.py:_stacked_dot``):
    a float32 product as its six bfloat16 partial products, three loads of
    the standing operand, each case held to a float64 product."""

    @staticmethod
    def _operands(product, pages, exact):
        """The score product's ``[16, 640] x [640, pages * 64]`` or the value
        product's ``[16, pages * 64] x [pages * 64, 512]``, over eight
        decades of magnitude or (``exact``) small whole numbers."""
        rng = np.random.default_rng(39 + pages)
        shapes = ((16, 640), (pages * 64, 640)) if product == "scores" \
            else ((16, pages * 64), (pages * 64, 512))
        if exact:       # exact in bfloat16, and every sum of products in f32
            return [rng.integers(-8, 9, n).astype(np.float32) for n in shapes]
        return [(rng.choice([-1.0, 1.0], n) * 10.0 ** rng.uniform(-4, 4, n)
                 ).astype(np.float32) for n in shapes]

    @staticmethod
    def _judged(product, a, b, dropped=None):
        """The stacked product of ``a`` and ``b`` (less the partial product
        ``dropped``), what three passes (``high``) leave of the same parts,
        the float64 product and the sum of its terms' magnitudes."""
        from paddle_tpu.ops.paged_attention import _bf16_parts, _stacked_dot
        sub = "rk,nk->rn" if product == "scores" else "rk,kn->rn"
        dims = _SCORES if product == "scores" else _VALUES
        a_parts, b_parts = _bf16_parts(jnp.asarray(a)), _bf16_parts(
            jnp.asarray(b))
        got = np.asarray(_stacked_dot(jnp.concatenate(a_parts), b_parts,
                                      dims), np.float64)
        a64, b64 = ([np.asarray(part, np.float64) for part in parts]
                    for parts in (a_parts, b_parts))
        if dropped is not None:
            got = got - np.einsum(sub, a64[dropped[0]], b64[dropped[1]])
        three = sum(np.einsum(sub, a64[i], b64[j]) for i, j in _SIX[:3])
        want = np.einsum(sub, a.astype(np.float64), b.astype(np.float64))
        terms = np.einsum(sub, np.abs(a).astype(np.float64),
                          np.abs(b).astype(np.float64))
        return got, three, want, terms

    @pytest.mark.parametrize("pages", [1, 2, 4, 8])
    @pytest.mark.parametrize("product", ["scores", "values"])
    def test_all_six_partial_products_over_eight_decades(self, product,
                                                         pages):
        """An entry lies within ``2**-21`` of the sum of its terms'
        magnitudes (the three products ``highest`` itself leaves out are
        ``2**-23`` of it), and 20 times closer than three passes come."""
        got, three, want, terms = self._judged(
            product, *self._operands(product, pages, exact=False))
        err, err_high = np.abs(got - want) / terms, np.abs(three - want) / terms
        assert err.max() <= 2.0 ** -21
        assert 20 * err.max() <= err_high.max()

    @pytest.mark.parametrize("pages", [1, 2, 4, 8])
    @pytest.mark.parametrize("product", ["scores", "values"])
    def test_operands_exact_in_bfloat16_give_the_exact_product(self, product,
                                                               pages):
        got, _, want, _ = self._judged(
            product, *self._operands(product, pages, exact=True))
        np.testing.assert_array_equal(got, want)

    @pytest.mark.parametrize("dropped", _SIX, ids=lambda d: "hml"[d[0]]
                             + "hml"[d[1]])
    @pytest.mark.parametrize("product", ["scores", "values"])
    def test_a_dropped_partial_product_is_seen(self, product, dropped):
        """Without any one of the six the result is no longer 20 times
        closer than three passes: the bound above is a bound on all six."""
        got, three, want, terms = self._judged(
            product, *self._operands(product, 8, exact=False),
            dropped=dropped)
        err, err_high = np.abs(got - want) / terms, np.abs(three - want) / terms
        assert 20 * err.max() > err_high.max()


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


class TestLivePageCounters:
    def test_counts_follow_the_requests_lengths_tick_by_tick(self, model):
        """``paged_attn.pages_live`` / ``pages_table`` grow with each step
        dispatched by what the lengths of the requests in flight give (the
        pages up to each write position; a table row of 8 pages a
        request), and ``stats()`` reports their ratio. The tick runs one
        step ahead: a request with a row in the step not yet fetched is one
        token further than the host has counted."""
        eng = _engine(model, kv_layout="paged", page_size=8)
        batcher, reg = eng._batcher, eng._registry
        name = eng._prefix + ".paged_attn.pages_"
        inner, seen = batcher._dispatch_step, []

        def counted_step(ahead):
            lens = [r.seq_len + (ahead is not None
                                 and ahead.reqs.get(slot) is r)
                    for slot, r in batcher._reqs.items()]
            before = reg.get(name + "live"), reg.get(name + "table")
            step = inner(ahead)
            seen.append((lens, reg.get(name + "live") - before[0],
                         reg.get(name + "table") - before[1]))
            return step

        assert eng.stats()["paged_attn_live_page_share"] is None
        batcher._dispatch_step = counted_step
        rng = np.random.default_rng(11)
        futs = [eng.submit(list(rng.integers(0, 64, n)), max_new_tokens=m)
                for n, m in ((5, 12), (20, 7), (33, 9))]
        for f in futs:
            f.result(120)
        assert len(seen) >= 11 and any(len(t[0]) == 3 for t in seen)
        for lens, live, table in seen:
            assert live == sum((n - 1) // 8 + 1 for n in lens)
            assert table == len(lens) * (64 // 8)
        share = eng.stats()["paged_attn_live_page_share"]
        assert share == sum(t[1] for t in seen) / sum(t[2] for t in seen)
        assert 0 < share < 1
        eng.drain(timeout=120)


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

    @pytest.mark.parametrize("impl,found", [("kernel", "vpu"),
                                            ("gather", None)])
    def test_stats_name_the_plain_walks_recurrence(self, model, impl, found):
        """A GPT engine has one query head a KV head: the VPU recurrence,
        named where the kernel lane runs and nowhere else."""
        eng = _engine(model, kv_layout="paged", page_size=8,
                      paged_attn_impl=impl, warmup=False)
        try:
            st = eng.stats()
            assert st["paged_attn_recurrence"] == found
            gauge = st["stats"].get(
                eng.config.stat_prefix + ".paged_attn.recurrence_mxu")
            assert gauge == (None if found is None else 0)
        finally:
            eng.drain(timeout=30)

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


    @pytest.mark.parametrize("shape,pages", [
        # (block_h, page, row width, itemsize, arenas, pages a sequence)
        ((16, 16, 128, 4, 2, 48), 8),     # serve-gpt1p3b-decode
        ((16, 16, 128, 4, 2, 68), 8),     # serve-gpt1p3b-longprompt
        ((8, 16, 128, 4, 1, 96), 32),     # serve-lfm2moe-decode, fused
        ((16, 16, 128, 4, 2, 6), 4),      # no more than the table has
        ((32, 256, 128, 4, 2, 48), 1),    # one page is over the budget
        ((1, 64, 640, 4, 1, 112), 8),     # serve-moonlight-longgen's rows
    ])
    def test_pages_a_step_follow_shapes_and_budget(self, shape, pages):
        from paddle_tpu.tuner import space
        assert space.paged_pages_per_step(*shape) == pages
        *dims, table = shape
        buffers = space.paged_buffer_bytes(pages, *dims)
        # the largest power of two that fits, unless not even one does
        assert buffers <= space.PAGED_BUFFER_BUDGET or pages == 1
        assert 2 * buffers > space.PAGED_BUFFER_BUDGET or 2 * pages > table

    @pytest.mark.parametrize("hkv,groups,fused,page", [
        (4, 1, False, 4), (2, 4, True, 4),      # the second: flat rows
        (3, 2, False, 2),                       # grouped, on the VPU
        (4, 8, False, 64), (8, 4, True, 16)])   # Trinity's and LFM2's heads
    def test_vmem_model_is_what_the_kernel_allocates(self, hkv, groups,
                                                     fused, page):
        """``paged_attn_vmem_bytes`` against the traced call: its VMEM
        scratch, and the q and out blocks the pipeline holds twice. The MXU
        recurrence keeps its buffers as flat rows, its accumulator as ``[Hq,
        row]`` and its statistics a column a query head: the same bytes."""
        from paddle_tpu.tuner.space import paged_attn_vmem_bytes
        pp, D = 6, 8
        row = 2 * D if fused else D
        arena = jnp.zeros((9, 2, page, hkv, row), jnp.float32)
        args = (jnp.zeros((2, hkv * groups, D)), arena,
                None if fused else arena, jnp.zeros((2, pp), jnp.int32),
                jnp.zeros((2,), jnp.int32))
        *_, jitted = jax.make_jaxpr(
            lambda *a: paged_attention(*a, interpret=True))(*args).eqns
        call, = [e for e in jitted.params["jaxpr"].eqns
                 if e.primitive.name == "pallas_call"]
        grid = call.params["grid_mapping"]
        scratch = call.params["jaxpr"].invars[-grid.num_scratch_operands:]
        held = sum(v.aval.size * v.aval.dtype.itemsize for v in scratch
                   if str(v.aval.memory_space) == "vmem")
        held += 2 * 2 * groups * hkv * row * 4          # q, out: twice each
        # the MXU recurrence's values: the largest product's scores, their
        # exp, and a bias a product size (that many pages, half, ... one)
        scores = max([v.aval.size * 4 for e in _eqns(call.params["jaxpr"])
                      if e.primitive.name == "dot_general"
                      for v in e.outvars], default=0)
        if scores:
            one_page = groups * hkv * page * hkv * 4
            held += 4 * scores - one_page
        assert held == paged_attn_vmem_bytes(
            hkv, page, row, 4, arenas=1 if fused else 2, groups=groups,
            pages_per_seq=pp)


    @pytest.mark.parametrize("shape,held", [
        # (block_h, page, row width, itemsize, arenas, groups, pages a seq)
        ((16, 16, 128, 4, 2, 1, 48), 4251648),    # serve-gpt1p3b-decode
        ((16, 16, 128, 4, 2, 1, 68), 4251648),    # serve-gpt1p3b-longprompt
        ((8, 16, 128, 4, 1, 4, 96), 6389760),     # serve-lfm2moe-decode
        ((4, 64, 128, 4, 2, 8, 272), 5324800),    # serve-trinity-mixedctx
    ])
    def test_vmem_model_at_the_served_shapes_is_what_it_was(self, shape,
                                                            held):
        """The latent walk's account (below) is the latent shape's alone."""
        from paddle_tpu.tuner.space import paged_attn_vmem_bytes
        assert paged_attn_vmem_bytes(*shape) == held

    def test_vmem_model_counts_the_latent_walks_parts(self):
        """ONE KV head in ONE arena on the MXU recurrence is the latent
        walk: no bias, and beside the buffers, the blocks, the scratch, the
        scores and their ``exp`` it holds the three bfloat16 parts of a loop
        step's rows (as converted and as the MXU takes them) and the float32
        rows a part leaves, the stacked parts of the queries and of ``exp``,
        and the partial results of both products. Under the 16 MiB scope
        with its 2.6 MB of page buffers; what the compiler builds for a
        described v5e is in the model's docstring."""
        from paddle_tpu.tuner import space
        heads, page, row, stack = 16, 64, 640, 8
        shape = (1, page, row, 4, 1, heads, 112)
        assert space.paged_recurrence(heads, 1, page, row, 4, 1) == "mxu"
        assert space.paged_pages_per_step(1, page, row, 4, 1, 112) == stack
        assert space.paged_stack_pages(stack, heads, page) == stack
        buffers = space.paged_buffer_bytes(stack, 1, page, row, 4, 1)
        assert buffers == 2621440
        rows, block = stack * page * row, heads * stack * page * 4
        parts = 3 * rows * 2
        assert parts == 1966080                     # the issue's 1.97 MB
        stacked = 3 * heads * row * 2 + 3 * heads * stack * page * 2
        partial = 6 * block + 6 * heads * row * 4
        held = (buffers + 2 * 2 * heads * row * 4   # q and out, twice each
                + heads * row * 4 + 2 * heads * 128 * 4     # acc, max, sum
                + 2 * block + 2 * parts + rows * 4 + stacked + partial)
        assert space.paged_attn_vmem_bytes(*shape) == held == 8704000
        assert held < space.VMEM_BUDGET < space.VMEM_BYTES == 16 * 2 ** 20


class TestAuditEntrypoint:
    def test_paged_decode_step_registered(self):
        from paddle_tpu.core.audit import load_default_entrypoints
        eps = load_default_entrypoints()
        assert "llm_paged_decode_step" in eps


# -- the seam between the engine and a decoder family -------------------------

@functools.lru_cache(maxsize=None)
def _toy_family(name):
    """``(decoder class, an unseeded toy model, its page size)``: the
    rehearsal widths of the family's benchmark configuration. Nothing here
    runs a program, so the weights are whatever the layers start with (and
    one model a family serves every test below)."""
    import importlib
    import json
    import os
    from benchmark import spec as bench_spec
    from paddle_tpu.serving.llm import paged
    if name == "GPT":
        return GPTPagedDecoder, _tiny_model(), 8
    adapter, config, cls = {
        "LFM2": ("lfm2_adapter", "lfm2-8b-a1b", paged.LFM2PagedDecoder),
        "SALA": ("sala_adapter", "minicpm-sala", paged.SALAPagedDecoder),
        "Trinity": ("trinity_adapter", "trinity-mini",
                    paged.TrinityPagedDecoder),
        "Moonlight": ("moonlight_adapter", "moonlight-16b-a3b",
                      paged.MoonlightPagedDecoder),
        "Qwen3-Next": ("qwen3next_adapter", "qwen3-next-80b-a3b",
                       paged.Qwen3NextPagedDecoder)}[name]
    with open(os.path.join(bench_spec.HERE, "configs",
                           config + ".json")) as f:
        cfg = json.load(f)
    cfg = bench_spec._merged(cfg, cfg["rehearsal"])
    net = importlib.import_module("benchmark." + adapter).build_net(cfg)
    net.eval()
    return cls, net, 8      # SALA's selection block at these widths is 8


FAMILIES = ["LFM2", "SALA", "Trinity", "Moonlight", "Qwen3-Next"]


class TestDecoderProtocol:
    """What ``PagedBatcher`` and ``LLMEngine`` ask of a decoder is declared
    (``paged/decode.py:PagedDecoderProtocol``), never probed."""

    @pytest.mark.parametrize("name", ["GPT"] + FAMILIES)
    def test_every_decoder_declares_the_protocol(self, name):
        import inspect
        import re
        from paddle_tpu.serving.llm.paged import PagedBatcher
        from paddle_tpu.serving.llm.paged.decode import (
            PagedDecoderProtocol, PagedFamilyDecoder)
        cls, _, _ = _toy_family(name)
        assert issubclass(cls, PagedDecoderProtocol)
        assert issubclass(cls, PagedFamilyDecoder) == (name != "GPT")
        assert cls.kv_layout == "paged"
        assert cls.supports_export is (name == "GPT")
        assert cls.prefills_in_chunks is (name not in ("GPT", "LFM2"))
        hooks = ["plain_walk", "publish_gauges", "note_lengths",
                 "note_chunk", "note_tick"]
        calls = ["check_config", "new_kv", "params", "prefix_sig", "prefill",
                 "decode_step"]
        if cls.prefills_in_chunks:
            calls.append("chunk_prefill")
        for hook in hooks + calls:
            assert callable(getattr(cls, hook)), hook
        # the hooks take what the protocol's take
        for hook in hooks:
            assert list(inspect.signature(getattr(cls, hook)).parameters) \
                == list(inspect.signature(
                    getattr(PagedDecoderProtocol, hook)).parameters), hook
        if name != "GPT":
            # a family's class holds what is its own; the calls are the base's
            own = set(vars(cls))
            assert not own & {"__init__", "model", "params", "decode_fn",
                              "chunk_prefill", "decode_step"}
            assert ("check_config" in own) == (name == "Trinity")
            assert cls.family == name
        # and the engine reads the declaration: no probe of a decoder but
        # the guard against a slot decoder handed in by mistake, and the
        # lane of a slot engine's decoder, which declares nothing
        probe = re.compile(
            r"(?:hasattr|getattr)\(\s*(?:self\.)?_?decoder,\s*\"(\w+)\"")
        assert probe.findall(inspect.getsource(PagedBatcher)) \
            == ["kv_layout"]
        assert set(probe.findall(inspect.getsource(LLMEngine))) \
            <= {"attn_impl"}

    @pytest.mark.parametrize("name", FAMILIES)
    def test_what_a_family_does_not_serve_raises_in_its_name(self, name):
        cls, net, page = _toy_family(name)
        the = f"the {name} paged decoder "
        with pytest.raises(NotImplementedError,
                           match=the + "does not serve over a mesh yet"):
            cls(net, mesh=object())
        for dtypes in ({"weight_dtype": "int8"}, {"kv_dtype": "int8"}):
            with pytest.raises(NotImplementedError,
                               match=the + "serves float32 weights and .* "
                               "only .*'int8'"):
                cls(net, page_size=page, **dtypes)
        with pytest.raises(ValueError, match="attn_impl must be 'auto', "
                           "'gather' or 'kernel', got 'flash'"):
            cls(net, page_size=page, attn_impl="flash")
        dec = cls(net, page_size=page, max_top_k=10 ** 6)
        assert dec.attn_impl == "gather"            # "auto" off the chip
        assert dec.max_top_k == getattr(dec.spec, cls.vocab)
        kw = dict(kv_layout="paged", page_size=page, max_seq=32,
                  warmup=False)
        dec.check_config(LLMEngineConfig(**kw))
        for option in ({"prefix_cache": True}, {"spec_k": 2}):
            with pytest.raises(NotImplementedError,
                               match=the + f"does not support "
                               f"{next(iter(option))} yet \\(.+\\)"):
                dec.check_config(LLMEngineConfig(**kw, **option))
        if cls.prefills_in_chunks:
            with pytest.raises(ValueError, match="prefill_chunk 12 must be "
                               "a multiple of the page size 8"):
                dec.check_config(LLMEngineConfig(**kw, prefill_chunk=12))
        else:
            with pytest.raises(NotImplementedError,
                               match=cls.__name__ + " has no chunked"):
                LLMEngine(net, LLMEngineConfig(**kw, prefill_chunk=8))
        with pytest.raises(ValueError, match="exceeds the model's"):
            dec.new_kv(2, dec.spec.max_position_embeddings + page)

    @pytest.mark.parametrize("name", ["GPT"] + FAMILIES)
    def test_the_recurrence_is_the_tuners_for_the_familys_arena(self, name):
        """``paged_attn_recurrence`` and its gauge against
        ``tuner.space.paged_recurrence`` called by hand with each arena's
        shape as the family's docstring gives it."""
        from paddle_tpu.tuner.space import paged_recurrence
        _, net, page = _toy_family(name)
        c = getattr(net, "config", None)
        by_hand = {
            # [P+1, L, page, H, D] twice: a query head a KV head
            # (``_tiny_model``: 4 heads of 32 / 4)
            "GPT": lambda: paged_recurrence(1, 4, page, 8, 4, 2),
            # [P+1, La, page, Hkv, 2D]
            "LFM2": lambda: paged_recurrence(
                c.num_attention_heads // c.num_key_value_heads,
                c.num_key_value_heads, page, 2 * c.head_dim, 4, 1),
            "SALA": lambda: None,       # every walk is over selected pages
            # [P+1, layers, page, Hkv, D] twice, the first group's
            "Trinity": lambda: paged_recurrence(
                c.num_attention_heads // c.num_key_value_heads,
                c.num_key_value_heads, page, c.head_dim, 4, 2),
            # [P+1, L, page, row]: every query head on a token's one row
            "Moonlight": lambda: paged_recurrence(
                c.num_attention_heads, 1, page,
                -(-c.latent_row // 128) * 128, 4, 1),
            # [P+1, L * Hkv, page, 2D]: a KV head a call
            "Qwen3-Next": lambda: paged_recurrence(
                c.num_attention_heads // c.num_key_value_heads, 1, page,
                2 * c.head_dim, 4, 1)}[name]()
        for impl, want in (("kernel", by_hand), ("gather", None)):
            eng = LLMEngine(net, LLMEngineConfig(
                kv_layout="paged", num_slots=2, max_seq=32, page_size=page,
                num_pages=10, prefill_buckets=[16], max_top_k=4,
                paged_attn_impl=impl, warmup=False),
                registry=StatRegistry())
            try:
                st = eng.stats()
                assert st["paged_attn_impl"] == impl
                assert st["paged_attn_recurrence"] == want
                gauge = st["stats"].get(
                    eng.config.stat_prefix + ".paged_attn.recurrence_mxu")
                assert gauge == (None if want is None
                                 else int(want == "mxu"))
            finally:
                eng.drain(timeout=10)
