"""paddle_tpu.tuner: autotuner search, winner-cache integrity, and the
tuned flash-attention/NMS kernel paths.

Covers the ISSUE-P11 satellite guarantees:
- odd sequence lengths stay numerically exact for any sane block config
  (the wrapper pads; the kernel core rejects non-dividing blocks),
- a corrupt/truncated/version-mismatched winner cache is ignored with a
  warning and retuned — never crashes, never silently applies bad blocks.
"""
import json
import os
import warnings

import numpy as np
import pytest

import jax
import jax.numpy as jnp

import paddle_tpu.tuner as tuner
from paddle_tpu.tuner import space, store
from paddle_tpu.ops.pallas_attention import (DEFAULT_BLOCK, _fa_fwd_with_lse,
                                             _sanitize_block,
                                             flash_attention)


@pytest.fixture()
def tune_cache(tmp_path, monkeypatch):
    """Point the winner cache at a fresh dir and reset all memo tiers."""
    monkeypatch.setenv("PADDLE_TPU_TUNE_CACHE", str(tmp_path))
    tuner.clear_memo()
    yield tmp_path
    tuner.clear_memo()


def _dense_ref(q, k, v, causal):
    qb, kb, vb = (np.moveaxis(x, 2, 1) for x in (q, k, v))
    s = np.einsum("bhqd,bhkd->bhqk", qb, kb) / np.sqrt(q.shape[-1])
    if causal:
        tri = np.tril(np.ones(s.shape[-2:], bool))
        s = np.where(tri, s, -1e30)
    p = np.exp(s - s.max(-1, keepdims=True))
    p /= p.sum(-1, keepdims=True)
    return np.moveaxis(np.einsum("bhqk,bhkd->bhqd", p, vb), 1, 2)


class TestOddLengthTails:
    """Satellite 1: seq_len not divisible by the chosen block must pad
    correctly (wrapper) or fail loudly (core) — never drop tail rows."""

    @pytest.mark.parametrize("s", [17, 33, 100, 130, 255])
    @pytest.mark.parametrize("causal", [False, True])
    def test_wrapper_matches_dense_for_odd_lengths(self, s, causal,
                                                   tune_cache):
        rng = np.random.RandomState(s)
        q = rng.randn(1, s, 2, 16).astype(np.float32)
        k = rng.randn(1, s, 2, 16).astype(np.float32)
        v = rng.randn(1, s, 2, 16).astype(np.float32)
        out, _ = flash_attention(jnp.array(q), jnp.array(k), jnp.array(v),
                                 causal=causal)
        np.testing.assert_allclose(np.asarray(out),
                                   _dense_ref(q, k, v, causal),
                                   atol=2e-5, rtol=2e-5)

    @pytest.mark.parametrize("bq,bk", [(16, 48), (48, 16), (32, 48)])
    def test_explicit_nondividing_blocks_still_exact(self, bq, bk,
                                                     tune_cache):
        # 100 rounds to 112; neither 48 nor the sanitized 48 divides it,
        # so the wrapper must pad up to the block grid and mask the tail
        s = 100
        rng = np.random.RandomState(7)
        q = rng.randn(1, s, 1, 16).astype(np.float32)
        k = rng.randn(1, s, 1, 16).astype(np.float32)
        v = rng.randn(1, s, 1, 16).astype(np.float32)
        out, _ = flash_attention(jnp.array(q), jnp.array(k), jnp.array(v),
                                 causal=True, block_q=bq, block_k=bk)
        np.testing.assert_allclose(np.asarray(out),
                                   _dense_ref(q, k, v, True),
                                   atol=2e-5, rtol=2e-5)

    def test_core_rejects_nondividing_blocks(self):
        q = jnp.zeros((2, 64, 8))
        with pytest.raises(ValueError, match="must divide"):
            _fa_fwd_with_lse(q, q, q, False, 1.0, 48, 16, True, 64)
        with pytest.raises(ValueError, match="must divide"):
            _fa_fwd_with_lse(q, q, q, False, 1.0, 16, 48, True, 64)

    def test_sanitize_block(self):
        assert _sanitize_block(128, 100) == 112   # clamp to ceil16(len)
        assert _sanitize_block(100, 4096) == 112  # round up to 16-multiple
        assert _sanitize_block(0, 4096) == DEFAULT_BLOCK
        assert _sanitize_block(-5, 64) == 64
        assert _sanitize_block(16, 7) == 16       # floor at one sublane


class TestWinnerStoreIntegrity:
    """Satellite 3: bad caches warn + retune, never crash."""

    def _winners_path(self, tmp):
        platform = jax.devices()[0].platform
        return os.path.join(str(tmp), f"winners-{platform}.json")

    def test_corrupt_file_ignored_with_warning(self, tune_cache):
        with open(self._winners_path(tune_cache), "w") as f:
            f.write("{ this is not json !!")
        with warnings.catch_warnings(record=True) as w:
            warnings.simplefilter("always")
            cfg = tuner.get_flash_blocks(999, 999, 32, "float32", False)
        assert cfg is None
        assert any("corrupt" in str(x.message) for x in w)

    def test_truncated_file_ignored_with_warning(self, tune_cache):
        with open(self._winners_path(tune_cache), "w") as f:
            f.write('{"version": 1, "entries": {"flash_fwd|cpu')
        with warnings.catch_warnings(record=True) as w:
            warnings.simplefilter("always")
            cfg = tuner.get_flash_blocks(999, 999, 32, "float32", False)
        assert cfg is None
        assert any("corrupt" in str(x.message) for x in w)

    def test_version_mismatch_ignored_with_warning(self, tune_cache):
        key = tuner.flash_key(999, 999, 32, "float32", False)
        with open(self._winners_path(tune_cache), "w") as f:
            json.dump({"version": tuner.CACHE_VERSION + 1,
                       "entries": {key: {"config": {"block_q": 32,
                                                    "block_k": 32}}}}, f)
        with warnings.catch_warnings(record=True) as w:
            warnings.simplefilter("always")
            cfg = tuner.get_flash_blocks(999, 999, 32, "float32", False)
        assert cfg is None
        assert any("version" in str(x.message) for x in w)

    def test_malformed_entries_dropped_good_kept(self, tune_cache):
        key = tuner.flash_key(999, 999, 32, "float32", False)
        with open(self._winners_path(tune_cache), "w") as f:
            json.dump({"version": tuner.CACHE_VERSION,
                       "platform": "cpu",
                       "entries": {key: {"config": {"block_q": 32,
                                                    "block_k": 64}},
                                   "bad1": "not a dict",
                                   "bad2": {"no_config": True}}}, f)
        with warnings.catch_warnings(record=True) as w:
            warnings.simplefilter("always")
            cfg = tuner.get_flash_blocks(999, 999, 32, "float32", False)
        assert cfg == (32, 64)
        assert any("malformed" in str(x.message) for x in w)

    def test_record_after_corruption_recovers(self, tune_cache):
        path = self._winners_path(tune_cache)
        with open(path, "w") as f:
            f.write("garbage")
        key = tuner.flash_key(999, 999, 32, "float32", False)
        with warnings.catch_warnings():
            warnings.simplefilter("ignore")
            tuner.record_winner(key, {"block_q": 64, "block_k": 64})
        tuner.clear_memo()
        assert tuner.get_flash_blocks(999, 999, 32, "float32",
                                      False) == (64, 64)
        # the rewritten file is valid versioned JSON again
        with open(path) as f:
            data = json.load(f)
        assert data["version"] == tuner.CACHE_VERSION

    def test_kernel_path_never_crashes_on_bad_cache(self, tune_cache):
        with open(self._winners_path(tune_cache), "w") as f:
            f.write("\x00\x01 binary trash")
        rng = np.random.RandomState(0)
        q = rng.randn(1, 32, 1, 16).astype(np.float32)
        with warnings.catch_warnings():
            warnings.simplefilter("ignore")
            out, _ = flash_attention(jnp.array(q), jnp.array(q),
                                     jnp.array(q), causal=False)
        np.testing.assert_allclose(np.asarray(out),
                                   _dense_ref(q, q, q, False),
                                   atol=2e-5, rtol=2e-5)


class TestResolutionTiers:
    def test_disk_winner_used_by_kernel(self, tune_cache):
        s, d = 100, 16
        key = tuner.flash_key(s, s, d, "float32", True)
        tuner.record_winner(key, {"block_q": 32, "block_k": 64})
        tuner.clear_memo()
        assert tuner.get_flash_blocks(s, s, d, "float32", True) == (32, 64)
        rng = np.random.RandomState(1)
        q = rng.randn(1, s, 2, d).astype(np.float32)
        out, _ = flash_attention(jnp.array(q), jnp.array(q), jnp.array(q),
                                 causal=True)
        np.testing.assert_allclose(np.asarray(out),
                                   _dense_ref(q, q, q, True),
                                   atol=2e-5, rtol=2e-5)

    def test_lengths_canonicalized_to_16(self, tune_cache):
        assert tuner.flash_key(4095, 4095, 64, "bfloat16", True,
                               platform="tpu") \
            == tuner.flash_key(4096, 4096, 64, "bfloat16", True,
                               platform="tpu")

    def test_defaults_table_ships_bench_winner(self, tune_cache):
        # the committed defaults must cover the GPT-small S=4096 bench
        # shape on TPU (acceptance criterion: cold fleet never tunes it)
        st = store.WinnerStore("tpu", directory=str(tune_cache))
        cfg = st.lookup("flash_fwd|tpu|bfloat16|d64|q4096|k4096|c1")
        assert cfg and cfg["block_q"] % 16 == 0 and cfg["block_k"] % 16 == 0

    def test_disk_shadows_defaults(self, tune_cache):
        key = "flash_fwd|tpu|bfloat16|d64|q4096|k4096|c1"
        st = store.WinnerStore("tpu", directory=str(tune_cache))
        shipped = st.lookup(key)
        st.record(key, {"block_q": 256, "block_k": 256})
        st2 = store.WinnerStore("tpu", directory=str(tune_cache))
        assert st2.lookup(key) == {"block_q": 256, "block_k": 256}
        assert shipped != st2.lookup(key)

    def test_memo_avoids_disk_after_first_lookup(self, tune_cache,
                                                 monkeypatch):
        key = tuner.flash_key(64, 64, 16, "float32", False)
        tuner.record_winner(key, {"block_q": 32, "block_k": 32})
        tuner.clear_memo()
        assert tuner.get_flash_blocks(64, 64, 16, "float32",
                                      False) == (32, 32)
        calls = {"n": 0}
        real = store.store_for

        def counting(platform):
            calls["n"] += 1
            return real(platform)
        monkeypatch.setattr(tuner.store, "store_for", counting)
        monkeypatch.setattr(tuner, "store_for", counting)
        for _ in range(5):
            assert tuner.get_flash_blocks(64, 64, 16, "float32",
                                          False) == (32, 32)
        assert calls["n"] == 0       # memo tier served every repeat


class TestCandidateSpace:
    def test_vmem_pruning(self):
        # kv=5120 at d=128 f32 leaves under 3 MiB beside the resident,
        # double-buffered K/V (10 MiB), so big score blocks must be
        # pruned while small ones survive
        cands = space.flash_candidates(5120, 5120, 128, itemsize=4)
        assert (128, 128) in cands and (512, 512) not in cands
        for bq, bk in cands:
            assert space.flash_vmem_bytes(bq, bk, 5120, 128,
                                          4) <= space.VMEM_BUDGET

    @pytest.mark.parametrize("args,bwd,fits", [
        # what the compiler for a described v5e builds (True) and
        # refuses for VMEM (False), PR 27 and again PR 37 (float32
        # 1024x1024 backward blocks at head size 64 build since dK/dV
        # turns no tile: the row is at head size 128 now); the two
        # bfloat16 backward rows bracket the budget's 20% of headroom
        ((512, 512, 4096, 64, 2), False, True),
        ((1024, 1024, 4096, 64, 2), False, True),
        ((2048, 2048, 4096, 64, 2), False, False),
        ((128, 128, 8192, 128, 4), False, False),    # K/V alone: 16 MiB
        ((512, 512, 16384, 64, 2), False, True),
        ((512, 512, 16384, 128, 2), False, False),
        ((512, 512, 4096, 64, 2), True, True),
        ((512, 512, 8192, 128, 2), True, True),
        ((1024, 1024, 4096, 128, 4), True, False),
        ((256, 256, 8192, 128, 4), True, False),
    ])
    def test_vmem_model_agrees_with_the_v5e_compiler(self, args, bwd, fits):
        assert (space.flash_vmem_bytes(*args, bwd=bwd)
                <= space.VMEM_BUDGET) is fits

    def test_backward_footprint_is_the_larger_program(self):
        # more in flight than the forward at the same blocks (two score
        # blocks, P and dS operand copies), and less once the operands
        # are bfloat16
        fwd = space.flash_vmem_bytes(256, 256, 4096, 64, 2)
        bwd = space.flash_vmem_bytes(256, 256, 4096, 64, 2, bwd=True)
        assert fwd < bwd < space.flash_vmem_bytes(256, 256, 4096, 64, 4,
                                                  bwd=True)

    def test_require_divides(self):
        cands = space.flash_candidates(96, 96, 16, require_divides=True)
        for bq, bk in cands:
            assert 96 % bq == 0 and 96 % bk == 0

    def test_all_blocks_sublane_multiples(self):
        for bq, bk in space.flash_candidates(1000, 1000, 64):
            assert bq % 16 == 0 and bk % 16 == 0

    def test_never_empty(self):
        assert space.flash_candidates(8, 8, 8) == [(16, 16)]


class TestAutotune:
    def test_search_records_and_reloads(self, tune_cache):
        res = tuner.autotune_flash(2, 64, 64, 16, trials=2)
        assert res["block_q"] % 16 == 0 and res["block_k"] % 16 == 0
        assert res["us"] > 0 and res["results"]
        tuner.clear_memo()
        assert tuner.get_flash_blocks(64, 64, 16, "float32", False) \
            == (res["block_q"], res["block_k"])

    def test_ring_search_respects_divisor_constraint(self, tune_cache):
        res = tuner.autotune_flash(1, 96, 96, 16, trials=1, ring=True)
        assert 96 % res["block_q"] == 0 and 96 % res["block_k"] == 0


class TestRingBlocks:
    def test_tuned_divisor_used(self, tune_cache):
        from paddle_tpu.distributed.fleet.sequence_parallel import \
            _ring_blocks
        key = tuner.flash_key(256, 256, 16, "float32", False, ring=True)
        tuner.record_winner(key, {"block_q": 64, "block_k": 64})
        tuner.clear_memo()
        assert _ring_blocks(256, 16, jnp.float32) == (64, 64)

    def test_nondividing_winner_discarded(self, tune_cache):
        from paddle_tpu.distributed.fleet.sequence_parallel import \
            _ring_blocks
        key = tuner.flash_key(256, 256, 16, "float32", False, ring=True)
        tuner.record_winner(key, {"block_q": 48, "block_k": 48})
        tuner.clear_memo()
        # 48 doesn't divide 256: fall back to the historical default
        assert _ring_blocks(256, 16, jnp.float32) == (128, 128)


class TestNMSUnroll:
    def test_unroll_preserves_result(self, tune_cache):
        from paddle_tpu.ops.custom import pallas_greedy_nms
        rng = np.random.RandomState(3)
        iou = jnp.array(rng.rand(16, 16).astype(np.float32))
        valid = jnp.ones((16,), jnp.int32)
        thr = jnp.array([0.5], jnp.float32)
        base = np.asarray(pallas_greedy_nms(iou, valid, thr,
                                            interpret=True, unroll=1))
        for u in (2, 4, 8):
            out = np.asarray(pallas_greedy_nms(iou, valid, thr,
                                               interpret=True, unroll=u))
            np.testing.assert_array_equal(base, out)

    def test_tuned_unroll_from_cache(self, tune_cache):
        from paddle_tpu.ops.custom import _nms_unroll
        tuner.record_winner(tuner.nms_key(16), {"unroll": 4})
        tuner.clear_memo()
        assert _nms_unroll(16) == 4
        # non-divisor winners are rejected
        tuner.record_winner(tuner.nms_key(18), {"unroll": 4})
        tuner.clear_memo()
        assert _nms_unroll(18) == 1


def test_emit_defaults_keeps_notes_and_writes_measured_times(tmp_path):
    """tools/autotune.py --emit-defaults: a retimed key keeps its curated
    note and gains the winner's measured time; the time never lands in
    the config the kernels read."""
    import sys
    sys.path.insert(0, os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))))
    from tools.autotune import emit_defaults
    path = tmp_path / "winners.json"
    path.write_text(json.dumps({"version": 1, "platform": "defaults",
                                "entries": {
        "flash_fwd|tpu|bfloat16|d64|q64|k64|c1": {
            "config": {"block_q": 16, "block_k": 16}, "note": "kept"},
        "nms|cpu|k64": {"config": {"unroll": 4}}}}))
    emit_defaults({"flash_fwd|tpu|bfloat16|d64|q64|k64|c1":
                   {"block_q": 32, "block_k": 64, "us": 123.456}},
                  str(path))
    entries = json.loads(path.read_text())["entries"]
    assert entries["flash_fwd|tpu|bfloat16|d64|q64|k64|c1"] == {
        "config": {"block_q": 32, "block_k": 64}, "us": 123.5,
        "note": "kept"}
    assert entries["nms|cpu|k64"] == {"config": {"unroll": 4}}
    assert store._load_table(str(path), "test table").keys() == \
        entries.keys()


def test_runner_waits_for_every_result_of_a_candidate():
    """The backward lane's candidates return ``(dq, dk, dv)``: a wait on
    the first attribute of the tuple found none and timed the dispatch
    alone (376 us for a 6.5 ms program, PERF.md PR 27)."""
    from paddle_tpu.tuner import runner
    waited = []

    class Result:
        def block_until_ready(self):
            waited.append(self)
            return self

    trio = (Result(), Result(), Result())
    runner.time_once(lambda: trio)
    assert waited == list(trio)
    del waited[:]
    assert runner.measure(lambda: {"a": trio[0], "b": [trio[1]]},
                          trials=2) is not None
    assert len(waited) == 2 * 3          # warm-up, then two timed runs
