"""Disaggregated LLM fleet: prefix KV reuse, speculative decoding, and
prefill/decode split routing (docs/serving.md "Disaggregated fleet").

Three invariant families:

* **Prefix store** — chain-hash lookup semantics, pin/unpin lifecycle
  (pinned entries survive LRU pressure; every engine exit path unpins),
  and bitwise-identical greedy output on the reuse path, including
  cross-engine reuse between decoders with different ``max_seq``.
* **Speculative decoding** — greedy output is bitwise-identical to the
  plain engine for ANY draft (self-draft and a genuinely different small
  draft), acceptance counters move, the per-tick host traffic stays at
  exactly ONE fetch, and the compiled spec step never retraces after
  warmup.
* **Router disaggregation** — role-aware dispatch, the prefill->decode
  KV handoff over the shared store, availability fallback when a phase
  loses its replicas, and the slow-lane end-to-end claim: a long-prompt
  storm does not degrade inter-token latency on a decode-role replica
  the way it degrades a single mixed engine.
"""
import json
import os
import threading
import time
import urllib.request

import numpy as np
import pytest

import paddle_tpu as paddle
from paddle_tpu.core.monitor import StatRegistry
from paddle_tpu.models import GPTConfig, GPTForCausalLM
from paddle_tpu.serving.llm import (ContinuousBatcher, GenerationRequest,
                                    GPTStaticDecoder, LLMEngine,
                                    LLMEngineConfig, PrefixStore,
                                    SamplingParams, chain_hashes)
from paddle_tpu.serving.llm.spec import get_spec_decode_step
from paddle_tpu.serving.request import (PHASE_DECODE, PHASE_PREFILL,
                                        REPLICA_ROLES, DeadlineExceeded)
from paddle_tpu.serving.router import Router, RouterConfig, llm_replica_factory
from paddle_tpu.utils.resilience import Deadline

import jax

VOCAB = 64


def _tiny_model(seed=0, vocab=VOCAB, hidden=32, layers=2, heads=4,
                max_pos=128):
    paddle.seed(seed)
    cfg = GPTConfig(vocab_size=vocab, hidden_size=hidden, num_layers=layers,
                    num_heads=heads, max_position_embeddings=max_pos,
                    hidden_dropout_prob=0.0, attention_dropout_prob=0.0)
    net = GPTForCausalLM(cfg)
    net.eval()
    return net


def _prompts():
    """Deterministic prompts straddling the 16-token block boundary:
    two short (never cacheable), three long enough to insert/reuse."""
    rng = np.random.RandomState(7)
    return [rng.randint(0, VOCAB, size=n).astype(np.int32)
            for n in (5, 12, 20, 24, 33)]


PROMPTS = _prompts()
MAX_NEW = 10


def _generate_all(engine, prompts=PROMPTS, max_new=MAX_NEW, **kw):
    reqs = [engine.submit(p, max_new_tokens=max_new, **kw) for p in prompts]
    return [r.result(timeout=60)["tokens"] for r in reqs]


@pytest.fixture(scope="module")
def model():
    return _tiny_model()


@pytest.fixture(scope="module")
def baseline(model):
    """Greedy tokens from the plain engine — the bitwise reference every
    prefix/spec variant must reproduce."""
    eng = LLMEngine(model, LLMEngineConfig(num_slots=4, max_seq=64,
                                           warmup=False))
    try:
        return _generate_all(eng)
    finally:
        eng.drain()


# ---------------------------------------------------------------------------
# prefix store unit behavior
# ---------------------------------------------------------------------------

class TestPrefixStoreUnit:
    SIG = (1, 1, 4, "float32")

    def _kv(self, n):
        k = np.arange(1 * n * 1 * 4, dtype=np.float32).reshape(1, n, 1, 4)
        return k, k + 0.5

    def test_chain_hashes_identify_prefixes(self):
        toks = np.arange(40, dtype=np.int32)
        h = chain_hashes(toks, 16)
        assert len(h) == 2                      # 40 // 16 complete blocks
        # the chain over a shorter prefix of the same tokens is a prefix
        # of the longer chain; a different first block changes every link
        assert chain_hashes(toks[:16], 16) == h[:1]
        other = toks.copy()
        other[0] += 1
        assert chain_hashes(other, 16)[0] != h[0]

    def test_lookup_returns_longest_block_prefix(self):
        store = PrefixStore(registry=StatRegistry(), block_tokens=16)
        toks = np.arange(32, dtype=np.int32)
        k, v = self._kv(32)
        entry = store.insert(toks, k, v, self.SIG)
        store.unpin(entry)
        # a prompt sharing only the first block reuses 16 tokens
        probe = np.concatenate([toks[:16], toks[:4] + 7])
        hit, n = store.lookup(probe, probe.size - 1, self.SIG)
        assert hit is entry and n == 16
        store.unpin(hit)
        # max_tokens caps reuse below the full entry
        hit, n = store.lookup(toks, 20, self.SIG)
        assert hit is entry and n == 16
        store.unpin(hit)
        # a mismatched shape signature never hits
        miss, n = store.lookup(toks, 31, (2, 1, 4, "float32"))
        assert miss is None and n == 0

    def test_insert_dedups_and_pins(self):
        store = PrefixStore(registry=StatRegistry(), block_tokens=16)
        toks = np.arange(16, dtype=np.int32)
        k, v = self._kv(16)
        a = store.insert(toks, k, v, self.SIG)
        b = store.insert(toks, k, v, self.SIG)
        assert a is b
        assert store.stats()["entries"] == 1
        assert store.stats()["pinned"] == 1     # refcounted, not boolean
        store.unpin(a)
        assert store.stats()["pinned"] == 1
        store.unpin(b)
        assert store.stats()["pinned"] == 0

    def test_lru_eviction_skips_pinned(self):
        # capacity fits two 512-byte entries; the OLDEST is pinned, so
        # pressure from a third evicts the unpinned middle one instead
        store = PrefixStore(capacity_bytes=1100, block_tokens=16,
                            registry=StatRegistry())
        rng = np.random.RandomState(3)
        toks = [rng.randint(0, VOCAB, size=16).astype(np.int32)
                for _ in range(3)]
        k, v = self._kv(16)
        pinned = store.insert(toks[0], k, v, self.SIG)   # stays pinned
        mid = store.insert(toks[1], k, v, self.SIG)
        store.unpin(mid)
        third = store.insert(toks[2], k, v, self.SIG)
        store.unpin(third)
        st = store.stats()
        assert st["entries"] == 2 and st["bytes"] <= 1100
        assert store.lookup(toks[0], 16, self.SIG)[1] == 16  # survived
        assert store.lookup(toks[1], 16, self.SIG)[1] == 0   # evicted
        store.unpin(pinned)


# ---------------------------------------------------------------------------
# engine-level prefix reuse
# ---------------------------------------------------------------------------

class TestPrefixReuse:
    def test_reuse_is_bitwise_identical(self, model, baseline):
        reg = StatRegistry()
        eng = LLMEngine(model, LLMEngineConfig(num_slots=4, max_seq=64,
                                               warmup=False,
                                               prefix_cache=True),
                        registry=reg)
        try:
            first = _generate_all(eng)          # misses populate the store
            second = _generate_all(eng)         # block-aligned heads hit
        finally:
            eng.drain()
        assert first == baseline
        assert second == baseline
        # three prompts exceed one block (20/24/33 tokens) -> three hits
        # reusing 16 + 16 + 32 cached tokens on the second pass
        assert reg.get("serving.llm.prefix.hits") >= 3
        assert reg.get("serving.llm.prefix.reused_tokens") >= 48
        assert reg.get("serving.llm.prefix.inserts") >= 3
        assert eng.prefix_store.stats()["pinned"] == 0

    def test_cross_engine_reuse_smaller_max_seq(self, model, baseline):
        """An entry exported by a max_seq=64 engine is reusable by a
        max_seq=32 engine — the shape signature excludes max_seq, and the
        shrink guard keeps offset + tail bucket inside the smaller row."""
        store = PrefixStore(registry=StatRegistry())
        reg_a, reg_b = StatRegistry(), StatRegistry()
        prompt = PROMPTS[3]                     # 24 tokens -> 16 cached
        eng_a = LLMEngine(model, LLMEngineConfig(num_slots=2, max_seq=64,
                                                 warmup=False),
                          registry=reg_a, prefix_store=store)
        try:
            tok_a = eng_a.submit(prompt, max_new_tokens=4).result(60)["tokens"]
        finally:
            eng_a.drain()
        assert store.stats()["entries"] == 1
        eng_b = LLMEngine(model, LLMEngineConfig(num_slots=2, max_seq=32,
                                                 warmup=False),
                          registry=reg_b, prefix_store=store)
        try:
            tok_b = eng_b.submit(prompt, max_new_tokens=4).result(60)["tokens"]
        finally:
            eng_b.drain()
        assert tok_b == tok_a == baseline[3][:4]
        assert reg_b.get("serving.llm.prefix.reused_tokens") == 16
        assert store.stats()["pinned"] == 0

    def test_deadline_eviction_unpins(self, model):
        """Mid-stream deadline eviction releases the request's pin — a
        dead consumer can never wedge an entry against eviction. Driven
        through the batcher directly so tick timing is deterministic."""
        reg = StatRegistry()
        store = PrefixStore(registry=reg)
        cfg = LLMEngineConfig(num_slots=2, max_seq=64, warmup=False)
        batcher = ContinuousBatcher(GPTStaticDecoder(model), cfg, reg,
                                    prefix_store=store)
        prompt = PROMPTS[3]
        seed = GenerationRequest(prompt, SamplingParams(max_new_tokens=2))
        batcher.admit(seed)                     # miss -> insert (pinned)
        while batcher.active:
            batcher.tick()
        assert seed.finish_reason == "length"
        assert store.stats()["pinned"] == 0
        doomed = GenerationRequest(prompt, SamplingParams(max_new_tokens=50),
                                   deadline=Deadline(0.03))
        batcher.admit(doomed)                   # hit -> entry pinned again
        assert store.stats()["pinned"] == 1
        time.sleep(0.05)
        batcher.tick()                          # expired -> evicted
        assert batcher.active == 0
        with pytest.raises(DeadlineExceeded):
            doomed.result(timeout=5)
        assert store.stats()["pinned"] == 0
        assert store.stats()["entries"] == 1    # the ENTRY survives
        assert reg.get("serving.llm.evicted_midstream") == 1


# ---------------------------------------------------------------------------
# speculative decoding
# ---------------------------------------------------------------------------

class TestSpeculativeDecoding:
    def test_self_draft_bitwise_with_full_acceptance(self, model, baseline):
        """Draft == target: every proposal verifies, so greedy output is
        the plain engine's bitwise and the acceptance counters saturate."""
        reg = StatRegistry()
        eng = LLMEngine(model, LLMEngineConfig(num_slots=4, max_seq=64,
                                               warmup=False, spec_k=2),
                        registry=reg, draft_model=model)
        try:
            toks = _generate_all(eng)
        finally:
            eng.drain()
        assert toks == baseline
        assert reg.get("serving.llm.spec.ticks") > 0
        assert reg.get("serving.llm.spec.accepted") > 0
        assert reg.get("serving.llm.spec.acceptance_rate") > 0.5

    def test_distinct_draft_bitwise(self, model, baseline):
        """A genuinely different draft (scaled-down config, different
        seed) may propose garbage — verification still makes the greedy
        stream bitwise-identical to the plain engine."""
        paddle.seed(99)
        draft = GPTForCausalLM(GPTConfig(
            vocab_size=VOCAB, hidden_size=32, num_layers=2, num_heads=4,
            max_position_embeddings=128, hidden_dropout_prob=0.0,
            attention_dropout_prob=0.0).draft(2))
        draft.eval()
        reg = StatRegistry()
        eng = LLMEngine(model, LLMEngineConfig(num_slots=4, max_seq=64,
                                               warmup=False, spec_k=3),
                        registry=reg, draft_model=draft)
        try:
            toks = _generate_all(eng)
        finally:
            eng.drain()
        assert toks == baseline
        assert reg.get("serving.llm.spec.ticks") > 0

    @pytest.mark.parametrize("kv_layout", ["slot", "paged"])
    def test_int8_weights_speculate_like_the_plain_int8_engine(
            self, model, kv_layout):
        """int8 weight leaves go through the verify step too (its
        projections were a bare ``@``: the worker died at its first tick
        with a TypeError): greedy tokens equal the same int8 engine's
        without speculation. The target is its own draft; the draft runs
        the float32 weights, so not every proposal verifies."""
        def run(**kw):
            reg = StatRegistry()
            eng = LLMEngine(model, LLMEngineConfig(
                num_slots=4, max_seq=64, warmup=False, weight_dtype="int8",
                kv_layout=kv_layout, **kw), registry=reg, draft_model=model)
            try:
                return _generate_all(eng), reg
            finally:
                eng.drain()

        plain, _ = run()
        toks, reg = run(spec_k=2)
        assert toks == plain
        assert reg.get("serving.llm.spec.ticks") > 0
        assert reg.get("serving.llm.spec.accepted") > 0

    def test_spec_with_prefix_reuse_bitwise(self, model, baseline):
        """Both features on at once: the draft cache prefills the full
        prompt even when the target reuses a cached head, and output
        stays bitwise."""
        reg = StatRegistry()
        eng = LLMEngine(model, LLMEngineConfig(num_slots=4, max_seq=64,
                                               warmup=False, spec_k=2,
                                               prefix_cache=True),
                        registry=reg, draft_model=model)
        try:
            first = _generate_all(eng)
            second = _generate_all(eng)
        finally:
            eng.drain()
        assert first == baseline and second == baseline
        assert reg.get("serving.llm.prefix.hits") >= 3

    def test_one_host_fetch_per_tick(self, model, monkeypatch):
        """THE disaggregation budget: admission fetches one [1]-token
        array, and every tick (speculative or fallback) fetches exactly
        one packed array — no hidden host round-trips."""
        reg = StatRegistry()
        eng = LLMEngine(model, LLMEngineConfig(num_slots=2, max_seq=64,
                                               warmup=True, spec_k=2),
                        registry=reg, draft_model=model)
        fetches = {"n": 0}
        real = jax.device_get

        def counting(x):
            fetches["n"] += 1
            return real(x)

        monkeypatch.setattr(jax, "device_get", counting)
        try:
            req = eng.submit(PROMPTS[2], max_new_tokens=9)
            req.result(timeout=60)
        finally:
            eng.drain()                  # worker joined: counters final
            monkeypatch.setattr(jax, "device_get", real)
        ticks = (reg.get("serving.llm.spec.ticks")
                 + reg.get("serving.llm.spec.fallback_ticks"))
        assert ticks > 0
        assert fetches["n"] == 1 + ticks, \
            f"{fetches['n']} fetches for {ticks} ticks + 1 admission"

    def test_spec_step_never_retraces_after_warmup(self, model):
        eng = LLMEngine(model, LLMEngineConfig(num_slots=2, max_seq=64,
                                               warmup=True, spec_k=2),
                        registry=StatRegistry(), draft_model=model)
        try:
            fn = get_spec_decode_step(eng.decoder.spec,
                                      eng._batcher.spec.dspec, 2,
                                      eng.decoder.max_top_k)
            traced = fn.trace_counter["traces"]
            assert traced >= 1               # warmup compiled it
            _generate_all(eng, prompts=PROMPTS[:3], max_new=6)
            _generate_all(eng, prompts=PROMPTS[2:], max_new=6)
            assert fn.trace_counter["traces"] == traced
        finally:
            eng.drain()

    def test_room_guard_falls_back_near_max_seq(self, model):
        """When a slot cannot absorb k+1 candidate rows the tick drops to
        the plain one-token step — output still bitwise, fallback counted."""
        reg_plain, reg_spec = StatRegistry(), StatRegistry()
        prompt = PROMPTS[2]                    # 20 tokens; budget = 12
        plain = LLMEngine(model, LLMEngineConfig(num_slots=2, max_seq=32,
                                                 warmup=False),
                          registry=reg_plain)
        try:
            want = plain.submit(prompt, max_new_tokens=12).result(60)["tokens"]
        finally:
            plain.drain()
        # k=4: full self-draft acceptance advances 5 tokens/tick
        # (1 -> 6 -> 11), landing where pos + k + 1 > max_seq
        eng = LLMEngine(model, LLMEngineConfig(num_slots=2, max_seq=32,
                                               warmup=False, spec_k=4),
                        registry=reg_spec, draft_model=model)
        try:
            got = eng.submit(prompt, max_new_tokens=12).result(60)["tokens"]
        finally:
            eng.drain()
        assert got == want
        assert reg_spec.get("serving.llm.spec.fallback_ticks") > 0
        assert reg_spec.get("serving.llm.spec.ticks") > 0

    def test_spec_requires_draft_model(self, model):
        with pytest.raises(ValueError, match="draft_model"):
            LLMEngine(model, LLMEngineConfig(num_slots=2, max_seq=32,
                                             warmup=False, spec_k=2))

    def test_audit_entrypoint_registered(self):
        from paddle_tpu.core.audit import load_default_entrypoints
        eps = load_default_entrypoints()
        assert "llm_spec_decode_step" in eps
        from tools.check_audit_regression import ENTRYPOINTS
        assert "llm_spec_decode_step" in ENTRYPOINTS
        repo = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
        with open(os.path.join(repo, "bench_audit_baseline.json")) as f:
            base = json.load(f)
        assert "llm_spec_decode_step" in base["entrypoints"]


# ---------------------------------------------------------------------------
# router disaggregation
# ---------------------------------------------------------------------------

class TestRouterRoles:
    def test_role_taxonomy(self):
        assert PHASE_PREFILL in REPLICA_ROLES
        assert PHASE_DECODE in REPLICA_ROLES
        assert "mixed" in REPLICA_ROLES

    def test_config_validation(self):
        with pytest.raises(ValueError, match="one role per replica"):
            RouterConfig(kind="llm", num_replicas=2, roles=("prefill",))
        with pytest.raises(ValueError, match="invalid roles"):
            RouterConfig(kind="llm", num_replicas=2,
                         roles=("prefill", "verifier"))
        with pytest.raises(ValueError, match="no replica serving"):
            RouterConfig(kind="llm", num_replicas=2,
                         roles=("prefill", "prefill"))
        with pytest.raises(ValueError, match="kind='llm'"):
            RouterConfig(kind="classifier", num_replicas=2,
                         roles=("prefill", "decode"))
        with pytest.raises(ValueError, match="prefill_threshold"):
            RouterConfig(kind="llm", num_replicas=2,
                         roles=("prefill", "decode"), prefill_threshold=0)


@pytest.fixture(scope="module")
def fleet(model):
    """A 2-replica disaggregated fleet sharing ONE prefix store: replica0
    prefills, replica1 decodes; long prompts hand off through the store."""
    reg = StatRegistry()
    store = PrefixStore(capacity_bytes=64 << 20, registry=reg)
    cfg = LLMEngineConfig(num_slots=2, max_seq=64, warmup=False)
    router = Router(
        llm_replica_factory(lambda r: model, cfg,
                            roles=("prefill", "decode"),
                            prefix_store=store),
        RouterConfig(kind="llm", num_replicas=2,
                     roles=("prefill", "decode"), prefill_threshold=32,
                     health_interval=5.0, auto_resurrect=False),
        registry=reg)
    yield router, reg, store
    router.drain(timeout=30)


class TestDisaggRouting:
    def test_short_prompt_goes_to_decode_replica(self, fleet, baseline):
        router, reg, _ = fleet
        toks = router.submit(PROMPTS[0],
                             max_new_tokens=MAX_NEW).result(60)["tokens"]
        assert toks == baseline[0]
        assert reg.get("serving.router.dispatched_role_decode") >= 1
        assert reg.get("serving.router.dispatched_phase_decode") >= 1

    def test_long_prompt_hands_off_kv(self, fleet, baseline):
        router, reg, store = fleet
        prompt = PROMPTS[4]                    # 33 tokens >= threshold 32
        toks = router.submit(prompt,
                             max_new_tokens=MAX_NEW).result(60)["tokens"]
        assert toks == baseline[4]             # bitwise across the handoff
        assert reg.get("serving.router.handoff_prefills") >= 1
        assert reg.get("serving.router.dispatched_role_prefill") >= 1
        assert reg.get("serving.router.dispatched_phase_prefill") >= 1
        # the decode replica reused the prefill replica's exported head
        assert reg.get("serving.llm.replica1.prefix.reused_tokens") >= 32
        assert store.stats()["entries"] >= 1
        assert store.stats()["pinned"] == 0

    def test_observability_surfaces_roles(self, fleet):
        router, reg, _ = fleet
        assert router.stats()["roles"] == ["prefill", "decode"]
        h = router.healthz()
        roles = {r["role"] for r in h["replicas"]}
        assert roles == {"prefill", "decode"}

    def test_phase_fallback_when_decode_drains(self, fleet, baseline):
        """Availability beats placement: with the decode replica
        draining, short prompts relax onto the prefill replica. Runs
        LAST in this class — it degrades the module fleet."""
        router, reg, _ = fleet
        router.replicas[1].engine.begin_drain()
        toks = router.submit(PROMPTS[1],
                             max_new_tokens=MAX_NEW).result(60)["tokens"]
        assert toks == baseline[1]
        assert reg.get("serving.router.phase_fallback") >= 1

    def test_no_shared_store_disables_handoff(self, model, baseline):
        """Roles without a shared store: long prompts are simply served
        end-to-end on the prefill replica — never a broken handoff."""
        reg = StatRegistry()
        cfg = LLMEngineConfig(num_slots=2, max_seq=64, warmup=False)
        router = Router(
            llm_replica_factory(lambda r: model, cfg,
                                roles=("prefill", "decode")),
            RouterConfig(kind="llm", num_replicas=2,
                         roles=("prefill", "decode"), prefill_threshold=32,
                         health_interval=5.0, auto_resurrect=False),
            registry=reg)
        try:
            toks = router.submit(PROMPTS[4],
                                 max_new_tokens=MAX_NEW).result(60)["tokens"]
        finally:
            router.drain(timeout=30)
        assert toks == baseline[4]
        assert reg.get("serving.router.handoff_prefills") == 0
        assert reg.get("serving.router.dispatched_role_prefill") >= 1


class TestHealthzRole:
    def test_llm_healthz_reports_role(self, model):
        from paddle_tpu.serving.http import make_server
        eng = LLMEngine(model, LLMEngineConfig(num_slots=2, max_seq=32,
                                               warmup=False, role="decode"),
                        registry=StatRegistry())
        httpd = make_server(None, port=0, llm_engine=eng)
        t = threading.Thread(target=httpd.serve_forever, daemon=True)
        t.start()
        try:
            port = httpd.server_address[1]
            with urllib.request.urlopen(
                    f"http://127.0.0.1:{port}/healthz", timeout=10) as r:
                body = json.loads(r.read())
            assert body["status"] == "ok"
            assert body["role"] == "decode"
        finally:
            httpd.shutdown()
            httpd.server_close()
            eng.drain()


# ---------------------------------------------------------------------------
# end-to-end: the disaggregation claim itself
# ---------------------------------------------------------------------------

@pytest.mark.slow
class TestDisaggE2E:
    def test_decode_loop_never_pays_full_prefill_under_storm(self):
        """The reason the fleet exists: a long prompt degrades resident
        decode streams only through the stall its admission injects into
        the serving loop. In the mixed engine that stall is a FULL
        256-bucket prefill; on a decode-role replica it is the tail
        prefill behind the handed-off KV head. Same model, same traffic
        (2 resident streams + a storm of 16 unique 200-token prompts),
        both topologies.

        The storm is sequential (one long prompt in flight) and the
        comparison uses per-admission stall medians rather than raw
        inter-token tails: CI may pin this suite to a single core, where
        the replicas timeslice against each other and wall-clock
        inter-token isolation is unmeasurable — the stall each admission
        imposes on its own serving loop is host-independent."""
        model = _tiny_model(seed=3, vocab=128, hidden=256, layers=2,
                            heads=4, max_pos=512)
        rng = np.random.RandomState(11)
        longs = [rng.randint(0, 128, size=200).astype(np.int32)
                 for _ in range(16)]
        short = rng.randint(0, 128, size=6).astype(np.int32)
        cfg = LLMEngineConfig(num_slots=4, max_seq=256, warmup=True)

        def drive(submit):
            residents = [submit(short, max_new_tokens=150)
                         for _ in range(2)]
            for p in longs:
                submit(p, max_new_tokens=4).result(timeout=120)
            for r in residents:
                r.result(timeout=120)

        # -- disaggregated fleet ----------------------------------------
        reg_fleet = StatRegistry()
        store = PrefixStore(capacity_bytes=512 << 20, registry=reg_fleet)
        router = Router(
            llm_replica_factory(lambda r: model, cfg,
                                roles=("prefill", "decode"),
                                prefix_store=store),
            RouterConfig(kind="llm", num_replicas=2,
                         roles=("prefill", "decode"), prefill_threshold=64,
                         health_interval=5.0, auto_resurrect=False),
            registry=reg_fleet)
        try:
            drive(router.submit)
        finally:
            router.drain(timeout=60)
        # every long prompt handed off, and every handoff admission on
        # the decode replica reused the full block-aligned head
        # (200 // 16 * 16 = 192 tokens) — it never ran a full prefill
        assert reg_fleet.get("serving.router.handoff_prefills") == 16
        assert reg_fleet.get(
            "serving.llm.replica1.prefix.reused_tokens") == 16 * 192
        fleet_stall = reg_fleet.quantile("serving.llm.replica1.prefill_ms",
                                         0.5)

        # -- single mixed engine, identical traffic ---------------------
        reg_mixed = StatRegistry()
        eng = LLMEngine(model, cfg, registry=reg_mixed)
        try:
            drive(eng.submit)
        finally:
            eng.drain()
        mixed_stall = reg_mixed.quantile("serving.llm.prefill_ms", 0.5)
        # the mixed loop's admission stall is full-prefill sized, and it
        # DID hit the resident streams' inter-token tail
        assert reg_mixed.quantile("serving.llm.intertoken_ms", 0.95) \
            > mixed_stall * 0.8

        assert fleet_stall > 0 and mixed_stall > 0
        assert fleet_stall < 0.7 * mixed_stall, \
            (f"decode-role admission stall p50 {fleet_stall:.2f}ms should "
             f"be well under the mixed engine's full-prefill stall "
             f"{mixed_stall:.2f}ms")
