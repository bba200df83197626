"""The LFM2-MoE family on the paged engine: prefill then paged decode through
``LLMEngine.submit`` and through the decoder's programs directly, against the
plain reference's full forward pass (``benchmark/reference/lfm2_ref.py``),
at toy width on the CPU.

Tolerances: logits agree to float32 reassociation, 5e-5 absolute on logits
of magnitude about 1 (the decode path sums attention by pages and the
convolution from its rolled state, the reference over the whole sequence).
Served tokens are compared as the benchmark compares them: the served
token's reference logit may lie below the reference's best by at most
``GAP`` = 1e-4, a third of the closest pair of logits the seeded embedding
makes (3e-4 of a row's spread) and far under what bfloat16 would show.
"""
import json
import os

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from benchmark import lfm2_adapter, lfm2_weights, spec as bench_spec
from benchmark.reference import lfm2_ref as ref
from paddle_tpu.core.monitor import StatRegistry
from paddle_tpu.models import GPTConfig, GPTForCausalLM
from paddle_tpu.models.lfm2 import lfm2_hidden
from paddle_tpu.ops.paged_attention import paged_attention
from paddle_tpu.serving.llm import LLMEngine, LLMEngineConfig
from paddle_tpu.serving.llm.decode import SamplingParams, pack_sampling
from paddle_tpu.serving.llm.paged import (GPTPagedDecoder, LFM2PagedDecoder,
                                          paged_decoder_class,
                                          paged_gather_rows)
from paddle_tpu.serving.llm.paged.lfm2 import PagedStep

pytestmark = pytest.mark.timeout_s(600)
GAP = 1e-4
PAGE = 8


@pytest.fixture(scope="module")
def seeded():
    with open(os.path.join(bench_spec.HERE, "configs",
                           "lfm2-8b-a1b.json")) as f:
        cfg = json.load(f)
    cfg = dict(cfg, **cfg["rehearsal"])     # the rehearsal's toy widths
    net = lfm2_adapter.build_net(cfg)
    lfm2_adapter.load_weights(net, cfg, 11)
    net.eval()
    return cfg, net, lfm2_weights.make_lfm2_weights(cfg, 11)


def _engine(net, impl="gather", **over):
    kw = dict(kv_layout="paged", num_slots=2, max_seq=32, page_size=PAGE,
              num_pages=10, prefill_buckets=[16], max_top_k=4,
              paged_attn_impl=impl)
    kw.update(over)
    return LLMEngine(net, LLMEngineConfig(**kw), registry=StatRegistry())


def _served_gap(cfg, w, prompt, tokens):
    """The benchmark's comparison of one request."""
    seq = np.concatenate([prompt, tokens[:-1]]).astype(np.int32)
    hid, _ = ref.hidden_states(w, ref.arch_of(cfg), jnp.asarray(seq))
    logits = np.asarray(ref.logits_of(w, hid))[len(prompt) - 1:]
    return float((logits.max(-1)
                  - logits[np.arange(len(tokens)), tokens]).max())


# -- through the engine's normal entry ---------------------------------------------

@pytest.mark.parametrize("impl", ["gather", "kernel"])
def test_engine_serves_what_the_reference_puts_first(seeded, impl):
    """Prompts shorter than the convolution's reach (1, 2), shorter than,
    equal to and longer than a page, and a full bucket."""
    cfg, net, w = seeded
    rng = np.random.default_rng(0)
    eng = _engine(net, impl)
    try:
        assert isinstance(eng.decoder, LFM2PagedDecoder)
        assert eng.stats()["paged_attn_impl"] == impl
        for plen in (1, 2, PAGE - 1, PAGE, PAGE + 5, 16):
            prompt = rng.integers(0, cfg["vocab_size"], plen).astype(np.int32)
            out = eng.submit(prompt, max_new_tokens=12).result(120)
            assert len(out["tokens"]) == 12
            assert _served_gap(cfg, w, prompt, np.asarray(out["tokens"])) \
                <= GAP, plen
            assert len(set(out["tokens"])) > 6     # not one repeated token
    finally:
        eng.drain(timeout=10)


def test_a_reused_slot_starts_from_its_own_prompt(seeded):
    """One slot: a long tenant, then a short one in the same slot, serves
    what a fresh engine serves (the state row and the pages of the last
    tenant do not leak)."""
    cfg, net, _ = seeded
    rng = np.random.default_rng(1)
    long_prompt = rng.integers(0, cfg["vocab_size"], 16).astype(np.int32)
    short = rng.integers(0, cfg["vocab_size"], 1).astype(np.int32)
    eng = _engine(net, num_slots=1, num_pages=4)
    try:
        eng.submit(long_prompt, max_new_tokens=14).result(120)
        after = eng.submit(short, max_new_tokens=10).result(120)["tokens"]
    finally:
        eng.drain(timeout=10)
    fresh_eng = _engine(net, num_slots=1, num_pages=4)
    try:
        fresh = fresh_eng.submit(short, max_new_tokens=10).result(120)
    finally:
        fresh_eng.drain(timeout=10)
    assert after == fresh["tokens"]


def test_two_slots_at_different_positions_do_not_mix(seeded):
    cfg, net, w = seeded
    rng = np.random.default_rng(2)
    prompts = [rng.integers(0, cfg["vocab_size"], n).astype(np.int32)
               for n in (3, 13)]
    eng = _engine(net)
    try:
        reqs = [eng.submit(p, max_new_tokens=9 + i)
                for i, p in enumerate(prompts)]
        outs = [np.asarray(r.result(120)["tokens"]) for r in reqs]
    finally:
        eng.drain(timeout=10)
    for p, o in zip(prompts, outs):
        assert _served_gap(cfg, w, p, o) <= GAP


def test_counters_and_the_state_gauge_are_in_the_engines_stats(seeded):
    cfg, net, _ = seeded
    eng = _engine(net)
    try:
        eng.submit(np.arange(5, dtype=np.int32), max_new_tokens=7).result(120)
        stats = eng.stats()["stats"]
    finally:
        eng.drain(timeout=10)
    n_conv = cfg["layer_types"].count("conv")
    assert stats["serving.llm.conv_state_bytes"] == \
        2 * n_conv * (cfg["conv_L_cache"] - 1) * cfg["hidden_size"] * 4
    ticks = 6                                  # 7 tokens, one from prefill
    expert_layers = cfg["num_hidden_layers"] - cfg["num_dense_layers"]
    assert stats["serving.llm.moe_pairs_routed"] == \
        ticks * cfg["num_experts_per_tok"] * expert_layers
    # both slots are computed every tick, so between k and 2k experts a layer
    k = cfg["num_experts_per_tok"]
    assert ticks * expert_layers * k <= stats[
        "serving.llm.moe_experts_active"] <= ticks * expert_layers * 2 * k
    assert ticks <= stats["serving.llm.moe_load_max"] <= 2 * ticks
    assert not eng.supports_migration


# -- what this family does not do yet ------------------------------------------------

@pytest.mark.parametrize("option", [
    {"kv_layout": "slot"}, {"prefix_cache": True}, {"spec_k": 2},
    {"weight_dtype": "int8"}, {"kv_dtype": "int8"}])
def test_unsupported_option_raises_at_construction(seeded, option):
    _, net, _ = seeded
    kw = dict(kv_layout="paged", num_slots=1, max_seq=32, page_size=PAGE,
              prefill_buckets=[16], warmup=False)
    kw.update(option)
    with pytest.raises(NotImplementedError):
        LLMEngine(net, LLMEngineConfig(**kw), draft_model=net)


def test_a_mesh_and_sequence_export_raise(seeded):
    _, net, _ = seeded
    with pytest.raises(NotImplementedError):
        LFM2PagedDecoder(net, mesh=object())
    eng = _engine(net, warmup=False)
    try:
        with pytest.raises(NotImplementedError):
            eng.export_sequences()
        assert eng.import_sequence(object()) is False
    finally:
        eng.drain(timeout=10)


def test_gpt_is_the_default_family():
    gpt = GPTForCausalLM(GPTConfig(
        vocab_size=64, hidden_size=16, num_layers=1, num_heads=2,
        max_position_embeddings=32, hidden_dropout_prob=0.0,
        attention_dropout_prob=0.0))
    assert paged_decoder_class(gpt) is GPTPagedDecoder
    assert paged_decoder_class(object()) is GPTPagedDecoder


# -- the decoder's programs called directly: logits ----------------------------------

@pytest.mark.parametrize("impl", ["gather", "kernel"])
@pytest.mark.parametrize("plen", [1, 2, PAGE, PAGE + 3])
def test_prefill_then_decode_logits_match_the_full_forward(seeded, impl,
                                                           plen):
    """The prefill program fills the pages and the state; then every
    position of the rest of the row is decoded through ``PagedStep`` and its
    logits are compared with the reference's full causal forward."""
    cfg, net, w = seeded
    dec = LFM2PagedDecoder(net, max_top_k=4, page_size=PAGE, num_pages=8,
                           attn_impl=impl)
    kv = dec.new_kv(2, 32)
    params = dec.params()
    row = np.random.default_rng(plen).integers(
        0, cfg["vocab_size"], 24).astype(np.int32)
    slot = kv.alloc()
    assert slot == 0 and kv.alloc() == 1
    kv.ensure_pages(slot, 24)
    padded = np.zeros((1, 16), np.int32)
    padded[0, :plen] = row[:plen]
    dec.prefill(kv, params, jnp.asarray(padded),
                jnp.asarray([plen], jnp.int32), jnp.asarray([slot], jnp.int32),
                jnp.zeros((2,), bool),
                pack_sampling([SamplingParams()]), jax.random.PRNGKey(0))
    hid, _ = ref.hidden_states(w, ref.arch_of(cfg), jnp.asarray(row))
    want = np.asarray(ref.logits_of(w, hid))

    @jax.jit
    def step(k, state, lengths, tokens):
        view = PagedStep(k, state, kv.block_tables, lengths, PAGE, impl)
        h, _ = lfm2_hidden(dec.spec, params, tokens[:, None],
                           lengths[:, None], view)
        return (h[:, 0] @ params["tok"].T, view.kvbuf, view.state,
                lengths + 1)

    k, state, lengths = kv.k, kv.state["conv"], kv.lengths
    for t in range(plen, 24):
        logits, k, state, lengths = step(
            k, state, lengths, jnp.asarray([row[t], 0], jnp.int32))
        np.testing.assert_allclose(logits[0], want[t], atol=5e-5, rtol=0,
                                   err_msg=f"position {t}")


def test_prefill_writes_kv_heads_only_and_zero_pads_the_state(seeded):
    cfg, net, _ = seeded
    dec = LFM2PagedDecoder(net, page_size=PAGE, num_pages=4,
                           attn_impl="gather")
    kv = dec.new_kv(2, 32)
    n_attn = cfg["layer_types"].count("full_attention")
    d = cfg["hidden_size"] // cfg["num_attention_heads"]
    # one arena of fused [K | V] rows over the KV heads of the attention
    # layers: the same bytes a page as two arenas of D-wide rows
    assert kv.k.shape == (5, n_attn, PAGE, cfg["num_key_value_heads"], 2 * d)
    assert kv.v.size == 0
    assert kv.page_nbytes() == 2 * PAGE * n_attn * cfg[
        "num_key_value_heads"] * d * 4
    assert kv.state["conv"].shape == (2, cfg["layer_types"].count("conv"),
                                      cfg["conv_L_cache"] - 1,
                                      cfg["hidden_size"])
    slot = kv.alloc()
    kv.ensure_pages(slot, 1)
    padded = np.zeros((1, 16), np.int32)
    padded[0, 0] = 3
    dec.prefill(kv, dec.params(), jnp.asarray(padded),
                jnp.asarray([1], jnp.int32), jnp.asarray([slot], jnp.int32),
                jnp.zeros((2,), bool), pack_sampling([SamplingParams()]),
                jax.random.PRNGKey(0))
    state = np.asarray(kv.state["conv"])
    assert np.all(state[0, :, 0] == 0)          # z_{-1}: before the prompt
    assert np.all(np.abs(state[0, :, 1]).max(-1) > 0)   # z_0
    assert np.all(state[1] == 0)                # the other slot untouched


# -- grouped-query paged attention ----------------------------------------------------

@pytest.mark.parametrize("heads,kv_heads,dim", [(4, 2, 16), (8, 2, 64),
                                                (4, 4, 16)])
def test_grouped_query_kernel_matches_the_gather_lane(heads, kv_heads, dim):
    rng = np.random.default_rng(heads * dim)
    slots, pages_per_seq, layers, n_pages = 3, 4, 2, 12
    arena = (n_pages + 1, layers, PAGE, kv_heads, dim)
    k = jnp.asarray(rng.normal(size=arena), jnp.float32)
    v = jnp.asarray(rng.normal(size=arena), jnp.float32)
    bt = jnp.asarray(rng.permutation(n_pages).reshape(slots, pages_per_seq),
                     jnp.int32)
    q = jnp.asarray(rng.normal(size=(slots, heads, dim)), jnp.float32)
    positions = jnp.asarray([0, 9, 31], jnp.int32)
    scale = dim ** -0.5
    for layer in range(layers):
        got = paged_attention(q, k, v, bt, positions, layer=layer,
                              scale=scale)
        fused = paged_attention(q, jnp.concatenate([k, v], axis=-1), None,
                                bt, positions, layer=layer)
        np.testing.assert_allclose(fused, got, atol=1e-6, rtol=1e-6)
        kd = paged_gather_rows(k, bt, layer)     # [S, max, Hkv, D]
        vd = paged_gather_rows(v, bt, layer)
        g = heads // kv_heads
        kd, vd = jnp.repeat(kd, g, axis=2), jnp.repeat(vd, g, axis=2)
        scores = jnp.einsum("shd,smhd->shm", q, kd) * scale
        mask = jnp.arange(PAGE * pages_per_seq)[None] <= positions[:, None]
        probs = jax.nn.softmax(jnp.where(mask[:, None], scores, -jnp.inf), -1)
        want = jnp.einsum("shm,smhd->shd", probs, vd)
        # blocked online softmax sums in another order: float tolerance
        np.testing.assert_allclose(got, want, atol=2e-6, rtol=1e-5)
