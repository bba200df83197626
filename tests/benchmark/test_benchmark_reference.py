"""The plain reference against the program on the CPU at a tiny size: the
forward pass of ``GPTForCausalLM``, and prefill-then-paged-decode through
the engine. The same comparison fails when the engine side runs in a lower
precision, and when the reference itself is computed in one (the control
of benchmark/check.py, at a size a test run can hold)."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmark import check, gpt_adapter, weights
from benchmark.reference import gpt2_ref as ref

CFG = {"vocab_size": 512, "n_positions": 64, "n_embd": 64, "n_layer": 2,
       "n_head": 2, "n_inner": 256, "layer_norm_epsilon": 1e-5}
SEED = 2**31 + 77
LIMIT = 1e-4      # toy width: float32 rounding reads 0, int8 and bfloat16 over 1e-2


@pytest.fixture(scope="module")
def w():
    return weights.make_gpt_weights(CFG, SEED, CFG["n_positions"])


def _net(w):
    net = gpt_adapter.build_net(CFG, CFG["n_positions"])
    gpt_adapter.load_weights(net, w)
    net.eval()
    return net


def test_weights_repeat_for_a_seed_and_embedding_rows_come_in_pairs(w):
    again = weights.make_gpt_weights(CFG, SEED, CFG["n_positions"])
    other = weights.make_gpt_weights(CFG, SEED + 1, CFG["n_positions"])
    assert all(np.array_equal(w[k], again[k]) for k in w)
    assert not np.array_equal(w["h0.q_w"], other["h0.q_w"])
    assert set(w) == set(weights.leaf_shapes(CFG, CFG["n_positions"]))
    assert all(v.dtype == jnp.float32 for v in w.values())
    pair = np.abs(np.asarray(w["wte"][0::2] - w["wte"][1::2])).max()
    assert 0 < pair < 10 * weights.PAIR_SHARE * weights.STD * 5


def test_reference_forward_agrees_with_the_programs_model(w):
    import paddle_tpu as paddle
    tokens = np.random.default_rng(0).integers(0, 512, 48).astype(np.int32)
    got = np.asarray(_net(w)(paddle.to_tensor(tokens[None])).numpy())[0]
    want = np.asarray(ref.logits_of(w, ref.hidden_states(
        w, ref.arch_of(CFG), jnp.asarray(tokens))))
    # float32 both sides, different order of sums: a few units in the last
    # place of logits of size ~1
    assert np.abs(got - want).max() < 2e-5


def _serve(w, prompts, max_new, **engine_kw):
    from paddle_tpu.serving.llm import LLMEngine, LLMEngineConfig
    engine_kw.setdefault("paged_attn_impl", "kernel")
    engine = LLMEngine(_net(w), LLMEngineConfig(
        kv_layout="paged", num_slots=2, max_seq=64, page_size=8,
        num_pages=16, prefill_buckets=[32], max_top_k=8, **engine_kw))
    try:
        reqs = [engine.submit(p, max_new_tokens=max_new) for p in prompts]
        return [{"prompt": p, "tokens": r.result(120)["tokens"],
                 "finished": True} for p, r in zip(prompts, reqs)]
    finally:
        engine.drain(timeout=30)


@pytest.fixture(scope="module")
def prompts():
    rng = np.random.default_rng(1)
    return [rng.integers(0, 512, n).astype(np.int32) for n in (9, 20, 31)]


def test_prefill_then_paged_decode_serves_the_references_tokens(w, prompts):
    served = _serve(w, prompts, max_new=24)
    out = check.serve_gaps(w, CFG, served, pad_len=64, max_new=24)
    assert out["tokens_compared"] == 3 * 24
    # every served token is the reference's first or its pair partner,
    # within float32 rounding of the logits
    assert out["served_token_gap"] <= LIMIT


def test_the_engine_in_a_lower_precision_fails_the_same_comparison(
        w, prompts):
    served = _serve(w, prompts, max_new=24, weight_dtype="int8")
    out = check.serve_gaps(w, CFG, served, pad_len=64, max_new=24)
    assert out["served_token_gap"] > LIMIT


def test_the_control_in_bfloat16_comes_out_as_not_correct(w, prompts):
    served = _serve(w, prompts, max_new=24)
    out = check.serve_gaps(w, CFG, served, pad_len=64, max_new=24,
                           control_modes=("bfloat16",))
    assert out["served_token_gap"] <= LIMIT < \
        out["control_bfloat16_token_gap"]
    assert not check.judge(
        {"served_token_gap": out["control_bfloat16_token_gap"]},
        {"served_token_gap": LIMIT})


def test_a_token_altered_after_serving_is_caught(w, prompts):
    served = _serve(w, prompts[:1], max_new=8)
    served[0]["tokens"][3] = (served[0]["tokens"][3] + 2) % 512
    out = check.serve_gaps(w, CFG, served, pad_len=64, max_new=8)
    assert out["served_token_gap"] > 100 * LIMIT


# -- training ------------------------------------------------------------------

OPT = {"lr": 3e-4, "beta1": 0.9, "beta2": 0.999, "epsilon": 1e-8,
       "weight_decay": 0.01}


def test_reference_training_numbers_and_their_fp8_control(w):
    rng = np.random.default_rng(2)
    batches = [rng.integers(0, 512, (2, 32)).astype(np.int32)
               for _ in range(3)]
    want = check.reference_train_numbers(w, CFG, batches, OPT)
    assert len(want["losses"]) == 3 and want["losses"][0] > 5.0
    # the reference against itself: nothing to see
    same = check.train_gaps(want, want)
    assert max(same.values()) == 0.0
    # key biases have no gradient (softmax does not see a shift of a row)
    assert want["grad_norms"]["h0.k_b"] < 1e-3 * np.median(
        list(want["grad_norms"].values()))
    low = check.reference_train_numbers(w, CFG, batches, OPT, mode="fp8")
    gaps = check.train_gaps(low, want)
    assert gaps["first_grad_gap"] > 0.02   # limits: see benchmark/limits/
    # a step that returns its state unchanged
    stuck = dict(want, delta_norms={k: 0.0 for k in want["delta_norms"]})
    assert check.train_gaps(stuck, want)["param_change_gap"] > 0.9
    # a quarter of the batch left out moves the loss
    part = check.reference_train_numbers(w, CFG, [b[:1] for b in batches],
                                         OPT)
    assert check.train_gaps(part, want)["loss_gap"] > 1e-4


def test_adamw_step_is_decoupled_decay_then_adam():
    p, g = {"x": jnp.asarray([1.0, -2.0])}, {"x": jnp.asarray([0.5, 0.25])}
    z = {"x": jnp.zeros(2)}
    new, m, v = ref.adamw_step(p, g, z, z, 1.0, 0.1, 0.9, 0.999, 1e-8, 0.01)
    # first step: m_hat / sqrt(v_hat) is the gradient's sign
    want = np.asarray([1.0, -2.0]) * (1 - 0.1 * 0.01) - 0.1
    assert np.allclose(new["x"], want, atol=1e-6)
    assert np.allclose(m["x"], 0.1 * np.asarray(g["x"]))
