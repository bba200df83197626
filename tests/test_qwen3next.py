"""The Qwen3-Next (``qwen3_next``) model against its plain reference
(``benchmark/reference/qwen3next_ref.py``: the gated delta rule a token a
step) at toy width on the CPU, what its partial rotary and its zero-centred
norms touch, what a chip's share of the experts and of the vocabulary is tied
to, and what the family refuses by name.

Tolerances. Logits agree to float32 reassociation, 1e-3 absolute on logits of
spread one. Three Gated-DeltaNet layers lie before the head, and each norms
an output that is a small difference of large terms (``o_t = S^T q_t`` of
spread 0.01-0.1 where ``v`` has spread one): a float32 recurrence and the
chunked form each lie 1e-5 from a float64 recurrence THERE
(``tests/test_gated_delta.py``), a layer's result differs by 1.5e-5 and the
logits behind four layers by up to 4e-4. Computing in bfloat16 moves the same
logits by 3e-2 and more (``tests/benchmark/test_benchmark_qwen3next.py``).
"""
import json
import os

import numpy as np
import pytest

import jax
import jax.numpy as jnp

import paddle_tpu as paddle
from benchmark import (qwen3next_adapter, qwen3next_weights,
                       spec as bench_spec)
from benchmark.reference import qwen3next_ref as ref
from paddle_tpu.models.lfm2 import rope
from paddle_tpu.models.qwen3next import (Qwen3NextConfig,
                                         Qwen3NextForCausalLM,
                                         ZeroCentredRMSNorm, zc_norm)
from paddle_tpu.nn.moe import rms_norm

pytestmark = pytest.mark.timeout_s(900)
SEED, ATOL = 11, 1e-3


def toy_config(**over):
    with open(os.path.join(bench_spec.HERE, "configs",
                           "qwen3-next-80b-a3b.json")) as f:
        cfg = json.load(f)
    cfg = bench_spec._merged(cfg, cfg["rehearsal"])
    return bench_spec._merged(cfg, over)


def uncut_config():
    """The toy model whole: all 8 experts and all 1,024 rows held."""
    return toy_config(num_experts=8, vocab_size=1024, share={
        "experts_held": [0, 8], "vocab_rows": [0, 1024]})


@pytest.fixture(scope="module")
def seeded():
    cfg = toy_config()
    net = qwen3next_adapter.build_net(cfg)
    qwen3next_adapter.load_weights(net, cfg, SEED)
    net.eval()
    return cfg, net


def reference_logits(cfg, tokens, seed=SEED, layer=None, mode="highest"):
    top = qwen3next_weights.make_top(cfg, seed)
    hid, margin, _ = ref.hidden_states(
        top, layer or (lambda i: qwen3next_weights.make_layer(cfg, seed, i)),
        ref.arch_of(cfg), jnp.asarray(tokens, jnp.int32), mode)
    return np.asarray(ref.logits_of(top, hid, mode)), np.asarray(margin)


def program_logits(net, ids):
    with paddle.no_grad():
        return np.asarray(net(paddle.to_tensor(np.asarray(ids)[None]))._data)[0]


# -- the model against the reference -------------------------------------------

@pytest.mark.parametrize("length", [12, 70, 200])
def test_forward_matches_the_reference(seeded, length):
    """Whole sequences, shorter than the rule's chunk, ragged against it and
    several chunks long (the chunked form against the recurrence)."""
    cfg, net = seeded
    ids = np.random.default_rng(length).integers(
        0, cfg["vocab_size"], length).astype(np.int32)
    want, _ = reference_logits(cfg, ids)
    got = program_logits(net, ids)
    assert np.abs(want).std() > 0.5                 # logits of spread one
    np.testing.assert_allclose(got, want, atol=ATOL, rtol=0)


@pytest.mark.parametrize("part", ["q_norm", "g_norm", "conv_w", "a_log",
                                  "dt_bias", "sg", "router", "ba_w", "n1"])
def test_a_part_left_out_moves_the_logits(seeded, part):
    """The bound would not hide a leaf: with one leaf of every layer that
    has it zeroed in the reference alone, the logits move by fifty bounds."""
    cfg, net = seeded
    ids = np.random.default_rng(1).integers(0, cfg["vocab_size"], 40)

    def layer(i):
        w = dict(qwen3next_weights.make_layer(cfg, SEED, i))
        if part in w:
            w[part] = jnp.zeros_like(w[part])
        return w

    want, _ = reference_logits(cfg, ids, layer=layer)
    assert np.abs(program_logits(net, ids) - want).max() > 50 * ATOL


def test_partial_rotary_touches_the_first_columns_only():
    """``rope(x, positions, theta, rotary_dim)``: the first ``rotary_dim``
    columns as a head of that size rotates, the others as they were; the
    default is the whole head, as every other family calls it."""
    x = jax.random.normal(jax.random.PRNGKey(0), (2, 9, 3, 32))
    pos = jnp.arange(9)[None] + jnp.asarray([[0], [40]])
    got = rope(x, pos, 1e7, 8)
    np.testing.assert_array_equal(got[..., 8:], x[..., 8:])
    np.testing.assert_array_equal(got[..., :8], rope(x[..., :8], pos, 1e7))
    assert np.abs(np.asarray(got[..., :8] - x[..., :8])).max() > 0.1
    np.testing.assert_array_equal(rope(x, pos, 1e7, 32), rope(x, pos, 1e7))
    # the reference rotates the same columns by the same angles
    np.testing.assert_allclose(ref._rope(x[0], 1e7, 8), got[0], atol=1e-6)
    # and the model's rotary width is the configuration's quarter of a head
    assert Qwen3NextConfig().rotary_dim == 64
    assert qwen3next_adapter.config_of(toy_config()).rotary_dim == 8


def test_the_norms_are_zero_centred_but_the_gate_norm():
    x = jax.random.normal(jax.random.PRNGKey(1), (5, 64))
    w = 0.1 * jax.random.normal(jax.random.PRNGKey(2), (64,))
    np.testing.assert_allclose(zc_norm(x, w, 1e-6),
                               rms_norm(x, 1.0 + w, 1e-6), atol=1e-7)
    np.testing.assert_allclose(zc_norm(x, w, 1e-6), ref.rms(x, w, 1e-6),
                               atol=1e-6)
    norm = ZeroCentredRMSNorm(64, 1e-6)
    assert float(jnp.abs(norm.weight._data).max()) == 0.0
    with paddle.no_grad():
        np.testing.assert_allclose(
            norm(paddle.to_tensor(np.asarray(x)))._data,
            rms_norm(x, jnp.ones((64,)), 1e-6), atol=1e-7)
    net = Qwen3NextForCausalLM(qwen3next_adapter.config_of(toy_config()))
    w = dict(net.named_parameters())
    for name in ("model.norm.weight", "model.layers.0.input_layernorm.weight",
                 "model.layers.3.self_attn.q_norm.weight"):
        assert float(jnp.abs(w[name]._data).max()) == 0.0, name
    gate = w["model.layers.0.linear_attn.norm.weight"]._data
    assert float(gate.min()) == 1.0 == float(gate.max())


# -- a chip's share -----------------------------------------------------------------

def test_the_shares_of_a_layer_add_up_to_the_uncut_layer():
    """Over all 8 shares of a toy layer (one expert each): the held experts'
    parts, with the router's choice, the mixer and the gated shared expert
    counted ONCE, add up to what the uncut reference gives for the layer."""
    from paddle_tpu.nn import MoEFeedForward
    whole = uncut_config()
    w = {k: np.asarray(v) for k, v in
         qwen3next_weights.make_layer(whole, SEED, 1).items()}
    jw = {k: jnp.asarray(v) for k, v in w.items()}
    arch = ref.arch_of(whole)
    h = jax.random.normal(jax.random.PRNGKey(0), (24, 64))
    # the layer whole, by the reference: mixer, then the expert sublayer
    want, _, _ = ref.block(jw, h, arch, "highest", jnp.zeros((24,)),
                           jnp.float32(0.0))
    mixed = h + ref.delta_layer(jw, ref.rms(h, jw["n1"], arch.eps), arch,
                                "highest", jnp.zeros((24,)))[0]
    f = np.asarray(ref.rms(mixed, jw["n2"], arch.eps))
    shared = np.asarray(
        jax.nn.sigmoid(f @ w["sg"])
        * ref.swiglu(jnp.asarray(f), w["s1"][0], w["s3"][0], w["s2"][0],
                     "highest"))
    total = np.zeros_like(f)
    for lo in range(8):
        layer = MoEFeedForward(64, 32, 8, 2, True, held=(lo, 1), shared=1,
                               scope="qwen3next", route="softmax",
                               shared_gate=True)
        layer.gate.weight.set_value(w["router"])
        layer.shared_expert_gate.weight.set_value(w["sg"])
        for m in ("w1", "w3", "w2"):
            getattr(layer.experts, m).set_value(w[m][lo:lo + 1])
            getattr(layer.shared_experts, m).set_value(w["s" + m[1]])
        with paddle.no_grad():
            part = np.asarray(layer(paddle.to_tensor(f))._data)
        total += part - shared                  # every holder computed it
        # and the reference given the same share gives the same part
        held = dict(jw, w1=jw["w1"][lo:lo + 1], w3=jw["w3"][lo:lo + 1],
                    w2=jw["w2"][lo:lo + 1])
        same, _, _ = ref.feed_forward(held, jnp.asarray(f),
                                      arch._replace(expert_lo=lo), "highest")
        np.testing.assert_allclose(part, same, atol=5e-6, rtol=0)
    assert np.abs(total).std() > 0.01           # the experts did add something
    np.testing.assert_allclose(np.asarray(mixed) + total + shared, want,
                               atol=2e-5, rtol=0)


def test_the_sliced_heads_logits_are_the_uncut_heads_rows():
    """A model holding rows ``[256, 768)`` of the vocabulary, with the uncut
    model's rows there: its logits are the uncut model's columns, for the
    same tokens under their indices INTO the slice."""
    whole = uncut_config()
    lo, n = 256, 512
    part = toy_config(num_experts=8, share={
        "experts_held": [0, 8], "vocab_rows": [lo, n]})
    full = qwen3next_adapter.build_net(whole)
    qwen3next_adapter.load_weights(full, whole, SEED)
    cut = qwen3next_adapter.build_net(part)
    state = {k: v._data for k, v in full.named_parameters()}
    state["model.embed_tokens.weight"] = \
        state["model.embed_tokens.weight"][lo:lo + n]
    state["lm_head.weight"] = state["lm_head.weight"][:, lo:lo + n]
    for name, p in cut.named_parameters():
        p.set_value(state[name])
    ids = np.random.default_rng(3).integers(0, n, 30)
    np.testing.assert_allclose(program_logits(cut, ids),
                               program_logits(full, ids + lo)[:, lo:lo + n],
                               atol=5e-6, rtol=0)


def test_a_share_shapes_the_parameters_and_nothing_else():
    net = Qwen3NextForCausalLM(qwen3next_adapter.config_of(toy_config()))
    shapes = {k: tuple(v.shape) for k, v in net.named_parameters()}
    assert shapes["model.embed_tokens.weight"] == (512, 64)
    assert shapes["lm_head.weight"] == (64, 512)
    mlp = "model.layers.1.mlp."
    assert shapes[mlp + "gate.weight"] == (64, 8)
    assert shapes[mlp + "experts.w1"] == (4, 64, 32)
    assert shapes[mlp + "shared_experts.w1"] == (1, 64, 32)
    assert shapes[mlp + "shared_expert_gate.weight"] == (64, 1)
    assert mlp + "expert_bias" not in shapes       # softmax routing has none
    lin = "model.layers.0.linear_attn."
    assert shapes[lin + "in_proj_qkvz.weight"] == (64, 2 * 32 + 2 * 64)
    assert shapes[lin + "in_proj_ba.weight"] == (64, 8)
    assert shapes[lin + "conv1d.weight"] == (128, 4)
    assert shapes[lin + "A_log"] == (4,) == shapes[lin + "dt_bias"]
    assert shapes[lin + "norm.weight"] == (16,)
    assert shapes[lin + "out_proj.weight"] == (64, 64)
    att = "model.layers.3.self_attn."
    assert shapes[att + "q_proj.weight"] == (64, 4 * 2 * 32)
    assert shapes[att + "k_proj.weight"] == (64, 2 * 32)
    assert shapes[att + "o_proj.weight"] == (4 * 32, 64)
    assert shapes[att + "q_norm.weight"] == (32,)
    assert not any("linear_attn" in k for k in shapes if ".3." in k)


# -- what the family refuses ---------------------------------------------------------

@pytest.mark.parametrize("key,value", [
    ("rope_scaling", "yarn"), ("tie_word_embeddings", True),
    ("mlp_only_layers", (1,)), ("decoder_sparse_step", 2),
    ("num_nextn_predict_layers", 1), ("attention_bias", True),
    ("use_sliding_window", True), ("hidden_act", "gelu")])
def test_what_the_family_cannot_run_is_refused_by_name(key, value):
    with pytest.raises(NotImplementedError, match=key):
        Qwen3NextConfig(**{key: value})


@pytest.mark.parametrize("over,message", [
    ({"num_key_value_heads": 3}, "multiple"),
    ({"linear_num_value_heads": 24}, "multiple"),
    ({"partial_rotary_factor": 0.0}, "even number"),
    ({"head_dim": 6, "partial_rotary_factor": 0.5}, "even number"),
    ({"vocab_rows": (151000, 2000)}, "vocab_rows"),
    ({"shared_expert_intermediate_size": 768}, "whole number")])
def test_sizes_that_do_not_fit_are_refused(over, message):
    with pytest.raises((ValueError, NotImplementedError), match=message):
        Qwen3NextConfig(**over)


def test_the_published_defaults_are_the_catalogs():
    """``Qwen3NextConfig()`` is the published model: every key of the
    configuration file that is not a cut."""
    with open(os.path.join(bench_spec.HERE, "configs",
                           "qwen3-next-80b-a3b.json")) as f:
        cfg = json.load(f)
    c = Qwen3NextConfig()
    for key in qwen3next_adapter.CONFIG_KEYS:
        if key in cfg["changed"]:
            continue
        want = cfg[key]
        assert getattr(c, key) == (tuple(want) if isinstance(want, list)
                                   else want), key
    assert c.num_hidden_layers == 48 and c.num_experts == 512
    assert c.vocab_size == cfg["share"]["vocab_size_published"]
    assert c.full_layers == tuple(range(3, 48, 4))
    assert len(c.linear_layers) == 36 and c.conv_width == 8192
