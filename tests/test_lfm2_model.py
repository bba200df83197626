"""The LFM2-MoE model family and its expert layer against the plain reference
(``benchmark/reference/lfm2_ref.py``: float32 ``jax.numpy`` at ``highest``,
every expert computed densely and masked), on seeded weights at toy width.

Tolerances: both sides compute in float32 on the CPU with the same
operations in a different order (the grouped product sums a token's experts
after gathering them, the reference before), so logits of magnitude about 1
agree to a few float32 roundings: 2e-5 absolute. A lower precision would
miss by 1e-3 or more (tests/benchmark/test_benchmark_lfm2.py).
"""
import json
import os

import numpy as np
import pytest

import jax
import jax.numpy as jnp

import paddle_tpu as paddle
from benchmark import lfm2_adapter, lfm2_weights, spec as bench_spec
from benchmark.reference import lfm2_ref as ref
from paddle_tpu.models.lfm2 import (LFM2Config, LFM2ForCausalLM,
                                    stack_checkpoint_experts)
from paddle_tpu.nn import MoEFeedForward, RMSNorm
from paddle_tpu.ops import moe

TOL = 2e-5


def toy_cfg(**over):
    """The configuration file at its rehearsal's toy widths."""
    with open(os.path.join(bench_spec.HERE, "configs",
                           "lfm2-8b-a1b.json")) as f:
        cfg = json.load(f)
    return dict(cfg, **cfg["rehearsal"], **over)


@pytest.fixture(scope="module")
def seeded():
    cfg = toy_cfg()
    net = lfm2_adapter.build_net(cfg)
    lfm2_adapter.load_weights(net, cfg, 7)
    net.eval()
    return cfg, net, lfm2_weights.make_lfm2_weights(cfg, 7)


# -- the model ---------------------------------------------------------------------

def test_layer_forward_matches_the_reference_logits(seeded):
    cfg, net, w = seeded
    toks = np.random.default_rng(0).integers(
        0, cfg["vocab_size"], size=(2, 19)).astype(np.int32)
    with paddle.no_grad():
        got = net(paddle.to_tensor(toks)).numpy()
    arch = ref.arch_of(cfg)
    for b in range(2):
        hid, _ = ref.hidden_states(w, arch, jnp.asarray(toks[b]))
        want = np.asarray(ref.logits_of(w, hid))
        assert np.abs(want).max() > 0.3       # logits of a real size
        np.testing.assert_allclose(got[b], want, atol=TOL, rtol=0)


def test_forward_is_causal(seeded):
    cfg, net, _ = seeded
    toks = np.random.default_rng(1).integers(
        0, cfg["vocab_size"], size=(1, 12)).astype(np.int32)
    later = toks.copy()
    later[0, 8:] = (later[0, 8:] + 1) % cfg["vocab_size"]
    with paddle.no_grad():
        a = net(paddle.to_tensor(toks)).numpy()
        b = net(paddle.to_tensor(later)).numpy()
    np.testing.assert_array_equal(a[0, :8], b[0, :8])
    assert np.abs(a[0, 8:] - b[0, 8:]).max() > 1e-3


def test_state_dict_names_follow_the_checkpoint(seeded):
    _, net, _ = seeded
    names = set(net.state_dict())
    for want in ("model.embed_tokens.weight", "model.embedding_norm.weight",
                 "model.layers.0.conv.in_proj.weight",
                 "model.layers.0.conv.conv.weight",
                 "model.layers.0.conv.out_proj.weight",
                 "model.layers.0.operator_norm.weight",
                 "model.layers.0.ffn_norm.weight",
                 "model.layers.0.feed_forward.w1.weight",
                 "model.layers.2.self_attn.q_proj.weight",
                 "model.layers.2.self_attn.k_layernorm.weight",
                 "model.layers.2.self_attn.out_proj.weight",
                 "model.layers.1.feed_forward.gate.weight",
                 "model.layers.1.feed_forward.expert_bias",
                 "model.layers.1.feed_forward.experts.w1",
                 "model.layers.1.feed_forward.experts.w2"):
        assert want in names, want


def test_checkpoint_experts_stack_into_the_models_layout():
    rng = np.random.default_rng(2)
    state = {f"model.layers.3.feed_forward.experts.{e}.w1.weight":
             rng.normal(size=(5, 7)).astype(np.float32) for e in range(4)}
    state["model.layers.3.feed_forward.gate.weight"] = np.zeros((7, 4))
    out = stack_checkpoint_experts(state, 4)
    stacked = out["model.layers.3.feed_forward.experts.w1"]
    assert stacked.shape == (4, 7, 5)         # [n, in, out]
    np.testing.assert_array_equal(
        stacked[2],
        state["model.layers.3.feed_forward.experts.2.w1.weight"].T)
    assert "model.layers.3.feed_forward.gate.weight" in out


@pytest.mark.parametrize("bad", [
    {"layer_types": ("conv",)},                       # count != layers
    {"layer_types": ("conv", "sliding", "conv", "conv")},
    {"conv_bias": True}, {"tie_word_embeddings": False},
    {"num_key_value_heads": 3}])
def test_config_refuses_what_it_cannot_run(bad):
    base = dict(vocab_size=64, hidden_size=16, intermediate_size=32,
                moe_intermediate_size=8, num_hidden_layers=4,
                layer_types=("conv", "conv", "full_attention", "conv"),
                num_attention_heads=4, num_key_value_heads=2,
                num_dense_layers=1, num_experts=4, num_experts_per_tok=2)
    with pytest.raises((ValueError, NotImplementedError)):
        LFM2Config(**dict(base, **bad))


def test_rms_norm_layer_is_the_equation():
    x = np.random.default_rng(3).normal(size=(3, 16)).astype(np.float32)
    layer = RMSNorm(16, epsilon=1e-5)
    w = np.linspace(0.5, 1.5, 16).astype(np.float32)
    layer.weight.set_value(w)
    want = x / np.sqrt((x * x).mean(-1, keepdims=True) + 1e-5) * w
    np.testing.assert_allclose(layer(paddle.to_tensor(x)).numpy(), want,
                               atol=1e-6)


# -- the expert layer ---------------------------------------------------------------

def _expert_weights(rng, h=64, f=32, e=32):
    def arr(*shape, scale):
        return jnp.asarray(rng.normal(size=shape) * scale, jnp.float32)
    return {"l0.gate": arr(h, e, scale=h ** -0.5),
            "l0.expert_bias": arr(e, scale=0.1),
            "l0.w1": arr(e, h, f, scale=h ** -0.5),
            "l0.w3": arr(e, h, f, scale=h ** -0.5),
            "l0.w2": arr(e, f, h, scale=f ** -0.5)}


def _arch(e=32, k=4):
    return ref.Arch(("conv",), 4, 2, 1e-5, 1e6, 3, 0, e, k, True, 1.0)


def _layer(w, x, k=4, lo=0, n=None):
    n = n or w["l0.w1"].shape[0]
    sl = slice(lo, lo + n)
    return moe.moe_feed_forward(
        x, w["l0.gate"], w["l0.expert_bias"], w["l0.w1"][sl], w["l0.w3"][sl],
        w["l0.w2"][sl], top_k=k, expert_lo=lo)


def _reference(w, x, arch):
    wts, margin = ref.route(w, "l0.", x, arch, "highest")
    return ref.experts(w, "l0.", x, wts, arch, "highest"), wts, margin


@pytest.mark.parametrize("tokens", [1, 8, 37, 128, 160, 300])
def test_expert_layer_matches_dense_and_masked(tokens):
    """One tile an expert (up to 128 tokens) and several (beyond)."""
    rng = np.random.default_rng(tokens)
    w = _expert_weights(rng)
    x = jnp.asarray(rng.normal(size=(tokens, 64)), jnp.float32)
    want, _, _ = _reference(w, x, _arch())
    got, counts = _layer(w, x)
    np.testing.assert_allclose(got, want, atol=TOL, rtol=0)
    assert int(counts.sum()) == 4 * tokens        # nothing dropped


@pytest.mark.parametrize("tokens", [9, 150])
def test_skewed_routing_one_expert_takes_most_and_some_take_none(tokens):
    rng = np.random.default_rng(5)
    w = _expert_weights(rng)
    bias = np.full(32, -3.0, np.float32)    # 5 experts can win at all
    bias[[3, 7, 11, 19, 23]] = 0.0
    bias[3] = 3.0                           # and expert 3 always does
    w["l0.expert_bias"] = jnp.asarray(bias)
    x = jnp.asarray(rng.normal(size=(tokens, 64)), jnp.float32)
    want, _, _ = _reference(w, x, _arch())
    got, counts = _layer(w, x)
    counts = np.asarray(counts)
    assert counts[3] == tokens and (counts == 0).sum() == 27
    np.testing.assert_allclose(got, want, atol=TOL, rtol=0)


def test_expert_bias_selects_but_does_not_weigh():
    rng = np.random.default_rng(6)
    w = _expert_weights(rng)
    x = jnp.asarray(rng.normal(size=(40, 64)), jnp.float32)
    idx0, wts0 = moe.route_sigmoid_topk(x, w["l0.gate"],
                                        jnp.zeros(32), 4)
    idx1, wts1 = moe.route_sigmoid_topk(x, w["l0.gate"],
                                        w["l0.expert_bias"] * 5, 4)
    assert (np.sort(idx0, -1) != np.sort(idx1, -1)).any()   # the set moved
    s = jax.nn.sigmoid(x @ w["l0.gate"])
    chosen = jnp.take_along_axis(s, idx1, -1)       # the scores, no bias
    np.testing.assert_allclose(
        wts1, chosen / (chosen.sum(-1, keepdims=True) + 1e-6), atol=1e-6)
    np.testing.assert_allclose(wts1.sum(-1), 1.0, atol=1e-4)


@pytest.mark.parametrize("cut", [16, 8])
def test_expert_shares_add_up_to_the_whole_layer(cut):
    """Experts below and from ``cut`` ({0-15} and {16-31}, say) on two
    holders: each routes over all 32 and computes its own experts' part;
    the parts add up to the layer."""
    rng = np.random.default_rng(8)
    w = _expert_weights(rng)
    x = jnp.asarray(rng.normal(size=(33, 64)), jnp.float32)
    want, _, _ = _reference(w, x, _arch())
    low, c_low = _layer(w, x, lo=0, n=cut)
    high, c_high = _layer(w, x, lo=cut, n=32 - cut)
    assert np.abs(np.asarray(low)).max() > 1e-2 < np.abs(high).max()
    assert int(c_low.sum() + c_high.sum()) == 4 * 33
    np.testing.assert_allclose(low + high, want, atol=TOL, rtol=0)
    # and the reference given a share computes that share
    part = ref.experts({k: (v[cut:] if k[3:] in ("w1", "w3", "w2") else v)
                        for k, v in w.items()}, "l0.", x,
                       ref.route(w, "l0.", x, _arch(), "highest")[0],
                       _arch()._replace(expert_lo=cut), "highest")
    np.testing.assert_allclose(high, part, atol=TOL, rtol=0)


def test_moe_layer_object_holds_its_share_and_matches():
    rng = np.random.default_rng(9)
    w = _expert_weights(rng, e=8)
    layer = MoEFeedForward(64, 32, 8, 2, held=(4, 4))
    layer.gate.weight.set_value(w["l0.gate"])
    layer.expert_bias.set_value(w["l0.expert_bias"])
    for m in ("w1", "w3", "w2"):
        getattr(layer.experts, m).set_value(w["l0." + m][4:])
    x = jnp.asarray(rng.normal(size=(2, 9, 64)), jnp.float32)
    with paddle.no_grad():
        got = layer(paddle.to_tensor(np.asarray(x))).numpy()
    want, _ = _layer(w, x.reshape(-1, 64), k=2, lo=4, n=4)
    np.testing.assert_allclose(got.reshape(-1, 64), want, atol=1e-6)
    with pytest.raises(ValueError):
        MoEFeedForward(64, 32, 8, 2, held=(6, 4))


def test_group_layout_gives_every_pair_a_row_of_its_experts_tile():
    rng = np.random.default_rng(10)
    idx = jnp.asarray(np.stack([rng.choice(32, 4, replace=False)
                                for _ in range(50)]), jnp.int32)
    lay = moe.group_layout(idx, 32)
    tm = lay["tm"]
    dest = np.asarray(lay["dest"])
    assert len(set(dest.ravel())) == 200          # no two pairs share a row
    tile_expert = np.asarray(lay["tile_expert"])
    np.testing.assert_array_equal(tile_expert[dest // tm], np.asarray(idx))
    np.testing.assert_array_equal(np.asarray(lay["src"])[dest],
                                  np.arange(50)[:, None].repeat(4, 1))
    assert int(lay["n_active"][0]) == int(
        np.ceil(np.asarray(lay["counts"]) / tm).sum())
