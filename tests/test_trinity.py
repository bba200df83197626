"""The Trinity (``afmoe``) model against its plain reference
(``benchmark/reference/trinity_ref.py``) at toy width on the CPU, what a
chip's share of the experts and of the vocabulary is tied to, the window of
the plain walk of ``paged_attention``, and what the family refuses by name.

Tolerances. Logits agree to float32 reassociation, 5e-5 absolute on logits of
spread one: the program routes pairs through the grouped Pallas product (one
tile an expert, a sum over the chosen in pair order) where the reference
computes every held expert on every row and weighs it, and the attention sums
over KV heads in another order. Computing in bfloat16 moves the same logits
by 1e-2 and more (``tests/benchmark/test_benchmark_trinity.py``), so the
bound separates the two by two orders.
"""
import json
import os

import numpy as np
import pytest

import jax
import jax.numpy as jnp

import paddle_tpu as paddle
from benchmark import spec as bench_spec, trinity_adapter, trinity_weights
from benchmark.reference import trinity_ref as ref
from paddle_tpu.models.trinity import (FULL, SLIDING, TrinityConfig,
                                       TrinityForCausalLM, trinity_logits)
from paddle_tpu.ops.paged_attention import paged_attention

pytestmark = pytest.mark.timeout_s(900)
SEED, ATOL = 11, 5e-5


def toy_config(**over):
    with open(os.path.join(bench_spec.HERE, "configs",
                           "trinity-mini.json")) as f:
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
    net = trinity_adapter.build_net(cfg)
    trinity_adapter.load_weights(net, cfg, SEED)
    net.eval()
    return cfg, net


def reference_logits(cfg, tokens, seed=SEED, layer=None):
    top = trinity_weights.make_top(cfg, seed)
    hid, margin, _ = ref.hidden_states(
        top, layer or (lambda i: trinity_weights.make_layer(cfg, seed, i)),
        ref.arch_of(cfg), jnp.asarray(tokens, jnp.int32))
    return np.asarray(ref.logits_of(top, hid)), np.asarray(margin)


# -- the model against the reference -------------------------------------------

@pytest.mark.parametrize("length", [12, 40, 96])
def test_forward_matches_the_reference(seeded, length):
    """12 rows lie inside the window of 16; 40 and 96 cross it, so the
    sliding layers mask and the full layer does not."""
    cfg, net = seeded
    ids = np.random.default_rng(length).integers(
        0, cfg["vocab_size"], length).astype(np.int32)
    with paddle.no_grad():
        got = np.asarray(net(paddle.to_tensor(ids[None]))._data)[0]
    want, _ = reference_logits(cfg, ids)
    np.testing.assert_allclose(got, want, atol=ATOL, rtol=0)


@pytest.mark.parametrize("over", [{"sliding_window": 15},
                                  {"num_shared_experts": 0}])
def test_a_part_left_out_moves_the_logits(seeded, over):
    """A window one row short, and the shared expert left out: each moves
    logits of spread one by far more than the tolerance."""
    cfg, net = seeded
    ids = np.random.default_rng(3).integers(0, cfg["vocab_size"],
                                            48).astype(np.int32)
    wrong = trinity_adapter.build_net(cfg, **over)
    trinity_adapter.load_weights(wrong, cfg, SEED)
    params = wrong.param_tree()
    got = np.asarray(trinity_logits(wrong.config, params,
                                    jnp.asarray(ids[None])))[0]
    want, _ = reference_logits(cfg, ids)
    assert np.abs(got - want).max() > 100 * ATOL


# -- a chip's share is tied to the model ----------------------------------------

def test_the_shares_of_an_expert_layer_add_up_to_the_uncut_layer():
    """Over all ``n`` shares of an expert layer: the held experts' parts and
    the shared expert counted ONCE add up to what the uncut reference gives
    for the whole layer."""
    from paddle_tpu.nn import MoEFeedForward
    whole = uncut_config()
    w = {k: np.asarray(v) for k, v in
         trinity_weights.make_layer(whole, SEED, 2).items()}
    f = np.asarray(jax.random.normal(jax.random.PRNGKey(0), (24, 64)))
    arch = ref.arch_of(whole)
    want, _, _ = ref.feed_forward({k: jnp.asarray(v) for k, v in w.items()},
                                  jnp.asarray(f), arch, "highest")
    shared = ref.swiglu(jnp.asarray(f), w["s1"][0], w["s3"][0], w["s2"][0],
                        "highest")
    total = np.zeros_like(f)
    for lo, n in ((0, 2), (2, 2), (4, 3), (7, 1)):
        layer = MoEFeedForward(64, 32, 8, 2, True, whole["route_scale"],
                               held=(lo, n), shared=1,
                               eps=whole["assumed"]["route_eps"],
                               scope="trinity")
        layer.gate.weight.set_value(w["router"])
        layer.expert_bias.set_value(w["expert_bias"])
        for m in ("w1", "w3", "w2"):
            getattr(layer.experts, m).set_value(w[m][lo:lo + n])
            getattr(layer.shared_experts, m).set_value(w["s" + m[1]])
        with paddle.no_grad():
            part = np.asarray(layer(paddle.to_tensor(f))._data)
        total += part - np.asarray(shared)      # every holder computed it
        # and the reference given the same share gives the same part
        held = dict(w, w1=w["w1"][lo:lo + n], w3=w["w3"][lo:lo + n],
                    w2=w["w2"][lo:lo + n])
        same, _, _ = ref.feed_forward(
            {k: jnp.asarray(v) for k, v in held.items()}, jnp.asarray(f),
            arch._replace(expert_lo=lo), "highest")
        np.testing.assert_allclose(part, same, atol=ATOL, rtol=0)
    np.testing.assert_allclose(total + np.asarray(shared), want, atol=ATOL,
                               rtol=0)


def test_the_sliced_heads_logits_are_the_uncut_heads_rows():
    """A model holding rows ``[256, 768)`` of the vocabulary, with the uncut
    model's rows there: its logits are the uncut model's columns, for the
    same tokens under their indices INTO the slice."""
    whole = uncut_config()
    lo, n = 256, 512
    top = trinity_weights.make_top(whole, SEED)
    layers = [trinity_weights.make_layer(whole, SEED, i) for i in range(5)]
    ids = np.random.default_rng(5).integers(lo, lo + n, 30).astype(np.int32)
    hid, _, _ = ref.hidden_states(top, lambda i: layers[i],
                                  ref.arch_of(whole), jnp.asarray(ids))
    want = np.asarray(ref.logits_of(top, hid))[:, lo:lo + n]
    cut = trinity_adapter.build_net(bench_spec._merged(whole, {
        "vocab_size": n, "share": {"vocab_rows": [lo, n]}}))
    assert cut.config.vocab_held == n and cut.config.vocab_size == 1024
    params = dict(cut.named_parameters())
    params["model.embed_tokens.weight"].set_value(top["embed"][lo:lo + n])
    params["lm_head.weight"].set_value(top["head"][:, lo:lo + n])
    params["model.norm.weight"].set_value(top["final_norm"])
    for i, layer in enumerate(layers):
        for leaf, value in layer.items():
            params[trinity_adapter.program_name(whole, i, leaf)].set_value(
                value)
    with paddle.no_grad():
        got = np.asarray(cut(paddle.to_tensor((ids - lo)[None]))._data)[0]
    np.testing.assert_allclose(got, want, atol=ATOL, rtol=0)


def test_the_renormalisations_epsilon_is_the_models_and_lfm2s_stays():
    from paddle_tpu.ops.moe import route_sigmoid_topk
    f = jnp.full((1, 4), -40.0)         # every score is about 4e-18
    gate, bias = jnp.eye(4), jnp.zeros(4)
    _, lfm2 = route_sigmoid_topk(f, gate, bias, 2)
    _, ours = route_sigmoid_topk(f, gate, bias, 2, eps=1e-20)
    assert float(lfm2.sum()) < 1e-10 and abs(float(ours.sum()) - 1.0) < 1e-2


# -- the window of the plain walk -------------------------------------------------

def _arenas(rng, pages, layers, page, hkv, d, fused):
    k = rng.standard_normal((pages + 1, layers, page, hkv, d)).astype(
        np.float32)
    v = rng.standard_normal((pages + 1, layers, page, hkv, d)).astype(
        np.float32)
    if fused:
        return jnp.asarray(np.concatenate([k, v], -1)), None, k, v
    return jnp.asarray(k), jnp.asarray(v), k, v


def _masked_reference(q, k, v, tables, positions, layer, scale, window):
    """Gather every row of the table and mask explicitly."""
    s, hq, d = q.shape
    hkv = k.shape[3]
    rows_k = k[tables, layer].reshape(s, -1, hkv, d)
    rows_v = v[tables, layer].reshape(s, -1, hkv, d)
    at = np.arange(rows_k.shape[1])[None]
    seen = at <= positions[:, None]
    if window is not None:
        seen &= at > positions[:, None] - window
    qg = q.reshape(s, hkv, hq // hkv, d) * scale
    scores = np.einsum("skgd,smkd->skgm", qg, rows_k)
    scores = np.where(seen[:, None, None], scores, -np.inf)
    w = np.exp(scores - scores.max(-1, keepdims=True))
    w /= w.sum(-1, keepdims=True)
    return np.einsum("skgm,smkd->skgd", w,
                     np.where(seen[:, :, None, None], rows_v, 0.0)
                     ).reshape(s, hq, d)


@pytest.mark.parametrize("fused", [False, True])
@pytest.mark.parametrize("window", [5, 8, 16, 19])
def test_windowed_walk_matches_an_explicit_mask(window, fused):
    """Positions inside the first page, on a page boundary, one past it,
    and past several windows; windows under a page, of a page, of two and
    one that starts mid-page. The pages behind a window hold NaN: the walk
    neither reads nor multiplies them."""
    rng = np.random.default_rng(window)
    page, pps, hkv, g, d = 8, 12, 2, 2, 16
    positions = np.asarray([0, 3, 7, 8, 15, 16, 40, 95], np.int32)
    s = len(positions)
    tables = rng.permutation(s * pps).reshape(s, pps).astype(np.int32)
    ka, va, k, v = _arenas(rng, s * pps, 2, page, hkv, d, fused)
    q = rng.standard_normal((s, hkv * g, d)).astype(np.float32)
    want = _masked_reference(q, k, v, tables, positions, 1, 0.25, window)
    # what lies wholly behind a window is poisoned, in arena and table
    poisoned_k, poisoned_v, behind = k.copy(), v.copy(), tables.copy()
    for i, pos in enumerate(positions):
        first = max(0, pos - window + 1) // page
        for p in tables[i, :first]:
            poisoned_k[p], poisoned_v[p] = np.nan, np.nan
        behind[i, :first] = s * pps            # the trash page
    poisoned_k[s * pps], poisoned_v[s * pps] = np.nan, np.nan
    if fused:
        ka = jnp.asarray(np.concatenate([poisoned_k, poisoned_v], -1))
    else:
        ka, va = jnp.asarray(poisoned_k), jnp.asarray(poisoned_v)
    got = paged_attention(jnp.asarray(q), ka, va, jnp.asarray(behind),
                          jnp.asarray(positions), layer=1, scale=0.25,
                          window=window, interpret=True)
    np.testing.assert_allclose(np.asarray(got), want, atol=2e-5, rtol=0)


def test_a_window_no_shorter_than_the_context_is_the_plain_walk():
    rng = np.random.default_rng(0)
    page, pps, hkv, d = 8, 6, 2, 16
    positions = np.asarray([0, 9, 31, 47], np.int32)
    tables = rng.permutation(4 * pps).reshape(4, pps).astype(np.int32)
    ka, va, _, _ = _arenas(rng, 4 * pps, 1, page, hkv, d, False)
    q = jnp.asarray(rng.standard_normal((4, 4, d)).astype(np.float32))
    args = (q, ka, va, jnp.asarray(tables), jnp.asarray(positions))
    plain = paged_attention(*args, interpret=True)
    wide = paged_attention(*args, window=48, interpret=True)
    np.testing.assert_allclose(np.asarray(wide), np.asarray(plain),
                               atol=1e-6, rtol=0)


@pytest.mark.parametrize("window,selected", [(0, None), (-3, None),
                                             (8, "tables")])
def test_a_window_the_walk_cannot_take_is_refused(window, selected):
    z = jnp.zeros
    sel = None if selected is None else (z((1, 1, 2), jnp.int32),
                                         z((1, 1), jnp.int32))
    with pytest.raises(ValueError, match="window"):
        paged_attention(z((1, 2, 16)), z((3, 1, 8, 32)) if sel else
                        z((3, 1, 8, 1, 16)), None if sel else
                        z((3, 1, 8, 1, 16)), z((1, 2), jnp.int32),
                        z((1,), jnp.int32), window=window, selected=sel,
                        interpret=True)


# -- what the family refuses, and what it takes from the catalog -------------------

@pytest.mark.parametrize("key,value", [
    ("num_expert_groups", 2), ("n_group", 4), ("topk_group", 2),
    ("num_limited_groups", 2), ("rope_scaling", "yarn"),
    ("score_func", "softmax"), ("hidden_act", "gelu"),
    ("tie_word_embeddings", True)])
def test_what_the_family_cannot_run_is_refused_by_name(key, value):
    with pytest.raises(NotImplementedError, match=key):
        TrinityConfig(layer_types=(SLIDING,) * 32, **{key: value})


@pytest.mark.parametrize("over,message", [
    ({"layer_types": (SLIDING,) * 3}, "layer_types"),
    ({"layer_types": ("conv",) * 32}, "unknown layer types"),
    ({"sliding_window": 0}, "sliding_window"),
    ({"vocab_rows": (200000, 4096)}, "vocab_rows"),
    ({"num_key_value_heads": 5}, "multiple")])
def test_sizes_that_do_not_fit_are_refused(over, message):
    kw = {"layer_types": (SLIDING,) * 32}
    kw.update(over)
    with pytest.raises(ValueError, match=message):
        TrinityConfig(**kw)


def test_the_published_defaults_are_the_catalogs():
    """Every key of the guide's catalog row is a field with that default
    (``layer_types`` has none: its length follows the depth)."""
    row = None
    path = "/opt/skills/guides/model-configs/architectures.jsonl"
    if not os.path.isfile(path):
        pytest.skip("no catalog beside the guide here")
    with open(path) as f:
        for line in f:
            if json.loads(line)["name"] == "Trinity-Mini":
                row = json.loads(line)["config"]
    c = TrinityConfig(layer_types=row["layer_types"])
    for key, value in row.items():
        got = getattr(c, key)
        assert (list(got) if isinstance(got, tuple) else got) == value, key
    assert c.window_layers == tuple(i for i in range(32) if i % 4 != 3)
    assert c.full_layers == (3, 7, 11, 15, 19, 23, 27, 31)
    assert c.window_of(0) == 2048 and c.window_of(3) is None
    assert abs(c.embed_scale - 2048 ** 0.5) < 1e-9 and c.vocab_held == 200192


def test_a_share_shapes_the_parameters_and_nothing_else():
    cfg = toy_config()
    net = TrinityForCausalLM(trinity_adapter.config_of(cfg))
    shapes = {k: tuple(v.shape) for k, v in net.named_parameters()}
    assert shapes["model.embed_tokens.weight"] == (512, 64)
    assert shapes["lm_head.weight"] == (64, 512)
    assert shapes["model.layers.1.mlp.gate.weight"] == (64, 8)   # all 8
    assert shapes["model.layers.1.mlp.experts.w1"] == (4, 64, 32)
    assert shapes["model.layers.1.mlp.shared_experts.w2"] == (1, 32, 64)
    assert "model.layers.0.mlp.w1.weight" in shapes               # dense
    assert net.config.layer_types == (SLIDING,) * 4 + (FULL,)
