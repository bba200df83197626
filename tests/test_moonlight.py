"""The Moonlight (``deepseek_v3``) model against its plain reference
(``benchmark/reference/moonlight_ref.py``) at toy width on the CPU, the two
orders of its latent attention against one another, what a chip's share of
the experts and of the vocabulary is tied to, the latent walk of
``paged_attention``, and what the family refuses by name.

Tolerances. Logits agree to float32 reassociation, 5e-5 absolute on logits of
spread one: the program routes pairs through the grouped Pallas product where
the reference computes every held expert on every row and weighs it. The
ABSORBED order (``q~ = qn W_UK^T``, one 40-long dot with the cached row,
``o~ W_UV``) and the EXPANDED one (a key and a value a head and row) multiply
the same numbers in another order: ``(qn W_UK^T) . c`` against ``qn . (c
W_UK)`` sums 32 x 16 products either way but rounds the partial sums at other
places, so attention outputs of spread one differ by a few 1e-6 and the logits
behind four layers by less than 5e-5; computing in bfloat16 moves the same
logits by 1e-2 and more (``tests/benchmark/test_benchmark_moonlight.py``), so
the bound separates the two by two orders.
"""
import json
import os

import numpy as np
import pytest

import jax
import jax.numpy as jnp

import paddle_tpu as paddle
from benchmark import moonlight_adapter, moonlight_weights, spec as bench_spec
from benchmark.reference import moonlight_ref as ref
from paddle_tpu.models.moonlight import (FullSequence, MoonlightConfig,
                                         MoonlightForCausalLM,
                                         moonlight_hidden, split_ukv)
from paddle_tpu.ops.paged_attention import paged_attention
from paddle_tpu.serving.llm.paged.moonlight import latent_gather_attention

pytestmark = pytest.mark.timeout_s(900)
SEED, ATOL = 11, 5e-5


def toy_config(**over):
    with open(os.path.join(bench_spec.HERE, "configs",
                           "moonlight-16b-a3b.json")) as f:
        cfg = json.load(f)
    cfg = bench_spec._merged(cfg, cfg["rehearsal"])
    return bench_spec._merged(cfg, over)


def uncut_config():
    """The toy model whole: all 8 experts and all 1,024 rows held."""
    return toy_config(n_routed_experts=8, vocab_size=1024, share={
        "experts_held": [0, 8], "vocab_rows": [0, 1024]})


@pytest.fixture(scope="module")
def seeded():
    cfg = toy_config()
    net = moonlight_adapter.build_net(cfg)
    moonlight_adapter.load_weights(net, cfg, SEED)
    net.eval()
    return cfg, net


def reference_logits(cfg, tokens, seed=SEED, layer=None):
    top = moonlight_weights.make_top(cfg, seed)
    hid, margin, _ = ref.hidden_states(
        top, layer or (lambda i: moonlight_weights.make_layer(cfg, seed, i)),
        ref.arch_of(cfg), jnp.asarray(tokens, jnp.int32))
    return np.asarray(ref.logits_of(top, hid)), np.asarray(margin)


# -- the model against the reference -------------------------------------------

@pytest.mark.parametrize("length", [12, 40, 96])
def test_forward_matches_the_reference(seeded, length):
    cfg, net = seeded
    ids = np.random.default_rng(length).integers(
        0, cfg["vocab_size"], length).astype(np.int32)
    want, _ = reference_logits(cfg, ids)
    with paddle.no_grad():
        got = np.asarray(net(paddle.to_tensor(ids[None]))._data)[0]
    assert np.abs(want).std() > 0.5                 # logits of spread one
    np.testing.assert_allclose(got, want, atol=ATOL, rtol=0)


class Absorbed:
    """The view with no past in the ABSORBED order: a score is one dot of
    ``[q~ | qr]`` with a row ``[c | r]``, the result ``(p c) W_UV``."""

    def __init__(self, cfg):
        self.cfg = cfg

    def attend(self, i, qn, qr, c, r, w_ukv, scale):
        wuk, wuv = split_ukv(self.cfg, w_ukv)
        q = jnp.concatenate([jnp.einsum("bqhd,chd->bqhc", qn, wuk), qr], -1)
        rows = jnp.concatenate([c, r], -1)                    # [B, T, 40]
        scores = jnp.einsum("bqhc,bkc->bhqk", q, rows) * scale
        t = q.shape[1]
        seen = jnp.arange(t)[None, :] <= jnp.arange(t)[:, None]
        probs = jax.nn.softmax(jnp.where(seen, scores, -1e30), axis=-1)
        return jnp.einsum("bqhc,chd->bqhd",
                          jnp.einsum("bhqk,bkc->bqhc", probs, c), wuv)


@pytest.mark.parametrize("length", [12, 40, 96])
def test_absorbed_and_expanded_give_the_same_logits(seeded, length):
    """Over the same rows ``[c | r]``: the two views record or read the same
    cache, and the logits agree to the reassociation bound above."""
    _, net = seeded
    cfg, params = net.config, net.param_tree()
    ids = jnp.asarray(np.random.default_rng(length).integers(
        0, cfg.vocab_held, (1, length)), jnp.int32)
    positions = jnp.arange(length, dtype=jnp.int32)[None]
    expanded = FullSequence(cfg)
    with jax.default_matmul_precision("highest"):
        h_exp, _ = moonlight_hidden(cfg, params, ids, positions, expanded)
        h_abs, _ = moonlight_hidden(cfg, params, ids, positions,
                                    Absorbed(cfg))
        a, b = h_exp @ params["head"], h_abs @ params["head"]
    assert len(expanded.rows) == cfg.num_hidden_layers
    assert expanded.rows[0][0].shape == (1, length, cfg.kv_lora_rank)
    assert expanded.rows[0][1].shape == (1, length, cfg.qk_rope_head_dim)
    np.testing.assert_allclose(np.asarray(a), np.asarray(b), atol=ATOL,
                               rtol=0)
    assert float(jnp.abs(a - b).max()) > 0      # another order, not a copy


@pytest.mark.parametrize("part", ["rotary part", "latent norm",
                                  "shared experts"])
def test_a_part_left_out_moves_the_logits(seeded, part):
    """What the controls of the benchmark leave out shows in the logits by
    far more than the tolerance."""
    cfg, net = seeded
    ids = np.random.default_rng(2).integers(0, cfg["vocab_size"],
                                            40).astype(np.int32)
    want, _ = reference_logits(cfg, ids)

    def layer(i):
        w = dict(moonlight_weights.make_layer(cfg, SEED, i))
        if part == "rotary part":
            w["q_w"] = w["q_w"].reshape(64, 4, 24).at[..., 16:].set(
                0.0).reshape(64, 96)
        elif part == "latent norm":
            w["kv_norm"] = jnp.ones_like(w["kv_norm"])
        elif part == "shared experts" and "s2" in w:
            w["s2"] = jnp.zeros_like(w["s2"])
        return w

    got, _ = reference_logits(cfg, ids, layer=layer)
    assert np.abs(got - want).max() > 100 * ATOL


def test_the_shares_of_an_expert_layer_add_up_to_the_uncut_layer():
    """Over all ``n`` shares of an expert layer: the held experts' parts and
    the shared experts counted ONCE add up to what the uncut reference gives
    for the whole layer."""
    from paddle_tpu.nn import MoEFeedForward
    whole = uncut_config()
    w = {k: np.asarray(v) for k, v in
         moonlight_weights.make_layer(whole, SEED, 2).items()}
    f = np.asarray(jax.random.normal(jax.random.PRNGKey(0), (24, 64)))
    arch = ref.arch_of(whole)
    want, _, _ = ref.feed_forward({k: jnp.asarray(v) for k, v in w.items()},
                                  jnp.asarray(f), arch, "highest")
    shared = ref.swiglu(jnp.asarray(f), w["s1"][0], w["s3"][0], w["s2"][0],
                        "highest")
    assert w["s1"].shape == (1, 64, 2 * 32)     # ONE SwiGLU of twice the width
    total = np.zeros_like(f)
    for lo, n in ((0, 2), (2, 2), (4, 3), (7, 1)):
        layer = MoEFeedForward(64, 32, 8, 2, True,
                               whole["routed_scaling_factor"], held=(lo, n),
                               shared=2, eps=whole["assumed"]["route_eps"],
                               scope="moonlight")
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
    top = moonlight_weights.make_top(whole, SEED)
    layers = [moonlight_weights.make_layer(whole, SEED, i) for i in range(4)]
    ids = np.random.default_rng(5).integers(lo, lo + n, 30).astype(np.int32)
    hid, _, _ = ref.hidden_states(top, lambda i: layers[i],
                                  ref.arch_of(whole), jnp.asarray(ids))
    want = np.asarray(ref.logits_of(top, hid))[:, lo:lo + n]
    cut = moonlight_adapter.build_net(bench_spec._merged(whole, {
        "vocab_size": n, "share": {"vocab_rows": [lo, n]}}))
    assert cut.config.vocab_held == n and cut.config.vocab_size == 1024
    params = dict(cut.named_parameters())
    params["model.embed_tokens.weight"].set_value(top["embed"][lo:lo + n])
    params["lm_head.weight"].set_value(top["head"][:, lo:lo + n])
    params["model.norm.weight"].set_value(top["final_norm"])
    for i, layer in enumerate(layers):
        for leaf, value in layer.items():
            params[moonlight_adapter.program_name(whole, i, leaf)].set_value(
                value)
    with paddle.no_grad():
        got = np.asarray(cut(paddle.to_tensor((ids - lo)[None]))._data)[0]
    np.testing.assert_allclose(got, want, atol=ATOL, rtol=0)


# -- the latent walk of paged_attention ------------------------------------------

H, VALUE, ROTARY, PAGE = 4, 32, 8, 8


def _latent_case(rng, row, seqs=5, pages_per_seq=6, layers=3):
    pages = seqs * pages_per_seq
    arena = rng.standard_normal((pages + 1, layers, PAGE, row)).astype(
        np.float32)
    arena[..., VALUE + ROTARY:] = 0.0               # what lies past a row
    arena[-1] = np.nan                              # the trash page
    tables = rng.permutation(pages).reshape(seqs, pages_per_seq).astype(
        np.int32)
    q = rng.standard_normal((seqs, H, VALUE + ROTARY)).astype(np.float32)
    return jnp.asarray(q), jnp.asarray(arena), jnp.asarray(tables)


@pytest.mark.parametrize("row", [40, 128])      # as they are; padded to tiles
@pytest.mark.parametrize("positions", [
    [0, 3, 7, 5, 1],            # in the first page
    [7, 15, 8, 16, 23],         # on a page's last row and the first of the next
    [47, 30, 41, 12, 39]])      # past several pages, to the table's end
def test_latent_walk_matches_the_gather_lane_and_an_expanded_walk(row,
                                                                  positions):
    """``paged_attention(latent=...)`` in interpret mode against the gather
    lane, and against an EXPANDED 4-head ``paged_attention`` over keys and
    values built from the same rows."""
    rng = np.random.default_rng(row + positions[0])
    q, arena, tables = _latent_case(rng, row)
    pos = jnp.asarray(positions, jnp.int32)
    scale, layer, latent = 0.2, 1, (VALUE, ROTARY)
    got = paged_attention(q, arena, None, tables, pos, layer=layer,
                          scale=scale, latent=latent)
    assert got.shape == (5, H, VALUE)
    want = latent_gather_attention(q, arena, tables, pos, layer, scale, latent)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), atol=2e-6,
                               rtol=0)
    # expanded: W_UK, W_UV a head; a key [c W_UK | r], a value c W_UV; the
    # absorbed query q~ = qn W_UK^T
    wuk = jnp.asarray(rng.standard_normal((VALUE, H, 16)), jnp.float32) * 0.2
    wuv = jnp.asarray(rng.standard_normal((VALUE, H, 16)), jnp.float32) * 0.2
    qn = jnp.asarray(rng.standard_normal((5, H, 16)), jnp.float32)
    q_abs = jnp.concatenate([jnp.einsum("shd,chd->shc", qn, wuk),
                             q[..., VALUE:]], -1)
    rows = jnp.nan_to_num(arena)
    c, r = rows[..., :VALUE], rows[..., VALUE:VALUE + ROTARY]
    keys = jnp.concatenate([
        jnp.einsum("plrc,chd->plrhd", c, wuk),
        jnp.broadcast_to(r[..., None, :], r.shape[:3] + (H, ROTARY))], -1)
    values = jnp.pad(jnp.einsum("plrc,chd->plrhd", c, wuv),
                     [(0, 0)] * 4 + [(0, ROTARY)])      # rows of one width
    q_exp = jnp.concatenate([qn, q[..., VALUE:]], -1)
    expanded = paged_attention(q_exp, keys, values, tables, pos, layer=layer,
                               scale=scale)[..., :16]
    absorbed = jnp.einsum("shc,chd->shd", paged_attention(
        q_abs, arena, None, tables, pos, layer=layer, scale=scale,
        latent=latent), wuv)
    np.testing.assert_allclose(np.asarray(absorbed), np.asarray(expanded),
                               atol=1e-5, rtol=0)


@pytest.mark.parametrize("bad", ["second arena", "window", "selection",
                                 "five dimensions", "query width",
                                 "narrow row"])
def test_a_latent_walk_the_kernel_cannot_take_is_refused(bad):
    q, arena, tables = _latent_case(np.random.default_rng(0), 40)
    pos, kw = jnp.zeros((5,), jnp.int32), {"latent": (VALUE, ROTARY)}
    v = None
    if bad == "second arena":
        v = arena
    elif bad == "window":
        kw["window"] = 4
    elif bad == "selection":
        kw["selected"] = (tables[:, None], jnp.ones((5, 1), jnp.int32))
    elif bad == "five dimensions":
        arena = arena[:, :, :, None]
    elif bad == "query width":
        q = q[..., :VALUE]
    elif bad == "narrow row":
        arena = arena[..., :VALUE]
    with pytest.raises(ValueError, match="latent rows"):
        paged_attention(q, arena, v, tables, pos, **kw)


def test_the_latent_shape_takes_the_mxu_recurrence():
    """``tuner.space.paged_recurrence`` from the call's shapes alone: 16
    query heads on the one row a token keeps, at either row width."""
    from paddle_tpu.tuner.space import paged_pages_per_step, paged_recurrence
    for row in (576, 640):
        assert paged_recurrence(16, 1, 64, row, 4, 1) == "mxu"
        assert paged_pages_per_step(1, 64, row, 4, 1, 112) == 8


# -- what the family refuses ------------------------------------------------------

@pytest.mark.parametrize("key,value", [
    ("q_lora_rank", 1536), ("n_group", 8), ("topk_group", 4),
    ("rope_scaling", "yarn"), ("scoring_func", "softmax"),
    ("num_nextn_predict_layers", 1), ("topk_method", "greedy"),
    ("moe_layer_freq", 2), ("attention_bias", True),
    ("tie_word_embeddings", True), ("hidden_act", "gelu")])
def test_what_the_family_cannot_run_is_refused_by_name(key, value):
    with pytest.raises(NotImplementedError, match=key):
        MoonlightConfig(**{key: value})


@pytest.mark.parametrize("over,message", [
    ({"num_key_value_heads": 4}, "num_key_value_heads"),
    ({"vocab_rows": (163000, 2048)}, "vocab_rows"),
    ({"vocab_rows": (0, 0)}, "vocab_rows")])
def test_sizes_that_do_not_fit_are_refused(over, message):
    with pytest.raises(ValueError, match=message):
        MoonlightConfig(**over)


def test_the_published_defaults_are_the_catalogs():
    catalog = "/opt/skills/guides/model-configs/architectures.jsonl"
    if not os.path.isfile(catalog):
        pytest.skip("no catalog beside the guide here")
    with open(catalog) as f:
        (row,) = [r for r in map(json.loads, f)
                  if r["name"] == "Moonlight-16B-A3B"]
    cfg = MoonlightConfig()
    for key, value in row["config"].items():
        assert getattr(cfg, key) == value, key
    assert (cfg.latent_row, cfg.qk_head_dim) == (576, 192)
    assert abs(cfg.softmax_scale - 192 ** -0.5) < 1e-12
    assert cfg.num_expert_layers == 26 and cfg.vocab_held == 163840


def test_a_share_shapes_the_parameters_and_nothing_else():
    cfg = moonlight_adapter.config_of(toy_config())
    net = MoonlightForCausalLM(cfg)
    shapes = {k: tuple(v.shape) for k, v in net.named_parameters()}
    assert shapes["model.embed_tokens.weight"] == (512, 64)
    assert shapes["lm_head.weight"] == (64, 512)
    assert shapes["model.layers.1.mlp.gate.weight"] == (64, 8)
    assert shapes["model.layers.1.mlp.experts.w1"] == (4, 64, 32)
    assert shapes["model.layers.1.mlp.shared_experts.w1"] == (1, 64, 64)
    assert shapes["model.layers.0.mlp.w1.weight"] == (64, 128)
    att = "model.layers.2.self_attn."
    assert shapes[att + "q_proj.weight"] == (64, 4 * 24)
    assert shapes[att + "kv_a_proj_with_mqa.weight"] == (64, 40)
    assert shapes[att + "kv_a_layernorm.weight"] == (32,)
    assert shapes[att + "kv_b_proj.weight"] == (32, 4 * 32)
    assert shapes[att + "o_proj.weight"] == (64, 64)
