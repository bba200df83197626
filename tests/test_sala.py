"""``models.sala``: the MiniCPM-SALA forward pass against the benchmark's
plain reference on seeded weights, the block selection against the
reference's, what the configuration refuses, and ``paged_attention``'s walk
over selected pages against a gather of the same rows and against the
whole-table kernel with every page selected."""
import json
import os

import numpy as np
import pytest

import jax.numpy as jnp

import paddle_tpu as paddle
from benchmark import sala_adapter, sala_weights, spec as bench_spec
from benchmark.reference import sala_ref as ref
from paddle_tpu.models.sala import (SALAConfig, compressed_keys,
                                    selected_blocks)
from paddle_tpu.ops.paged_attention import paged_attention

SEED = 11


def toy_config(**assumed):
    """The rehearsal widths of the benchmark's configuration file: hidden
    64, 4 + 2 heads of 16, sparse-linear-linear-linear, block 8, kernel 4 /
    stride 2, top-k 4, window 8, dense_len 32."""
    with open(os.path.join(bench_spec.HERE, "configs",
                           "minicpm-sala.json")) as f:
        cfg = json.load(f)
    cfg = bench_spec._merged(cfg, cfg["rehearsal"])
    cfg["assumed"].update(assumed)
    return cfg


@pytest.fixture(scope="module")
def seeded():
    cfg = toy_config()
    net = sala_adapter.build_net(cfg)
    sala_adapter.load_weights(net, cfg, SEED)
    net.eval()
    return cfg, net


def reference_logits(cfg, tokens, mode="highest", topk=None):
    arch = ref.arch_of(cfg, topk)
    top = sala_weights.make_top(cfg, SEED)
    hid, margin = ref.hidden_states(
        top, lambda i: sala_weights.make_layer(cfg, SEED, i), arch,
        jnp.asarray(tokens), mode)
    return np.asarray(ref.logits_of(top, hid, mode)), np.asarray(margin)


@pytest.mark.parametrize("length", [24, 48, 96])
def test_forward_matches_the_reference(seeded, length):
    """Whole sequences, below and far past ``dense_len`` (32): every query
    past it selects 4 of up to 12 blocks."""
    cfg, net = seeded
    tokens = np.random.default_rng(length).integers(
        0, cfg["vocab_size"], length).astype(np.int32)
    got = np.asarray(net(paddle.to_tensor(tokens[None]))._data)[0]
    want, margin = reference_logits(cfg, tokens)
    assert np.isfinite(margin[:32]).sum() == 0      # dense: nothing chosen
    if length > 40:
        assert np.isfinite(margin[40:]).all() and margin[40:].min() > 1e-6
    # logits of spread one through 4 layers in float32: sums in another order
    np.testing.assert_allclose(got, want, atol=2e-5, rtol=0)


def test_one_block_fewer_moves_the_logits(seeded):
    """The reference with top-k 3 is another function past ``dense_len`` and
    the same one before it: the selection is not decoration."""
    cfg, _ = seeded
    tokens = np.random.default_rng(5).integers(0, cfg["vocab_size"],
                                               96).astype(np.int32)
    full, _ = reference_logits(cfg, tokens)
    short, _ = reference_logits(cfg, tokens, topk=3)
    np.testing.assert_array_equal(full[:32], short[:32])
    assert np.abs(full[40:] - short[40:]).max() > 1e-2


def test_the_selection_is_the_references(seeded):
    """``selected_blocks`` against ``sala_ref._select`` row by row, on random
    queries and keys: forced blocks, top-k by score, the dense range."""
    cfg, net = seeded
    c = net.config
    rng = np.random.default_rng(2)
    t, hkv, g, d = 96, c.num_key_value_heads, c.groups, c.head_dim
    q = jnp.asarray(rng.normal(size=(t, hkv, g, d)) * 3, jnp.float32)
    k = jnp.asarray(rng.normal(size=(1, t, hkv, d)), jnp.float32)
    ck = compressed_keys(c, k, t // c.sparse_block_size)[0]
    n = jnp.arange(1, t + 1)
    got = np.asarray(selected_blocks(c, q, ck, n, d ** -0.5))
    arch = ref.arch_of(cfg)
    for row in range(t):
        want, _ = ref._select(arch, q[row], ck, row + 1, "highest")
        np.testing.assert_array_equal(got[row], np.asarray(want), str(row))
    counts = got.sum(-1)
    assert (counts[40:] == 4).all() and (counts[:32] <= 4).all()
    # past dense_len the first block and the window's are always read
    for row in range(40, t):
        assert got[row, :, 0].all() and got[row, :, row // 8].all()


def test_compressed_keys_are_means_of_overlapping_windows(seeded):
    _, net = seeded
    c = net.config
    k = jnp.asarray(np.random.default_rng(4).normal(size=(1, 40, 2, 16)),
                    jnp.float32)
    ck = np.asarray(compressed_keys(c, k, 5))
    assert ck.shape == (1, 20, 2, 16)
    for j in range(19):     # kernel j: rows [2 j, 2 j + 4); 19 reaches row 40
        np.testing.assert_allclose(
            ck[0, j], np.asarray(k)[0, 2 * j:2 * j + 4].mean(0), atol=1e-6)


@pytest.mark.parametrize("key,value", [
    ("attention_bias", True), ("attn_use_rope", True),
    ("lightning_use_rope", False), ("qk_norm", False),
    ("use_output_gate", False), ("use_output_norm", False),
    ("attn_use_output_gate", False), ("hidden_act", "gelu"),
    ("lightning_scale", "1"), ("tie_word_embeddings", True),
    ("lightning_nkv", 2)])
def test_what_the_family_cannot_run_is_refused_by_name(key, value):
    cfg = toy_config()
    with pytest.raises(NotImplementedError, match=key.split("_")[0]):
        sala_adapter.config_of(cfg, **{key: value})


@pytest.mark.parametrize("over,message", [
    ({"mixer_types": ["minicpm4"]}, "mixer_types"),
    ({"mixer_types": ["minicpm4", "x", "x", "x"]}, "unknown mixer"),
    ({"sparse_kernel_size": 3}, "multiples of the stride"),
    ({"sparse_dense_len": 16}, "dense_len"),
    ({"num_key_value_heads": 3}, "multiple of KV heads")])
def test_sizes_that_do_not_fit_are_refused(over, message):
    with pytest.raises(ValueError, match=message):
        sala_adapter.config_of(toy_config(), **over)


def test_the_published_defaults_are_the_catalogs():
    c = SALAConfig(mixer_types=["minicpm4"] * 32)
    assert (c.hidden_size, c.num_attention_heads, c.num_key_value_heads,
            c.head_dim, c.intermediate_size, c.vocab_size) \
        == (4096, 32, 2, 128, 16384, 73448)
    assert abs(c.residual_scale - 1.4 / 32 ** 0.5) < 1e-12
    assert c.logit_divisor == 16 and c.kernels_per_block == 4
    rates = np.asarray(c.decay_rates())
    assert rates.shape == (32,) and abs(rates[0] - 2 ** -0.25) < 1e-7 \
        and abs(rates[-1] - 2 ** -8) < 1e-9


# -- the walk over selected pages -------------------------------------------------

def _arena_and_tables(rng, every_page: bool):
    s, hkv, g, d, page, pages, layers, width = 3, 2, 2, 16, 8, 20, 2, 6
    arena = jnp.asarray(rng.normal(size=(pages + 1, layers * hkv, page,
                                         2 * d)), jnp.float32)
    q = jnp.asarray(rng.normal(size=(s, hkv * g, d)), jnp.float32)
    pos = np.array([37, 8, 47])
    bt = rng.permutation(pages)[:s * 6].reshape(s, 6).astype(np.int32)
    tables = np.zeros((s, hkv, width), np.int32)
    counts = np.zeros((s, hkv), np.int32)
    logical = {}
    for i in range(s):
        live = pos[i] // page + 1
        for h in range(hkv):
            others = np.arange(live - 1) if every_page else rng.permutation(
                live - 1)[:rng.integers(0, live)]
            sel = np.sort(np.concatenate([others, [live - 1]])).astype(int)
            logical[i, h] = sel
            tables[i, h, :len(sel)] = bt[i, sel]
            counts[i, h] = len(sel)
    return arena, q, pos, bt, tables, counts, logical, (hkv, g, d, page)


def _gathered(arena, q, pos, bt, logical, layer, dims):
    hkv, g, d, page = dims
    out = np.zeros(q.shape, np.float32)
    arena, q = np.asarray(arena), np.asarray(q)
    for (i, h), sel in logical.items():
        rows = np.concatenate([arena[bt[i, b], layer * hkv + h] for b in sel])
        at = np.concatenate([b * page + np.arange(page) for b in sel])
        k, v = rows[at <= pos[i], :d], rows[at <= pos[i], d:]
        for j in range(g):
            sc = (q[i, h * g + j] @ k.T) * d ** -0.5
            p = np.exp(sc - sc.max())
            out[i, h * g + j] = (p / p.sum()) @ v
    return out


@pytest.mark.parametrize("layer", [0, 1])
def test_selected_walk_reads_its_pages_and_no_others(layer):
    rng = np.random.default_rng(layer)
    arena, q, pos, bt, tables, counts, logical, dims = _arena_and_tables(
        rng, every_page=False)
    got = paged_attention(
        q, arena, None, jnp.asarray(bt), jnp.asarray(pos), layer=layer,
        scale=dims[2] ** -0.5, interpret=True,
        selected=(jnp.asarray(tables), jnp.asarray(counts)))
    # online softmax over pages against one softmax over the rows
    np.testing.assert_allclose(
        np.asarray(got), _gathered(arena, q, pos, bt, logical, layer, dims),
        atol=2e-6)


def test_every_page_selected_is_the_whole_table_walk():
    """The same rows through the kernel of the other families: a 5-D arena
    ``[P+1, L, page, Hkv, 2 D]`` walked by the block table."""
    rng = np.random.default_rng(9)
    arena, q, pos, bt, tables, counts, _, dims = _arena_and_tables(
        rng, every_page=True)
    hkv, _, d, _ = dims
    got = paged_attention(
        q, arena, None, jnp.asarray(bt), jnp.asarray(pos), layer=1,
        scale=d ** -0.5, interpret=True,
        selected=(jnp.asarray(tables), jnp.asarray(counts)))
    pages, _, page, _ = arena.shape
    whole = jnp.moveaxis(arena.reshape(pages, 2, hkv, page, 2 * d), 2, 3)
    want = paged_attention(q, whole, None, jnp.asarray(bt), jnp.asarray(pos),
                           layer=1, scale=d ** -0.5, interpret=True)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), atol=2e-6)


def test_a_selected_walk_refuses_an_arena_it_cannot_read():
    q = jnp.zeros((1, 4, 16))
    sel = (jnp.zeros((1, 2, 2), jnp.int32), jnp.ones((1, 2), jnp.int32))
    five_d = jnp.zeros((3, 1, 8, 2, 32))
    with pytest.raises(ValueError, match="head-major"):
        paged_attention(q, five_d, None, jnp.zeros((1, 2), jnp.int32),
                        jnp.zeros((1,), jnp.int32), selected=sel)
