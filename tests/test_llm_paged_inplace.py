"""The paged KV arena is written where it lies (serving/llm/paged/).

Every paged program threads the whole ``[P+1, L, page, H, D]`` arena
through its layer loop as one value: rows scatter in by (page, layer,
offset), attention reads by (page, layer), the jitted getters donate
both arenas. These tests pin the three things that make the update in
place — no arena-shaped value but a scatter's, donation marked in the
lowered program, the donated arrays gone and the cache's live — and
that none of it moved a token. CPU only; nothing here is a timing.
"""
import re

import numpy as np
import pytest

import jax
import jax.numpy as jnp

import paddle_tpu as paddle
from paddle_tpu.core.monitor import StatRegistry
from paddle_tpu.models import GPTConfig, GPTForCausalLM
from paddle_tpu.ops.paged_attention import paged_attention
from paddle_tpu.serving.llm import LLMEngine, LLMEngineConfig
from paddle_tpu.serving.llm.decode import (GPTDecodeSpec, extract_gpt_params,
                                           pack_sampling, SamplingParams)
from paddle_tpu.serving.llm.paged import GPTPagedDecoder
from paddle_tpu.serving.llm.paged import decode as pdecode
from paddle_tpu.serving.llm.paged import pool as ppool
from paddle_tpu.serving.llm.paged import spec as pspec

# 10 pages (+ trash = 11: a size no other axis has), 2 layers, 4-token
# pages, 4 heads of 8: arena [11, 2, 4, 4, 8], one layer's view of it
# [11, 4, 4, 8]
SLOTS, PAGE, PP, PAGES, TOP_K = 2, 4, 4, 10, 4
MAX_SEQ = PAGE * PP


def _tiny_model(seed=0, layers=2):
    paddle.seed(seed)
    net = GPTForCausalLM(GPTConfig(
        vocab_size=64, hidden_size=32, num_layers=layers, num_heads=4,
        max_position_embeddings=128, hidden_dropout_prob=0.0,
        attention_dropout_prob=0.0))
    net.eval()
    return net


@pytest.fixture(scope="module")
def model():
    return _tiny_model()


@pytest.fixture(scope="module")
def draft_model():
    return _tiny_model(seed=1, layers=1)


def _arena(spec, kv_dtype=None):
    shape = (PAGES + 1, spec.num_layers, PAGE, spec.num_heads,
             spec.head_dim)
    if kv_dtype == "int8":
        return {"q": jnp.zeros(shape, jnp.int8),
                "s": jnp.zeros(shape[:3], jnp.float32)}
    return jnp.zeros(shape, jnp.float32)


def _slot_state():
    return {"tables": jnp.asarray([[0, 1, 2, 3], [4, 5, 6, 7]], jnp.int32),
            "lengths": jnp.asarray([5, 3], jnp.int32),
            "finished": jnp.zeros((SLOTS,), bool),
            "last": jnp.asarray([7, 9], jnp.int32),
            "key": jax.random.PRNGKey(0)}


def _program(name, kv_dtype, model, draft_model):
    """(raw builder, jitted getter, positional args, indices of kbuf and
    vbuf among them) of one paged program at the tiny geometry."""
    spec = GPTDecodeSpec.from_model(model)
    params = extract_gpt_params(model)
    st = _slot_state()
    kb, vb = _arena(spec, kv_dtype), _arena(spec, kv_dtype)
    samp = pack_sampling([SamplingParams()] * SLOTS)
    one = pack_sampling([SamplingParams()])
    slot0 = jnp.asarray([0], jnp.int32)
    tokens = jnp.ones((1, 8), jnp.int32)
    if name in ("decode_gather", "decode_kernel"):
        lane = name.split("_")[1]
        return (pdecode.build_paged_decode_step(spec, TOP_K, PAGE, lane),
                pdecode.get_paged_decode_step(spec, TOP_K, PAGE, lane),
                (params, kb, vb, st["tables"], st["lengths"],
                 st["finished"], st["last"], *samp, st["key"]), (1, 2))
    if name == "prefill":
        return (pdecode.build_paged_prefill_fn(spec, TOP_K, PAGE),
                pdecode.get_paged_prefill_fn(spec, TOP_K, PAGE),
                (params, tokens, jnp.asarray([6], jnp.int32), kb, vb,
                 st["tables"], st["lengths"], st["finished"], slot0, *one,
                 st["key"]), (3, 4))
    if name == "tail_prefill":
        return (pdecode.build_paged_tail_prefill_fn(spec, TOP_K, PAGE),
                pdecode.get_paged_tail_prefill_fn(spec, TOP_K, PAGE),
                (params, tokens, jnp.asarray([6], jnp.int32),
                 jnp.asarray([4], jnp.int32), kb, vb, st["tables"],
                 st["lengths"], st["finished"], slot0, *one, st["key"]),
                (4, 5))
    assert name == "spec_step"
    dspec = GPTDecodeSpec.from_model(draft_model)
    dbuf = jnp.zeros((SLOTS, dspec.num_layers, MAX_SEQ, dspec.num_heads,
                      dspec.head_dim), jnp.float32)
    return (pspec.build_paged_spec_decode_step(spec, dspec, 2, TOP_K, PAGE),
            pspec.get_paged_spec_decode_step(spec, dspec, 2, TOP_K, PAGE),
            (params, extract_gpt_params(draft_model), kb, vb, dbuf, dbuf,
             st["tables"], st["lengths"], st["finished"], st["last"],
             *samp, st["key"]), (2, 3))


PROGRAMS = [("decode_gather", None), ("decode_kernel", None),
            ("decode_gather", "int8"), ("prefill", None),
            ("prefill", "int8"), ("tail_prefill", None),
            ("spec_step", None)]
PROGRAM_IDS = [f"{n}-{d or 'dense'}" for n, d in PROGRAMS]


def _leaf_shapes(buf):
    return [tuple(x.shape) for x in jax.tree_util.tree_leaves(buf)]


def _equations(jaxpr):
    """Every equation of a jaxpr and of the jaxprs its equations carry
    (jitted helpers, the kernel's body)."""
    for eqn in jaxpr.eqns:
        yield eqn
        for val in eqn.params.values():
            for sub in (val if isinstance(val, (tuple, list)) else (val,)):
                inner = getattr(sub, "jaxpr", sub)
                if hasattr(inner, "eqns"):
                    yield from _equations(inner)


# -- (a) nothing arena-shaped but a scatter's result --------------------------

@pytest.mark.parametrize("name,kv_dtype", PROGRAMS, ids=PROGRAM_IDS)
def test_no_arena_shaped_value_but_a_scatter(name, kv_dtype, model,
                                             draft_model):
    raw, _, args, (ki, _) = _program(name, kv_dtype, model, draft_model)
    whole = set(_leaf_shapes(args[ki]))
    views = {s[:1] + s[2:] for s in whole}            # one layer cut out
    closed = jax.make_jaxpr(raw)(*args)
    made = {}
    for eqn in _equations(closed.jaxpr):
        for out in eqn.outvars:
            shape = tuple(getattr(out.aval, "shape", ()))
            if shape in whole or shape in views:
                made.setdefault(eqn.primitive.name, set()).add(shape)
    assert set(made) == {"scatter"}, made
    assert made["scatter"] == whole                   # every leaf written


# -- (b) the lowered programs donate both arenas to the matching outputs ------

_ALIASED = re.compile(
    r"%arg\d+: tensor<([^>]+)> \{[^}]*tf\.aliasing_output = (\d+)")


def _aliased_outputs(lowered):
    """{output index: tensor type} of the arguments the lowered module
    aliases to an output."""
    return {int(out): ty for ty, out in _ALIASED.findall(lowered.as_text())}


def _tensor_type(x):
    dt = {"float32": "f32", "int8": "i8"}[str(x.dtype)]
    return "x".join(map(str, x.shape)) + "x" + dt


@pytest.mark.parametrize("name,kv_dtype", PROGRAMS, ids=PROGRAM_IDS)
def test_getter_donates_both_arenas(name, kv_dtype, model, draft_model):
    _, jitted, args, (ki, vi) = _program(name, kv_dtype, model, draft_model)
    lowered = jitted.lower(*args)
    donated = [jax.tree_util.tree_map(lambda a: a.donated, info)
               for info in lowered.args_info[0]]
    for i, flags in enumerate(donated):
        want = i in (ki, vi)
        assert all(f == want for f in jax.tree_util.tree_leaves(flags)), \
            (i, flags)
    # kbuf's leaves are outputs 0.., vbuf's follow
    leaves = (jax.tree_util.tree_leaves(args[ki])
              + jax.tree_util.tree_leaves(args[vi]))
    assert _aliased_outputs(lowered) == {
        i: _tensor_type(x) for i, x in enumerate(leaves)}


@pytest.mark.parametrize("kv_dtype", [None, "int8"], ids=["dense", "int8"])
@pytest.mark.parametrize("which", ["copy_page", "write_page"])
def test_page_maintenance_donates_the_arena(which, kv_dtype, model):
    buf = _arena(GPTDecodeSpec.from_model(model), kv_dtype)
    dst = jnp.asarray(3, jnp.int32)
    if which == "copy_page":
        lowered = ppool._arena_copy_page.lower(buf, dst, dst)
    else:
        page = jax.tree_util.tree_map(lambda x: x[0], buf)
        lowered = ppool._arena_write_page.lower(buf, dst, page)
    leaves = jax.tree_util.tree_leaves(buf)
    assert _aliased_outputs(lowered) == {
        i: _tensor_type(x) for i, x in enumerate(leaves)}


# -- (c) the cache holds live arrays; the ones passed in are gone -------------

def _deleted(buf):
    return [x.is_deleted() for x in jax.tree_util.tree_leaves(buf)]


def _assert_swapped(kv, old_k, old_v):
    assert all(_deleted(old_k)) and all(_deleted(old_v))
    assert not any(_deleted(kv.k)) and not any(_deleted(kv.v))
    for leaf in jax.tree_util.tree_leaves((kv.k, kv.v)):
        np.asarray(leaf[0])                           # readable


@pytest.mark.parametrize("kv_dtype", ["float32", "int8"])
def test_decoder_calls_leave_the_cache_live(kv_dtype, model):
    dec = GPTPagedDecoder(model, max_top_k=TOP_K, kv_dtype=kv_dtype,
                          page_size=PAGE, num_pages=PAGES,
                          attn_impl="gather")
    kv = dec.new_kv(SLOTS, MAX_SEQ)
    params = dec.params()
    nbytes = kv.kv_bytes()
    one = pack_sampling([SamplingParams()])
    slot = kv.alloc()
    kv.ensure_pages(slot, 8)
    finished = jnp.zeros((SLOTS,), bool)

    old = kv.k, kv.v
    _, finished = dec.prefill(
        kv, params, jnp.ones((1, 8), jnp.int32), jnp.asarray([6], jnp.int32),
        jnp.asarray([slot], jnp.int32), finished, one, jax.random.PRNGKey(1))
    _assert_swapped(kv, *old)

    if kv_dtype != "int8":        # prefix reuse is gated off for int8 pages
        old = kv.k, kv.v
        _, finished = dec.tail_prefill(
            kv, params, jnp.ones((1, 4), jnp.int32),
            jnp.asarray([2], jnp.int32), jnp.asarray([4], jnp.int32),
            jnp.asarray([slot], jnp.int32), finished, one,
            jax.random.PRNGKey(2))
        _assert_swapped(kv, *old)

    old = kv.k, kv.v
    dec.decode_step(kv, params, finished, jnp.zeros((SLOTS,), jnp.int32),
                    pack_sampling([SamplingParams()] * SLOTS),
                    jax.random.PRNGKey(3))
    _assert_swapped(kv, *old)

    # copy-on-write split and migration import: one page each, in place
    src = kv.slot_page_ids(slot)[0]
    other = kv.alloc()
    old = kv.k, kv.v
    new_pid = kv.adopt_copied_page(other, src)
    _assert_swapped(kv, *old)
    k_pages, v_pages = kv.read_pages([src])
    tmap = jax.tree_util.tree_map
    old = kv.k, kv.v
    kv.write_page(new_pid, tmap(lambda x: x[0], k_pages),
                  tmap(lambda x: x[0], v_pages))
    _assert_swapped(kv, *old)
    for a, b in zip(jax.tree_util.tree_leaves(kv.read_pages([new_pid])),
                    jax.tree_util.tree_leaves((k_pages, v_pages))):
        assert (a == b).all()
    # byte counters never read a donated array
    assert kv.kv_bytes() == nbytes == sum(
        x.nbytes for x in jax.tree_util.tree_leaves((kv.k, kv.v)))


def _engine(model, **kw):
    cfg = dict(num_slots=4, max_seq=128, prefill_buckets=(8, 16, 40),
               warmup=True, seed=3)
    cfg.update(kw)
    return LLMEngine(model, LLMEngineConfig(**cfg), registry=StatRegistry())


def test_engine_warmup_leaves_the_cache_live(model):
    eng = _engine(model, kv_layout="paged", page_size=8, warmup=False,
                  prefix_cache=True)
    try:
        batcher = eng._batcher
        kv = batcher.kv

        def _warm():
            old = kv.k, kv.v
            batcher.warmup()
            return old
        old = eng._run_on_worker(_warm, timeout=300)
        _assert_swapped(kv, *old)
        assert eng.stats()["pages"]["free"] == kv.pool.num_pages
        out = eng.generate([3, 1, 4, 1, 5], max_new_tokens=4)
        assert len(out["tokens"]) == 4
        assert not any(_deleted(kv.k)) and not any(_deleted(kv.v))
    finally:
        eng.drain(timeout=120)


# -- (d) the kernel reads a layer out of the whole arena ----------------------

@pytest.mark.parametrize("block_h", [8, 16])
def test_kernel_on_whole_arena_equals_kernel_on_layer_view(block_h):
    rng = np.random.default_rng(11)
    S, L, H, D, page, pp = 3, 3, 16, 8, 4, 3
    n_pages = S * pp
    q = jnp.asarray(rng.standard_normal((S, H, D)), jnp.float32)
    ka = jnp.asarray(rng.standard_normal((n_pages + 1, L, page, H, D)),
                     jnp.float32)
    va = jnp.asarray(rng.standard_normal(ka.shape), jnp.float32)
    bt = jnp.asarray(rng.permutation(n_pages).reshape(S, pp), jnp.int32)
    pos = jnp.asarray([2, 7, 11], jnp.int32)
    for li in range(L):
        whole = paged_attention(q, ka, va, bt, pos, layer=li,
                                block_h=block_h, interpret=True)
        # the old call: the layer cut out first, as an arena of one layer
        view = paged_attention(q, ka[:, li][:, None], va[:, li][:, None],
                               bt, pos, block_h=block_h, interpret=True)
        assert (np.asarray(whole) == np.asarray(view)).all(), li
        kg = ppool.paged_gather_rows(ka, bt, li)
        vg = ppool.paged_gather_rows(va, bt, li)
        logits = jnp.einsum("shd,sthd->sht", q / np.sqrt(D), kg)
        mask = jnp.arange(pp * page)[None, None, :] <= pos[:, None, None]
        w = jax.nn.softmax(jnp.where(mask, logits, -1e30), axis=-1)
        ref = jnp.einsum("sht,sthd->shd", w, vg)
        np.testing.assert_allclose(np.asarray(whole), np.asarray(ref),
                                   rtol=2e-5, atol=2e-5)


def test_every_layer_runs_one_kernel():
    """The layer is an input of the kernel, not a constant of it: the
    layer loop's calls lower to one function that holds the one Mosaic
    kernel (traced and lowered once however many layers call it)."""
    q = jnp.zeros((2, 8, 128), jnp.float32)
    arena = jnp.zeros((5, 3, 16, 8, 128), jnp.float32)
    bt = jnp.zeros((2, 2), jnp.int32)
    pos = jnp.zeros((2,), jnp.int32)

    def layer_loop(a, k, v, t, p):
        for li in range(arena.shape[1]):
            a = a + paged_attention(a, k, v, t, p, layer=li,
                                    interpret=False)
        return a

    text = jax.jit(layer_loop).trace(q, arena, arena, bt, pos).lower(
        lowering_platforms=("tpu",)).as_text()
    assert text.count('kernel_name = "paged_attn"') == 1
    assert text.count("func.func private @_paged_attention") == 1
    assert len(re.findall(r"call @_paged_attention\b", text)) \
        == arena.shape[1]


# -- (e) 64 ticks through the engine, both paged lanes: the slot plane's tokens

@pytest.mark.parametrize("attn_impl", ["gather", "kernel"])
def test_64_tick_engine_run_matches_the_slot_engine(attn_impl, model):
    rng = np.random.default_rng(5)
    prompts = [list(rng.integers(0, 64, n)) for n in (5, 11, 20, 33)]

    def run(**kw):
        eng = _engine(model, **kw)
        try:
            futs = [eng.submit(p, max_new_tokens=64) for p in prompts]
            return [f.result(600)["tokens"] for f in futs]
        finally:
            eng.drain(timeout=120)

    slot = run()
    paged = run(kv_layout="paged", page_size=8, paged_attn_impl=attn_impl)
    assert all(len(t) == 64 for t in slot)
    assert paged == slot
