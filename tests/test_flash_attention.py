"""Flash-attention Pallas kernel (ops/pallas_attention.py): exact
equivalence with dense attention — forward and all three gradients,
causal and full, including non-block-multiple sequence lengths (tail
padding) and cross-attention (kv length != q length). Runs in interpret
mode on CPU; the TPU-compiled path is numerics-checked by the bench
probes (docs/perf_notes.md round 4)."""
import numpy as np
import pytest

import jax
import jax.numpy as jnp

import paddle_tpu as paddle
import paddle_tpu.nn.functional as F


def _dense(q, k, v, causal, scale):
    qd = jnp.moveaxis(q, 2, 1)
    kd = jnp.moveaxis(k, 2, 1)
    vd = jnp.moveaxis(v, 2, 1)
    s = jnp.einsum("bhqd,bhkd->bhqk", qd, kd) * scale
    if causal:
        Sq, Sk = s.shape[-2], s.shape[-1]
        mask = jnp.arange(Sq)[:, None] >= jnp.arange(Sk)[None, :]
        s = jnp.where(mask, s, -1e30)
    p = jax.nn.softmax(s, axis=-1)
    return jnp.moveaxis(jnp.einsum("bhqk,bhkd->bhqd", p, vd), 1, 2)


@pytest.mark.parametrize("causal", [False, True])
@pytest.mark.parametrize("S", [128, 200, 100])
def test_forward_matches_dense(causal, S):
    rng = np.random.RandomState(0)
    B, H, D = 2, 4, 64
    q = rng.randn(B, S, H, D).astype(np.float32) * 0.5
    k = rng.randn(B, S, H, D).astype(np.float32) * 0.5
    v = rng.randn(B, S, H, D).astype(np.float32)
    out, _ = F.flash_attention(paddle.to_tensor(q), paddle.to_tensor(k),
                               paddle.to_tensor(v), causal=causal)
    out = out.numpy()
    ref = np.asarray(_dense(q, k, v, causal, 1 / np.sqrt(D)))
    np.testing.assert_allclose(out, ref, rtol=1e-4, atol=2e-5)


@pytest.mark.parametrize("causal", [False, True])
def test_gradients_match_dense(causal):
    rng = np.random.RandomState(1)
    B, S, H, D = 1, 96, 2, 32
    q = rng.randn(B, S, H, D).astype(np.float32) * 0.5
    k = rng.randn(B, S, H, D).astype(np.float32) * 0.5
    v = rng.randn(B, S, H, D).astype(np.float32)
    qt, kt, vt = map(paddle.to_tensor, (q, k, v))
    for t in (qt, kt, vt):
        t.stop_gradient = False
    out, _ = F.flash_attention(qt, kt, vt, causal=causal)
    (out * out).sum().backward()

    def loss(q, k, v):
        o = _dense(q, k, v, causal, 1 / np.sqrt(D))
        return jnp.sum(o * o)
    gq, gk, gv = jax.grad(loss, (0, 1, 2))(q, k, v)
    for got, want in [(qt.grad, gq), (kt.grad, gk), (vt.grad, gv)]:
        got, want = np.asarray(got.numpy()), np.asarray(want)
        rel = np.abs(got - want).max() / (np.abs(want).max() + 1e-9)
        assert rel < 1e-4, rel


def test_cross_attention_kv_length():
    rng = np.random.RandomState(2)
    B, Sq, Skv, H, D = 2, 64, 160, 2, 32
    q = rng.randn(B, Sq, H, D).astype(np.float32)
    k = rng.randn(B, Skv, H, D).astype(np.float32)
    v = rng.randn(B, Skv, H, D).astype(np.float32)
    out, _ = F.flash_attention(paddle.to_tensor(q), paddle.to_tensor(k),
                               paddle.to_tensor(v))
    out = out.numpy()
    ref = np.asarray(_dense(q, k, v, False, 1 / np.sqrt(D)))
    np.testing.assert_allclose(out, ref, rtol=1e-4, atol=2e-5)


def test_dropout_rejected_and_scale():
    rng = np.random.RandomState(3)
    x = paddle.to_tensor(rng.randn(1, 32, 2, 16).astype(np.float32))
    with pytest.raises(ValueError, match="dropout"):
        F.flash_attention(x, x, x, dropout=0.1)
    with pytest.raises(ValueError, match="return_softmax"):
        F.flash_attention(x, x, x, return_softmax=True)
    # custom scale honored
    out1, _ = F.flash_attention(x, x, x, scale=0.5)
    out1 = out1.numpy()
    ref = np.asarray(_dense(x.numpy(), x.numpy(), x.numpy(), False, 0.5))
    np.testing.assert_allclose(out1, ref, rtol=1e-4, atol=2e-5)


@pytest.mark.parametrize("bq,bk", [(32, 48), (48, 32), (16, 128)])
def test_block_size_boundaries_causal(bq, bk):
    """The causal early-exit arithmetic (n_k ceil and the dkv start
    block) under block_q != block_k — fwd and grads."""
    rng = np.random.RandomState(4)
    B, S, H, D = 1, 160, 2, 32
    q = rng.randn(B, S, H, D).astype(np.float32) * 0.5
    k = rng.randn(B, S, H, D).astype(np.float32) * 0.5
    v = rng.randn(B, S, H, D).astype(np.float32)
    qt, kt, vt = map(paddle.to_tensor, (q, k, v))
    for t in (qt, kt, vt):
        t.stop_gradient = False
    out, _ = F.flash_attention(qt, kt, vt, causal=True, block_q=bq,
                               block_k=bk)
    ref = np.asarray(_dense(q, k, v, True, 1 / np.sqrt(D)))
    np.testing.assert_allclose(out.numpy(), ref, rtol=1e-4, atol=2e-5)
    (out * out).sum().backward()

    def loss(q, k, v):
        o = _dense(q, k, v, True, 1 / np.sqrt(D))
        return jnp.sum(o * o)
    gq, gk, gv = jax.grad(loss, (0, 1, 2))(q, k, v)
    for got, want in [(qt.grad, gq), (kt.grad, gk), (vt.grad, gv)]:
        got, want = np.asarray(got.numpy()), np.asarray(want)
        rel = np.abs(got - want).max() / (np.abs(want).max() + 1e-9)
        assert rel < 1e-4, rel


# -- the dots follow the input's dtype (PR 27) --------------------------------

#: stated tolerances per input dtype. float32: the file's own (above).
#: bfloat16: chip_smoke.py's — forward 2e-2 absolute, gradients 2^-6 of
#: the reference gradient's abs max (two bf16 ulps at its top).
FWD_TOL = {"float32": dict(rtol=1e-4, atol=2e-5),
           "bfloat16": dict(rtol=0.0, atol=2e-2)}
GRAD_RTOL = {"float32": 1e-4, "bfloat16": 2.0 ** -6}


@pytest.mark.parametrize("blocks", [(32, 64), (128, 128)],
                         ids=["b32x64", "b128x128"])
@pytest.mark.parametrize("S", [128, 200])
@pytest.mark.parametrize("causal", [False, True], ids=["full", "causal"])
@pytest.mark.parametrize("dtype", ["float32", "bfloat16"])
def test_matches_dense_float32_of_the_same_inputs(dtype, causal, S, blocks):
    """Forward and dQ/dK/dV against dense float32 attention of the SAME
    (already rounded) inputs: what the kernel's own arithmetic costs,
    not what the inputs' rounding does."""
    rng = np.random.RandomState(5)
    B, H, D = 1, 2, 64
    q, k, v, do = (jnp.asarray(rng.randn(B, S, H, D).astype(np.float32)
                               * s, dtype)
                   for s in (0.5, 0.5, 1.0, 1.0))
    scale = 1 / np.sqrt(D)

    def flash(a, b, c):
        out, _ = F.flash_attention(paddle.Tensor(a), paddle.Tensor(b),
                                   paddle.Tensor(c), causal=causal,
                                   block_q=blocks[0], block_k=blocks[1])
        return out._data

    def dense(a, b, c):
        return _dense(*(x.astype(jnp.float32) for x in (a, b, c)),
                      causal, scale)

    def with_grads(fn):
        def loss(a, b, c):
            o = fn(a, b, c)
            return jnp.sum(o.astype(jnp.float32)
                           * do.astype(jnp.float32)), o
        return jax.value_and_grad(loss, argnums=(0, 1, 2), has_aux=True)

    (_, o_k), g_k = with_grads(flash)(q, k, v)
    (_, o_r), g_r = with_grads(dense)(q, k, v)
    assert o_k.dtype == jnp.dtype(dtype)
    np.testing.assert_allclose(np.asarray(o_k.astype(jnp.float32)),
                               np.asarray(o_r), **FWD_TOL[dtype])
    for name, gk, gr in zip(("dQ", "dK", "dV"), g_k, g_r):
        assert gk.dtype == jnp.dtype(dtype), name
        gk, gr = (np.asarray(x.astype(jnp.float32)) for x in (gk, gr))
        err = np.abs(gk - gr).max()
        assert err <= GRAD_RTOL[dtype] * np.abs(gr).max(), (name, err)


def _kernel_dots(fn, *args):
    """[(lhs dtype, rhs dtype, result dtype, precision)] of every
    dot_general inside the Pallas kernels ``fn`` traces, and the text of
    those kernels' jaxprs."""
    dots, texts = [], []

    def walk(jaxpr, in_kernel):
        for eqn in jaxpr.eqns:
            if in_kernel and eqn.primitive.name == "dot_general":
                prec = eqn.params["precision"]
                dots.append((*(v.aval.dtype.name for v in eqn.invars),
                             eqn.outvars[0].aval.dtype.name,
                             None if prec is None else
                             tuple(str(p) for p in prec)))
            for val in eqn.params.values():
                sub = getattr(val, "jaxpr", val)
                if hasattr(sub, "eqns"):
                    kernel = in_kernel or eqn.primitive.name == "pallas_call"
                    if kernel and not in_kernel:
                        texts.append(str(sub))
                    walk(sub, kernel)
    walk(jax.make_jaxpr(fn)(*args).jaxpr, False)
    return dots, "\n".join(texts)


def _fwd_and_bwd(q, k, v, do):
    from paddle_tpu.ops import pallas_attention as fa
    out, lse = fa._fa_fwd_with_lse(q, k, v, True, 0.125, 64, 64, True, 128)
    return fa._fa_bwd_with_lse(q, k, v, do, out, lse, True, 0.125, 64, 64,
                               True, 128, grad_dtypes=(jnp.float32,) * 3)


def test_bfloat16_inputs_reach_every_dot_as_bfloat16():
    """All nine dots (2 forward, 3 dQ, 4 dK/dV) take bf16 operands and
    accumulate in float32 at Precision.DEFAULT: under the package-wide
    ``highest`` Mosaic refuses a bf16 contraction ("Bad lhs type", met
    when the kernels were compiled for a described v5e, PR 27). The
    ring's float32 ``grad_dtypes`` are still honoured."""
    x = jnp.zeros((2, 128, 64), jnp.bfloat16)
    dots, _ = _kernel_dots(_fwd_and_bwd, x, x, x, x)
    assert len(dots) == 9
    for lhs, rhs, out, prec in dots:
        assert (lhs, rhs, out) == ("bfloat16", "bfloat16", "float32")
        assert prec is None or set(prec) == {"DEFAULT"}, prec
    grads = jax.eval_shape(_fwd_and_bwd, x, x, x, x)
    assert [g.dtype for g in grads] == [jnp.float32] * 3


@pytest.mark.parametrize("dtypes", [("float32",) * 4,
                                    ("bfloat16", "float32", "bfloat16",
                                     "bfloat16")],
                         ids=["float32", "mixed"])
def test_float32_kernels_are_what_they_were(dtypes):
    """float32 (or mixed) inputs: every block is upcast and every dot is
    float32 x float32 at the precision the package sets, as before PR 27
    (whose parent the outputs and gradients match bitwise, PERF.md); a
    float32 kernel holds no bfloat16 value at all."""
    q, k, v, do = (jnp.zeros((2, 128, 64), dt) for dt in dtypes)
    dots, text = _kernel_dots(_fwd_and_bwd, q, k, v, do)
    assert len(dots) == 9
    for lhs, rhs, out, prec in dots:
        assert (lhs, rhs, out) == ("float32",) * 3
        assert set(prec) == {"HIGHEST"}, prec
    if set(dtypes) == {"float32"}:
        assert "bf16" not in text and "f32" in text


# -- where a tile's statistics lie (PR 37) ------------------------------------

@pytest.mark.parametrize("lengths", [(200, 200), (128, 320)],
                         ids=["S200-padded", "q128-kv320"])
@pytest.mark.parametrize("blocks", [(64, 128), (128, 64)],
                         ids=["b64x128", "b128x64"])
@pytest.mark.parametrize("causal", [False, True], ids=["full", "causal"])
@pytest.mark.parametrize("dtype", ["float32", "bfloat16"])
def test_the_tiles_orientation_against_dense_float32(dtype, causal, blocks,
                                                     lengths):
    """Forward and dQ/dK/dV against dense float32 of the same inputs where
    a tile is not square and the lengths differ or are padded: a swapped
    iota, a mask on the wrong axis of dK/dV's transposed tile, or a
    statistic of the other side's length shows here and nowhere else."""
    rng = np.random.RandomState(6)
    B, H, D = 1, 2, 64
    sq, skv = lengths
    q, do = (jnp.asarray(rng.randn(B, sq, H, D).astype(np.float32) * s,
                         dtype) for s in (0.5, 1.0))
    k, v = (jnp.asarray(rng.randn(B, skv, H, D).astype(np.float32) * s,
                        dtype) for s in (0.5, 1.0))
    scale = 1 / np.sqrt(D)

    def flash(a, b, c):
        out, _ = F.flash_attention(paddle.Tensor(a), paddle.Tensor(b),
                                   paddle.Tensor(c), causal=causal,
                                   block_q=blocks[0], block_k=blocks[1])
        return out._data

    def dense(a, b, c):
        return _dense(*(x.astype(jnp.float32) for x in (a, b, c)),
                      causal, scale)

    def with_grads(fn):
        def loss(a, b, c):
            o = fn(a, b, c)
            return jnp.sum(o.astype(jnp.float32)
                           * do.astype(jnp.float32)), o
        return jax.value_and_grad(loss, argnums=(0, 1, 2), has_aux=True)

    (_, o_k), g_k = with_grads(flash)(q, k, v)
    (_, o_r), g_r = with_grads(dense)(q, k, v)
    np.testing.assert_allclose(np.asarray(o_k.astype(jnp.float32)),
                               np.asarray(o_r), **FWD_TOL[dtype])
    for name, gk, gr in zip(("dQ", "dK", "dV"), g_k, g_r):
        assert gk.shape == gr.shape and gk.dtype == jnp.dtype(dtype), name
        gk, gr = (np.asarray(x.astype(jnp.float32)) for x in (gk, gr))
        err = np.abs(gk - gr).max()
        assert err <= GRAD_RTOL[dtype] * np.abs(gr).max(), (name, err)


def _eqns(jaxpr):
    """Every equation of ``jaxpr`` and of the jaxprs its equations hold."""
    for eqn in jaxpr.eqns:
        yield eqn
        for val in eqn.params.values():
            sub = getattr(val, "jaxpr", val)
            if hasattr(sub, "eqns"):
                yield from _eqns(sub)


def _kernels(fn, *args):
    """{kernel name: its jaxpr} of the Pallas calls ``fn`` traces."""
    return {str(e.params["name"]): e.params["jaxpr"]
            for e in _eqns(jax.make_jaxpr(fn)(*args).jaxpr)
            if e.primitive.name == "pallas_call"}


@pytest.mark.parametrize("dtype", ["float32", "bfloat16"])
def test_no_loop_carries_a_statistic_and_dkv_turns_no_tile(dtype):
    """What PR 37 changed, as the jaxpr shows it. (1) No loop inside a
    flash kernel carries a float value: the accumulators and the running
    max and sum live in VMEM scratch (a carried value is copied between
    spill slots at both ends of every trip), and so no rank-1 float32
    statistic is carried either. (2) dK/dV computes its score tile
    transposed: none of its dots contracts dimension 0 of a tile-shaped
    operand, which is what put a transpose of P and of dS before a dot."""
    from paddle_tpu.ops import pallas_attention as fa
    bq, bk = 64, 128

    def fwd_and_bwd(q, k, v, do):
        out, lse = fa._fa_fwd_with_lse(q, k, v, True, 0.125, bq, bk, True,
                                       250)
        return fa._fa_bwd_with_lse(q, k, v, do, out, lse, True, 0.125, bq,
                                   bk, True, 250)
    x = jnp.zeros((2, 256, 32), dtype)        # head size unlike a block
    kernels = _kernels(fwd_and_bwd, x, x, x, x)
    assert sorted(kernels) == ["flash_bwd_dkv", "flash_bwd_dq", "flash_fwd"]
    for name, jaxpr in kernels.items():
        loops = [e for e in _eqns(jaxpr) if e.primitive.name == "while"]
        assert loops, name
        for loop in loops:
            carried = [(v.aval.shape, v.aval.dtype.name)
                       for v in loop.outvars]
            assert not [c for c in carried if "float" in c[1]], (name,
                                                                 carried)
    tiles = {(bq, bk), (bk, bq)}
    dots = [e for e in _eqns(kernels["flash_bwd_dkv"])
            if e.primitive.name == "dot_general"]
    assert len(dots) == 4
    for e in dots:
        (lhs_c, rhs_c), _ = e.params["dimension_numbers"]
        for operand, contract in zip(e.invars, (lhs_c, rhs_c)):
            if tuple(operand.aval.shape) in tiles:
                assert tuple(contract) == (1,), (operand.aval, contract)
    # two of the four take a tile, as their left operand, [bk, bq]
    assert sum(tuple(e.invars[0].aval.shape) == (bk, bq)
               for e in dots) == 2


# -- what recompute keeps of the kernel (PR 47) -------------------------------

def _recomputed_stack(attn_impl, layers, dtype):
    """(loss(raws, x), raws, x): ``layers`` encoder blocks, each through
    ``fleet.utils.recompute``, as a pure function of the parameters."""
    from paddle_tpu.distributed.fleet.utils import recompute
    from paddle_tpu.jit.functionalize import build_pure
    paddle.seed(3)
    blocks = [paddle.nn.TransformerEncoderLayer(
        32, 2, 64, dropout=0.0, normalize_before=True, attn_impl=attn_impl)
        for _ in range(layers)]
    params = [p for blk in blocks for p in blk.parameters()]

    def stack(h):
        for blk in blocks:
            h = recompute(blk.forward, h)
        return h
    pure, _ = build_pure(stack, params)

    def loss(raws, x):
        out, = pure(raws, [x], jax.random.PRNGKey(0), None)
        return out.astype(jnp.float32).sum()
    return (loss, [p._data.astype(dtype) for p in params],
            jnp.ones((2, 128, 32), dtype))


@pytest.mark.parametrize("layers", [1, 2])
@pytest.mark.parametrize("dtype", ["float32", "bfloat16"])
def test_recompute_runs_each_flash_kernel_once_a_layer(dtype, layers):
    """The gradient of a recomputed block launches ``flash_fwd`` once: the
    checkpoint's policy keeps the kernel's named ``out`` and ``lse``
    (``RESIDUAL_NAMES``), so the backward pass does not make them again.
    A bare ``jax.checkpoint`` holds two a layer. Counted on the live part
    of the jaxpr: ``recompute`` traces a block once more for the structure
    of its result, and nothing reads that trace."""
    from jax.interpreters import partial_eval as pe
    loss, raws, x = _recomputed_stack("flash", layers, dtype)
    jaxpr = jax.make_jaxpr(jax.value_and_grad(loss))(raws, x).jaxpr
    live, _ = pe.dce_jaxpr(jaxpr, [True] * len(jaxpr.outvars))
    calls = [str(e.params["name"]) for e in _eqns(live)
             if e.primitive.name == "pallas_call"]
    assert sorted(calls) == sorted(
        ["flash_fwd", "flash_bwd_dq", "flash_bwd_dkv"] * layers)


@pytest.mark.parametrize("attn_impl,kept", [
    ("dense", []), ("flash", ["f32[4,128,16]", "f32[4,1,128]"])],
    ids=["dense", "flash"])
def test_recompute_keeps_the_blocks_inputs_and_the_kernels_names(
        capsys, attn_impl, kept):
    """A block with no flash call inside holds no name, and its checkpoint
    saves what a bare one saved, the block's inputs; with the kernel inside
    it saves ``out`` [heads, rows, head size] and ``lse`` [heads, 1, rows]
    besides, and nothing else."""
    from jax.ad_checkpoint import print_saved_residuals
    loss, raws, x = _recomputed_stack(attn_impl, 1, "float32")
    print_saved_residuals(loss, raws, x)
    saved = capsys.readouterr().out.strip().splitlines()
    inputs = [line for line in saved if " from the argument " in line]
    assert len(inputs) >= len(raws)          # a bias may not be needed
    assert [line.split()[0] for line in saved
            if line not in inputs] == kept
