"""Flash attention is load-bearing (round-5): MultiHeadAttention routes
eligible calls to the Pallas kernel, GPT uses it through the CAUSAL_MASK
sentinel, and the two long-context mechanisms (flash kernel, ring
attention SP) agree numerically. Kernel numerics themselves are pinned in
test_flash_attention.py; this file pins the WIRING."""
import numpy as np
import pytest

import paddle_tpu as paddle
import paddle_tpu.nn as nn
from paddle_tpu.nn.transformer import CAUSAL_MASK, FLASH_CROSSOVER


def _mha(attn_impl, dropout=0.0, need_weights=False):
    paddle.seed(11)
    return nn.MultiHeadAttention(32, 4, dropout=dropout,
                                 need_weights=need_weights,
                                 attn_impl=attn_impl)


def _x(b=2, s=24, e=32, seed=0):
    rng = np.random.RandomState(seed)
    return paddle.to_tensor(rng.randn(b, s, e).astype(np.float32) * 0.3)


class TestMhaRouting:
    def test_flash_forced_matches_dense(self):
        x = _x()
        dense = _mha("dense")
        flash = _mha("flash")
        flash.set_state_dict(dense.state_dict())
        np.testing.assert_allclose(flash(x).numpy(), dense(x).numpy(),
                                   rtol=1e-4, atol=1e-5)

    def test_flash_causal_sentinel_matches_dense_triu(self):
        x = _x(seed=1)
        dense = _mha("dense")
        flash = _mha("flash")
        flash.set_state_dict(dense.state_dict())
        np.testing.assert_allclose(
            flash(x, attn_mask=CAUSAL_MASK).numpy(),
            dense(x, attn_mask=CAUSAL_MASK).numpy(),
            rtol=1e-4, atol=1e-5)

    def test_auto_selects_by_crossover(self):
        m = _mha("auto")
        assert not m._flash_eligible(None, None, FLASH_CROSSOVER - 1)
        assert m._flash_eligible(None, None, FLASH_CROSSOVER)
        assert m._flash_eligible(CAUSAL_MASK, None, FLASH_CROSSOVER)

    def test_ineligible_calls_stay_dense(self):
        long = FLASH_CROSSOVER + 64
        # explicit additive mask -> dense
        assert not _mha("flash")._flash_eligible(
            paddle.to_tensor(np.zeros((4, 4), np.float32)), None, long)
        # attention dropout in training mode -> dense
        m = _mha("flash", dropout=0.1)
        m.train()
        assert not m._flash_eligible(None, None, long)
        m.eval()
        assert m._flash_eligible(None, None, long)
        # need_weights (prob matrix must materialise) -> dense
        assert not _mha("flash", need_weights=True)._flash_eligible(
            None, None, long)
        # incremental decode cache -> dense
        m2 = _mha("flash")
        cache = m2.gen_cache(_x())
        assert not m2._flash_eligible(None, cache, long)

    def test_grad_flash_matches_dense(self):
        xd, xf = _x(seed=2), _x(seed=2)
        xd.stop_gradient = False
        xf.stop_gradient = False
        dense = _mha("dense")
        flash = _mha("flash")
        flash.set_state_dict(dense.state_dict())
        dense(xd, attn_mask=CAUSAL_MASK).sum().backward()
        flash(xf, attn_mask=CAUSAL_MASK).sum().backward()
        np.testing.assert_allclose(xf.grad.numpy(), xd.grad.numpy(),
                                   rtol=1e-3, atol=1e-5)


class TestGptFlash:
    def _cfg(self, attn_impl):
        from paddle_tpu.models import GPTConfig
        return GPTConfig(vocab_size=128, hidden_size=32, num_layers=2,
                         num_heads=4, max_position_embeddings=64,
                         hidden_dropout_prob=0.0,
                         attention_dropout_prob=0.0, attn_impl=attn_impl)

    def test_gpt_flash_equals_dense(self):
        from paddle_tpu.models import GPTForCausalLM
        paddle.seed(5)
        dense = GPTForCausalLM(self._cfg("dense"))
        paddle.seed(5)
        flash = GPTForCausalLM(self._cfg("flash"))
        flash.set_state_dict(dense.state_dict())
        dense.eval()
        flash.eval()
        ids = paddle.to_tensor(
            np.random.RandomState(0).randint(0, 128, (2, 24)).astype(np.int32))
        np.testing.assert_allclose(flash(ids).numpy(), dense(ids).numpy(),
                                   rtol=2e-3, atol=2e-4)

    def test_gpt_flash_trains(self):
        from paddle_tpu.models import GPTForCausalLM, GPTPretrainingCriterion
        import paddle_tpu.optimizer as optim
        paddle.seed(6)
        net = GPTForCausalLM(self._cfg("flash"))
        m = paddle.Model(net)
        m.prepare(optim.AdamW(learning_rate=1e-3,
                              parameters=net.parameters()),
                  GPTPretrainingCriterion())
        ids = np.random.RandomState(1).randint(0, 128, (2, 24))
        losses = [m.train_batch([paddle.to_tensor(ids.astype(np.int32))],
                                [paddle.to_tensor(ids.astype(np.int64))])[0]
                  for _ in range(6)]
        assert np.isfinite(losses).all()
        assert losses[-1] < losses[0]


class TestFlashRingComposition:
    def test_flash_single_chip_equals_ring_sharded(self):
        """The two long-context mechanisms must agree: full-sequence flash
        attention on one device == ring attention with the sequence dim
        sharded over an sp mesh (both causal)."""
        import jax
        import jax.numpy as jnp
        import paddle_tpu.distributed as dist
        from paddle_tpu.distributed.fleet.sequence_parallel import (
            ring_attention)
        from paddle_tpu.ops.pallas_attention import flash_attention

        rng = np.random.RandomState(3)
        B, H, S, D = 1, 2, 64, 16
        q = rng.randn(B, S, H, D).astype(np.float32) * 0.4
        k = rng.randn(B, S, H, D).astype(np.float32) * 0.4
        v = rng.randn(B, S, H, D).astype(np.float32)

        out_flash, _ = flash_attention(paddle.to_tensor(q),
                                       paddle.to_tensor(k),
                                       paddle.to_tensor(v), causal=True)

        mesh = dist.build_mesh({"sp": 8})
        dist.set_mesh(mesh)
        try:
            bhsd = lambda a: jnp.moveaxis(jnp.asarray(a), 2, 1)  # BSHD->BHSD
            out_ring = ring_attention(bhsd(q), bhsd(k), bhsd(v),
                                      mesh=mesh, axis="sp", causal=True)
            out_ring = np.moveaxis(np.asarray(out_ring), 1, 2)
        finally:
            dist.set_mesh(None)
        np.testing.assert_allclose(out_flash.numpy(), out_ring,
                                   rtol=1e-4, atol=1e-5)


class TestRingFlashComposition:
    """ring_flash_attention: the Pallas kernel as the per-chunk compute
    INSIDE the sequence-parallel ring (lse-merge across chunks) — the
    full composition, not just the equivalence pin above."""

    def _qkv(self, B, H, T, D, seed=0):
        import jax.numpy as jnp
        rng = np.random.RandomState(seed)
        return (jnp.asarray(rng.randn(B, H, T, D), jnp.float32) * 0.4,
                jnp.asarray(rng.randn(B, H, T, D), jnp.float32) * 0.4,
                jnp.asarray(rng.randn(B, H, T, D), jnp.float32))

    @pytest.mark.parametrize("causal", [False, True])
    def test_matches_dense_ring(self, causal):
        import paddle_tpu.distributed as dist
        from paddle_tpu.distributed.fleet.sequence_parallel import (
            ring_attention, ring_flash_attention)
        mesh = dist.build_mesh({"sp": 8})
        dist.set_mesh(mesh)
        try:
            q, k, v = self._qkv(1, 2, 128, 16)
            ref = np.asarray(ring_attention(q, k, v, mesh=mesh,
                                            causal=causal))
            got = np.asarray(ring_flash_attention(q, k, v, mesh=mesh,
                                                  causal=causal))
            np.testing.assert_allclose(got, ref, rtol=1e-5, atol=1e-6)
        finally:
            dist.set_mesh(None)

    def test_under_jit_with_dp(self):
        import jax
        import paddle_tpu.distributed as dist
        from paddle_tpu.distributed.fleet.sequence_parallel import (
            ring_attention, ring_flash_attention)
        mesh = dist.build_mesh({"dp": 2, "sp": 4})
        dist.set_mesh(mesh)
        try:
            q, k, v = self._qkv(2, 2, 64, 16, seed=1)

            @jax.jit
            def f(q, k, v):
                return ring_flash_attention(q, k, v, mesh=mesh,
                                            causal=True,
                                            batch_axes="dp")
            got = np.asarray(f(q, k, v))
            ref = np.asarray(ring_attention(q, k, v, mesh=mesh,
                                            causal=True,
                                            batch_axes="dp"))
            np.testing.assert_allclose(got, ref, rtol=1e-5, atol=1e-6)
        finally:
            dist.set_mesh(None)

    def test_shard_size_constraint(self):
        import paddle_tpu.distributed as dist
        from paddle_tpu.distributed.fleet.sequence_parallel import (
            ring_flash_attention)
        mesh = dist.build_mesh({"sp": 8})
        dist.set_mesh(mesh)
        try:
            q, k, v = self._qkv(1, 1, 40, 16)   # Tl=5: not 16-multiple
            with pytest.raises(Exception, match="multiple of 16"):
                np.asarray(ring_flash_attention(q, k, v, mesh=mesh))
        finally:
            dist.set_mesh(None)


def test_ring_attention_wrapper_use_flash():
    import jax.numpy as jnp
    import paddle_tpu.distributed as dist
    from paddle_tpu.distributed.fleet.sequence_parallel import RingAttention
    mesh = dist.build_mesh({"sp": 8})
    dist.set_mesh(mesh)
    try:
        rng = np.random.RandomState(4)
        q = jnp.asarray(rng.randn(1, 2, 128, 16), jnp.float32) * 0.4
        dense = RingAttention(causal=True)(
            paddle.to_tensor(q), paddle.to_tensor(q), paddle.to_tensor(q))
        flash = RingAttention(causal=True, use_flash=True)(
            paddle.to_tensor(q), paddle.to_tensor(q), paddle.to_tensor(q))
        np.testing.assert_allclose(flash.numpy(), dense.numpy(),
                                   rtol=1e-5, atol=1e-6)
    finally:
        dist.set_mesh(None)


def test_ring_flash_grad_through_wrapper():
    """RingAttention(use_flash=True) is trainable: backprop through the
    op-funnel tape reaches the ring-flash custom_vjp backward and matches
    the dense-ring path's gradients (tests/test_ring_flash_backward.py
    covers the raw-jax surface exhaustively)."""
    import jax.numpy as jnp
    import paddle_tpu.distributed as dist
    from paddle_tpu.distributed.fleet.sequence_parallel import RingAttention
    mesh = dist.build_mesh({"sp": 8})
    dist.set_mesh(mesh)
    try:
        rng = np.random.RandomState(5)
        q_np = rng.randn(1, 2, 128, 16).astype(np.float32) * 0.3

        def grads(use_flash):
            q = paddle.to_tensor(q_np.copy(), stop_gradient=False)
            out = RingAttention(causal=True, use_flash=use_flash)(q, q, q)
            out.sum().backward()
            return q.grad.numpy()

        gd, gf = grads(False), grads(True)
        assert np.all(np.isfinite(gf))
        assert np.any(gf != 0.0)
        np.testing.assert_allclose(gf, gd, rtol=2e-4, atol=2e-5)
    finally:
        dist.set_mesh(None)


def test_gpt_generate_greedy_and_sampling():
    """GPTForCausalLM.generate (PaddleNLP GenerationMixin capability):
    greedy is deterministic and equals stepwise argmax; sampling with
    top_k stays in the top-k support."""
    from paddle_tpu.models import GPTConfig, GPTForCausalLM
    import jax.numpy as jnp
    paddle.seed(0)
    cfg = GPTConfig(vocab_size=32, hidden_size=16, num_layers=1,
                    num_heads=2, max_position_embeddings=64,
                    hidden_dropout_prob=0.0, attention_dropout_prob=0.0)
    net = GPTForCausalLM(cfg)
    net.eval()
    ids = paddle.to_tensor(np.array([[1, 2, 3]], np.int32))
    out = net.generate(ids, max_length=4)
    assert tuple(out.shape) == (1, 7)
    # greedy equals manual stepwise argmax
    cur = ids.numpy()
    for _ in range(4):
        logits = net(paddle.to_tensor(cur.astype(np.int32))).numpy()
        nxt = logits[:, -1].argmax(-1).astype(np.int32)
        cur = np.concatenate([cur, nxt[:, None]], axis=1)
    np.testing.assert_array_equal(out.numpy(), cur)
    paddle.seed(3)
    s = net.generate(ids, max_length=4, decode_strategy="sampling",
                     top_k=5)
    assert tuple(s.shape) == (1, 7)
    # every sampled token lies in the stepwise top-5 of the true logits
    sn = s.numpy()
    for t in range(3, 7):
        logits = net(paddle.to_tensor(sn[:, :t].astype(np.int32))).numpy()
        top5 = np.argsort(-logits[0, -1])[:5]
        assert sn[0, t] in top5, (t, sn[0, t], top5)
    with pytest.raises(ValueError, match="decode_strategy"):
        net.generate(ids, max_length=2, decode_strategy="beam")


def test_gpt_generate_per_row_eos_freeze():
    from paddle_tpu.models import GPTConfig, GPTForCausalLM
    paddle.seed(1)
    cfg = GPTConfig(vocab_size=16, hidden_size=16, num_layers=1,
                    num_heads=2, max_position_embeddings=64,
                    hidden_dropout_prob=0.0, attention_dropout_prob=0.0)
    net = GPTForCausalLM(cfg)
    net.eval()
    ids = paddle.to_tensor(np.array([[1, 2], [3, 4]], np.int32))
    # find a token some row emits greedily, then use it as eos
    first = net.generate(ids, max_length=1).numpy()[:, -1]
    eos = int(first[0])
    out = net.generate(ids, max_length=6, eos_token_id=eos).numpy()
    # row 0 hit eos at step 1: every later token must stay eos
    row0 = out[0, 2:]
    hit = np.where(row0 == eos)[0]
    assert hit.size and (row0[hit[0]:] == eos).all()


def test_gpt_generate_kv_cache_equals_recompute():
    """use_cache=True (incremental KV decoding through the MHA cache +
    position offsets) must be token-identical to full-prefix recompute."""
    from paddle_tpu.models import GPTConfig, GPTForCausalLM
    paddle.seed(2)
    cfg = GPTConfig(vocab_size=32, hidden_size=16, num_layers=2,
                    num_heads=2, max_position_embeddings=64,
                    hidden_dropout_prob=0.0, attention_dropout_prob=0.0)
    net = GPTForCausalLM(cfg)
    net.eval()
    ids = paddle.to_tensor(np.array([[1, 2, 3], [4, 5, 6]], np.int32))
    slow = net.generate(ids, max_length=6, use_cache=False).numpy()
    fast = net.generate(ids, max_length=6, use_cache=True).numpy()
    np.testing.assert_array_equal(slow, fast)
    # cached forward returns (logits, new_cache) and grows the cache
    cache = net.gpt.gen_cache(ids)
    logits, cache = net(ids, cache=cache)
    assert tuple(logits.shape) == (2, 3, 32)
    assert int(cache[0].k.shape[2]) == 3
    logits2, cache = net(paddle.to_tensor(
        np.array([[7], [8]], np.int32)), cache=cache)
    assert tuple(logits2.shape) == (2, 1, 32)
    assert int(cache[0].k.shape[2]) == 4


@pytest.mark.parametrize("layers", [1, 2])
@pytest.mark.parametrize("causal", [True, False], ids=["causal", "full"])
def test_gpt_recompute_equals_plain_exactly(causal, layers):
    """Per-block ``fleet.utils.recompute`` changes what a compiled train
    step keeps, never what it computes: loss and every parameter's
    gradient of a float32 GPT on the flash path are the plain step's, bit
    for bit. The checkpoint keeps the kernel's ``out`` and ``lse``, and the
    kept ``out`` is the value a second launch would have made. Evaluated
    operation by operation (``jax.disable_jit``), so ``np.array_equal``:
    compiled as one program the CPU fuses the two steps differently and a
    last digit of a sum may round the other way."""
    import jax
    from paddle_tpu.distributed.fleet.utils import recompute
    from paddle_tpu.jit.functionalize import build_pure
    from paddle_tpu.models import (GPTConfig, GPTForCausalLM,
                                   GPTPretrainingCriterion)
    paddle.seed(4)
    net = GPTForCausalLM(GPTConfig(
        vocab_size=64, hidden_size=32, num_layers=layers, num_heads=2,
        max_position_embeddings=128, hidden_dropout_prob=0.0,
        attention_dropout_prob=0.0, attn_impl="flash"))
    if not causal:                          # the same stack, no mask
        net.gpt.decoder.forward = (
            lambda h, src_mask=None, __f=net.gpt.decoder.forward:
            __f(h, src_mask=None))
    names, params = zip(*net.named_parameters())
    raws = [p._data for p in params]
    ids = np.random.RandomState(0).randint(0, 64, (2, 128)).astype("int64")
    pure, _ = build_pure(
        lambda x: GPTPretrainingCriterion()(net(x), x), list(params))

    def loss_and_grads():
        with jax.disable_jit():
            return jax.value_and_grad(lambda rs: pure(
                rs, [ids], jax.random.PRNGKey(0), None)[0])(raws)
    plain_loss, plain = loss_and_grads()
    for blk in net.gpt.decoder.layers:
        blk.forward = (lambda *a, __f=blk.forward, **k:
                       recompute(__f, *a, **k))
    loss, grads = loss_and_grads()
    assert np.array_equal(np.asarray(loss), np.asarray(plain_loss))
    assert float(plain_loss) > 0 and len(grads) == len(names)
    for name, g, want in zip(names, grads, plain):
        assert np.abs(np.asarray(want)).max() > 0 or "k_proj.bias" in name
        assert np.array_equal(np.asarray(g), np.asarray(want)), name
