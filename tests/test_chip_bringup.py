"""What the chip machine needs from the program, checked on the CPU.

One process per chip (importing the package must not take the backend), a
compile cache that can be placed from outside, no fallback that hides the
device, and kernels whose committed TPU configs pass the JAX-side Mosaic
lowering (the three refusals the first chip run met — an illegal head
block, a batched M=1 dot, an unroll factor — were all raised there, before
any chip was needed, so ``lower(lowering_platforms=("tpu",))`` sees them).
"""
import json
import os
import re
import subprocess
import sys

import pytest

import jax
import jax.numpy as jnp

import paddle_tpu as paddle
from paddle_tpu.core import pallas_mode
from paddle_tpu.serving import cache as cache_mod

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
SMOKE = os.path.join(REPO, "chip_smoke.py")


def _run(cmd, cwd=REPO, timeout=600, **env):
    full = dict(os.environ, JAX_PLATFORMS="cpu",
                PYTHONPATH=REPO + os.pathsep + os.environ.get("PYTHONPATH", ""))
    full.update(env)
    return subprocess.run(cmd, cwd=cwd, env=full, capture_output=True,
                          text=True, timeout=timeout)


# -- one process per chip ------------------------------------------------------

def test_import_leaves_backend_uninitialised():
    proc = _run([sys.executable, "-c",
                 "import paddle_tpu, paddle_tpu.distributed.launch\n"
                 "from jax._src import xla_bridge\n"
                 "print(xla_bridge.backends_are_initialized())"])
    assert proc.returncode == 0, proc.stderr[-2000:]
    assert proc.stdout.strip().splitlines()[-1] == "False"


# -- chip_smoke.py -------------------------------------------------------------

def test_smoke_without_a_tpu_fails_and_prints_no_result():
    proc = _run([sys.executable, SMOKE])
    assert proc.returncode != 0
    assert "no TPU" in proc.stderr
    assert not any(l.startswith("{") for l in proc.stdout.splitlines())


def test_smoke_alone_in_a_directory_fails(tmp_path):
    (tmp_path / "chip_smoke.py").write_text(open(SMOKE).read())
    proc = _run([sys.executable, "chip_smoke.py"], cwd=tmp_path,
                PYTHONPATH="")
    assert proc.returncode != 0
    assert not any(l.startswith("{") for l in proc.stdout.splitlines())


@pytest.mark.slow
@pytest.mark.timeout_s(900)
def test_smoke_rehearsal_runs_both_phases(tmp_path):
    proc = _run([sys.executable, SMOKE, "--rehearse-on-cpu"], timeout=850,
                JAX_COMPILATION_CACHE_DIR=str(tmp_path / "jaxcache"))
    assert proc.returncode == 0, proc.stdout[-3000:] + proc.stderr[-3000:]
    assert "REHEARSAL" in proc.stdout
    assert "phase 1: train" in proc.stdout and "phase 2: serve" in proc.stdout
    last = json.loads(proc.stdout.strip().splitlines()[-1])
    assert last["ok"] is True and last["rehearsal"] is True
    assert last["device"]["platform"] == "cpu"
    # the placed cache took the executables; the checkout's did not
    assert os.listdir(tmp_path / "jaxcache")


# -- the compile cache can be placed from outside ------------------------------

@pytest.fixture()
def jax_cache_config():
    keys = ("jax_compilation_cache_dir",
            "jax_persistent_cache_min_compile_time_secs",
            "jax_persistent_cache_min_entry_size_bytes")
    saved = {k: getattr(jax.config, k) for k in keys}
    yield
    for k, v in saved.items():
        jax.config.update(k, v)


def test_cache_dir_set_outside_is_left_alone(monkeypatch, tmp_path,
                                             jax_cache_config):
    before = jax.config.jax_compilation_cache_dir
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path))
    assert cache_mod.place_jax_compilation_cache() == str(tmp_path)
    assert jax.config.jax_compilation_cache_dir == before


def test_cache_dir_unset_is_the_fixed_in_checkout_path(monkeypatch,
                                                       jax_cache_config):
    monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
    assert cache_mod.place_jax_compilation_cache() == \
        os.path.join(REPO, ".jax_cache")
    assert jax.config.jax_compilation_cache_dir == \
        os.path.join(REPO, ".jax_cache")
    ignored = open(os.path.join(REPO, ".gitignore")).read().split()
    assert ".jax_cache/" in ignored and "chiprun_out/" in ignored


def test_one_code_path_sets_the_jax_cache_dir():
    hits = []
    for root in ("paddle_tpu", "tools"):
        for dirpath, _, files in os.walk(os.path.join(REPO, root)):
            hits += [os.path.join(dirpath, f) for f in files
                     if f.endswith(".py")]
    hits += [os.path.join(REPO, f) for f in ("bench.py", "chip_smoke.py",
                                             "__graft_entry__.py")]
    setters = [os.path.relpath(p, REPO) for p in hits
               if re.search(r'update\(\s*"jax_compilation_cache_dir"',
                            open(p).read())]
    assert setters == [os.path.join("paddle_tpu", "serving", "cache.py")]


# -- no fallback that hides the device -----------------------------------------

def test_set_device_names_only_devices_that_exist():
    try:
        with pytest.raises(RuntimeError, match="no 'tpu' device"):
            paddle.set_device("tpu")
        with pytest.raises(ValueError, match="out of range"):
            paddle.set_device("cpu:99")
        with pytest.raises(ValueError, match="out of range"):
            paddle.core.device.Place("cpu", 99).jax_device()
        assert paddle.get_device() == "cpu:0"
        paddle.set_device("cpu:1")                  # 8 virtual CPU devices
        assert paddle.get_device() == "cpu:1"
        assert paddle.ones([2]).place == paddle.core.device.Place("cpu", 1)
    finally:
        paddle.set_device("cpu:0")
        jax.config.update("jax_default_device", None)


def test_pallas_mode_records_what_ran():
    from paddle_tpu.ops.custom import pallas_greedy_nms
    k = 16
    iou = jnp.eye(k, dtype=jnp.float32)
    out = pallas_greedy_nms(iou, jnp.ones((k,), jnp.int32),
                            jnp.asarray([0.5], jnp.float32))
    assert int(out.sum()) == k
    assert pallas_mode.chosen_modes()["greedy_nms"] is True   # CPU: interpreted
    assert pallas_mode.resolve_interpret("probe", False) is False
    assert pallas_mode.chosen_modes()["probe"] is False


@pytest.mark.parametrize("amp,want", [("bfloat16", ("bfloat16",)),
                                      (None, ("float32",))],
                         ids=["autocast-bf16", "float32"])
def test_pallas_mode_records_the_flash_operand_dtype(monkeypatch, amp, want):
    """A train step's flash kernels (forward, recompute's second forward,
    dQ, dK/dV) feed the MXU the dtype they are given: bfloat16 under
    autocast, float32 for a float32 model (chip_smoke.py asserts the
    first on the chip)."""
    import contextlib
    import numpy as np
    from paddle_tpu.distributed.fleet.utils import recompute
    from paddle_tpu.models import (GPTConfig, GPTForCausalLM,
                                   GPTPretrainingCriterion)
    monkeypatch.setattr(pallas_mode, "_OPERANDS", {})
    net = GPTForCausalLM(GPTConfig(
        vocab_size=64, hidden_size=32, num_layers=2, num_heads=2,
        max_position_embeddings=64, hidden_dropout_prob=0.0,
        attention_dropout_prob=0.0, attn_impl="flash"))
    for blk in net.gpt.decoder.layers:
        blk.forward = (lambda *a, __f=blk.forward, **k:
                       recompute(__f, *a, **k))
    ids = paddle.to_tensor(np.arange(128, dtype=np.int32).reshape(2, 64) % 64)
    cast = (paddle.amp.auto_cast(enable=True, dtype=amp) if amp
            else contextlib.nullcontext())
    with cast:
        loss = GPTPretrainingCriterion()(net(ids), ids.astype("int64"))
    loss.backward()
    got = pallas_mode.chosen_operand_dtypes()
    assert got == {k: want for k in ("flash_fwd", "flash_bwd_dq",
                                     "flash_bwd_dkv")}


def test_engine_stats_name_the_paged_lane():
    from paddle_tpu.models import GPTConfig, GPTForCausalLM
    from paddle_tpu.serving.llm import LLMEngine, LLMEngineConfig
    net = GPTForCausalLM(GPTConfig(
        vocab_size=64, hidden_size=16, num_layers=1, num_heads=2,
        max_position_embeddings=32, hidden_dropout_prob=0.0,
        attention_dropout_prob=0.0))
    net.eval()
    eng = LLMEngine(net, LLMEngineConfig(kv_layout="paged", num_slots=2,
                                         max_seq=16, page_size=8,
                                         warmup=False))
    try:
        assert eng.stats()["paged_attn_impl"] == "gather"    # auto, off-TPU
    finally:
        eng.drain(timeout=30)


# -- committed TPU configs pass the JAX-side Mosaic lowering -------------------

def _tpu_winners():
    path = os.path.join(REPO, "paddle_tpu", "tuner", "default_winners.json")
    entries = json.load(open(path))["entries"]
    return sorted((k, v["config"]) for k, v in entries.items()
                  if k.split("|")[1] == "tpu")


TPU_WINNERS = _tpu_winners()


def _lower_for_tpu(fn, *args):
    return jax.jit(fn).trace(*args).lower(
        lowering_platforms=("tpu",)).as_text()


@pytest.mark.parametrize("key,cfg", TPU_WINNERS,
                         ids=[k for k, _ in TPU_WINNERS])
def test_committed_tpu_winner_lowers(key, cfg):
    from paddle_tpu.ops import pallas_attention as fa
    from paddle_tpu.ops.paged_attention import paged_attention
    fam, _, dtype, *rest = key.split("|")
    f = {p[0]: int(p[1:]) for p in rest}
    dt = jnp.dtype(dtype)
    if fam == "paged_attn":
        q = jnp.zeros((2, f["h"], f["d"]), dt)
        arena = jnp.zeros((9, 2, f["p"], f["h"], f["d"]), dt)
        bt = jnp.zeros((2, 4), jnp.int32)
        text = _lower_for_tpu(
            lambda a, k, v, t, p: paged_attention(
                a, k, v, t, p, layer=1, block_h=cfg["block_h"],
                interpret=False),
            q, arena, arena, bt, jnp.zeros((2,), jnp.int32))
        assert text.count('kernel_name = "paged_attn"') == 1
        return
    assert fam in ("flash_fwd", "flash_bwd", "ring_flash", "ring_flash_bwd")
    bq, bk, causal = cfg["block_q"], cfg["block_k"], bool(f["c"])
    q = jnp.zeros((2, f["q"], f["d"]), dt)
    kv = jnp.zeros((2, f["k"], f["d"]), dt)
    if not fam.endswith("bwd"):
        text = _lower_for_tpu(
            lambda a, b, c: fa._fa_fwd_with_lse(
                a, b, c, causal, 0.125, bq, bk, False, f["k"]), q, kv, kv)
        assert text.count('kernel_name = "flash_fwd"') == 1
        return
    lse = jnp.zeros((2, 1, f["q"]), jnp.float32)
    text = _lower_for_tpu(
        lambda a, b, c, g, o, l: fa._fa_bwd_with_lse(
            a, b, c, g, o, l, causal, 0.125, bq, bk, False, f["k"]),
        q, kv, kv, q, q, lse)
    assert text.count('kernel_name = "flash_bwd_dq"') == 1
    assert text.count('kernel_name = "flash_bwd_dkv"') == 1


@pytest.mark.parametrize("window", [None, 2048],
                         ids=["to_the_position", "window"])
@pytest.mark.parametrize("cell,hq,hkv,d,page,fused", [
    ("serve-trinity-mixedctx", 32, 4, 128, 64, False),
    ("serve-lfm2moe-decode", 32, 8, 64, 16, True)])
def test_the_mxu_recurrence_lowers_at_the_cells_shapes(cell, hq, hkv, d, page,
                                                       fused, window):
    """The grouped-query cells' calls of ``paged_attn`` (the rule sends both
    to the MXU recurrence) through the JAX-side Mosaic lowering: flat rows,
    stacked pages, both products, with and without a window."""
    from paddle_tpu.ops.paged_attention import paged_attention
    from paddle_tpu.tuner.space import paged_recurrence
    row, arenas = (2 * d, 1) if fused else (d, 2)
    assert paged_recurrence(hq // hkv, hkv, page, row, 4, arenas) == "mxu"
    arena = jnp.zeros((9, 2, page, hkv, row), jnp.float32)
    text = _lower_for_tpu(
        lambda a, k, v, t, p: paged_attention(
            a, k, v, t, p, layer=1, window=window, interpret=False),
        jnp.zeros((2, hq, d), jnp.float32), arena, None if fused else arena,
        jnp.zeros((2, 64), jnp.int32), jnp.zeros((2,), jnp.int32))
    assert text.count('kernel_name = "paged_attn"') == 1
    # the arena reaches the call as its flat rows
    assert f"tensor<9x2x{page * hkv}x{row}xf32>" in text


def test_nms_kernel_lowers_for_tpu_at_its_default_unroll():
    from paddle_tpu.ops.custom import pallas_greedy_nms
    k = 128
    text = _lower_for_tpu(
        lambda a, b, c: pallas_greedy_nms(a, b, c, interpret=False,
                                          unroll=1),
        jnp.zeros((k, k), jnp.float32), jnp.ones((k,), jnp.int32),
        jnp.asarray([0.5], jnp.float32))
    assert 'kernel_name = "greedy_nms"' in text
    # Mosaic lowers unroll=1 and unroll=k only; no committed winner may
    # ask for anything else
    with pytest.raises(NotImplementedError, match="unroll"):
        _lower_for_tpu(
            lambda a, b, c: pallas_greedy_nms(a, b, c, interpret=False,
                                              unroll=4),
            jnp.zeros((k, k), jnp.float32), jnp.ones((k,), jnp.int32),
            jnp.asarray([0.5], jnp.float32))


def test_paged_head_block_is_sanitized_to_a_legal_tile():
    from paddle_tpu.ops.paged_attention import _sanitize_block_h
    from paddle_tpu.tuner.space import paged_attn_candidates
    assert _sanitize_block_h(6, 12) == 12       # 6: not a multiple of 8
    assert _sanitize_block_h(8, 12) == 12       # 8 does not divide 12
    assert _sanitize_block_h(8, 16) == 8
    assert _sanitize_block_h(8, 16, itemsize=2) == 16    # bf16 tile is 16
    assert _sanitize_block_h(64, 32) == 32
    assert [c["block_h"] for c in paged_attn_candidates(12, 64, 16)] == [12]
    assert [c["block_h"] for c in paged_attn_candidates(32, 64, 16)] == \
        [8, 16, 32]


# -- the criterion shifts the labels, never the logits -------------------------

def test_gpt_criterion_never_slices_the_logits():
    """[B, S-1, V] -> [B*(S-1), V] is a relayout the TPU compiler took
    ~427 s over at GPT-small width (PERF.md, PR 21); the sliced form stays
    here as the reference the criterion must equal."""
    from paddle_tpu import ops
    from paddle_tpu.models import GPTPretrainingCriterion
    from paddle_tpu.nn import functional as F
    b, s, v = 2, 16, 40
    logits = jax.random.normal(jax.random.PRNGKey(0), (b, s, v))
    labels = jax.random.randint(jax.random.PRNGKey(1), (b, s), 0, v)

    def criterion(lg, lb):
        return GPTPretrainingCriterion()(paddle.Tensor(lg),
                                         paddle.Tensor(lb))._data

    def sliced(lg, lb):
        lg, lb = paddle.Tensor(lg), paddle.Tensor(lb)
        return F.cross_entropy(ops.reshape(lg[:, :-1, :], [-1, v]),
                               ops.reshape(lb[:, 1:], [-1]))._data

    got, g_got = jax.value_and_grad(criterion)(logits, labels)
    ref, g_ref = jax.value_and_grad(sliced)(logits, labels)
    assert float(got) == pytest.approx(float(ref), rel=1e-6)
    assert float(jnp.max(jnp.abs(g_got - g_ref))) < 1e-7
    text = jax.jit(jax.grad(criterion)).lower(logits, labels).as_text()
    assert f"{b}x{s - 1}x{v}x" not in text
    assert f"{b}x{s - 1}x{v}x" in \
        jax.jit(jax.grad(sliced)).lower(logits, labels).as_text()
