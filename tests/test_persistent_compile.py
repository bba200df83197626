"""Persistent fleet-wide compilation cache (serving/cache.py).

The acceptance property: a SECOND process starting against a warm cache
root performs zero XLA compiles for the predictor signatures the first
process already served — the serialized-executable tier loads whole AOT
executables without even issuing a compile request, and any remaining
jit compile request is served by JAX's persistent compilation cache.

Plus the integrity story, mirroring the tuner cache: corrupt entries are
dropped with a warning and recompiled, never crash, never serve garbage.
"""
import json
import os
import pickle
import subprocess
import sys
import warnings

import numpy as np
import pytest

import paddle_tpu as paddle
import paddle_tpu.nn as nn
from paddle_tpu.core import monitor as _mon
from paddle_tpu.serving import cache as cache_mod
from paddle_tpu.serving.cache import (ExecutableCache,
                                      PersistentExecutableStore,
                                      enable_persistent_compilation,
                                      persistent_root, persistent_store)
from paddle_tpu.static import InputSpec

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _export_artifact(tmp_path):
    paddle.seed(7)

    class Net(nn.Layer):
        def __init__(self):
            super().__init__()
            self.fc = nn.Linear(6, 4)

        def forward(self, x):
            return nn.functional.relu(self.fc(x))

    prefix = str(tmp_path / "persist_net")
    paddle.jit.save(Net(), prefix,
                    input_spec=[InputSpec([None, 6], "float32", "x")])
    return prefix


@pytest.fixture()
def persist_env(tmp_path, monkeypatch):
    """Fresh executable-store root + reset process-wide cache state."""
    monkeypatch.setenv("PADDLE_TPU_COMPILE_CACHE", str(tmp_path))
    cache_mod._reset_persistence_for_tests()
    cache_mod._reset_default_cache_for_tests()
    yield tmp_path
    cache_mod._reset_persistence_for_tests()
    cache_mod._reset_default_cache_for_tests()


# ---------------------------------------------------------------------------
# the zero-compile warm-start acceptance test: two real processes

_CHILD = r"""
import json, os, sys
import numpy as np
import jax
from jax import monitoring

requests = []
hits = []
monitoring.register_event_listener(lambda name, **kw: (
    requests.append(1) if name == "/jax/compilation_cache/compile_requests_use_cache"
    else hits.append(1) if name == "/jax/compilation_cache/cache_hits" else None))

from paddle_tpu.core import monitor as _mon
from paddle_tpu.inference import Config, create_predictor

prefix = sys.argv[1]
pred = create_predictor(Config(prefix))
x = np.ones((3, 6), np.float32)
out1 = pred.run([x])[0]
out2 = pred.run([x])[0]          # second call: in-memory hit
assert np.array_equal(out1, out2)
print(json.dumps({
    "out_sum": float(out1.sum()),
    "compile_requests": len(requests),
    "xla_cache_hits": len(hits),
    "disk_hits": int(_mon.stat_get("serving.executable_cache.disk_hits")),
    "disk_writes": int(_mon.stat_get("serving.executable_cache.disk_writes")),
    "compile_fn_calls": int(_mon.stat_get("jit.cache_misses")),
}))
"""


def _run_child(prefix, cache_root, tmp_path):
    script = tmp_path / "child.py"
    script.write_text(_CHILD)
    # two tiers, placed separately: the executable store by the repo's
    # variable, jax's own cache by jax's
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               PADDLE_TPU_COMPILE_CACHE=str(cache_root),
               JAX_COMPILATION_CACHE_DIR=str(cache_root / "xla"),
               JAX_PERSISTENT_CACHE_MIN_COMPILE_TIME_SECS="0",
               JAX_PERSISTENT_CACHE_MIN_ENTRY_SIZE_BYTES="-1",
               PYTHONPATH=REPO + os.pathsep + os.environ.get("PYTHONPATH",
                                                             ""))
    proc = subprocess.run([sys.executable, str(script), prefix],
                          capture_output=True, text=True, timeout=300,
                          env=env, cwd=REPO)
    assert proc.returncode == 0, proc.stderr[-3000:]
    return json.loads(proc.stdout.strip().splitlines()[-1])


@pytest.mark.slow
def test_warm_start_performs_zero_xla_compiles(tmp_path):
    prefix = _export_artifact(tmp_path)
    cache_root = tmp_path / "compile-cache"

    cold = _run_child(prefix, cache_root, tmp_path)
    # cold start: the predictor signature compiled once and was persisted
    assert cold["compile_fn_calls"] >= 1
    assert cold["disk_writes"] >= 1
    assert cold["disk_hits"] == 0

    warm = _run_child(prefix, cache_root, tmp_path)
    # warm start: the serialized executable loaded — compile_fn never ran
    assert warm["compile_fn_calls"] == 0
    assert warm["disk_hits"] >= 1
    # and every jit compile request that DID happen (internal utility
    # ops) was served by the persistent XLA cache: zero backend compiles
    assert warm["compile_requests"] == warm["xla_cache_hits"]
    # same numbers out of both processes
    assert warm["out_sum"] == cold["out_sum"]
    # each tier wrote under its own directory and nowhere else
    assert sorted(os.listdir(cache_root)) == ["executables", "xla"]
    assert os.listdir(cache_root / "xla")


# ---------------------------------------------------------------------------
# in-process: store round-trip, corruption tolerance, fold + counters

class TestPersistentExecutableStore:
    def _compiled(self, mul=2.0):
        import jax
        import jax.numpy as jnp
        return jax.jit(lambda x: x * mul).lower(
            jnp.zeros((4,), jnp.float32)).compile()

    def test_round_trip(self, tmp_path):
        import jax.numpy as jnp
        store = PersistentExecutableStore(str(tmp_path))
        assert store.save("k1", self._compiled()) is True
        exe = store.load("k1")
        assert exe is not None
        np.testing.assert_allclose(
            np.asarray(exe(jnp.arange(4, dtype=jnp.float32))),
            [0.0, 2.0, 4.0, 6.0])

    def test_missing_is_silent_miss(self, tmp_path):
        store = PersistentExecutableStore(str(tmp_path))
        assert store.load("nope") is None

    def test_corrupt_entry_warns_and_misses(self, tmp_path):
        store = PersistentExecutableStore(str(tmp_path))
        store.save("k1", self._compiled())
        path = store._path("k1")
        with open(path, "wb") as f:
            f.write(b"\x00garbage not a pickle")
        with warnings.catch_warnings(record=True) as w:
            warnings.simplefilter("always")
            assert store.load("k1") is None
        assert any("unreadable" in str(x.message) for x in w)
        # the bad file was removed so the rewritten entry loads cleanly
        assert not os.path.exists(path)
        store.save("k1", self._compiled())
        assert store.load("k1") is not None

    def test_truncated_pickle_warns_and_misses(self, tmp_path):
        store = PersistentExecutableStore(str(tmp_path))
        store.save("k1", self._compiled())
        path = store._path("k1")
        blob = open(path, "rb").read()
        with open(path, "wb") as f:
            f.write(blob[:len(blob) // 3])
        with warnings.catch_warnings(record=True) as w:
            warnings.simplefilter("always")
            assert store.load("k1") is None
        assert any("unreadable" in str(x.message) for x in w)

    def test_version_and_platform_partition_keys(self, tmp_path):
        # same key under a different store version must hash differently
        store = PersistentExecutableStore(str(tmp_path))
        p1 = store._path("k1")
        old = cache_mod._STORE_VERSION
        try:
            cache_mod._STORE_VERSION = old + 1
            p2 = store._path("k1")
        finally:
            cache_mod._STORE_VERSION = old
        assert p1 != p2

    def test_jit_wrapper_silently_stays_memory_only(self, tmp_path):
        import jax
        store = PersistentExecutableStore(str(tmp_path))
        assert store.save("k1", jax.jit(lambda x: x)) is False
        assert os.listdir(tmp_path) == [] if os.path.isdir(tmp_path) \
            else True


class TestCacheDiskTier:
    def test_get_or_compile_uses_disk_tier(self, persist_env):
        import jax
        import jax.numpy as jnp
        enable_persistent_compilation()
        cache = ExecutableCache()
        calls = {"n": 0}

        def compile_fn():
            calls["n"] += 1
            return jax.jit(lambda x: x + 1).lower(
                jnp.zeros((2,), jnp.float32)).compile()

        cache.get_or_compile("key-a", compile_fn, persist_key="key-a")
        assert calls["n"] == 1
        # a FRESH in-memory cache (new process stand-in) loads from disk
        cache2 = ExecutableCache()
        exe = cache2.get_or_compile("key-a", compile_fn,
                                    persist_key="key-a")
        assert calls["n"] == 1           # compile_fn not called again
        np.testing.assert_allclose(
            np.asarray(exe(jnp.zeros((2,), jnp.float32))), [1.0, 1.0])

    def test_no_persist_key_no_disk(self, persist_env):
        import jax
        import jax.numpy as jnp
        enable_persistent_compilation()
        cache = ExecutableCache()
        cache.get_or_compile(
            "key-b", lambda: jax.jit(lambda x: x).lower(
                jnp.zeros((2,), jnp.float32)).compile())
        exe_dir = os.path.join(persistent_root(), "executables")
        assert not os.path.isdir(exe_dir) or os.listdir(exe_dir) == []

    def test_persistence_off_without_env(self, monkeypatch):
        monkeypatch.delenv("PADDLE_TPU_COMPILE_CACHE", raising=False)
        cache_mod._reset_persistence_for_tests()
        try:
            assert persistent_root() is None
            assert persistent_store() is None
        finally:
            cache_mod._reset_persistence_for_tests()


class TestSharedDefaultCacheAndCounters:
    def test_llm_decoder_defaults_to_process_cache(self):
        from paddle_tpu.serving.cache import default_cache
        from paddle_tpu.serving.llm.decode import GPTStaticDecoder
        from paddle_tpu.models.gpt import GPTConfig, GPTForCausalLM

        model = GPTForCausalLM(GPTConfig(
            vocab_size=64, hidden_size=16, num_layers=1, num_heads=2,
            max_position_embeddings=32))
        dec = GPTStaticDecoder(model)
        assert dec.exec_cache is default_cache()

    def test_engine_callable_defaults_to_process_cache(self):
        from paddle_tpu.serving import Engine, EngineConfig
        from paddle_tpu.serving.cache import default_cache

        eng = Engine(lambda x: x * 2,
                     EngineConfig(max_batch=4, max_batch_delay=0.01))
        try:
            assert eng.cache is default_cache()
            # key embeds the fn object, not a recyclable id()
            key_fn = eng._model_key[1]
            assert callable(key_fn)
        finally:
            eng.drain(timeout=10)

    def test_counters_published_to_default_registry(self):
        reg = _mon.default_registry()
        base_h = reg.get("serving.executable_cache.hits")
        base_m = reg.get("serving.executable_cache.misses")
        cache = ExecutableCache(capacity=1)
        cache.get_or_compile("a", lambda: "exe-a")
        cache.get_or_compile("a", lambda: "exe-a")
        cache.get_or_compile("b", lambda: "exe-b")   # evicts "a"
        assert reg.get("serving.executable_cache.hits") == base_h + 1
        assert reg.get("serving.executable_cache.misses") == base_m + 2
        assert reg.get("serving.executable_cache.evictions") >= 1
        assert reg.get("serving.executable_cache.size") == 1

    def test_metricsz_exposes_executable_cache(self):
        from paddle_tpu.observability.metrics import render_prometheus
        cache = ExecutableCache()
        cache.get_or_compile("m", lambda: "exe")
        text = render_prometheus()
        assert "paddle_tpu_serving_executable_cache_misses_total" in text
        assert "paddle_tpu_serving_executable_cache_size" in text
