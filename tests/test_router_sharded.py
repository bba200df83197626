"""Sharded multi-replica serving: the GSPMD sharding substrate
(ShardingSpec / sidecar / resolve / cache tokens), the sharded Predictor
path, health-stamped checkpoint selection, the health-aware replica
Router, and the 2x4 replica-by-model acceptance run."""
import json
import os
import signal
import threading
import time

import numpy as np
import pytest

import jax
from jax.sharding import Mesh, PartitionSpec

import paddle_tpu as paddle
import paddle_tpu.nn as nn
from paddle_tpu.core.monitor import StatRegistry
from paddle_tpu.incubate.checkpoint.sharded import (
    _corrupt_first_shard_file, newest_healthy_checkpoint, save_sharded,
    write_health_stamp)
from paddle_tpu.inference import Config, create_predictor
from paddle_tpu.serving import (
    EngineConfig, EngineDraining, NoHealthyReplicas, Replica, Router,
    RouterConfig, ShardingSpec, predictor_replica_factory)
from paddle_tpu.serving import sharding as shmod
from paddle_tpu.serving.cache import default_cache
from paddle_tpu.serving.engine import Engine
from paddle_tpu.serving.replica import DEAD, HEALTHY
from paddle_tpu.static import InputSpec


def _model_mesh(n=4, offset=0):
    devs = jax.devices()[offset:offset + n]
    return Mesh(np.array(devs), ("model",))


def _double(*arrays):
    return [np.asarray(a) * 2.0 for a in arrays]


def _callable_factory(fn=_double, **cfg):
    """Router engine factory over a plain callable (no artifact needed)."""
    cfg.setdefault("max_batch", 8)
    cfg.setdefault("max_batch_delay", 0.005)

    def factory(replica):
        ec = EngineConfig(**cfg)
        ec.stat_prefix = f"serving.replica{replica.replica_id}"
        return Engine(fn, ec, registry=replica.registry)
    return factory


def _mk_router(fn=_double, *, factory=None, **rcfg):
    rcfg.setdefault("num_replicas", 2)
    rcfg.setdefault("health_interval", 0.02)
    return Router(factory or _callable_factory(fn), RouterConfig(**rcfg),
                  registry=StatRegistry())


def _get(port, path):
    import urllib.error
    import urllib.request
    try:
        with urllib.request.urlopen(
                f"http://127.0.0.1:{port}{path}") as r:
            return r.status, json.loads(r.read())
    except urllib.error.HTTPError as e:
        return e.code, json.loads(e.read())


def _post(port, path, payload):
    import urllib.error
    import urllib.request
    body = json.dumps(payload).encode()
    req = urllib.request.Request(
        f"http://127.0.0.1:{port}{path}", data=body, method="POST",
        headers={"Content-Type": "application/json"})
    try:
        with urllib.request.urlopen(req) as r:
            return r.status, json.loads(r.read())
    except urllib.error.HTTPError as e:
        return e.code, json.loads(e.read())


def _wait_for(pred, timeout=10.0, interval=0.01):
    deadline = time.monotonic() + timeout
    while time.monotonic() < deadline:
        if pred():
            return True
        time.sleep(interval)
    return pred()


def _export(tmp_path, sharding=None, in_features=6):
    """jit.save a tiny softmax MLP; optional sharding sidecar rides along."""
    paddle.seed(0)

    class Net(nn.Layer):
        def __init__(self):
            super().__init__()
            self.fc1 = nn.Linear(in_features, 16)
            self.fc2 = nn.Linear(16, 5)

        def forward(self, x):
            return nn.functional.softmax(
                self.fc2(nn.functional.relu(self.fc1(x))), axis=-1)

    prefix = str(tmp_path / "served")
    kwargs = {} if sharding is None else {"sharding": sharding}
    paddle.jit.save(Net(), prefix,
                    input_spec=[InputSpec([None, in_features], "float32",
                                          "x")],
                    **kwargs)
    return prefix


# ---------------------------------------------------------------------------
class TestShardingSpec:
    def test_json_roundtrip(self):
        spec = ShardingSpec({"model": 4},
                            inputs=[PartitionSpec("model")],
                            params=[None, PartitionSpec(None, "model")])
        doc = json.loads(json.dumps(spec.to_json_dict()))
        back = ShardingSpec.from_json_dict(doc)
        assert back.mesh_axes == {"model": 4}
        assert back.inputs == [PartitionSpec("model")]
        # None entries (replicated) survive the round trip as None
        assert back.params == [None, PartitionSpec(None, "model")]

    def test_mesh_token_distinguishes_device_subsets(self):
        t0 = shmod.mesh_token(_model_mesh(4, offset=0))
        t1 = shmod.mesh_token(_model_mesh(4, offset=4))
        assert t0 != t1                      # same names+shape, other devices
        assert t0 == shmod.mesh_token(_model_mesh(4, offset=0))

    def test_sidecar_roundtrip_and_malformed(self, tmp_path):
        prefix = str(tmp_path / "m")
        shmod.save_sidecar(prefix, ShardingSpec({"model": 2},
                                                inputs=[["model"]]))
        spec = shmod.load_sidecar(prefix)
        assert spec.mesh_axes == {"model": 2}
        assert spec.inputs == [PartitionSpec("model")]
        with open(shmod.sidecar_path(prefix), "w") as f:
            f.write("{not json")
        with pytest.warns(UserWarning, match="unreadable"):
            assert shmod.load_sidecar(prefix) is None
        assert shmod.load_sidecar(str(tmp_path / "absent")) is None

    def test_resolve_too_few_devices_falls_back(self):
        spec = ShardingSpec({"model": 64})
        with pytest.warns(UserWarning, match="falling back to replicated"):
            assert shmod.resolve(spec) is None

    def test_resolve_unknown_axis_falls_back(self):
        spec = ShardingSpec({"model": 2}, inputs=[PartitionSpec("data")])
        with pytest.warns(UserWarning, match="absent from mesh"):
            assert shmod.resolve(spec, n_inputs=1) is None

    def test_resolve_input_count_drift_falls_back(self):
        spec = ShardingSpec({"model": 2}, inputs=[None, None])
        with pytest.warns(UserWarning, match="falling back to replicated"):
            assert shmod.resolve(spec, n_inputs=1) is None

    def test_resolve_binds_shardings(self):
        spec = ShardingSpec({"model": 4}, inputs=[PartitionSpec("model")])
        rs = shmod.resolve(spec, n_inputs=1, n_params=3)
        assert rs is not None
        assert len(rs.input_shardings) == 1
        assert len(rs.param_shardings) == 3  # filled replicated
        assert rs.token[0] == "sharded"


# ---------------------------------------------------------------------------
class TestShardedPredictor:
    def test_sidecar_autoload_bitwise(self, tmp_path):
        prefix = _export(tmp_path,
                         sharding=ShardingSpec(
                             {"model": 4},
                             inputs=[PartitionSpec("model")]))
        sharded = create_predictor(Config(prefix))
        assert sharded.sharding is not None
        plain = create_predictor(Config(prefix).disable_sharding())
        assert plain.sharding is None
        x = np.random.RandomState(0).randn(8, 6).astype(np.float32)
        ys = sharded.run([x])[0]
        yp = plain.run([x])[0]
        # batch-axis sharding: each device owns whole rows, no reduction
        # is split, so the partitioned run is bitwise-identical
        assert np.array_equal(ys, yp)

    def test_dict_sharding_through_jit_save(self, tmp_path):
        prefix = _export(tmp_path, sharding={"mesh_axes": {"model": 4},
                                             "inputs": [["model"]]})
        spec = shmod.load_sidecar(prefix)
        assert spec.inputs == [PartitionSpec("model")]

    def test_cache_keys_never_collide(self, tmp_path):
        """Unsharded + two replicas over disjoint device subsets, same
        artifact and same input signature: three distinct executables."""
        prefix = _export(tmp_path)
        preds = [
            create_predictor(Config(prefix).disable_sharding()),
            create_predictor(Config(prefix).enable_sharding(
                mesh=_model_mesh(4, offset=0),
                input_specs=[PartitionSpec("model")])),
            create_predictor(Config(prefix).enable_sharding(
                mesh=_model_mesh(4, offset=4),
                input_specs=[PartitionSpec("model")])),
        ]
        x = np.ones((8, 6), np.float32)
        before = default_cache().stats()["misses"]
        outs = [p.run([x])[0] for p in preds]
        assert default_cache().stats()["misses"] == before + 3
        # and a second pass hits every cached executable
        for p in preds:
            p.run([x])
        assert default_cache().stats()["misses"] == before + 3
        assert np.array_equal(outs[0], outs[1])
        assert np.array_equal(outs[0], outs[2])


# ---------------------------------------------------------------------------
class TestNewestHealthyCheckpoint:
    def _mk(self, root, name, step):
        path = str(root / name)
        save_sharded({"w": np.arange(4, dtype=np.float32), "step": step},
                     path)
        return path

    def test_picks_newest_healthy(self, tmp_path):
        p1 = self._mk(tmp_path, "step_100", 100)
        p2 = self._mk(tmp_path, "step_200", 200)
        p3 = self._mk(tmp_path, "step_300", 300)
        assert newest_healthy_checkpoint(str(tmp_path)) == p3
        write_health_stamp(p3, healthy=False, reason="diverged")
        with pytest.warns(UserWarning, match="unhealthy"):
            assert newest_healthy_checkpoint(str(tmp_path)) == p2
        _corrupt_first_shard_file(p2)
        with pytest.warns(UserWarning):
            assert newest_healthy_checkpoint(str(tmp_path)) == p1

    def test_root_may_be_a_checkpoint_dir(self, tmp_path):
        p = self._mk(tmp_path, "only", 1)
        assert newest_healthy_checkpoint(p) == p

    def test_nothing_survives(self, tmp_path):
        assert newest_healthy_checkpoint(str(tmp_path)) is None
        p = self._mk(tmp_path, "step_1", 1)
        write_health_stamp(p, healthy=False)
        with pytest.warns(UserWarning, match="unhealthy"):
            assert newest_healthy_checkpoint(str(tmp_path)) is None


# ---------------------------------------------------------------------------
class TestRouter:
    def test_dispatch_balances(self):
        router = _mk_router()
        try:
            x = np.ones((2, 3), np.float32)
            for _ in range(8):
                y, = router.submit([x]).result(timeout=30)
                assert np.array_equal(y, x * 2.0)
            st = router.stats()
            assert st["total_dispatched"] == 8
            counts = [p["dispatched"] for p in st["replicas"].values()]
            assert counts == [4, 4]          # rotating tie-break
            assert st["balance_factor"] == 1.0
        finally:
            router.drain(timeout=30)

    def test_model_axes_pool_too_small(self):
        with pytest.raises(ValueError, match="devices"):
            _mk_router(num_replicas=4, model_axes={"model": 4})

    def test_draining_router_rejects(self):
        router = _mk_router()
        router.drain(timeout=30)
        with pytest.raises(EngineDraining):
            router.submit([np.ones((1, 2), np.float32)])

    def test_unhealthy_replica_drained_service_continues(self):
        router = _mk_router(auto_resurrect=False)
        try:
            r0, r1 = router.replicas
            r0.mark_unhealthy("test verdict")
            with pytest.warns(UserWarning, match="draining replica 0"):
                assert _wait_for(lambda: r0.state == DEAD)
            assert router.healthz()["status"] == "degraded"
            # traffic keeps flowing through the survivor
            y, = router.submit([np.ones((1, 2), np.float32)]) \
                       .result(timeout=30)
            assert y[0, 0] == 2.0
            assert r1.stats()["dispatched"] >= 1
            r1.mark_unhealthy("test verdict")
            assert _wait_for(lambda: r1.state == DEAD)
            with pytest.raises(NoHealthyReplicas):
                router.submit([np.ones((1, 2), np.float32)])
            assert router.healthz()["status"] == "unhealthy"
        finally:
            router.drain(timeout=30)

    def test_resurrect_boots_from_health_stamped_checkpoint(self, tmp_path):
        p1 = str(tmp_path / "step_1")
        p2 = str(tmp_path / "step_2")
        save_sharded({"w": np.zeros(2, np.float32)}, p1)
        save_sharded({"w": np.ones(2, np.float32)}, p2)
        write_health_stamp(p2, healthy=False, reason="diverged")
        router = _mk_router(restart_backoff=0.02, max_restarts=3,
                            checkpoint_root=str(tmp_path))
        try:
            r0 = router.replicas[0]
            assert r0.boot_checkpoint == p1     # newest healthy, not newest
            r0.mark_unhealthy("sentinel says no")
            with pytest.warns(UserWarning):
                assert _wait_for(lambda: r0.state == DEAD)
                assert _wait_for(lambda: r0.state == HEALTHY)
            st = r0.stats()
            assert st["restarts"] == 1
            assert st["boot_checkpoint"] == p1
            assert router.budget.used == 1
            assert _wait_for(
                lambda: router.healthz()["status"] == "ok")
            y, = router.submit([np.ones((1, 2), np.float32)]) \
                       .result(timeout=30)
            assert y[0, 0] == 2.0
        finally:
            router.drain(timeout=30)

    def test_exhausted_budget_stays_dead(self):
        router = _mk_router(max_restarts=0, auto_resurrect=True)
        try:
            r0 = router.replicas[0]
            r0.mark_unhealthy("bad")
            with pytest.warns(UserWarning, match="budget"):
                assert _wait_for(lambda: r0.state == DEAD)
                time.sleep(0.1)                 # a few sweeps
            assert r0.state == DEAD
            assert r0.stats()["restarts"] == 0
            # direct resurrection is budget-gated too
            assert r0.resurrect() is False
        finally:
            router.drain(timeout=30)

    def test_sigterm_fans_out_drain(self):
        router = _mk_router()
        router.install_drain_signal_handler()
        fut = router.submit([np.ones((1, 2), np.float32)])
        os.kill(os.getpid(), signal.SIGTERM)
        assert router._stopped.wait(timeout=30)
        assert fut.result(timeout=5)[0][0, 0] == 2.0   # in-flight resolved
        assert all(r.state == DEAD for r in router.replicas)
        router.drain(timeout=5)                 # idempotent + uninstalls

    def test_labeled_gauges_and_registry_dedup(self):
        router = _mk_router()
        try:
            assert _wait_for(lambda: len(router.registry.labeled(
                "serving.router.replica_healthy")) == 2)
            from paddle_tpu.observability.metrics import render_prometheus
            regs = router.registries()
            assert len(regs) == 1               # replicas share the registry
            text = render_prometheus(regs[0])
            assert 'replica="0"' in text and 'replica="1"' in text
        finally:
            router.drain(timeout=30)


# ---------------------------------------------------------------------------
class TestLLMReplicaPrefixes:
    def test_stats_do_not_cross_prefix_boundaries(self):
        """serving.llm.replica1 must not swallow serving.llm.replica10
        counters (the trailing-dot prefix fix in LLMEngine.stats)."""
        from paddle_tpu.models.gpt import GPTConfig, GPTForCausalLM
        from paddle_tpu.serving.llm import LLMEngine, LLMEngineConfig
        net = GPTForCausalLM(GPTConfig(
            vocab_size=64, hidden_size=32, num_layers=2, num_heads=4,
            max_position_embeddings=128, hidden_dropout_prob=0.0,
            attention_dropout_prob=0.0))
        net.eval()
        reg = StatRegistry()
        cfg = LLMEngineConfig(num_slots=4, max_seq=32, warmup=False,
                              stat_prefix="serving.llm.replica1")
        eng = LLMEngine(net, cfg, registry=reg)
        try:
            reg.add("serving.llm.replica10.queued", 7)   # foreign replica
            keys = set(eng.stats()["stats"])
            assert not any(k.startswith("serving.llm.replica10.")
                           for k in keys)
        finally:
            eng.drain(timeout=30)


@pytest.mark.slow
class TestShardedLLMDecode:
    @pytest.mark.timeout_s(240)
    def test_slot_sharded_tokens_identical(self):
        from paddle_tpu.models.gpt import GPTConfig, GPTForCausalLM
        from paddle_tpu.serving.llm import LLMEngine, LLMEngineConfig
        paddle.seed(7)
        net = GPTForCausalLM(GPTConfig(
            vocab_size=64, hidden_size=32, num_layers=2, num_heads=4,
            max_position_embeddings=128, hidden_dropout_prob=0.0,
            attention_dropout_prob=0.0))
        net.eval()
        prompts = [[1, 2, 3], [4, 5], [6, 7, 8, 9]]

        def run(mesh):
            cfg = LLMEngineConfig(num_slots=8, max_seq=64, warmup=False)
            eng = LLMEngine(net, cfg, mesh=mesh)
            try:
                reqs = [eng.submit(p, max_new_tokens=8) for p in prompts]
                return [r.result(timeout=120)["tokens"] for r in reqs]
            finally:
                eng.drain(timeout=60)

        plain = run(None)
        sharded = run(_model_mesh(4))
        # KV slots sharded over the model axis: every slot's rows live
        # whole on one device, so greedy decode is token-identical
        assert plain == sharded


# ---------------------------------------------------------------------------
class TestHTTPRouter:
    @pytest.fixture()
    def served(self):
        from paddle_tpu.serving.http import make_server
        router = _mk_router()
        srv = make_server(None, port=0, router=router)
        t = threading.Thread(target=srv.serve_forever, daemon=True)
        t.start()
        yield router, srv.server_address[1]
        srv.shutdown()
        srv.server_close()
        router.drain(timeout=30)

    def test_endpoints(self, served):
        router, port = served
        code, body = _get(port, "/healthz")
        assert code == 200 and body["status"] == "ok"
        assert len(body["replicas"]) == 2

        x = [[1.0, 2.0], [3.0, 4.0]]
        code, body = _post(port, "/predict", {"inputs": [x]})
        assert code == 200
        assert np.allclose(body["outputs"][0], np.asarray(x) * 2.0)

        code, body = _get(port, "/statsz")
        assert code == 200 and body["router"]["total_dispatched"] >= 1

        import urllib.request
        assert _wait_for(lambda: router.registry.labeled(
            "serving.router.replica_healthy"))
        with urllib.request.urlopen(
                f"http://127.0.0.1:{port}/metricsz") as r:
            text = r.read().decode()
        assert 'paddle_tpu_serving_router_replica_healthy{replica="0"}' \
            in text

    def test_drain_flips_healthz(self, served):
        router, port = served
        router.begin_drain()
        assert router._stopped.wait(timeout=30)
        code, body = _get(port, "/healthz")
        assert code == 503 and body["status"] == "draining"


# ---------------------------------------------------------------------------
class TestAcceptance2x4:
    """The issue's acceptance run: a 2-replica x 4-way-model router serves
    a GSPMD-partitioned predictor bitwise-identically to single-device,
    keeps serving while one replica drains unhealthy, and resurrects it
    from a health-stamped checkpoint."""

    @pytest.mark.timeout_s(240)
    def test_full_cycle(self, tmp_path):
        prefix = _export(tmp_path,
                         sharding=ShardingSpec(
                             {"model": 4},
                             inputs=[PartitionSpec("model")]))
        ckroot = tmp_path / "ckpts"
        ckroot.mkdir()
        good = str(ckroot / "step_10")
        bad = str(ckroot / "step_20")
        save_sharded({"w": np.zeros(2, np.float32)}, good)
        save_sharded({"w": np.ones(2, np.float32)}, bad)
        write_health_stamp(bad, healthy=False, reason="diverged")

        ref = create_predictor(Config(prefix).disable_sharding())
        rng = np.random.RandomState(3)
        sizes = [1, 2, 3, 4, 5, 6, 7, 8] * 2
        payloads = [rng.randn(n, 6).astype(np.float32) for n in sizes]
        serial = [ref.run([x])[0] for x in payloads]

        # batch buckets 4/8: every padded batch divides the 4-way model
        # axis, so the batch-sharded device_put always lands
        ecfg = EngineConfig(batch_buckets=[4, 8], max_batch=8,
                            max_batch_delay=0.01, max_queue=64)
        router = Router(
            predictor_replica_factory(prefix, ecfg),
            RouterConfig(num_replicas=2, model_axes={"model": 4},
                         health_interval=0.05, restart_backoff=0.02,
                         checkpoint_root=str(ckroot)),
            registry=StatRegistry())
        try:
            meshes = [r.mesh for r in router.replicas]
            assert all(m is not None for m in meshes)
            ids = [set(d.id for d in m.devices.flat) for m in meshes]
            assert ids[0].isdisjoint(ids[1])    # 2 x 4 disjoint sub-meshes
            assert all(r.boot_checkpoint == good for r in router.replicas)

            misses_before = default_cache().stats()["misses"]
            futs = [router.submit([x]) for x in payloads]
            for fut, want in zip(futs, serial):
                got, = fut.result(timeout=120)
                # a row of a padded batch against the row alone: equal to
                # the last places (this CPU backend rounds the two
                # executables apart by an ulp)
                np.testing.assert_array_max_ulp(got, want, maxulp=4)
            # the replicas compiled their own GSPMD executables (distinct
            # sharded cache keys; the reference's unsharded compiles all
            # happened before this window)
            assert default_cache().stats()["misses"] >= misses_before + 2
            st = router.stats()
            assert st["total_dispatched"] == len(payloads)
            counts = [p["dispatched"] for p in st["replicas"].values()]
            assert all(c > 0 for c in counts)

            # one replica turns unhealthy: drained, service continues
            r0 = router.replicas[0]
            r0.mark_unhealthy("sentinel divergence")
            with pytest.warns(UserWarning):
                assert _wait_for(lambda: r0.state == DEAD, timeout=60)
                assert router.healthz()["status"] == "degraded"
                for x, want in zip(payloads[:4], serial[:4]):
                    got, = router.submit([x]).result(timeout=120)
                    np.testing.assert_array_max_ulp(got, want, maxulp=4)
                # ... and resurrects from the health-stamped checkpoint
                assert _wait_for(lambda: r0.state == HEALTHY, timeout=120)
            assert r0.stats()["restarts"] == 1
            assert r0.boot_checkpoint == good
            assert _wait_for(
                lambda: router.healthz()["status"] == "ok", timeout=60)
            got, = router.submit([payloads[0]]).result(timeout=120)
            np.testing.assert_array_max_ulp(got, serial[0], maxulp=4)
        finally:
            router.drain(timeout=60)
        with pytest.raises(EngineDraining):
            router.submit([payloads[0]])


# ---------------------------------------------------------------------------
@pytest.mark.slow
class TestServeCLIPort0:
    @pytest.mark.timeout_s(240)
    def test_ephemeral_port_and_replicas(self, tmp_path):
        import subprocess
        import sys
        import urllib.request
        prefix = _export(tmp_path)
        env = dict(os.environ, JAX_PLATFORMS="cpu")
        proc = subprocess.Popen(
            [sys.executable, "-m", "paddle_tpu.serving", "serve",
             "--model", prefix, "--port", "0", "--replicas", "2",
             "--max-delay-ms", "1"],
            stdout=subprocess.PIPE, stderr=subprocess.STDOUT, text=True,
            env=env)
        try:
            port = None
            for _ in range(200):
                line = proc.stdout.readline()
                if not line:
                    break
                if line.startswith("PADDLE_TPU_SERVING_PORT="):
                    port = int(line.strip().split("=", 1)[1])
                    break
            assert port, "server never printed its port"
            with urllib.request.urlopen(
                    f"http://127.0.0.1:{port}/healthz", timeout=30) as r:
                body = json.loads(r.read())
            assert body["status"] == "ok"
            assert len(body["replicas"]) == 2
            proc.send_signal(signal.SIGTERM)
            assert proc.wait(timeout=120) == 0
        finally:
            if proc.poll() is None:
                proc.kill()
                proc.wait(timeout=30)
