"""Launcher + spawn tests (reference analogs: test_fleet_launch_*.sh driven
by dist_test.sh; test_spawn.py). A real 2-process CPU launch runs
init_parallel_env -> jax.distributed -> a cross-process allgather."""
import os
import subprocess
import sys
import textwrap

import numpy as np
import pytest


TRAIN_SCRIPT = textwrap.dedent("""
    import os, sys
    os.environ["JAX_PLATFORMS"] = "cpu"
    import jax
    sys.path.insert(0, {repo!r})
    import paddle_tpu.distributed as dist

    env = dist.init_parallel_env()
    assert dist.get_world_size() == 2, dist.get_world_size()
    import jax.numpy as jnp
    from jax.experimental import multihost_utils
    out = multihost_utils.process_allgather(
        jnp.asarray([float(dist.get_rank() + 1)]))
    assert out.reshape(-1).tolist() == [1.0, 2.0], out
    print("RANK", dist.get_rank(), "OK", flush=True)
""")


@pytest.fixture
def train_script(tmp_path):
    path = tmp_path / "train.py"
    path.write_text(TRAIN_SCRIPT.format(repo="/root/repo"))
    return str(path)


class TestLauncher:
    @pytest.mark.slow
    def test_two_process_launch(self, train_script, tmp_path):
        log_dir = str(tmp_path / "logs")
        proc = subprocess.run(
            [sys.executable, "-m", "paddle_tpu.distributed.launch",
             "--nproc_per_node", "2", "--start_port", "12455",
             "--log_dir", log_dir, train_script],
            cwd="/root/repo", capture_output=True, text=True, timeout=180)
        logs = ""
        for rank in range(2):
            with open(os.path.join(log_dir, f"workerlog.{rank}")) as f:
                logs += f.read()
        assert proc.returncode == 0, (proc.stderr, logs)
        assert "RANK 0 OK" in logs and "RANK 1 OK" in logs

    @pytest.mark.slow
    def test_failing_child_tears_down(self, tmp_path):
        bad = tmp_path / "bad.py"
        bad.write_text(
            "import os, sys, time\n"
            "if os.environ['PADDLE_TRAINER_ID'] == '1':\n"
            "    sys.exit(3)\n"
            "time.sleep(60)\n")
        proc = subprocess.run(
            [sys.executable, "-m", "paddle_tpu.distributed.launch",
             "--nproc_per_node", "2", "--start_port", "12475", str(bad)],
            cwd="/root/repo", capture_output=True, text=True, timeout=60)
        assert proc.returncode == 3
        assert "exited with code 3" in proc.stderr

    def test_get_cluster_endpoints(self):
        from paddle_tpu.distributed.launch import get_cluster
        eps = get_cluster(["10.0.0.1", "10.0.0.2"], 2, 6070)
        assert eps == ["10.0.0.1:6070", "10.0.0.1:6071",
                       "10.0.0.2:6070", "10.0.0.2:6071"]


def _spawn_target(value):
    import os
    rank = int(os.environ["PADDLE_TRAINER_ID"])
    assert os.environ["PADDLE_TRAINERS_NUM"] == "2"
    if value != 42:
        raise ValueError("bad arg plumb")
    # write a marker so the parent can verify both ranks ran
    open(f"/tmp/spawn_ok_{rank}", "w").write("ok")


def _spawn_failer():
    import os
    if os.environ["PADDLE_TRAINER_ID"] == "1":
        raise RuntimeError("boom from rank 1")


class TestSpawn:
    @pytest.mark.slow
    def test_spawn_two_procs(self):
        import paddle_tpu.distributed as dist
        for r in range(2):
            try:
                os.remove(f"/tmp/spawn_ok_{r}")
            except FileNotFoundError:
                pass
        dist.spawn(_spawn_target, args=(42,), nprocs=2,
                   start_port=12495)
        for r in range(2):
            assert os.path.exists(f"/tmp/spawn_ok_{r}")

    def test_spawn_surfaces_child_error(self):
        import paddle_tpu.distributed as dist
        with pytest.raises(RuntimeError, match="boom from rank 1"):
            dist.spawn(_spawn_failer, nprocs=2, start_port=12515)
