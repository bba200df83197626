"""Elastic launcher tests (docs/fault_tolerance.md).

Supervisor semantics (restart budget, free preemption restarts, workerlog
tailing, graceful drain) are exercised in-process with throwaway stdlib
child scripts — no paddle import per child, so they're tier-1 fast. The
end-to-end proof (injected crash at epoch 3 of 4 under ``--elastic``,
bit-identical final state vs an uninterrupted run) runs the real CLI.
"""
import os
import signal
import subprocess
import sys
import textwrap
import threading

import numpy as np
import pytest

from paddle_tpu.distributed.launch import ElasticSupervisor, _tail_log
from paddle_tpu.distributed.elastic import (PREEMPTION_EXIT_CODE,
                                            ELASTIC_ENV_VAR)
from paddle_tpu.utils.resilience import FAULT_CRASH_EXIT_CODE


def _write(tmp_path, name, body):
    p = tmp_path / name
    p.write_text(textwrap.dedent(body))
    return str(p)


def _supervise(tmp_path, script, max_restarts=2, grace_period=5.0,
               log_dir=None, capsys=None):
    sup = ElasticSupervisor(
        ["127.0.0.1:0"], script, [], log_dir=log_dir,
        max_restarts=max_restarts, grace_period=grace_period,
        restart_backoff=0.05, poll_interval=0.05)
    return sup, sup.run()


class TestElasticSupervisor:
    def test_crash_once_then_succeed(self, tmp_path, capsys):
        marker = tmp_path / "ran_once"
        script = _write(tmp_path, "child.py", f"""
            import os, sys
            m = {str(marker)!r}
            if not os.path.exists(m):
                open(m, "w").write("x")
                sys.exit(7)   # first incarnation crashes
            sys.exit(0)       # restarted incarnation succeeds
        """)
        sup, rc = _supervise(tmp_path, script, max_restarts=2)
        assert rc == 0
        assert sup.restarts_used == 1
        err = capsys.readouterr().err
        assert "exited with code 7" in err and "restarting in" in err

    def test_restart_budget_exhaustion_propagates_exit_code(
            self, tmp_path, capsys):
        script = _write(tmp_path, "child.py", """
            import sys
            print("boom-diagnostic-line", flush=True)
            sys.exit(9)
        """)
        log_dir = str(tmp_path / "logs")
        sup, rc = _supervise(tmp_path, script, max_restarts=1,
                             log_dir=log_dir)
        assert rc == 9
        assert sup.restarts_used == 1
        err = capsys.readouterr().err
        assert "budget (1) exhausted" in err
        # the dead rank's workerlog was tailed into supervisor stderr
        assert "workerlog.0 (tail)" in err
        assert "boom-diagnostic-line" in err

    def test_preemption_exit_restarts_for_free(self, tmp_path, capsys):
        marker = tmp_path / "preempted_once"
        ok = tmp_path / "finished"
        script = _write(tmp_path, "child.py", f"""
            import os, sys
            assert os.environ.get({ELASTIC_ENV_VAR!r}) == "1"
            m = {str(marker)!r}
            if not os.path.exists(m):
                open(m, "w").write("x")
                sys.exit({PREEMPTION_EXIT_CODE})  # drained after preemption
            open({str(ok)!r}, "w").write("x")
            sys.exit(0)
        """)
        # max_restarts=0: only a free (preemption) restart can succeed
        sup, rc = _supervise(tmp_path, script, max_restarts=0)
        assert rc == 0
        assert ok.exists()
        assert sup.restarts_used == 0
        assert "free" in capsys.readouterr().err

    def test_restart_env_counter_and_workerlog_append(self, tmp_path):
        script = _write(tmp_path, "child.py", """
            import os, sys
            n = int(os.environ["PADDLE_TPU_RESTART_NUM"])
            print("incarnation", n, flush=True)
            sys.exit(5 if n == 0 else 0)
        """)
        log_dir = str(tmp_path / "logs")
        sup, rc = _supervise(tmp_path, script, max_restarts=1,
                             log_dir=log_dir)
        assert rc == 0
        log = open(os.path.join(log_dir, "workerlog.0")).read()
        # both incarnations in ONE file, separated by a restart marker
        assert "incarnation 0" in log and "incarnation 1" in log
        assert "----- restart 1 -----" in log

    def test_graceful_drain_on_sigterm(self, tmp_path, capsys):
        drained = tmp_path / "drained"
        started = tmp_path / "started"
        script = _write(tmp_path, "child.py", f"""
            import os, signal, sys, time
            def onterm(signum, frame):
                open({str(drained)!r}, "w").write("x")
                sys.exit({PREEMPTION_EXIT_CODE})
            signal.signal(signal.SIGTERM, onterm)
            open({str(started)!r}, "w").write("x")
            time.sleep(60)
        """)
        sup = ElasticSupervisor(
            ["127.0.0.1:0"], script, [], max_restarts=2,
            grace_period=10.0, restart_backoff=0.05, poll_interval=0.05)

        def drain_when_started():
            import time
            for _ in range(400):
                if started.exists():
                    break
                time.sleep(0.05)
            sup.request_drain()

        t = threading.Thread(target=drain_when_started)
        t.start()
        rc = sup.run()
        t.join()
        assert rc == 1
        assert drained.exists()  # child got SIGTERM and drained in grace
        assert "draining" in capsys.readouterr().err

    def test_tail_log_missing_file(self):
        assert _tail_log(None) == ""
        assert _tail_log("/nonexistent/x.log") == ""


TRAIN_SCRIPT = """
    import os, sys
    os.environ["JAX_PLATFORMS"] = "cpu"
    import jax
    sys.path.insert(0, "/root/repo")
    import numpy as np
    import paddle_tpu as paddle
    import paddle_tpu.nn as nn
    import paddle_tpu.optimizer as optim
    from paddle_tpu.incubate.checkpoint import TrainEpochRange

    ckpt_dir, out_npz = sys.argv[1], sys.argv[2]
    paddle.seed(7)
    net = nn.Linear(4, 2)
    opt = optim.SGD(learning_rate=0.05, parameters=net.parameters())
    rng = np.random.RandomState(0)
    X = rng.randn(16, 4).astype(np.float32)
    Y = rng.randn(16, 2).astype(np.float32)

    r = TrainEpochRange(4, "job_e2e", model=net, optimizer=opt,
                        checkpoint_path=ckpt_dir)
    for epoch in r:
        x, y = paddle.to_tensor(X), paddle.to_tensor(Y)
        loss = paddle.mean((net(x) - y) ** 2)
        loss.backward()
        opt.step()
        opt.clear_grad()
        print("epoch", epoch, "loss", float(loss.numpy()), flush=True)

    state = {k: np.asarray(v.numpy())
             for k, v in net.state_dict().items()}
    np.savez(out_npz, **state)
    print("TRAIN DONE", flush=True)
"""


class TestElasticEndToEnd:
    def test_injected_crash_resumes_bit_identical(self, tmp_path):
        """Acceptance proof: --elastic --max_restarts 2 + crash injected at
        epoch 3 of 4 → job completes rc 0 and the restored run's final
        state_dict is bit-identical (CPU) to an uninterrupted run."""
        script = _write(tmp_path, "train.py", TRAIN_SCRIPT)
        env_base = {k: v for k, v in os.environ.items()}

        # uninterrupted reference run (no launcher, no faults)
        out_a = str(tmp_path / "a.npz")
        proc = subprocess.run(
            [sys.executable, script, str(tmp_path / "ckA"), out_a],
            capture_output=True, text=True, timeout=240, env=env_base,
            cwd="/root/repo")
        assert proc.returncode == 0, (proc.stdout, proc.stderr)

        # elastic run: hard crash at the start of the 3rd epoch iteration
        out_b = str(tmp_path / "b.npz")
        env = dict(env_base)
        env["PADDLE_TPU_FAULT_SPEC"] = "epoch:3:crash"
        log_dir = str(tmp_path / "logs")
        proc = subprocess.run(
            [sys.executable, "-m", "paddle_tpu.distributed.launch",
             "--elastic", "--max_restarts", "2", "--restart_backoff", "0.1",
             "--log_dir", log_dir, script, str(tmp_path / "ckB"), out_b],
            capture_output=True, text=True, timeout=420, env=env,
            cwd="/root/repo")
        assert proc.returncode == 0, (proc.stdout, proc.stderr)
        assert f"exited with code {FAULT_CRASH_EXIT_CODE}" in proc.stderr
        assert "restarting in" in proc.stderr
        log = open(os.path.join(log_dir, "workerlog.0")).read()
        assert "[FaultInjector] crash at epoch:3" in log
        assert "TRAIN DONE" in log

        a, b = np.load(out_a), np.load(out_b)
        assert sorted(a.files) == sorted(b.files)
        for k in a.files:
            assert a[k].dtype == b[k].dtype
            assert np.array_equal(a[k], b[k]), (
                f"state {k} diverged after crash+resume")
