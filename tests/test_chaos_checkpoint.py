"""Chaos campaign for the async checkpointer (docs/fault_tolerance.md,
"Async checkpointing" crash matrix).

A real training subprocess (TrainEpochRange with ``async_save=True``) is
hard-killed at randomized points of the commit pipeline — snapshot fetch,
shard write, just before and just after the atomic rename, and (for
re-saves over the same path) inside the swap window where the previous
commit is parked as ``*.old`` — via the
``kill_during_commit`` fault action (``os._exit``, no cleanup, same as a
SIGKILL from the checkpoint's point of view), plus one case with an
actual ``SIGKILL`` landed from outside while ``slow_io`` holds the commit
window open. After every crash:

* no published (non-``.tmp``) checkpoint is torn — each one passes full
  checksum verification, and
* a plain rerun resumes from the newest intact commit and finishes with a
  final state_dict bit-identical to an uninterrupted run.

Unit-level protocol tests live in tests/test_async_checkpoint.py; this
file is the end-to-end proof.
"""
import os
import random
import signal
import subprocess
import sys
import textwrap
import time

import numpy as np
import pytest

from paddle_tpu.incubate.checkpoint import (OLD_SUFFIX, STAGING_SUFFIX,
                                            verify_checkpoint)
from paddle_tpu.utils.resilience import FAULT_CRASH_EXIT_CODE

#: the four commit-pipeline stations, in pipeline order
SITES = ("ckpt_fetch", "ckpt_shard_write", "ckpt_pre_rename",
         "ckpt_post_rename")

# 4 epochs, save every epoch, async writer: the first save and the final
# drained save are always processed even under maximal coalescing, so any
# occurrence in {1, 2} of every site is guaranteed to fire.
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
    paddle.seed(11)
    net = nn.Linear(4, 2)
    opt = optim.SGD(learning_rate=0.05, parameters=net.parameters())
    rng = np.random.RandomState(3)
    X = rng.randn(16, 4).astype(np.float32)
    Y = rng.randn(16, 2).astype(np.float32)

    r = TrainEpochRange(4, "job_chaos", model=net, optimizer=opt,
                        checkpoint_path=ckpt_dir, async_save=True,
                        keep_last=8)
    for epoch in r:
        x, y = paddle.to_tensor(X), paddle.to_tensor(Y)
        loss = paddle.mean((net(x) - y) ** 2)
        loss.backward()
        opt.step()
        opt.clear_grad()
        print("epoch", epoch, flush=True)

    state = {k: np.asarray(v.numpy())
             for k, v in net.state_dict().items()}
    np.savez(out_npz, **state)
    print("TRAIN DONE", flush=True)
"""


def _write_script(tmp_path):
    p = tmp_path / "train.py"
    p.write_text(textwrap.dedent(TRAIN_SCRIPT))
    return str(p)


# Re-saves over the SAME path (FaultToleranceCallback's "latest" pattern):
# the swap parks commit #1 as *.old before publishing commit #2, so a kill
# inside that window must leave the parked commit recoverable — never a
# zero-checkpoint state.
RESAVE_SCRIPT = """
    import os, sys
    os.environ["JAX_PLATFORMS"] = "cpu"
    import jax
    sys.path.insert(0, "/root/repo")
    import numpy as np
    from paddle_tpu.incubate.checkpoint import commit_checkpoint
    path = sys.argv[1]
    commit_checkpoint({"w": np.arange(4.0)}, path, step=1)
    commit_checkpoint({"w": np.arange(4.0) * 2}, path, step=2)
    print("RESAVE DONE", flush=True)
"""


def _run(script, ckpt_dir, out_npz, extra_env=None, timeout=240):
    env = {k: v for k, v in os.environ.items()
           if k != "PADDLE_TPU_FAULT_SPEC"}
    env.update(extra_env or {})
    return subprocess.run(
        [sys.executable, script, str(ckpt_dir), str(out_npz)],
        capture_output=True, text=True, timeout=timeout, env=env,
        cwd="/root/repo")


def _assert_no_torn_survivor(job_dir):
    """Every PUBLISHED checkpoint must be intact — the atomic-rename
    protocol means a crash can leave staging debris but never a
    half-written final directory."""
    if not os.path.isdir(job_dir):
        return
    for name in sorted(os.listdir(job_dir)):
        full = os.path.join(job_dir, name)
        if not os.path.isdir(full) or name.endswith(STAGING_SUFFIX):
            continue
        if name.startswith("epoch_"):
            verify_checkpoint(full)  # raises CheckpointIntegrityError if torn


def _assert_bit_identical(golden_npz, got_npz):
    a, b = np.load(golden_npz), np.load(got_npz)
    assert sorted(a.files) == sorted(b.files)
    for k in a.files:
        assert a[k].dtype == b[k].dtype
        assert np.array_equal(a[k], b[k]), (
            f"state {k} diverged after crash+resume")


@pytest.fixture(scope="module")
def golden(tmp_path_factory):
    """One uninterrupted run; (script_path, final-state npz path)."""
    root = tmp_path_factory.mktemp("chaos_golden")
    script = _write_script(root)
    out = str(root / "golden.npz")
    proc = _run(script, root / "ck_golden", out)
    assert proc.returncode == 0, (proc.stdout, proc.stderr)
    return script, out


class TestChaosMatrix:
    @pytest.mark.parametrize("site", SITES)
    def test_kill_during_commit_resumes_bit_identical(self, site, tmp_path,
                                                      golden):
        script, golden_npz = golden
        # randomized-but-reproducible kill point within the pipeline
        occurrence = random.Random(f"chaos-{site}").choice((1, 2))
        ckpt_dir = tmp_path / "ck"
        out = str(tmp_path / "out.npz")

        crashed = _run(script, ckpt_dir, out, extra_env={
            "PADDLE_TPU_FAULT_SPEC":
                f"{site}:{occurrence}:kill_during_commit"})
        assert crashed.returncode == FAULT_CRASH_EXIT_CODE, (
            site, occurrence, crashed.stdout, crashed.stderr)
        assert f"[FaultInjector] kill_during_commit at {site}" \
            in crashed.stdout + crashed.stderr
        assert not os.path.exists(out)  # died before finishing

        job_dir = str(ckpt_dir / "job_chaos")
        _assert_no_torn_survivor(job_dir)

        resumed = _run(script, ckpt_dir, out)
        assert resumed.returncode == 0, (resumed.stdout, resumed.stderr)
        _assert_bit_identical(golden_npz, out)
        # the rerun's startup sweep cleared any staging debris
        if os.path.isdir(job_dir):
            assert not [n for n in os.listdir(job_dir)
                        if n.endswith(STAGING_SUFFIX)]

    def test_kill_inside_swap_window_recovers_parked_commit(self, tmp_path):
        """Kill between parking the old checkpoint and publishing the new
        one, re-saving the SAME path — the window where the pre-fix
        protocol (rmtree before replace) left ZERO restorable checkpoints.
        The parked *.old commit must be recovered on restart."""
        import numpy as np
        from paddle_tpu.incubate.checkpoint import (cleanup_stale_staging,
                                                    load_sharded)
        script = str(tmp_path / "resave.py")
        with open(script, "w") as f:
            f.write(textwrap.dedent(RESAVE_SCRIPT))
        path = str(tmp_path / "latest")

        # occurrence 1: the first commit has nothing to park, so the site
        # first fires during commit #2's swap
        crashed = _run(script, path, "unused", extra_env={
            "PADDLE_TPU_FAULT_SPEC": "ckpt_swap_window:1:kill_during_commit"})
        assert crashed.returncode == FAULT_CRASH_EXIT_CODE, (
            crashed.stdout, crashed.stderr)
        assert not os.path.isdir(path)          # mid-swap: final not yet in
        assert os.path.isdir(path + OLD_SUFFIX)  # ...but commit #1 is parked

        # the startup sweep un-parks commit #1 and drops the staged debris
        cleanup_stale_staging(str(tmp_path))
        verify_checkpoint(path)
        out = load_sharded(path, return_tensor=False)
        np.testing.assert_allclose(out["w"], np.arange(4.0))
        assert not os.path.isdir(path + OLD_SUFFIX)
        assert not os.path.isdir(path + STAGING_SUFFIX)

        # a clean rerun republishes the newer state over the recovered one
        ok = _run(script, path, "unused")
        assert ok.returncode == 0, (ok.stdout, ok.stderr)
        out = load_sharded(path, return_tensor=False)
        np.testing.assert_allclose(out["w"], np.arange(4.0) * 2)
        assert not os.path.isdir(path + OLD_SUFFIX)

    def test_external_sigkill_mid_commit_window(self, tmp_path, golden):
        """A real SIGKILL from outside, landed while slow_io holds the
        pre-rename window open (staging on disk, final not yet renamed) —
        the nastiest torn-state candidate."""
        script, golden_npz = golden
        ckpt_dir = tmp_path / "ck"
        out = str(tmp_path / "out.npz")
        job_dir = str(ckpt_dir / "job_chaos")

        env = {k: v for k, v in os.environ.items()
               if k != "PADDLE_TPU_FAULT_SPEC"}
        env["PADDLE_TPU_FAULT_SPEC"] = "ckpt_pre_rename:1:slow_io"
        env["PADDLE_TPU_FAULT_SLOW_IO_S"] = "60"
        proc = subprocess.Popen(
            [sys.executable, script, str(ckpt_dir), out],
            stdout=subprocess.DEVNULL, stderr=subprocess.DEVNULL, env=env,
            cwd="/root/repo")
        try:
            deadline = time.monotonic() + 120
            staged = None
            while time.monotonic() < deadline:
                if os.path.isdir(job_dir):
                    staged = [n for n in os.listdir(job_dir)
                              if n.endswith(STAGING_SUFFIX)]
                    if staged:
                        break
                if proc.poll() is not None:
                    pytest.fail("trainer exited before staging appeared "
                                f"(rc={proc.returncode})")
                time.sleep(0.02)
            assert staged, "never saw a staging dir inside the slow_io window"
            proc.send_signal(signal.SIGKILL)
            assert proc.wait(timeout=30) == -signal.SIGKILL
        finally:
            if proc.poll() is None:
                proc.kill()
                proc.wait(timeout=30)

        _assert_no_torn_survivor(job_dir)
        resumed = _run(script, ckpt_dir, out)
        assert resumed.returncode == 0, (resumed.stdout, resumed.stderr)
        _assert_bit_identical(golden_npz, out)
        assert not [n for n in os.listdir(job_dir)
                    if n.endswith(STAGING_SUFFIX)]
