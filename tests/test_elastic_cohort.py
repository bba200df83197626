"""Chaos end-to-end for cohort re-formation (docs/fault_tolerance.md,
"Surviving host loss").

A real 2-process CPU training job (``launch --elastic --step_deadline``,
DataParallel over the 2-device global mesh, per-epoch checkpoints through
TrainEpochRange) loses a host mid-step:

* ``collective_hang:3:hang`` wedges rank 0 inside its 3rd guarded step —
  the in-process stand-in for "my peer was SIGKILLed mid-allreduce". Rank 1
  then blocks inside a *real* collective (its dp gradient allreduce needs
  both processes), so its watchdog converts a genuinely hung XLA collective
  into exit 121 within the configured deadline.
* The cohort supervisor treats the 121s as one host-loss event: tears down
  the whole generation, bumps ``PADDLE_TPU_COHORT_GEN``, respawns, and the
  new generation restores from the newest committed multi-host checkpoint.
* Acceptance: the resumed run's final model state is **bit-identical** to
  an uninterrupted run at the same world size.

The shrink variant hard-kills rank 1 (``host_kill:3:crash``) under
``--shrink_on_loss``: generation 1 is a 1-process world whose restore
re-shards the 2-host checkpoint onto the smaller world.

Unit-level semantics (heartbeat, watchdog, supervisor state machine) live
in tests/test_elastic_runtime.py; this file is the end-to-end proof.
"""
import glob
import json
import os
import subprocess
import sys
import textwrap

import numpy as np
import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

# Guarded-step deadline for the e2e: must clear the WORST honest epoch
# (first-epoch XLA compile + checkpoint commit can take tens of seconds on
# a loaded CI box) while staying far under the 3600s injected hang, so a
# firing is unambiguous evidence of the hang, never of a slow compile.
DEADLINE_S = 30.0

# 6 epochs, committed every epoch. The chaos fires on the 3rd guarded
# epoch (index 2), so epochs 0-1 are committed when the world wedges and
# the resumed generation re-runs epochs 2-5 exactly.
TRAIN_SCRIPT = """
    import json, os, sys
    ckpt_dir, out_dir = sys.argv[1], sys.argv[2]
    nprocs = int(os.environ.get("PADDLE_TRAINERS_NUM", "1"))
    rank = os.environ.get("PADDLE_TRAINER_ID", "0")
    gen = os.environ.get("PADDLE_TPU_COHORT_GEN", "0")
    chaos = os.environ.get("TEST_COHORT_CHAOS", "")
    if chaos and gen == "0":
        spec = {"hang": {"0": "collective_hang:3:hang"},
                "kill": {"1": "host_kill:3:crash",
                         "0": "collective_hang:3:hang"}}[chaos].get(rank)
        if spec:
            os.environ["PADDLE_TPU_FAULT_SPEC"] = spec
    os.environ["XLA_FLAGS"] = (os.environ.get("XLA_FLAGS", "")
        + f" --xla_force_host_platform_device_count={2 // nprocs}")
    os.environ["JAX_PLATFORMS"] = "cpu"
    import jax
    sys.path.insert(0, REPO)
    import numpy as np
    import paddle_tpu as paddle
    import paddle_tpu.distributed as dist
    import paddle_tpu.optimizer as optim
    from paddle_tpu.incubate.checkpoint import TrainEpochRange

    dist.init_parallel_env()
    world = dist.get_world_size()
    assert jax.device_count() == 2, jax.device_count()
    dist.set_mesh(dist.build_mesh({"dp": 2}))

    paddle.seed(42)
    net = paddle.nn.Sequential(
        paddle.nn.Linear(8, 16), paddle.nn.ReLU(),
        paddle.nn.Linear(16, 4))
    net = dist.DataParallel(net)
    opt = optim.Momentum(learning_rate=0.1, momentum=0.9,
                         parameters=net.parameters())
    ce = paddle.nn.CrossEntropyLoss()

    rng = np.random.RandomState(7)           # same global data everywhere
    X = rng.randn(6, 8, 8).astype(np.float32)
    Y = rng.randint(0, 4, (6, 8)).astype(np.int64)

    r = TrainEpochRange(6, "job_cohort", model=net, optimizer=opt,
                        checkpoint_path=ckpt_dir, keep_last=16)
    losses = []
    for epoch in r:
        if world > 1:
            lo = int(rank) * (8 // world)
            xb = dist.build_global_batch(X[epoch, lo:lo + 8 // world])
            yb = dist.build_global_batch(Y[epoch, lo:lo + 8 // world])
        else:
            xb = dist.shard_batch(paddle.to_tensor(X[epoch]))
            yb = dist.shard_batch(paddle.to_tensor(Y[epoch]))
        loss = ce(net(xb), yb)
        loss.backward()
        opt.step()
        opt.clear_grad()
        losses.append(float(np.asarray(
            loss._data if hasattr(loss, "_data") else loss)))
    print("COHORT_LOSSES " + json.dumps(losses), flush=True)
    state = {k: np.asarray(v.numpy()) for k, v in net.state_dict().items()}
    np.savez(os.path.join(out_dir, f"state_g{gen}_r{rank}.npz"), **state)
    print(f"TRAIN DONE gen={gen} world={world} "
          f"restored={r.restored_epoch}", flush=True)
"""


def _write_script(tmp_path):
    p = tmp_path / "cohort_train.py"
    p.write_text("REPO = " + repr(REPO) + "\n"
                 + textwrap.dedent(TRAIN_SCRIPT))
    return str(p)


def _launch(script, ckpt_dir, out_dir, log_dir, start_port, chaos="",
            extra_args=(), timeout=600):
    env = {k: v for k, v in os.environ.items()
           if k not in ("PADDLE_TPU_FAULT_SPEC", "TEST_COHORT_CHAOS",
                        "PADDLE_TPU_COHORT_GEN")}
    if chaos:
        env["TEST_COHORT_CHAOS"] = chaos
    env["PADDLE_TPU_RESTART_BACKOFF"] = "0.05"
    os.makedirs(out_dir, exist_ok=True)
    return subprocess.run(
        [sys.executable, "-m", "paddle_tpu.distributed.launch",
         "--nproc_per_node", "2", "--start_port", str(start_port),
         "--log_dir", log_dir, "--elastic",
         "--step_deadline", str(DEADLINE_S),
         "--grace_period", "8", *extra_args, script,
         str(ckpt_dir), str(out_dir)],
        cwd=REPO, capture_output=True, text=True, timeout=timeout, env=env)


def _workerlogs(log_dir, n=2):
    out = {}
    for rank in range(n):
        p = os.path.join(log_dir, f"workerlog.{rank}")
        out[rank] = open(p).read() if os.path.exists(p) else "(none)"
    return out


def _losses(text):
    got = None
    for line in text.splitlines():
        if line.startswith("COHORT_LOSSES "):
            got = json.loads(line[len("COHORT_LOSSES "):])
    return got


def _state(out_dir, gen, rank):
    path = os.path.join(out_dir, f"state_g{gen}_r{rank}.npz")
    assert os.path.exists(path), sorted(os.listdir(out_dir))
    with np.load(path) as z:
        return {k: z[k] for k in z.files}


@pytest.mark.slow
@pytest.mark.timeout_s(900)
def test_host_loss_watchdog_reform_bit_identical(tmp_path):
    script = _write_script(tmp_path)

    # uninterrupted reference at the same world size
    clean = _launch(script, tmp_path / "ckpt_clean", tmp_path / "out_clean",
                    str(tmp_path / "logs_clean"), start_port=12731)
    clean_logs = _workerlogs(str(tmp_path / "logs_clean"))
    assert clean.returncode == 0, (clean.stderr[-3000:], clean_logs)
    # the reference must be genuinely uninterrupted — a reform here means
    # the deadline is tighter than an honest epoch on this machine
    assert "re-forming" not in clean.stderr, clean.stderr[-3000:]
    ref_losses = _losses(clean_logs[0])
    assert ref_losses is not None and len(ref_losses) == 6

    # chaos run: rank 0's 3rd guarded step hangs "mid-allreduce"; rank 1
    # wedges inside the real dp collective and its watchdog must fire
    chaos = _launch(script, tmp_path / "ckpt", tmp_path / "out",
                    str(tmp_path / "logs"), start_port=12741, chaos="hang")
    logs = _workerlogs(str(tmp_path / "logs"))
    assert chaos.returncode == 0, (chaos.stderr[-3000:], logs)

    # the supervisor re-formed exactly once, on the host-lost exit code
    assert "re-forming" in chaos.stderr, chaos.stderr[-3000:]
    assert "generation 1 up" in chaos.stderr
    assert "TRAIN DONE gen=1 world=2" in logs[0], logs[0][-1500:]
    # the resumed generation restored the last committed epoch, it did not
    # retrain from scratch
    assert "restored=1" in logs[0]

    # the watchdog's terminal path dumped a flight record before exit 121
    dumps = glob.glob(os.path.join(str(tmp_path / "logs"),
                                   "flight_*.jsonl"))
    assert dumps, "no watchdog flight dump landed in the log dir"
    header = json.loads(open(dumps[0]).readline())
    assert header["schema"] == "paddle-tpu-flight/2"
    assert header["process_count"] == 2
    fired = [json.loads(line) for d in dumps for line in open(d)
             if '"distributed.watchdog_fired"' in line]
    assert fired and all(f["elapsed_s"] >= DEADLINE_S for f in fired)

    # the acceptance bar: bit-identical final state vs the clean run
    for rank in ("0", "1"):
        got = _state(str(tmp_path / "out"), 1, rank)
        want = _state(str(tmp_path / "out_clean"), 0, rank)
        assert sorted(got) == sorted(want)
        for k in want:
            np.testing.assert_array_equal(
                got[k], want[k],
                err_msg=f"rank {rank} param {k} diverged after reform")
    # and the resumed loss curve is the clean curve's tail
    resumed = _losses(logs[0])
    np.testing.assert_allclose(resumed, ref_losses[-len(resumed):],
                               rtol=1e-6, atol=1e-7)


@pytest.mark.slow
@pytest.mark.timeout_s(900)
def test_shrink_to_fit_reforms_smaller_world(tmp_path):
    script = _write_script(tmp_path)
    res = _launch(script, tmp_path / "ckpt", tmp_path / "out",
                  str(tmp_path / "logs"), start_port=12751, chaos="kill",
                  extra_args=("--shrink_on_loss",))
    logs = _workerlogs(str(tmp_path / "logs"))
    assert res.returncode == 0, (res.stderr[-3000:], logs)
    assert "shrink-to-fit" in res.stderr, res.stderr[-3000:]
    # generation 1 is a 1-process world: the 2-host checkpoint re-sharded
    # onto it, training resumed from the last committed epoch
    assert "TRAIN DONE gen=1 world=1" in logs[0], logs[0][-1500:]
    assert "restored=1" in logs[0]
    state = _state(str(tmp_path / "out"), 1, "0")
    assert state  # the re-sharded restore produced a full state dict
    # the resumed generation ran exactly the un-committed epochs (2..5)
    # and stayed numerically sane through the re-sharded restore
    losses = _losses(logs[0])
    assert losses is not None and len(losses) == 4
    assert all(np.isfinite(losses))
