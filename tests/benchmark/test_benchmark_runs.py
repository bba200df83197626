"""Every cell's ``--rehearse-on-cpu`` path end to end at toy width; no
result without a TPU; and a run whose timed path is broken underneath comes
out as not correct."""
import json
import os
import subprocess
import sys

import pytest

from benchmark import spec

pytestmark = pytest.mark.timeout_s(600)
BENCH = spec.load_benchmark()
CELLS = [c["name"] for c in BENCH["workloads"]]


def _run(args, tmp_path, cwd=spec.ROOT, extra_path=()):
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               JAX_COMPILATION_CACHE_DIR=str(tmp_path / "jax_cache"),
               PYTHONPATH=os.pathsep.join([*extra_path, spec.ROOT]))
    return subprocess.run([sys.executable, *BENCH["command"][1:], *args],
                          cwd=cwd, env=env, capture_output=True, text=True,
                          timeout=500)


@pytest.mark.parametrize("cell", CELLS)
def test_rehearsal_runs_end_to_end_and_prints_no_metric(cell, tmp_path):
    out = _run(["--workload", cell, "--seed", str(2**31 + 5), "--seconds",
                "1", "--trace", "1", "--rehearse-on-cpu"], tmp_path)
    assert out.returncode == 0, out.stderr[-2000:]
    last = json.loads(out.stdout.strip().splitlines()[-1])
    assert last["rehearsal"] is True and last["metrics"] == {}
    assert last["correct"] is True and last["failed"] == 0
    assert last["attempted"] > 0
    assert last["device"]["platform"] == "cpu"
    assert "check: " in out.stdout and "(limit " in out.stdout
    # the per-layer readers ran (their values are not printed off the chip)
    assert any(n.startswith("compiles_in_window")
               for n in last["readers_that_found_something"])


def test_no_tpu_no_result(tmp_path):
    out = _run(["--workload", CELLS[0], "--seed", "1", "--seconds", "1",
                "--trace", "0"], tmp_path)
    assert out.returncode == 1 and out.stdout.strip() == ""
    assert "no TPU" in out.stderr


def test_unknown_workload_is_refused(tmp_path):
    out = _run(["--workload", "nope", "--seed", "1", "--seconds", "1",
                "--trace", "0", "--rehearse-on-cpu"], tmp_path)
    assert out.returncode != 0 and "no workload" in out.stderr + out.stdout


# -- the timed path broken underneath -------------------------------------------

def _main(monkeypatch, tmp_path, capsys, cell):
    from benchmark import run
    monkeypatch.setattr("benchmark.harness.place_compile_cache",
                        lambda root: str(tmp_path))
    rc = run.main(["--workload", cell, "--seed", "11", "--seconds", "1",
                   "--trace", "0", "--rehearse-on-cpu"])
    assert rc == 0
    return json.loads(capsys.readouterr().out.strip().splitlines()[-1])


def test_a_served_token_altered_where_it_is_produced_is_not_correct(
        monkeypatch, tmp_path, capsys):
    from paddle_tpu.serving.llm import scheduler
    real = scheduler.GenerationRequest._emit

    def emit(self, tok):
        # every fifth token of a request comes out as its neighbour's pair
        # partner's neighbour: a wrong token, at the place it is delivered
        if len(self.tokens) % 5 == 4:
            tok = (tok + 2) % 512
        return real(self, tok)

    monkeypatch.setattr(scheduler.GenerationRequest, "_emit", emit)
    last = _main(monkeypatch, tmp_path, capsys, "serve-gpt1p3b-decode")
    assert last["correct"] is False and last["attempted"] > 0


def test_a_train_step_that_keeps_its_state_is_not_correct(
        monkeypatch, tmp_path, capsys):
    import paddle_tpu as paddle
    monkeypatch.setattr(paddle.optimizer.AdamW, "_update",
                        lambda self, p, g, s, lr, step, ctx=None: (p, s))
    last = _main(monkeypatch, tmp_path, capsys, "train-gpt2s-s4096")
    assert last["correct"] is False and last["attempted"] > 0
