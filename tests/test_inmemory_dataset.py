"""InMemoryDataset / QueueDataset — the reference's industrial bulk
pipeline (fleet/dataset/dataset.py:253 over data_set.h:43): file-sharded
ingestion, local + global shuffle, batch iteration; the 2-process global
shuffle runs through the launcher and must partition the instance set.
"""
import json
import os
import subprocess
import sys
import textwrap

import numpy as np
import pytest

import paddle_tpu.distributed as dist

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _write_files(tmp_path, n_files=4, rows_per=8, width=3):
    files = []
    v = 0
    for i in range(n_files):
        p = tmp_path / f"part-{i:03d}.txt"
        with open(p, "w") as f:
            for _ in range(rows_per):
                f.write(" ".join(str(v * width + j) for j in range(width))
                        + "\n")
                v += 1
        files.append(str(p))
    return files


def test_load_and_batches(tmp_path):
    files = _write_files(tmp_path)
    ds = dist.InMemoryDataset()
    ds.init(batch_size=5, thread_num=2)
    ds.set_filelist(files)
    ds.load_into_memory()
    assert ds.get_memory_data_size() == 32
    batches = list(ds.batch_iterator())
    assert [b.shape for b in batches] == [(5, 3)] * 6 + [(2, 3)]
    # all 32 rows present exactly once
    allrows = np.concatenate(batches)
    assert sorted(allrows[:, 0].tolist()) == [float(3 * i) for i in range(32)]
    # drop_last
    ds.init(batch_size=5, thread_num=2, drop_last=True)
    assert len(list(ds.batch_iterator())) == 6 and len(ds) == 6


def test_local_shuffle_deterministic(tmp_path):
    files = _write_files(tmp_path)
    ds = dist.InMemoryDataset()
    ds.init(batch_size=32)
    ds.set_filelist(files)
    ds.load_into_memory()
    before = np.concatenate(list(ds.batch_iterator()))
    ds.local_shuffle(seed=7)
    after = np.concatenate(list(ds.batch_iterator()))
    assert not np.array_equal(before, after)
    np.testing.assert_allclose(np.sort(before[:, 0]), np.sort(after[:, 0]))
    # single-process global_shuffle degenerates to local
    ds.global_shuffle(seed=7)
    assert ds.get_shuffle_data_size() == 32


def test_custom_parse_fn_tuple_samples(tmp_path):
    p = tmp_path / "f.txt"
    p.write_text("1 2 3 0\n4 5 6 1\n7 8 9 0\n")
    ds = dist.InMemoryDataset()
    ds.init(batch_size=2, parse_fn=lambda line: (
        np.asarray([float(v) for v in line.split()[:-1]], np.float32),
        np.int64(line.split()[-1])))
    ds.set_filelist([str(p)])
    ds.load_into_memory()
    x, y = next(iter(ds))
    assert x.shape == (2, 3) and y.shape == (2,)
    np.testing.assert_array_equal(y, [0, 1])


def test_queue_dataset_streams(tmp_path):
    files = _write_files(tmp_path, n_files=2, rows_per=5)
    ds = dist.QueueDataset()
    ds.init(batch_size=4)
    ds.set_filelist(files)
    got = np.concatenate(list(ds))
    assert got.shape == (10, 3)
    with pytest.raises(RuntimeError):
        ds.local_shuffle()


GLOBAL_SHUFFLE_SCRIPT = textwrap.dedent("""
    import json, os, sys
    os.environ["JAX_PLATFORMS"] = "cpu"
    import jax
    sys.path.insert(0, {repo!r})
    import numpy as np
    import paddle_tpu.distributed as dist

    dist.init_parallel_env()
    files = json.loads(os.environ["DS_FILES"])
    ds = dist.InMemoryDataset()
    ds.init(batch_size=4, thread_num=2)
    ds.set_filelist(files)
    ds.load_into_memory()
    total_before = ds.get_memory_data_size()
    ds.global_shuffle(seed=3)
    mine = sorted(int(b[0]) // 3 for b in ds._samples)
    print("DS_RESULT " + json.dumps({{
        "rank": dist.get_rank(), "total": total_before, "mine": mine,
        "post_total": ds.get_shuffle_data_size()}}), flush=True)
""")


@pytest.mark.slow
@pytest.mark.timeout_s(300)
def test_global_shuffle_partitions_two_procs(tmp_path):
    files = _write_files(tmp_path, n_files=4, rows_per=8)
    script = tmp_path / "gs.py"
    script.write_text(GLOBAL_SHUFFLE_SCRIPT.format(repo=REPO))
    log_dir = str(tmp_path / "logs")
    env = {**os.environ, "DS_FILES": json.dumps(files)}
    proc = subprocess.run(
        [sys.executable, "-m", "paddle_tpu.distributed.launch",
         "--nproc_per_node", "2", "--start_port", "12641",
         "--log_dir", log_dir, str(script)],
        cwd=REPO, capture_output=True, text=True, timeout=240, env=env)
    results = {}
    for rank in range(2):
        with open(os.path.join(log_dir, f"workerlog.{rank}")) as f:
            for line in f:
                if line.startswith("DS_RESULT "):
                    r = json.loads(line[len("DS_RESULT "):])
                    results[r["rank"]] = r
    assert proc.returncode == 0, (proc.stderr, results)
    assert set(results) == {0, 1}
    # file-level sharding before shuffle: each proc saw 16 of 32; totals
    # are global
    assert results[0]["total"] == results[1]["total"] == 32
    assert results[0]["post_total"] == 32
    # after global shuffle: a disjoint partition of all 32 instances
    m0, m1 = set(results[0]["mine"]), set(results[1]["mine"])
    assert m0.isdisjoint(m1)
    assert m0 | m1 == set(range(32))
    # hash-routing actually crossed processes (not identity)
    assert m0 != set(range(16))
