"""The Qwen3-Next-80B-A3B configuration and its cell
``serve-qwen3next-longdoc``: the published sizes against the catalog row, the
cut and the share, the cell's entries and files, the costs' functions (counted
from the recurrence and the engine's counters alone), the rehearsal of the
cell on the CPU (correct) and its faults (the four the driver builds into the
ENGINE: no decay, no correction, rotary over every column, the shared expert
ungated, each through the whole run; the bfloat16 control through the
comparison itself: each not correct), the readers on an empty run and on a
made one, and the decode step and the chunk program compiled for a described
(not attached) TPU v5e at the cell's sizes, their memory recorded.

``test_benchmark_spec.py::test_every_file_the_benchmark_names_exists`` holds
every cell's driver to ``("fit", "closed", "open")`` and so fails on this
cell's ``closed_qwen3next`` as it does on the four before it, at that line
alone; the test of the entries below asserts the same things with the drivers
read from ``benchmark/drivers/``.

The compile describes the topology inside a fixture (see the
``on-chip-measurement`` guide); nothing runs and no number here is a
measurement.
"""
import json
import os
import re
import shutil
import subprocess
import sys

import numpy as np
import pytest

import jax
import jax.numpy as jnp
from jax.sharding import SingleDeviceSharding

from benchmark import (check, costs, costs_qwen3next, costs_trinity,
                       qwen3next_adapter, qwen3next_weights,
                       spec as bench_spec)
from benchmark.drivers import closed_qwen3next
from benchmark.reference import qwen3next_ref as ref

pytestmark = pytest.mark.timeout_s(1200)
CELL = "serve-qwen3next-longdoc"
CONFIG = "qwen3-next-80b-a3b"
BENCH = bench_spec.load_benchmark()
CATALOG = "/opt/skills/guides/model-configs/architectures.jsonl"
REDUCED = ["num_hidden_layers", "num_experts", "vocab_size"]


def _cell(rehearsal=False):
    return bench_spec.load_cell(BENCH, CELL, rehearsal=rehearsal)


# -- the configuration ------------------------------------------------------------

#: the catalog row's ``config`` but for what is reduced, as this PR read it
#: (the catalog itself is compared where it is present)
PUBLISHED = {
    "decoder_sparse_step": 1, "full_attention_interval": 4, "head_dim": 256,
    "hidden_act": "silu", "hidden_size": 2048, "intermediate_size": 5120,
    "linear_conv_kernel_dim": 4, "linear_key_head_dim": 128,
    "linear_num_key_heads": 16, "linear_num_value_heads": 32,
    "linear_value_head_dim": 128, "max_position_embeddings": 262144,
    "mlp_only_layers": [], "model_type": "qwen3_next",
    "moe_intermediate_size": 512, "norm_topk_prob": True,
    "num_attention_heads": 16, "num_experts_per_tok": 10,
    "num_key_value_heads": 2, "partial_rotary_factor": 0.25,
    "rms_norm_eps": 1e-06, "rope_scaling": None, "rope_theta": 10000000,
    "shared_expert_intermediate_size": 512, "tie_word_embeddings": False,
    "use_sliding_window": False}


@pytest.mark.parametrize("key", sorted(PUBLISHED))
def test_published_size_is_unchanged(key):
    assert _cell()["config_data"][key] == PUBLISHED[key]


def test_the_file_holds_the_catalogs_row_but_for_what_is_reduced():
    if not os.path.isfile(CATALOG):
        pytest.skip("no catalog beside the guide here")
    with open(CATALOG) as f:
        (row,) = [r for r in map(json.loads, f)
                  if r["name"] == "Qwen3-Next-80B-A3B-Instruct"]
    cfg = _cell()["config_data"]
    (entry,) = [c for c in BENCH["configs"] if c["name"] == CONFIG]
    assert entry["source"] == row["source_url"] == cfg["source"]
    differs = sorted(k for k, v in row["config"].items() if cfg.get(k) != v)
    assert differs == sorted(REDUCED) == sorted(entry["reduced"])
    assert sorted(cfg["changed"]) == sorted(REDUCED)
    assert PUBLISHED == {k: row["config"][k] for k in PUBLISHED}
    # the published values stand beside the cut ones
    assert "published 48" in cfg["changed"]["num_hidden_layers"]
    assert "published 512" in cfg["changed"]["num_experts"]
    assert "published 151,936" in cfg["changed"]["vocab_size"]
    # no width is among the cuts
    assert not any(k.endswith(("_dim", "_size", "_rank")) and k != "vocab_size"
                   for k in REDUCED)


@pytest.mark.parametrize("item", ref.ASSUMED)
def test_assumed_item_is_stated_with_its_source(item):
    assumed = _cell()["config_data"]["assumed"]
    assert item in assumed
    assert "modeling_qwen3_next" in assumed["source of every item"]
    assert "2412.06464" in assumed["source of every item"]


def test_the_cut_and_the_share_are_what_the_files_say():
    cfg = _cell()["config_data"]
    share = cfg["share"]
    assert share == {"chips_sharing_a_layer": 8, "num_experts_published": 512,
                     "experts_held": [0, 64], "vocab_size_published": 151936,
                     "vocab_rows": [0, 18992]}
    # two whole periods, and the guide's floors: 8 experts, an eighth of the
    # vocabulary, a period and four layers
    assert cfg["num_hidden_layers"] == 8 == 2 * cfg["full_attention_interval"]
    assert cfg["num_experts"] == 64 >= 8
    assert cfg["vocab_size"] == 18992 == 151936 // 8
    assert "8 chips share each layer" in cfg["deployment"]
    assert "48 chips" in cfg["deployment"] and "float32" in cfg["precision"]
    net = qwen3next_adapter.config_of(cfg)
    assert (net.num_experts, net.experts_held) == (512, (0, 64))
    assert (net.vocab_size, net.vocab_held) == (151936, 18992)
    assert net.full_layers == (3, 7) and len(net.linear_layers) == 6
    assert net.rotary_dim == 64 and net.conv_width == 8192
    # 1,978.8 M parameters, 7.92 GB
    count = 2 * 18992 * 2048 + 2048 + sum(
        int(np.prod(shape)) for i in range(8)
        for shape in qwen3next_weights.layer_shapes(cfg, i).values())
    assert abs(count - 1978.8e6) < 0.3e6
    assert "1,978.8 M" in cfg["parameters"] and "7.92 GB" in cfg["parameters"]
    shapes = qwen3next_weights.layer_shapes

    def held(i, names):
        return sum(int(np.prod(shapes(cfg, i)[k])) for k in names)

    # the table of the issue: the two mixers, the expert layer's rest
    assert abs(held(0, ("qkvz_w", "ba_w", "conv_w", "a_log", "dt_bias",
                        "g_norm", "out_w")) - 33.72e6) < 0.01e6
    assert abs(held(3, ("q_w", "k_w", "v_w", "o_w", "q_norm", "k_norm"))
               - 27.26e6) < 0.01e6
    assert abs(held(0, ("router", "s1", "s3", "s2", "sg", "n1", "n2"))
               - 4.20e6) < 0.01e6
    assert held(0, ("w1", "w3", "w2")) == 64 * 3 * 2048 * 512


def test_the_traffic_file_holds_the_issues_numbers():
    tr = _cell()["traffic_data"]
    assert tr["prompt_lens"] == [4096, 8192, 12288, 16384, 20480, 32768,
                                 6144, 16384]
    assert tr["output_lens"] == [512, 384, 640, 512, 256, 384, 768, 512]
    assert np.mean(tr["prompt_lens"]) == 14592
    assert np.mean(tr["output_lens"]) == 496
    assert (tr["clients"], tr["client_stagger_s"]) == (32, 0.5)
    assert (tr["warm_seconds"], tr["drain_seconds"],
            tr["request_timeout_s"], tr["check_requests"],
            tr["trace_from_s"], tr["trace_seconds"]) == (75, 10, 300, 8, 4, 3)
    eng = tr["engine"]
    assert eng["num_slots"] == 32 and eng["max_seq"] == 33792 == 528 * 64
    assert (eng["page_size"], eng["prefill_chunk"], eng["max_top_k"],
            eng["max_queue"]) == (64, 1024, 8, 64)
    assert eng["num_pages"] == 7936
    cfg = _cell()["config_data"]
    # a row 4,096 B a token and full layer, a page of 64 rows over both
    # layers 524,288 B: 4.16 GB of pages beside 7.92 GB of weights
    page = closed_qwen3next.page_bytes(cfg, 64)
    assert page == 524288 == 64 * 2 * 4096
    assert abs(7937 * page / 1e9 - 4.161) < 0.001
    # the 32 slots' replayed rows in step: 482,816 tokens, 7,544 pages
    ends = [p + o for p, o in zip(tr["prompt_lens"], tr["output_lens"])]
    assert 4 * sum(ends) == 482816
    assert 4 * sum(-(-e // 64) for e in ends) == 7544 <= eng["num_pages"]
    # the state: 6 x (2,097,152 + 98,304) B a slot, 0.42 GB over 32
    state = 6 * (costs_qwen3next.state_bytes(cfg) + 3 * 8192 * 4)
    assert costs_qwen3next.state_bytes(cfg) == 2097152
    assert state == 13172736 and abs(32 * state / 1e9 - 0.4215) < 0.001
    requests = closed_qwen3next.replayed_requests(tr, 2**31 + 7,
                                                  cfg["vocab_size"])
    assert [len(r["prompt"]) for r in requests[3][:3]] == [16384, 20480,
                                                          32768]
    assert max(r["prompt"].max() for r in requests[0]) < cfg["vocab_size"]
    assert max(ends) == 33152 < eng["max_seq"] <= cfg[
        "max_position_embeddings"]


#: the cell's entries: the benchmark holds at most 128 per-layer metrics and
#: 114 were taken, so 14 of the 21 the issue names are entered (PERF.md
#: section 7 names the seven left to the run's ``window:`` line)
NEW = ("decode_tick_ms_p50.qwen3next", "prefill_chunk_ms_p50.qwen3next",
       "prefill_chunk_share_pct.qwen3next",
       "paged_attn_ms_per_tick.qwen3next", "device_idle_pct.qwen3next",
       "peak_hbm_gb.qwen3next", "compiles_in_window.qwen3next",
       "held_experts_roofline.qwen3next", "gdn_ms_per_chunk",
       "gdn_scan_ms_per_chunk", "gdn_scan_roofline", "gdn_step_ms_per_tick",
       "gdn_step_roofline", "gdn_state_gb")
OURS = [m["name"] for m in bench_spec.metrics_for(BENCH, "per_layer", CELL)]


def test_the_cells_entries_are_legal_and_name_files_that_exist():
    """What ``test_benchmark_spec.py`` asserts of every cell, of this one,
    with the drivers read from ``benchmark/drivers/``."""
    name = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.\-]{0,63}$")
    unit = re.compile(r"^[A-Za-z0-9_/%.\-]{1,16}$")
    (config,) = [c for c in BENCH["configs"] if c["name"] == CONFIG]
    (cell,) = [w for w in BENCH["workloads"] if w["config"] == CONFIG]
    assert sorted(OURS) == sorted(NEW)
    new = [m for m in BENCH["per_layer"] if m["name"] in NEW]
    # appended behind what the benchmark had (a later PR appends behind
    # these, so "last" is not asserted)
    assert BENCH["configs"].index(config) == 6 \
        and BENCH["workloads"].index(cell) == 7
    assert BENCH["per_layer"][114:128] == new and len(new) == len(NEW)
    assert len(BENCH["per_layer"]) <= 128
    assert os.path.isfile(os.path.join(bench_spec.ROOT, config["file"]))
    assert config["file"].startswith(tuple(BENCH["paths"]))
    assert config["reduced"] == REDUCED and len(config["why"]) <= 200
    assert (cell["name"], cell["config"], cell["chips"], cell["traffic"]) \
        == (CELL, CONFIG, 1, "closed-32-long-in-mid-out")
    assert "8 chips to a layer" in cell["why"] and len(cell["why"]) <= 200
    assert all(name.match(n) for n in
               [cell["name"], cell["traffic"], config["name"],
                *config["reduced"], *NEW])
    loaded = _cell()
    drivers = {f[:-3] for f in os.listdir(
        os.path.join(bench_spec.HERE, "drivers"))
        if f.endswith(".py") and f not in ("__init__.py", "serving.py")}
    assert loaded["traffic_data"]["driver"] == "closed_qwen3next" in drivers
    assert callable(bench_spec.load_driver("closed_qwen3next"))
    assert set(loaded["limits"]) == {
        "served_token_gap", "left_out_share", "served_not_first_share",
        "first_token_gap"}
    with open(os.path.join(bench_spec.ROOT, "PERF.md")) as f:
        perf = f.read()
    for m in new:
        assert unit.match(m["unit"]) and m["better"] in ("lower", "higher")
        assert (m["moves"], m["workloads"]) == ("serve_tok_s", [CELL])
        assert set(m) == {"name", "unit", "better", "source", "layer",
                          "moves", "workloads"}
        assert m["layer"] in perf, m["layer"]
        assert m["name"] in perf, m["name"]
        assert callable(bench_spec.load_reader(m["name"]))
    # no accepted metric's list of cells was touched
    assert all(CELL not in m["workloads"] for m in BENCH["per_layer"][:114])
    assert not any("mfu" in n for n in NEW)
    e2e = {m["name"]: m for m in bench_spec.metrics_for(BENCH, "end_to_end",
                                                        CELL)}
    assert sorted(e2e) == ["serve_tok_s", "setup_s"]
    assert e2e["serve_tok_s"]["workloads"].index(CELL) == 5
    assert len(json.dumps(BENCH, indent=1)) < 64 * 1024
    # everything this PR adds under the benchmark's paths is named legally
    for folder, _, files in os.walk(bench_spec.HERE):
        for f in files:
            if "qwen3next" in f or f.startswith("gdn_"):
                assert re.match(r"^[A-Za-z0-9_.\-]+$", f), f


@pytest.mark.parametrize("name", OURS)
def test_a_reader_of_the_cell_that_finds_nothing_to_read_returns_nothing(name):
    """As ``test_benchmark_spec.py`` asks of every accepted reader: on the
    parent, which has no such span or counter, the line leaves it out, and
    in a cell of another configuration the new readers find nothing."""
    empty = {"records": [], "hist": {}, "cell": _cell()}
    assert bench_spec.load_reader(name)(empty) is None
    other = bench_spec.load_cell(BENCH, "serve-sala-longctx")
    if name.startswith("gdn_"):
        assert bench_spec.load_reader(name)(
            {"records": [], "hist": {"decode_tick_ms": {"count": 3}},
             "cell": other, "counters": {"moe_experts_active": 5.0},
             "trace_counters": {"gdn.chunk_rows": 7, "gdn.step_rows": 7,
                                "prefill_chunks": 1}}) is None


def test_the_costs_count_the_recurrence_and_the_counters_alone():
    cell = _cell()
    cfg = cell["config_data"]
    assert costs_qwen3next.linear_layers(cfg) == 6
    # a (token, layer): 32 value heads of 7 x 128 x 128 operations; its
    # q k (16 x 128 each), v o (32 x 128 each), g beta (32 each) in float32
    assert costs_qwen3next.row_flops(cfg) == 32 * 7 * 128 * 128 == 3670016
    assert costs_qwen3next.row_bytes(cfg) == 4 * (4096 + 8192 + 64) == 49408
    # a chunk of 1,024 rows through the 6 linear layers
    scan = costs_qwen3next.scan_cost(cfg, 6 * 1024, 6)
    assert scan["flops"] == 6 * 1024 * 3670016
    assert scan["bytes"] == 6 * 1024 * 49408 + 6 * 2 * 2097152
    assert abs(scan["flops"] / 1e9 - 22.55) < 0.01
    # a tick of 30 slots: each (slot, layer) reads and writes 2 MB of state
    step = costs_qwen3next.step_cost(cfg, 6 * 30)
    assert step["bytes"] == 180 * (49408 + 2 * 2097152)
    assert abs(step["bytes"] / 1e9 - 0.764) < 0.001
    peaks = {"flops_per_s": 197e12, "bytes_per_s": 819e9}
    assert costs.least_seconds(step, peaks)[1] == "memory"
    assert costs.least_seconds(scan, peaks)[1] == "memory"
    # nothing here knows the operator's chunk or whether it is a kernel
    import inspect
    text = inspect.getsource(costs_qwen3next)
    assert "gated_delta" not in text and "CHUNK =" not in text
    # 26 of 64 held experts a layer, 8 layers; an eighth of the 2,400 pairs
    held = costs_trinity.held_tick_cost(cfg, 26 * 8, 30 * 10 * 8)
    assert held["bytes"] == 26 * 8 * 3 * 2048 * 512 * 4
    assert held["flops"] == 6.0 * 2048 * 512 * 300
    run = {"cell": cell, "records": [],
           "hist": {"decode_tick_ms": {"count": 10, "p50": 11.0}},
           "gauges": {"gdn_state_bytes": 32 * 13172736,
                      "kv_row_bytes": 4096},
           "counters": {"moe_experts_active": 10 * 8 * 26,
                        "moe_load_max": 50.0}}
    assert abs(bench_spec.load_reader("gdn_state_gb")(run) - 0.4215) < 1e-3
    assert bench_spec.load_reader("moe_load_max_mean")(run) == 5.0
    assert bench_spec.load_reader("decode_tick_ms_p50.qwen3next")(run) == 11.0


def test_a_share_over_100_percent_raises_and_is_not_clipped(monkeypatch):
    """The roofline readers go through ``costs.share_pct``: device time
    shorter than the least time the counted work could take is a fault of
    the counting, and raises."""
    cell = _cell()
    run = {"cell": cell, "peaks": {"flops_per_s": 197e12,
                                   "bytes_per_s": 819e9},
           "trace_counters": {"gdn.step_rows": 180, "gdn.chunk_rows": 6144,
                              "prefill_chunks": 1}}
    monkeypatch.setattr(costs_qwen3next, "scope_seconds",
                        lambda run, program, scope: 2e-3)
    assert abs(costs_qwen3next.step_roofline(run) - 46.7) < 0.1
    assert 0 < costs_qwen3next.scan_roofline(run) < 100
    monkeypatch.setattr(costs_qwen3next, "scope_seconds",
                        lambda run, program, scope: 1e-4)
    with pytest.raises(ValueError, match="counted too high"):
        costs_qwen3next.step_roofline(run)
    monkeypatch.setattr(costs_qwen3next, "scope_seconds",
                        lambda run, program, scope: None)
    assert costs_qwen3next.step_roofline(run) is None


# -- the rehearsal and its faults -------------------------------------------------

def _rehearse(tmp_path, *extra, fault=None):
    """The cell's rehearsal from a copy that holds ``BENCHMARK.json`` and
    ``benchmark/`` alone (what the driver lays over another checkout)."""
    root = tmp_path / "copy"
    shutil.copytree(bench_spec.HERE, root / "benchmark",
                    ignore=shutil.ignore_patterns("__pycache__"))
    (root / "BENCHMARK.json").write_text(json.dumps(BENCH))
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               JAX_COMPILATION_CACHE_DIR=str(tmp_path / "jax_cache"),
               PYTHONPATH=os.pathsep.join([str(root), bench_spec.ROOT]))
    argv = ["--workload", CELL, "--seed", str(2**31 + 5), "--seconds", "1",
            "--trace", "1", "--rehearse-on-cpu"]
    code = "import sys; from benchmark import run; " + (fault or "") \
        + f"sys.exit(run.main({argv!r}, control_modes={tuple(extra)!r}))"
    out = subprocess.run([sys.executable, "-c", code], cwd=root, env=env,
                         capture_output=True, text=True, timeout=1000)
    assert out.returncode == 0, out.stderr[-2000:]
    return json.loads(out.stdout.strip().splitlines()[-1]), out.stdout


def test_the_cell_rehearses_correct_from_a_copy_of_the_benchmark_files(
        tmp_path):
    last, stdout = _rehearse(tmp_path)
    assert last["rehearsal"] is True and last["metrics"] == {}
    assert last["correct"] is True and last["failed"] == 0
    assert last["attempted"] > 0
    for number in ("served_token_gap", "left_out_share",
                   "served_not_first_share", "first_token_gap"):
        assert "check: " + number in stdout
    assert "28 pages of 8 rows of 512 bytes a full layer" in stdout
    assert "in ONE arena" in stdout and "recurrence 'mxu'" in stdout
    found = set(last["readers_that_found_something"])
    assert {"compiles_in_window.qwen3next", "decode_tick_ms_p50.qwen3next",
            "prefill_chunk_ms_p50.qwen3next",
            "prefill_chunk_share_pct.qwen3next", "gdn_state_gb"} <= found
    # (the readers of the device trace find something when a whole tick
    # falls inside the half second traced, which a loaded machine may not
    # grant: tests/test_serving_qwen3next.py holds the scopes they read)
    # finished requests went through the reference, the longest first (one
    # of EACH length on an idle machine; a loaded one finishes fewer inside
    # the second the window lasts, so the whole list is not asserted)
    assert "prompts and outputs [(" in stdout


@pytest.mark.parametrize("mode", closed_qwen3next.PROGRAM_MODES)
def test_a_faulty_engine_rehearses_not_correct(tmp_path, mode):
    last, stdout = _rehearse(tmp_path, mode)
    assert "control: the engine" in stdout
    assert last["correct"] is False, stdout[-1500:]
    assert "FAILED" in stdout


def test_a_faulty_program_is_put_back():
    """The calibration modes change the program inside a ``with`` and leave
    it as it was."""
    from paddle_tpu.models import qwen3next as model
    from paddle_tpu.serving.llm.paged import qwen3next as paged
    sound = (paged.gated_delta_step, paged.gated_delta_chunked,
             model.gated_shared_expert)
    with closed_qwen3next.faulty_program(
            closed_qwen3next.PROGRAM_MODES) as config_cls:
        assert paged.gated_delta_step is not sound[0]
        assert paged.gated_delta_chunked is not sound[1]
        assert model.gated_shared_expert is not sound[2]
        assert config_cls().rotary_dim == 256
    assert (paged.gated_delta_step, paged.gated_delta_chunked,
            model.gated_shared_expert) == sound
    assert model.Qwen3NextConfig().rotary_dim == 64
    with closed_qwen3next.faulty_program(()) as config_cls:
        assert config_cls is model.Qwen3NextConfig
        assert paged.gated_delta_step is sound[0]
    # the uncorrected rule is the sound one wherever the state is empty and
    # stays so: one token from zeros writes beta k v^T either way
    q, k, v, g, beta = (jax.random.normal(jax.random.PRNGKey(i), shape)
                        for i, shape in enumerate(
                            [(1, 2, 8), (1, 2, 8), (1, 4, 8), (1, 4), (1, 4)]))
    zero = jnp.zeros((1, 4, 8, 8))
    want = sound[0](q, k, v, -jnp.abs(g), jax.nn.sigmoid(beta), zero)
    got = closed_qwen3next._uncorrected_step(
        q, k, v, -jnp.abs(g), jax.nn.sigmoid(beta), zero)
    np.testing.assert_allclose(got[0], want[0], atol=1e-6)
    two = closed_qwen3next._uncorrected_chunked(
        q[:, None], k[:, None], v[:, None], -jnp.abs(g)[:, None],
        jax.nn.sigmoid(beta)[:, None], zero, jnp.asarray([1]))
    np.testing.assert_allclose(two[1], want[1], atol=1e-6)


def test_the_bfloat16_control_of_the_reference_is_not_correct():
    """The control the chip runs read beside the program: the tokens a
    bfloat16 pass of the reference puts first, under the reference; and the
    rule of this cell on the reference's own risk, which a linear layer's
    state carries on."""
    cfg = _cell(rehearsal=True)["config_data"]
    rng = np.random.default_rng(3)
    arch = ref.arch_of(cfg)
    top = qwen3next_weights.make_top(cfg, 5)

    def layer(i):
        return qwen3next_weights.make_layer(cfg, 5, i)

    records = []
    for plen in (12, 40, 70):
        seq = np.zeros(96, np.int32)
        seq[:plen] = rng.integers(0, cfg["vocab_size"], plen)
        for at in range(plen - 1, plen + 11):   # greedy under the reference
            hid, _, _ = ref.hidden_states(top, layer, arch, jnp.asarray(seq))
            seq[at + 1] = int(jnp.argmax(ref.logits_of(top, hid[at][None])))
        records.append({"prompt": seq[:plen].copy(), "finished": True,
                        "tokens": [int(t) for t in seq[plen:plen + 12]]})
    numbers = closed_qwen3next.serve_gaps(
        cfg, 5, records, 0.0, 1.0, pad_len=96, max_new=12,
        control_modes=("bfloat16",))
    limits = {"served_token_gap": 1e-4, "left_out_share": 0.5}
    assert check.judge(numbers, limits)
    assert numbers["tokens_compared"] == 36 == numbers["tokens_sampled"]
    # greedy under the reference: every served token is its first choice,
    # and bfloat16 puts another first at some positions
    assert numbers["served_not_first_share"] == 0.0 == numbers[
        "first_token_gap"] == numbers["widest_token_gap"]
    assert numbers["control_bfloat16_token_gap"] > 1e-4
    assert numbers["control_bfloat16_not_first_share"] >= 1 / 36
    # no margin is under 0, so nothing is at risk; with the eight smallest
    # margins under tau, the first of them that touches a held expert is a
    # source, nothing before it is at risk, and what follows it is: through
    # the one full layer's weights and through the linear layers' states
    seq = np.zeros(96, np.int32)
    seq[:70] = records[2]["prompt"]
    _, margin, none = ref.hidden_states(top, layer, arch, jnp.asarray(seq))
    tau = float(np.sort(np.asarray(margin))[7]) * 1.0001
    _, _, some = ref.hidden_states(top, layer, arch, jnp.asarray(seq),
                                   tau=tau)
    some, low = np.asarray(some), np.asarray(margin) < tau
    assert not np.asarray(none).any()
    source = some == 1.0
    assert low.sum() == 8 and source.sum() >= 1
    first = int(np.flatnonzero(source)[0])
    assert low[first] and (some[:first] == 0).all() and (some <= 1.0).all()
    assert ((some[first:] > 0) & (some[first:] < 1)).sum() > 10
    # the row behind a source carries it on in the states it reads
    assert 0 < some[first + 1]
    strict = closed_qwen3next.serve_gaps(cfg, 5, records, 1e9, 1e-9, 96, 12)
    assert strict["left_out_share"] == 1.0 and not check.judge(strict, limits)
    own = closed_qwen3next.serve_gaps(cfg, 5, records, 0.0, 1.0, 96, 12,
                                      own_tau=1e9)
    assert own["left_out_share"] == 1.0
    # a served token that is not the reference's first: a FIRST token that
    # is wide is spared by nothing
    wrong = [dict(r, tokens=list(r["tokens"])) for r in records]
    wrong[1]["tokens"] = [(wrong[1]["tokens"][0] + 2) % cfg["vocab_size"]]
    off = closed_qwen3next.serve_gaps(cfg, 5, wrong, 0.0, 1.0, 96, 12,
                                      spared_share=0.5)
    assert off["tokens_spared"] == 12 and off["tokens_not_first"] == 1
    assert off["first_token_gap"] == off["widest_token_gap"] > 0.015
    assert not check.judge(off, _cell()["limits"])


# -- compiled for the chip ---------------------------------------------------------

@pytest.fixture(scope="module")
def topo():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies
    try:
        return topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # noqa: BLE001 -- any failure to describe means skip
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


@pytest.fixture(scope="module")
def no_persistent_cache():
    """A compile for a described chip is written to the persistent cache
    but cannot be read back without the chip; keep these out of it."""
    from jax.experimental.compilation_cache import compilation_cache
    before = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield
    jax.config.update("jax_enable_compilation_cache", before)
    compilation_cache.reset_cache()


@pytest.fixture()
def mosaic_kernels(monkeypatch):
    """Off the chip the program would interpret its Pallas kernels; the
    programs compiled here must hold the Mosaic kernels."""
    from paddle_tpu.ops import moe, paged_attention
    for module in (paged_attention, moe):
        monkeypatch.setattr(module, "resolve_interpret",
                            lambda kernel, requested=None: False)


#: what the issue holds the cell's peak to: 95% of the chip's 15.75 GiB
PEAK_LIMIT = 0.95 * 15.75 * 2 ** 30


def test_the_cells_programs_compile_for_v5e_and_fit(
        one_chip, no_persistent_cache, mosaic_kernels, record_property):
    """The decode step and the chunk program at the cell's sizes: arguments
    (7.92 GB of weights, the arena of the full layers' pages, the states),
    aliased outputs and temporaries; both programs are loaded at once, so the
    sum holds the arguments once and both programs' temporaries."""
    from paddle_tpu.serving.llm.paged.qwen3next import (
        build_qwen3next_paged_chunk_fn, build_qwen3next_paged_decode_step,
        state_rows)
    cell = _cell()
    cfg, eng = cell["config_data"], cell["traffic_data"]["engine"]
    net = qwen3next_adapter.config_of(cfg)

    def s(shape, dtype=jnp.float32):
        return jax.ShapeDtypeStruct(tuple(shape), dtype, sharding=one_chip)

    names = {"n1": "n1", "n2": "n2", "router": "gate", "w1": "w1",
             "w3": "w3", "w2": "w2", "s1": "s1", "s3": "s3", "s2": "s2",
             "sg": "sg", "q_w": "qw", "k_w": "kw", "v_w": "vw", "o_w": "ow",
             "q_norm": "qn", "k_norm": "kn", "qkvz_w": "qkvz", "ba_w": "ba",
             "conv_w": "conv", "a_log": "alog", "dt_bias": "dtb",
             "g_norm": "gn", "out_w": "out"}
    layers = tuple({names[k]: s(v) for k, v in
                    qwen3next_weights.layer_shapes(cfg, i).items()}
                   for i in range(cfg["num_hidden_layers"]))
    h, vocab = cfg["hidden_size"], cfg["vocab_size"]
    params = {"tok": s((vocab, h)), "fnw": s((h,)), "head": s((h, vocab)),
              "layers": layers}
    slots, page = eng["num_slots"], eng["page_size"]
    arena = s((eng["num_pages"] + 1, 2 * 2, page, 512))
    state = {k: s((slots,) + shape)
             for k, (_, shape) in state_rows(net).items()}
    tables = s((slots, eng["max_seq"] // page), jnp.int32)

    def vec(n, dtype=jnp.float32):
        return s((n,), dtype)

    step = build_qwen3next_paged_decode_step(net, eng["max_top_k"], "kernel")
    compiled = jax.jit(step, donate_argnums=(1, 2)).lower(
        params, arena, state, tables, vec(slots, jnp.int32),
        vec(slots, bool), vec(slots, jnp.int32), vec(slots),
        vec(slots, jnp.int32), vec(slots, bool), vec(slots, jnp.int32),
        s((2,), jnp.uint32)).compile()
    text = compiled.as_text()
    # a walk of paged_attn a KV head and full layer, two expert kernels a layer
    assert text.count('custom_call_target="tpu_custom_call"') == 2 * 2 + 2 * 8
    m = compiled.memory_analysis()
    held = int(np.prod(arena.shape)) * 4
    assert held == 7937 * 524288
    states = 32 * 13172736
    record_property("decode_step_qwen3next", json.dumps(
        {"argument_bytes": m.argument_size_in_bytes,
         "temp_bytes": m.temp_size_in_bytes,
         "alias_bytes": m.alias_size_in_bytes}))
    # the arena and the states are updated in place, and not copied: a copy
    # of either would show among the temporaries
    assert m.alias_size_in_bytes >= held + states
    assert m.temp_size_in_bytes < 0.2e9
    decode_temp = m.temp_size_in_bytes

    chunk = build_qwen3next_paged_chunk_fn(net, eng["max_top_k"])
    compiled = jax.jit(chunk, donate_argnums=(5, 6)).lower(
        params, s((1, eng["prefill_chunk"]), jnp.int32), s((), jnp.int32),
        s((), jnp.int32), s((), bool), arena, state, tables,
        vec(slots, jnp.int32), vec(slots, bool), s((), jnp.int32), vec(1),
        vec(1, jnp.int32), vec(1, bool), vec(1, jnp.int32),
        s((2,), jnp.uint32)).compile()
    m = compiled.memory_analysis()
    record_property("prefill_chunk_qwen3next", json.dumps(
        {"argument_bytes": m.argument_size_in_bytes,
         "temp_bytes": m.temp_size_in_bytes,
         "alias_bytes": m.alias_size_in_bytes}))
    assert m.alias_size_in_bytes >= held + states
    # no temporary of a chunk passes 0.8 GB
    assert m.temp_size_in_bytes < 0.8e9
    both = m.argument_size_in_bytes + decode_temp + m.temp_size_in_bytes
    record_property("both_programs_bytes", int(both))
    assert 7.92e9 + held + states < both < PEAK_LIMIT
