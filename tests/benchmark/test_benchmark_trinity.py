"""The Trinity-Mini configuration and its cell ``serve-trinity-mixedctx``: the
published sizes against the catalog row, the cut and the share, the cell's
entries and files, the rehearsal of the cell on the CPU (correct) and its
faults (a served token altered where it is produced, a window one row short
and the shared expert left out, each through the whole run; the bfloat16
control through the comparison itself, held to the toy width's own limit:
each not correct), the readers on an empty run, and the decode step and the
chunk program compiled for a described (not attached) TPU v5e at the cell's
sizes, their memory recorded.

``test_benchmark_spec.py::test_every_file_the_benchmark_names_exists`` holds
every cell's driver to ``("fit", "closed", "open")`` and so fails on this
cell's ``closed_trinity`` as it does on ``closed_lfm2`` and ``closed_sala``,
at that line alone; the test of the entries below asserts the same things
with the drivers read from ``benchmark/drivers/``.

The compiles describe the topology inside a fixture (see the
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

from benchmark import (check, costs_trinity, spec as bench_spec,
                       trinity_adapter, trinity_weights)
from benchmark.drivers import closed_trinity
from benchmark.reference import trinity_ref as ref

pytestmark = pytest.mark.timeout_s(1200)
CELL = "serve-trinity-mixedctx"
BENCH = bench_spec.load_benchmark()
CATALOG = "/opt/skills/guides/model-configs/architectures.jsonl"
REDUCED = ["num_hidden_layers", "num_dense_layers", "layer_types",
           "num_experts", "vocab_size"]


def _cell(rehearsal=False):
    return bench_spec.load_cell(BENCH, CELL, rehearsal=rehearsal)


# -- the configuration ------------------------------------------------------------

#: the catalog row's ``config``, as this PR read it (the catalog itself is
#: compared where it is present)
PUBLISHED = {
    "global_attn_every_n_layers": 4, "head_dim": 128, "hidden_act": "silu",
    "hidden_size": 2048, "intermediate_size": 6144,
    "load_balance_coeff": 0.001, "max_position_embeddings": 131072,
    "model_type": "afmoe", "moe_intermediate_size": 1024,
    "mup_enabled": True, "n_group": 1, "num_attention_heads": 32,
    "num_expert_groups": 1, "num_experts_per_tok": 8,
    "num_key_value_heads": 4, "num_limited_groups": 1,
    "num_shared_experts": 1, "rms_norm_eps": 1e-05, "rope_scaling": None,
    "rope_theta": 10000, "route_norm": True, "route_scale": 2.826,
    "score_func": "sigmoid", "sliding_window": 2048,
    "tie_word_embeddings": False, "topk_group": 1, "use_grouped_mm": True}
ASSUMED = ("mup embedding scale", "four norms a layer", "q/k norms",
           "positions", "window", "output gate", "expert_bias", "route_eps",
           "shared expert", "no biases")


@pytest.mark.parametrize("key", sorted(PUBLISHED))
def test_published_size_is_unchanged(key):
    assert _cell()["config_data"][key] == PUBLISHED[key]


def test_the_file_holds_the_catalogs_row_but_for_what_is_reduced():
    if not os.path.isfile(CATALOG):
        pytest.skip("no catalog beside the guide here")
    with open(CATALOG) as f:
        (row,) = [r for r in map(json.loads, f) if r["name"] == "Trinity-Mini"]
    cfg = _cell()["config_data"]
    (entry,) = [c for c in BENCH["configs"] if c["name"] == "trinity-mini"]
    assert entry["source"] == row["source_url"] == cfg["source"]
    differs = sorted(k for k, v in row["config"].items() if cfg.get(k) != v)
    assert differs == sorted(REDUCED) == sorted(entry["reduced"])
    assert sorted(cfg["changed"]) == sorted(REDUCED)
    # a contiguous slice of the published pattern, 3 sliding : 1 full
    assert cfg["layer_types"] == row["config"]["layer_types"][1:10]
    assert PUBLISHED == {k: row["config"][k] for k in PUBLISHED}


@pytest.mark.parametrize("item", ASSUMED)
def test_assumed_item_is_stated_with_its_source(item):
    assumed = _cell()["config_data"]["assumed"]
    assert item in assumed and "modeling_afmoe" in assumed[
        "source of every item"]


def test_the_cut_and_the_share_are_what_the_files_say():
    cfg = _cell()["config_data"]
    share = cfg["share"]
    assert (cfg["num_hidden_layers"], cfg["num_dense_layers"]) == (9, 1)
    assert cfg["layer_types"].count("full_attention") == 2
    assert cfg["layer_types"].count("sliding_attention") == 7
    assert share == {"chips_sharing_a_layer": 8, "num_experts_published": 128,
                     "experts_held": [0, 16], "vocab_size_published": 200192,
                     "vocab_rows": [0, 25024]}
    # the guide's floors: 8 experts, an eighth of the vocabulary, a period
    # and four layers behind the dense ones
    assert cfg["num_experts"] == 16 >= 8
    assert cfg["vocab_size"] == 25024 == 200192 // 8
    assert "8 chips share each layer" in cfg["deployment"]
    assert "float32" in cfg["precision"]
    net = trinity_adapter.config_of(cfg)
    assert (net.num_experts, net.experts_held) == (128, (0, 16))
    assert (net.vocab_size, net.vocab_held) == (200192, 25024)
    assert net.route_eps == 1e-20 and net.sliding_window == 2048
    # 1,243.3 M parameters, 4.97 GB
    count = 2 * 25024 * 2048 + 2048 + sum(
        int(np.prod(shape)) for i in range(9)
        for shape in trinity_weights.layer_shapes(cfg, i).values())
    assert abs(count - 1243.3e6) < 0.3e6
    assert "4.97 GB" in cfg["parameters"]


def test_the_traffic_file_holds_the_issues_numbers():
    tr = _cell()["traffic_data"]
    assert tr["prompt_lens"] == [1024, 8192, 2048, 16384, 4096, 12288, 1536,
                                 6144]
    assert tr["output_lens"] == [512, 384, 640, 256, 768, 512, 640, 384]
    assert (tr["clients"], tr["client_stagger_s"]) == (32, 0.4)
    assert (tr["warm_seconds"], tr["drain_seconds"],
            tr["request_timeout_s"], tr["check_requests"],
            tr["trace_from_s"], tr["trace_seconds"]) == (60, 10, 300, 8, 4, 3)
    eng = tr["engine"]
    assert eng["num_slots"] == 32 and eng["max_seq"] == 17408 == 272 * 64
    assert (eng["page_size"], eng["prefill_chunk"], eng["max_top_k"],
            eng["max_queue"]) == (64, 1024, 8, 64)
    assert eng["num_pages_full"] == 32 * 272 + 64
    assert eng["num_pages_window"] == 32 * (-(-(2048 + 1024) // 64) + 2 + 1)
    cfg = _cell()["config_data"]
    full = closed_trinity.page_bytes(cfg, 64, "full_attention")
    window = closed_trinity.page_bytes(cfg, 64, "sliding_attention")
    assert (full, window) == (524288, 1835008)
    # 4.60 + 2.99 GB of pages beside 4.97 GB of weights: 12.56 GB held
    assert abs((8769 * full + 1633 * window) / 1e9 - 7.594) < 0.001
    requests = closed_trinity.replayed_requests(tr, 2**31 + 7,
                                                cfg["vocab_size"])
    assert [len(r["prompt"]) for r in requests[3][:3]] == [16384, 4096, 12288]
    assert max(r["prompt"].max() for r in requests[0]) < cfg["vocab_size"]
    assert max(len(r["prompt"]) + r["max_new_tokens"]
               for c in requests for r in c) < eng["max_seq"]


def test_the_cells_entries_are_legal_and_name_files_that_exist():
    """What ``test_benchmark_spec.py`` asserts of every cell, of this one,
    with the drivers read from ``benchmark/drivers/``."""
    name = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.\-]{0,63}$")
    unit = re.compile(r"^[A-Za-z0-9_/%.\-]{1,16}$")
    (config,) = [c for c in BENCH["configs"] if c["name"] == "trinity-mini"]
    (cell,) = [w for w in BENCH["workloads"] if w["config"] == config["name"]]
    ours = [m for m in BENCH["per_layer"] if CELL in m.get("workloads", ())]
    # appended behind what the benchmark had (a later PR appends behind
    # these, so "last" is not asserted)
    assert BENCH["configs"].index(config) == 4 \
        and BENCH["workloads"].index(cell) == 5
    first = BENCH["per_layer"].index(ours[0])
    assert BENCH["per_layer"][first:first + 18] == ours and first == 65
    assert os.path.isfile(os.path.join(bench_spec.ROOT, config["file"]))
    assert config["file"].startswith(tuple(BENCH["paths"]))
    assert config["reduced"] == REDUCED and len(config["why"]) <= 200
    assert (cell["name"], cell["config"], cell["chips"], cell["traffic"]) \
        == (CELL, config["name"], 1, "closed-32-mixed-in-mid-out")
    assert cell["why"] == (
        "closed loop, 32 slots, prompts 1k-16k replayed short and long in "
        "one queue, outputs 256-768: 7 window layers keep 2,048 rows, 2 "
        "full layers all; 16 of 128 experts held, 1/8 of the vocabulary")
    assert len(cell["why"]) <= 200
    assert all(name.match(n) for n in
               [cell["name"], cell["traffic"], config["name"],
                *config["reduced"], *(m["name"] for m in ours)])
    loaded = _cell()
    drivers = {f[:-3] for f in os.listdir(
        os.path.join(bench_spec.HERE, "drivers"))
        if f.endswith(".py") and f not in ("__init__.py", "serving.py")}
    assert loaded["traffic_data"]["driver"] == "closed_trinity" in drivers
    assert callable(bench_spec.load_driver("closed_trinity"))
    assert set(loaded["limits"]) == {"served_token_gap", "left_out_share"}
    with open(os.path.join(bench_spec.ROOT, "PERF.md")) as f:
        perf = f.read()
    for m in ours:
        assert unit.match(m["unit"]) and m["better"] in ("lower", "higher")
        assert (m["moves"], m["workloads"]) == ("serve_tok_s", [CELL])
        assert set(m) == {"name", "unit", "better", "source", "layer",
                          "moves", "workloads"}
        assert m["layer"] in perf, m["layer"]
        assert m["name"] in perf, m["name"]
    e2e = {m["name"]: m for m in bench_spec.metrics_for(BENCH, "end_to_end",
                                                        CELL)}
    assert sorted(e2e) == ["serve_tok_s", "setup_s"]
    assert e2e["serve_tok_s"]["workloads"].index(CELL) == 3
    assert len(json.dumps(BENCH, indent=1)) < 64 * 1024
    # everything this PR adds under the benchmark's paths is named legally
    for folder, _, files in os.walk(bench_spec.HERE):
        for f in files:
            if "trinity" in f or "window_" in f or "held_experts" in f:
                assert re.match(r"^[A-Za-z0-9_.\-]+$", f), f


OURS = [m["name"] for m in bench_spec.metrics_for(BENCH, "per_layer", CELL)
        if CELL in m.get("workloads", ())]


@pytest.mark.parametrize("name", OURS)
def test_a_reader_of_the_cell_that_finds_nothing_to_read_returns_nothing(name):
    """As ``test_benchmark_spec.py`` asks of every accepted reader: on the
    parent, which has no such span or counter, the line leaves it out, and
    in a cell of another configuration the new readers find nothing."""
    empty = {"records": [], "hist": {}, "cell": _cell()}
    assert bench_spec.load_reader(name)(empty) is None
    other = bench_spec.load_cell(BENCH, "serve-lfm2moe-decode")
    if not name.endswith(".trinity"):
        assert bench_spec.load_reader(name)(
            {"records": [], "hist": {}, "cell": other, "counters": {
                "moe_experts_active": 5.0},
             "trace_counters": {"paged_attn.pages_live": 7}}) is None


def test_the_new_readers_read_what_the_engine_counts():
    cell = _cell()
    cfg = cell["config_data"]
    run = {"cell": cell, "records": [],
           "hist": {"decode_tick_ms": {"count": 10, "p50": 14.0}},
           "counters": {"window_attn.pages_walked": 33 * 7 * 32,
                        "window_attn.pages_live": 105 * 7 * 32,
                        "kv_pages.window_held": 340,
                        "kv_pages.window_unbounded": 1000,
                        "moe_experts_active": 10 * 8 * 14,
                        "moe_load_max": 70.0}}
    assert bench_spec.load_reader("window_walk_page_share")(run) == 33 / 105
    assert bench_spec.load_reader("window_held_page_share")(run) == 0.34
    assert bench_spec.load_reader("moe_experts_active_mean.trinity")(run) == 14
    assert bench_spec.load_reader("moe_load_max_mean.trinity")(run) == 7.0
    # a tick of 32 sequences at 6.4k rows: 100 pages a full layer, 33 a
    # window layer; a page is 64 rows of 4 KV heads' keys and values
    pages = costs_trinity.walked_pages(cfg, {
        "paged_attn.pages_live": 100 * 32,
        "window_attn.pages_walked": 33 * 7 * 32})
    assert pages == (2 * 100 + 7 * 33) * 32
    cost = costs_trinity.walk_cost(cfg, 64, pages, 32 * 9)
    assert cost["bytes"] == (2 * pages * 64 * 4 + 2 * 32 * 9 * 32) * 128 * 4
    assert cost["flops"] == 4.0 * pages * 64 * 32 * 128
    # 14 of 16 held experts a layer, 8 layers; an eighth of the 2,048 pairs
    held = costs_trinity.held_tick_cost(cfg, 14 * 8, 32 * 8 * 8)
    assert held["bytes"] == 14 * 8 * 3 * 2048 * 1024 * 4
    assert held["flops"] == 6.0 * 2048 * 1024 * 256


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
    assert "check: served_token_gap" in stdout
    assert "check: left_out_share" in stdout
    assert "page groups {'full': 44, 'window': 14}" in stdout
    found = set(last["readers_that_found_something"])
    assert {"compiles_in_window.trinity", "tick_batch_mean.trinity",
            "decode_tick_ms_p50.trinity", "prefill_chunk_ms_p50.trinity",
            "prefill_chunk_share_pct.trinity", "worker_host_pct.trinity",
            "tick_host_ms_mean.trinity", "moe_experts_active_mean.trinity",
            "moe_load_max_mean.trinity", "window_walk_page_share",
            "window_held_page_share"} <= found
    # one request of each prompt length went through the reference
    assert "prompts [130, 100, 70, 50, 40, 20, 16, 12]" in stdout


#: served tokens changed where the engine hands them to its clients
ALTERED = (
    "from paddle_tpu.serving.llm.scheduler import GenerationRequest as G; "
    "emit = G._emit; "
    "G._emit = lambda self, tok: emit(self, (tok + 2) % 512 "
    "if len(self.tokens) == 3 else tok); ")


@pytest.mark.parametrize("fault,modes", [
    (ALTERED, ()), (None, (closed_trinity.PROGRAM_WINDOW_SHORT,)),
    (None, (closed_trinity.PROGRAM_NO_SHARED,))],
    ids=["altered-token", "window-one-short", "no-shared-expert"])
def test_a_faulty_engine_rehearses_not_correct(tmp_path, fault, modes):
    last, stdout = _rehearse(tmp_path, *modes, fault=fault)
    assert last["correct"] is False, stdout[-1500:]
    assert "check: served_token_gap" in stdout and "FAILED" in stdout


def test_the_bfloat16_control_of_the_reference_is_not_correct():
    """The control the chip runs read beside the program: the tokens a
    bfloat16 pass of the reference puts first, under the reference."""
    cfg = _cell(rehearsal=True)["config_data"]
    rng = np.random.default_rng(3)
    arch = ref.arch_of(cfg)
    top = trinity_weights.make_top(cfg, 5)

    def layer(i):
        return trinity_weights.make_layer(cfg, 5, i)

    records = []
    for plen in (12, 40, 70):
        seq = np.zeros(96, np.int32)
        seq[:plen] = rng.integers(0, cfg["vocab_size"], plen)
        for at in range(plen - 1, plen + 11):   # greedy under the reference
            hid, _, _ = ref.hidden_states(top, layer, arch, jnp.asarray(seq))
            seq[at + 1] = int(jnp.argmax(ref.logits_of(top, hid[at][None])))
        records.append({"prompt": seq[:plen].copy(), "finished": True,
                        "tokens": [int(t) for t in seq[plen:plen + 12]]})
    numbers = closed_trinity.serve_gaps(
        cfg, 5, records, 0.0, pad_len=96, max_new=12,
        control_modes=("bfloat16",), rule="own")
    limits = {"served_token_gap": 1e-4, "left_out_share": 0.5}
    assert check.judge(numbers, limits)
    assert numbers["tokens_compared"] == 36 == numbers["tokens_sampled"]
    assert numbers["control_bfloat16_token_gap"] > 1e-4
    for rule in closed_trinity.RULES:
        far = closed_trinity.serve_gaps(cfg, 5, records, 1.0, 96, 12,
                                        rule=rule)
        assert far["left_out_share"] == 1.0 and not check.judge(far, limits)
        assert far["smallest_margin"] >= far["smallest_margin_anywhere"]
    # the rule of this cell: a token is left out by the risk at its
    # predicting position. No margin is under 0, so nothing is at risk; all
    # are under 1, so from the first expert layer on everything is; between,
    # a position is at risk by its own margin or by what it attends to
    seq = np.zeros(96, np.int32)
    seq[:70] = records[2]["prompt"]
    _, margin, none = ref.hidden_states(top, layer, arch, jnp.asarray(seq))
    _, _, every = ref.hidden_states(top, layer, arch, jnp.asarray(seq),
                                    tau=1.0)
    tau = float(np.sort(np.asarray(margin))[7]) * 1.0001
    _, _, some = ref.hidden_states(top, layer, arch, jnp.asarray(seq),
                                   tau=tau)
    some, low = np.asarray(some), np.asarray(margin) < tau
    assert not np.asarray(none).any() and (np.asarray(every) >= 0).all()
    # a source is a near-tie that touches a held expert (4 of 8 here)
    source = some == 1.0
    assert low.sum() == 8 and 1 <= source.sum() <= 8 and not (
        source & ~low).any()
    first = int(np.flatnonzero(source)[0])
    assert (some[:first] == 0).all() and (some <= 1.0).all()
    assert ((some[first:] > 0) & (some[first:] < 1)).sum() > 10
    keep = closed_trinity.keeps(margin, some, plen=70, n=12, tau=tau,
                               rule=closed_trinity.ATTENDED, rho=0.05)
    assert keep.tolist() == (some[69:81] < 0.05).tolist()
    strict = closed_trinity.serve_gaps(cfg, 5, records, 1.0, 96, 12,
                                       rho=1e-9)
    assert strict["left_out_share"] == 1.0 and not check.judge(strict, limits)
    # one finished request of each length, the longest first
    picked = closed_trinity.sample_by_length(
        records + [dict(records[0], finished=False)], 9, [12, 70, 40, 33])
    assert [len(r["prompt"]) for r in picked] == [70, 40, 12]


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
    from jax.experimental.compilation_cache import compilation_cache
    before = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield
    jax.config.update("jax_enable_compilation_cache", before)
    compilation_cache.reset_cache()


@pytest.fixture()
def mosaic_kernels(monkeypatch):
    """The programs compiled here must hold the Mosaic kernels, so the test
    (not a program option) answers the compile-or-interpret question."""
    from paddle_tpu.ops import moe, paged_attention
    for module in (paged_attention, moe):
        monkeypatch.setattr(module, "resolve_interpret",
                            lambda kernel, requested=None: False)


def _shapes(one_chip):
    cell = _cell()
    cfg, eng = cell["config_data"], cell["traffic_data"]["engine"]
    net_cfg = trinity_adapter.config_of(cfg)

    def s(shape, dtype=jnp.float32):
        return jax.ShapeDtypeStruct(tuple(shape), dtype, sharding=one_chip)

    names = {"n1": "n1", "n2": "n2", "n3": "n3", "n4": "n4", "q_w": "qw",
             "k_w": "kw", "v_w": "vw", "gate_w": "gw", "o_w": "ow",
             "q_norm": "qn", "k_norm": "kn", "w1": "w1", "w3": "w3",
             "w2": "w2", "router": "gate", "expert_bias": "bias", "s1": "s1",
             "s3": "s3", "s2": "s2"}
    layers = tuple({names[k]: s(v)
                    for k, v in trinity_weights.layer_shapes(cfg, i).items()}
                   for i in range(cfg["num_hidden_layers"]))
    h, vocab = cfg["hidden_size"], cfg["vocab_size"]
    params = {"tok": s((vocab, h)), "fnw": s((h,)), "head": s((h, vocab)),
              "layers": layers}
    hkv, d, page = cfg["num_key_value_heads"], cfg["head_dim"], eng[
        "page_size"]
    arenas = (s((eng["num_pages_full"] + 1, len(net_cfg.full_layers), page,
                 hkv, d)),
              s((eng["num_pages_window"] + 1, len(net_cfg.window_layers),
                 page, hkv, d)))
    slots = eng["num_slots"]
    table = s((slots, eng["max_seq"] // page), jnp.int32)
    per_slot = {
        "tables": (table, table),
        "lengths": s((slots,), jnp.int32), "finished": s((slots,), bool),
        "last": s((slots,), jnp.int32), "temperature": s((slots,)),
        "top_k": s((slots,), jnp.int32), "do_sample": s((slots,), bool),
        "eos": s((slots,), jnp.int32), "key": s((2,), jnp.uint32)}
    return net_cfg, eng, params, arenas, per_slot, s


#: what the issue holds the cell's peak to, of the chip's 15.75 GB
PEAK_LIMIT = 14.8e9


def _record(name, compiled, record_property):
    m = compiled.memory_analysis()
    found = {"argument_bytes": m.argument_size_in_bytes,
             "output_bytes": m.output_size_in_bytes,
             "temp_bytes": m.temp_size_in_bytes,
             "alias_bytes": m.alias_size_in_bytes}
    record_property(name, json.dumps(found))
    print(name, found)
    return found


def test_the_cells_programs_compile_for_v5e_and_fit(
        one_chip, no_persistent_cache, mosaic_kernels, record_property):
    """The decode step and the chunk program at the cell's sizes: arguments
    (4.97 GB of weights, both groups' arenas), aliased outputs and
    temporaries; both programs are loaded at once, so the sum holds the
    arguments once and both programs' temporaries."""
    from paddle_tpu.serving.llm.paged.trinity import (
        build_trinity_paged_chunk_fn, build_trinity_paged_decode_step)
    cfg, eng, params, arenas, p, s = _shapes(one_chip)
    step = build_trinity_paged_decode_step(cfg, eng["max_top_k"], "kernel")
    compiled = jax.jit(step, donate_argnums=(1, 2)).lower(
        params, arenas, arenas, p["tables"], p["lengths"], p["finished"],
        p["last"], p["temperature"], p["top_k"], p["do_sample"], p["eos"],
        p["key"]).compile()
    text = compiled.as_text()
    # one walk of paged_attn a layer, and two expert kernels an expert layer
    assert text.count('custom_call_target="tpu_custom_call"') \
        == cfg.num_hidden_layers + 2 * cfg.num_expert_layers
    decode = _record("decode_step_trinity", compiled, record_property)
    held = 2 * sum(int(np.prod(a.shape)) for a in arenas) * 4
    # both groups' arenas are updated in place, and not copied
    assert decode["alias_bytes"] >= held
    for arena in arenas:
        assert "copy(" not in "".join(
            line for line in text.splitlines()
            if f"f32[{arena.shape[0]}," in line.split(" = ")[-1][:20])

    def one(dtype=jnp.float32):
        return s((1,), dtype)

    chunk = build_trinity_paged_chunk_fn(cfg, eng["max_top_k"])
    compiled = jax.jit(chunk, donate_argnums=(5, 6)).lower(
        params, s((1, eng["prefill_chunk"]), jnp.int32), s((), jnp.int32),
        s((), jnp.int32), s((), bool), arenas, arenas, p["tables"],
        p["lengths"], p["finished"], s((), jnp.int32), one(),
        one(jnp.int32), one(bool), one(jnp.int32), p["key"]).compile()
    prefill = _record("prefill_chunk_trinity", compiled, record_property)
    assert prefill["alias_bytes"] >= held
    both = (decode["argument_bytes"] + decode["temp_bytes"]
            + prefill["temp_bytes"])
    record_property("both_programs_bytes", int(both))
    assert 4.97e9 + held < both < PEAK_LIMIT
