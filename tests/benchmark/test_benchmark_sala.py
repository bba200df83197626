"""The MiniCPM-SALA configuration and its cell ``serve-sala-longctx``: the
published sizes against the catalog row, the cell's entries and files, the
rehearsal of the cell on the CPU (correct) and its faults (a served token
altered where it is produced and a selection one block short, each through
the whole run; the bfloat16 control through the comparison itself, held to
the toy width's own limit: each not correct), the readers on an empty run, and the decode step
and the chunk program compiled for a described (not attached) TPU v5e at the
cell's sizes, their memory recorded.

``test_benchmark_spec.py::test_every_file_the_benchmark_names_exists`` holds
every cell's driver to ``("fit", "closed", "open")`` and so fails on this
cell's ``closed_sala`` as it does on ``closed_lfm2``, at that line alone; the
test of the entries below asserts the same things with the drivers read from
``benchmark/drivers/``.

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

from benchmark import check, sala_adapter, sala_weights, spec as bench_spec
from benchmark.drivers import closed_sala
from benchmark.reference import sala_ref as ref

pytestmark = pytest.mark.timeout_s(1200)
CELL = "serve-sala-longctx"
BENCH = bench_spec.load_benchmark()


def _cell(rehearsal=False):
    return bench_spec.load_cell(BENCH, CELL, rehearsal=rehearsal)


# -- the configuration ------------------------------------------------------------

PUBLISHED = {
    "hidden_size": 4096, "num_attention_heads": 32, "num_key_value_heads": 2,
    "head_dim": 128, "intermediate_size": 16384, "vocab_size": 73448,
    "lightning_nh": 32, "lightning_nkv": 32, "lightning_head_dim": 128,
    "rope_theta": 10000, "rms_norm_eps": 1e-06, "scale_emb": 12,
    "scale_depth": 1.4, "dim_model_base": 256, "mup_denominator": 32,
    "max_position_embeddings": 524288, "tie_word_embeddings": False,
    "attn_use_rope": False, "lightning_use_rope": True, "qk_norm": True,
    "use_output_gate": True, "use_output_norm": True,
    "attn_use_output_gate": True}
ASSUMED = {"sparse_kernel_size": 32, "sparse_kernel_stride": 16,
           "sparse_block_size": 64, "sparse_topk": 64,
           "sparse_init_blocks": 1, "sparse_window_size": 2048,
           "sparse_dense_len": 8192, "residual_depth": 32}


@pytest.mark.parametrize("key", sorted(PUBLISHED))
def test_published_size_is_unchanged(key):
    assert _cell()["config_data"][key] == PUBLISHED[key]


@pytest.mark.parametrize("key", sorted(ASSUMED))
def test_assumed_size_is_stated_with_its_reason(key):
    assumed = _cell()["config_data"]["assumed"]
    assert assumed[key] == ASSUMED[key]
    assert any(k.startswith("why") and len(v) > 40
               for k, v in assumed.items())


def test_the_cut_is_what_the_files_say():
    cfg = _cell()["config_data"]
    entry = {c["name"]: c for c in BENCH["configs"]}["minicpm-sala"]
    assert sorted(cfg["changed"]) == sorted(entry["reduced"]) \
        == ["mixer_types", "num_hidden_layers"]
    assert cfg["num_hidden_layers"] == 8 == len(cfg["mixer_types"])
    assert cfg["mixer_types"] == ["minicpm4"] + ["lightning-attn"] * 4 \
        + ["minicpm4"] + ["lightning-attn"] * 2
    assert "float32" in cfg["precision"] and "pipeline" in cfg["deployment"]
    # 2 x 253.8 M + 6 x 285.2 M in the layers, 601.7 M in embedding and head
    params = sum(int(np.prod(s)) for i in range(8)
                 for s in sala_weights.layer_shapes(cfg, i).values()) \
        + 2 * cfg["vocab_size"] * cfg["hidden_size"] + cfg["hidden_size"]
    assert round(params / 1e6, 1) == 2820.5
    catalog = "/opt/skills/guides/model-configs/architectures.jsonl"
    if os.path.exists(catalog):     # every key of the row, but the cut ones
        with open(catalog) as f:
            row = next(r for r in map(json.loads, f)
                       if r["name"] == "MiniCPM-SALA")
        assert entry["source"] == row["source_url"]
        for k, v in row["config"].items():
            if k not in entry["reduced"]:
                assert cfg[k] == v, k
        assert cfg["mixer_types"] == row["config"]["mixer_types"][17:25]


def test_the_traffic_file_holds_the_issues_numbers():
    tr = _cell()["traffic_data"]
    eng = tr["engine"]
    assert (tr["clients"], eng["num_slots"]) == (8, 8)
    assert tr["prompt_lens"] == [12288, 16384, 20480, 32768]
    assert tr["output_lens"] == [384, 512, 640, 512]
    assert (eng["max_seq"], eng["page_size"], eng["prefill_chunk"],
            eng["max_top_k"]) == (33792, 64, 1024, 8)
    assert (tr["warm_seconds"], tr["drain_seconds"], tr["request_timeout_s"],
            tr["check_requests"], tr["trace_from_s"], tr["trace_seconds"]) \
        == (75, 10, 300, 6, 4, 3)
    cfg = _cell()["config_data"]
    assert closed_sala.page_bytes(cfg, 64) == 262144
    assert eng["kv_arena_bytes"] // 262144 == 4288 == 8 * 528 + 64
    # lengths are replayed, client c from entry c mod 4; the seed draws ids
    a = closed_sala.replayed_requests(tr, 5, cfg["vocab_size"])
    b = closed_sala.replayed_requests(tr, 2**31 + 9, cfg["vocab_size"])
    assert [len(r["prompt"]) for r in a[5][:5]] \
        == [16384, 20480, 32768, 12288, 16384]
    assert [r["max_new_tokens"] for r in a[2][:4]] == [640, 512, 384, 512]
    assert [[len(r["prompt"]) for r in c] for c in a] \
        == [[len(r["prompt"]) for r in c] for c in b]
    assert not np.array_equal(a[0][0]["prompt"], b[0][0]["prompt"])
    assert a[0][0]["prompt"].max() > 65536 > a[0][0]["prompt"].min() >= 0


def test_the_cells_entries_are_legal_and_name_files_that_exist():
    """What ``test_benchmark_spec.py`` asserts of every cell, of this one,
    with the drivers read from ``benchmark/drivers/``."""
    name = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.\-]{0,63}$")
    unit = re.compile(r"^[A-Za-z0-9_/%.\-]{1,16}$")
    (config,) = [c for c in BENCH["configs"] if c["name"] == "minicpm-sala"]
    (cell,) = [w for w in BENCH["workloads"] if w["config"] == config["name"]]
    ours = [m for m in BENCH["per_layer"] if CELL in m.get("workloads", ())]
    # appended behind what the benchmark had (a later PR appends behind
    # these, so "last" is not asserted: test_benchmark_lfm2.py asserts it of
    # its own entries and fails from this PR on, see CHANGES.md)
    assert BENCH["configs"].index(config) == 3 \
        and BENCH["workloads"].index(cell) == 4
    first = BENCH["per_layer"].index(ours[0])
    assert BENCH["per_layer"][first:first + 13] == ours and first == 49
    assert os.path.isfile(os.path.join(bench_spec.ROOT, config["file"]))
    assert config["file"].startswith(tuple(BENCH["paths"]))
    assert (cell["name"], cell["config"], cell["chips"]) \
        == (CELL, config["name"], 1) and len(cell["why"]) <= 200
    assert all(name.match(n) for n in
               [cell["name"], cell["traffic"], config["name"],
                *config["reduced"], *(m["name"] for m in ours)])
    loaded = _cell()
    drivers = {f[:-3] for f in os.listdir(
        os.path.join(bench_spec.HERE, "drivers"))
        if f.endswith(".py") and f not in ("__init__.py", "serving.py")}
    assert loaded["traffic_data"]["driver"] == "closed_sala" in drivers
    assert callable(bench_spec.load_driver("closed_sala"))
    assert set(loaded["limits"]) == {"served_token_gap", "left_out_share"}
    with open(os.path.join(bench_spec.ROOT, "PERF.md")) as f:
        perf = f.read()
    for m in ours:
        assert unit.match(m["unit"]) and m["better"] in ("lower", "higher")
        assert (m["moves"], m["workloads"]) == ("serve_tok_s", [CELL])
        assert m["layer"] in perf, m["layer"]
    e2e = {m["name"]: m for m in bench_spec.metrics_for(BENCH, "end_to_end",
                                                        CELL)}
    assert sorted(e2e) == ["serve_tok_s", "setup_s"]
    assert e2e["serve_tok_s"]["workloads"].index(CELL) == 2
    assert len(json.dumps(BENCH)) < 64 * 1024


@pytest.mark.parametrize("name", [
    m["name"] for m in bench_spec.metrics_for(BENCH, "per_layer", CELL)
    if CELL in m.get("workloads", ())])
def test_a_reader_of_the_cell_that_finds_nothing_to_read_returns_nothing(name):
    """As ``test_benchmark_spec.py`` asks of every accepted reader: on the
    parent, which has no such span or counter, the line leaves it out."""
    assert bench_spec.load_reader(name)({"records": [], "hist": {}}) is None


def test_the_new_readers_read_what_the_engine_counts():
    cell = _cell()
    run = {"cell": cell, "records": [],
           "hist": {"prefill_chunk_ms": {"count": 3, "p50": 212.5}},
           "counters": {"worker.loop_s": 50.0, "worker.prefill_chunk_s": 5.0,
                        "sparse_attn.pages_selected": 64 * 32,
                        "sparse_attn.pages_live": 256 * 32}}
    assert bench_spec.load_reader("prefill_chunk_ms_p50")(run) == 212.5
    assert bench_spec.load_reader("prefill_chunk_share_pct")(run) == 10.0
    assert bench_spec.load_reader("sparse_selected_page_share")(run) == 0.25
    from benchmark import costs_sala
    cost = costs_sala.selected_walk_cost(cell["config_data"], 64, 4096, 16)
    # 4,096 (page, head, layer) triples of 64 rows of a key and a value
    assert cost["bytes"] == (2 * 4096 * 64 * 128 + 2 * 16 * 32 * 128) * 4
    assert cost["flops"] == 4.0 * 4096 * 64 * 16 * 128


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
    found = set(last["readers_that_found_something"])
    assert {"compiles_in_window.sala", "tick_batch_mean.sala",
            "decode_tick_ms_p50.sala", "prefill_chunk_ms_p50",
            "prefill_chunk_share_pct", "sparse_selected_page_share"} <= found


#: served tokens changed where the engine hands them to its clients
ALTERED = (
    "from paddle_tpu.serving.llm.scheduler import GenerationRequest as G; "
    "emit = G._emit; "
    "G._emit = lambda self, tok: emit(self, (tok + 2) % 512 "
    "if len(self.tokens) == 3 else tok); ")

@pytest.mark.parametrize("fault,modes", [
    (ALTERED, ()), (None, (closed_sala.PROGRAM_TOPK_SHORT,))],
    ids=["altered-token", "topk-one-short"])
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
    top = sala_weights.make_top(cfg, 5)

    def layer(i):
        return sala_weights.make_layer(cfg, 5, i)

    records = []
    for plen in (34, 48, 61):
        seq = np.zeros(96, np.int32)
        seq[:plen] = rng.integers(0, cfg["vocab_size"], plen)
        for at in range(plen - 1, plen + 11):   # greedy under the reference
            hid, _ = ref.hidden_states(top, layer, arch, jnp.asarray(seq))
            seq[at + 1] = int(jnp.argmax(ref.logits_of(top, hid[at][None])))
        records.append({"prompt": seq[:plen].copy(), "finished": True,
                        "tokens": [int(t) for t in seq[plen:plen + 12]]})
    numbers = closed_sala.serve_gaps(cfg, 5, records, 0.0, pad_len=96,
                                     max_new=12, control_modes=("bfloat16",))
    limits = {"served_token_gap": 1e-4, "left_out_share": 0.5}
    assert check.judge(numbers, limits)
    assert numbers["tokens_compared"] == 36 == numbers["tokens_sampled"]
    assert numbers["control_bfloat16_token_gap"] > 1e-4
    # a near-tie at a served position cuts the request there; one inside
    # the prompt does not
    for rule in closed_sala.RULES:
        far = closed_sala.serve_gaps(cfg, 5, records, 1.0, 96, 12, rule=rule)
        assert far["left_out_share"] == 1.0 and not check.judge(far, limits)
        assert far["smallest_margin"] >= far["smallest_margin_anywhere"]


@pytest.mark.parametrize("rule,low_at,want", [
    ("own", None, [1, 1, 1, 1, 1, 1]),
    ("own", 2, [1, 1, 1, 1, 1, 1]),      # inside the prompt: not held to it
    ("own", 4, [0, 1, 1, 1, 1, 1]),      # the position of the first token
    ("own", 7, [1, 1, 1, 0, 1, 1]),
    ("cut", 7, [1, 1, 1, 0, 0, 0]),      # and everything behind it
    ("cut", 2, [1, 1, 1, 1, 1, 1]),
    ("own", 15, [1, 1, 1, 1, 1, 1])])    # behind the request: ignored
def test_near_tie_rules_leave_out_what_they_should(rule, low_at, want):
    margin = np.full(32, 0.01)
    if low_at is not None:
        margin[low_at] = 1e-9
    margin[:4] = np.inf         # as serve_gaps blanks the prompt's
    got = closed_sala.compared(margin, plen=5, n=6, tau=1e-4, rule=rule)
    assert got.astype(int).tolist() == want
    with pytest.raises(ValueError, match="near_tie_rule"):
        closed_sala.compared(margin, 5, 6, 1e-4, "first")


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
    """The programs compiled here must hold the Mosaic kernel, so the test
    (not a program option) answers the compile-or-interpret question."""
    from paddle_tpu.ops import paged_attention
    monkeypatch.setattr(paged_attention, "resolve_interpret",
                        lambda kernel, requested=None: False)


def _shapes(one_chip):
    cell = _cell()
    cfg, eng = cell["config_data"], cell["traffic_data"]["engine"]
    net_cfg = sala_adapter.config_of(cfg)

    def s(shape, dtype=jnp.float32):
        return jax.ShapeDtypeStruct(tuple(shape), dtype, sharding=one_chip)

    names = {"n1": "n1", "n2": "n2", "w1": "w1", "w3": "w3", "w2": "w2",
             "q_w": "qw", "k_w": "kw", "v_w": "vw", "o_w": "ow",
             "gate_w": "gw", "z_w": "zw", "q_norm": "qn", "k_norm": "kn",
             "o_norm": "on"}
    layers = tuple({names[k]: s(v)
                    for k, v in sala_weights.layer_shapes(cfg, i).items()}
                   for i in range(cfg["num_hidden_layers"]))
    h, vocab = cfg["hidden_size"], cfg["vocab_size"]
    params = {"tok": s((vocab, h)), "fnw": s((h,)), "head": s((h, vocab)),
              "layers": layers}
    pages = eng["kv_arena_bytes"] // closed_sala.page_bytes(
        cfg, eng["page_size"])
    hkv, d = cfg["num_key_value_heads"], cfg["head_dim"]
    sparse, linear = len(net_cfg.sparse_layers), len(net_cfg.linear_layers)
    arena = s((pages + 1, sparse * hkv, eng["page_size"], 2 * d))
    slots = eng["num_slots"]
    state = {"lin": s((slots, linear, cfg["lightning_nh"], d, d)),
             "ckey": s((pages + 1, sparse, net_cfg.kernels_per_block, hkv,
                        d))}
    per_slot = {
        "tables": s((slots, eng["max_seq"] // eng["page_size"]), jnp.int32),
        "lengths": s((slots,), jnp.int32), "finished": s((slots,), bool),
        "last": s((slots,), jnp.int32), "temperature": s((slots,)),
        "top_k": s((slots,), jnp.int32), "do_sample": s((slots,), bool),
        "eos": s((slots,), jnp.int32), "key": s((2,), jnp.uint32)}
    return net_cfg, eng, params, arena, state, per_slot, s


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
    (11.28 GB of weights, the arena, the states), aliased outputs and
    temporaries; both programs are loaded at once, so the sum holds the
    arguments once and both programs' temporaries."""
    from paddle_tpu.serving.llm.paged.sala import (
        build_sala_paged_chunk_fn, build_sala_paged_decode_step)
    cfg, eng, params, arena, state, p, s = _shapes(one_chip)
    step = build_sala_paged_decode_step(cfg, eng["max_top_k"], "kernel")
    compiled = jax.jit(step, donate_argnums=(1, 2)).lower(
        params, arena, state, p["tables"], p["lengths"], p["finished"],
        p["last"], p["temperature"], p["top_k"], p["do_sample"], p["eos"],
        p["key"]).compile()
    text = compiled.as_text()
    # one selected walk a sparse layer
    assert text.count('custom_call_target="tpu_custom_call"') \
        == len(cfg.sparse_layers)
    decode = _record("decode_step_sala", compiled, record_property)
    held = (np.prod(arena.shape) + sum(np.prod(v.shape)
                                       for v in state.values())) * 4
    # the arena and both states are updated in place, and not copied
    assert decode["alias_bytes"] >= held
    assert "copy(" not in "".join(
        line for line in text.splitlines()
        if f"f32[{arena.shape[0]}," in line.split(" = ")[-1][:20])

    def one(dtype=jnp.float32):
        return s((1,), dtype)

    chunk = build_sala_paged_chunk_fn(cfg, eng["max_top_k"])
    compiled = jax.jit(chunk, donate_argnums=(5, 6)).lower(
        params, s((1, eng["prefill_chunk"]), jnp.int32), s((), jnp.int32),
        s((), jnp.int32), s((), bool), arena, state, p["tables"],
        p["lengths"], p["finished"], s((), jnp.int32), one(),
        one(jnp.int32), one(bool), one(jnp.int32), p["key"]).compile()
    prefill = _record("prefill_chunk_sala", compiled, record_property)
    assert prefill["alias_bytes"] >= held
    both = (decode["argument_bytes"] + decode["temp_bytes"]
            + prefill["temp_bytes"])
    record_property("both_programs_bytes", int(both))
    assert 11.28e9 + held < both < PEAK_LIMIT
