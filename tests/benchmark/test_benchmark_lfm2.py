"""The LFM2-8B-A1B configuration and its cell: the published sizes against
the catalog row, the decode step and the 512-token prefill compiled for a
described (not attached) TPU v5e at the cell's sizes, the near-tie rule of
the comparison, and a comparison that fails what it should.

The cell is in ``BENCHMARK.json`` with a driver of its own, ``closed_lfm2``
(``spec.load_driver`` imports whatever driver a traffic file names).
``test_benchmark_spec.py::test_every_file_the_benchmark_names_exists`` holds
every cell's driver to ``("fit", "closed", "open")`` and so fails on this
cell, at that line alone; the test of the entries below asserts the same
things with the drivers read from ``benchmark/drivers/``.

The compiles describe the topology inside a fixture (see the
``on-chip-measurement`` guide); nothing runs and no number here is a
measurement.
"""
import functools
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

from benchmark import lfm2_adapter, lfm2_weights, spec as bench_spec
from benchmark.drivers import closed_lfm2
from benchmark.reference import lfm2_ref as ref

pytestmark = pytest.mark.timeout_s(900)
CELL = "serve-lfm2moe-decode"
BENCH = bench_spec.load_benchmark()


def _cell(rehearsal=False):
    return bench_spec.load_cell(BENCH, CELL, rehearsal=rehearsal)


# -- the configuration ------------------------------------------------------------

PUBLISHED = {"hidden_size": 2048, "num_attention_heads": 32,
             "num_key_value_heads": 8, "intermediate_size": 7168,
             "moe_intermediate_size": 1792, "num_experts": 32,
             "num_experts_per_tok": 4, "vocab_size": 65536,
             "conv_L_cache": 3, "rope_theta": 1000000, "num_dense_layers": 2,
             "norm_eps": 1e-05, "norm_topk_prob": True,
             "routed_scaling_factor": 1, "use_expert_bias": True,
             "conv_bias": False, "max_position_embeddings": 128000}


@pytest.mark.parametrize("key", sorted(PUBLISHED))
def test_published_size_is_unchanged(key):
    assert _cell()["config_data"][key] == PUBLISHED[key]


def test_the_cut_is_what_the_files_say():
    cfg = _cell()["config_data"]
    entry = {c["name"]: c for c in BENCH["configs"]}["lfm2-8b-a1b"]
    assert sorted(cfg["changed"]) == sorted(entry["reduced"])
    assert cfg["num_hidden_layers"] == 8 == len(cfg["layer_types"])
    assert cfg["layer_types"] == ["conv", "conv", "full_attention", "conv",
                                  "conv", "conv", "full_attention", "conv"]
    assert cfg["assumed"]["tie_word_embeddings"] is True
    assert "float32" in cfg["precision"] and "pipeline" in cfg["deployment"]
    catalog = "/opt/skills/guides/model-configs/architectures.jsonl"
    if os.path.exists(catalog):     # every key of the row, but the cut ones
        with open(catalog) as f:
            row = next(r for r in map(json.loads, f)
                       if r["name"] == "LFM2-8B-A1B")
        assert entry["source"] == row["source_url"]
        for k, v in row["config"].items():
            if k not in entry["reduced"]:
                assert cfg[k] == v, k
        assert cfg["layer_types"] == row["config"]["layer_types"][:8]


def test_the_traffic_file_holds_the_issues_numbers():
    tr = _cell()["traffic_data"]
    eng = tr["engine"]
    assert (tr["clients"], eng["num_slots"]) == (32, 32)
    assert (tr["prompt_len"]["lo"], tr["prompt_len"]["hi"]) == (128, 512)
    assert (tr["output_len"]["lo"], tr["output_len"]["hi"]) == (512, 1024)
    assert (eng["max_seq"], eng["page_size"]) == (1536, 16)
    assert eng["prefill_buckets"] == [128, 256, 512]
    cfg = _cell()["config_data"]
    assert closed_lfm2.page_bytes(cfg, 16) == 131072
    assert eng["kv_arena_bytes"] // 131072 == 3328


def test_the_cells_entries_are_legal_and_name_files_that_exist():
    """What ``test_benchmark_spec.py`` asserts of every cell, of this one,
    with the drivers read from ``benchmark/drivers/``."""
    name = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.\-]{0,63}$")
    unit = re.compile(r"^[A-Za-z0-9_/%.\-]{1,16}$")
    (config,) = [c for c in BENCH["configs"] if c["name"] == "lfm2-8b-a1b"]
    (cell,) = [w for w in BENCH["workloads"] if w["config"] == config["name"]]
    ours = [m for m in BENCH["per_layer"] if CELL in m.get("workloads", ())]
    assert BENCH["configs"][-1] is config and BENCH["workloads"][-1] is cell
    assert BENCH["per_layer"][-len(ours):] == ours and len(ours) == 14
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
    assert loaded["traffic_data"]["driver"] == "closed_lfm2" in drivers
    assert callable(bench_spec.load_driver("closed_lfm2"))
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
    assert e2e["serve_tok_s"]["workloads"][-1] == CELL
    assert len(json.dumps(BENCH)) < 64 * 1024


@pytest.mark.parametrize("name", [
    m["name"] for m in bench_spec.metrics_for(BENCH, "per_layer", CELL)
    if CELL in m.get("workloads", ())])
def test_a_reader_of_the_cell_that_finds_nothing_to_read_returns_nothing(name):
    """As ``test_benchmark_spec.py`` asks of every accepted reader: on the
    parent, which has no such span or counter, the line leaves it out."""
    assert bench_spec.load_reader(name)({"records": [], "hist": {}}) is None


def test_the_cell_rehearses_from_a_copy_of_the_benchmark_files(tmp_path):
    """``test_benchmark_runs.py``'s rehearsal from a copy that holds
    ``BENCHMARK.json`` and ``benchmark/`` alone (what the driver lays over
    another checkout), with what this cell's comparison and readers print."""
    root = tmp_path / "copy"
    shutil.copytree(bench_spec.HERE, root / "benchmark",
                    ignore=shutil.ignore_patterns("__pycache__"))
    (root / "BENCHMARK.json").write_text(json.dumps(BENCH))
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               JAX_COMPILATION_CACHE_DIR=str(tmp_path / "jax_cache"),
               PYTHONPATH=os.pathsep.join([str(root), bench_spec.ROOT]))
    out = subprocess.run(
        [sys.executable, *BENCH["command"][1:], "--workload", CELL, "--seed",
         str(2**31 + 5), "--seconds", "1", "--trace", "1",
         "--rehearse-on-cpu"], cwd=root, env=env, capture_output=True,
        text=True, timeout=800)
    assert out.returncode == 0, out.stderr[-2000:]
    last = json.loads(out.stdout.strip().splitlines()[-1])
    assert last["rehearsal"] is True and last["metrics"] == {}
    assert last["correct"] is True and last["failed"] == 0
    assert last["attempted"] > 0
    assert "check: served_token_gap" in out.stdout
    assert "check: left_out_share" in out.stdout
    found = set(last["readers_that_found_something"])
    assert {"compiles_in_window.moe", "moe_experts_active_mean",
            "moe_load_max_mean", "tick_batch_mean.moe"} <= found


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
    for module in (moe, paged_attention):
        monkeypatch.setattr(module, "resolve_interpret",
                            lambda kernel, requested=None: False)


def _shapes(one_chip):
    cell = _cell()
    cfg, eng = cell["config_data"], cell["traffic_data"]["engine"]
    net_cfg = lfm2_adapter.config_of(cfg)

    def s(shape, dtype=jnp.float32):
        return jax.ShapeDtypeStruct(tuple(shape), dtype, sharding=one_chip)

    names = {"op_norm": "n1", "ffn_norm": "n2", "conv_in": "in",
             "conv_k": "k", "conv_out": "out", "q_w": "qw", "k_w": "kw",
             "v_w": "vw", "o_w": "ow", "q_norm": "qn", "k_norm": "kn",
             "gate": "gate", "expert_bias": "bias", "w1": "w1", "w3": "w3",
             "w2": "w2"}
    layers = tuple({names[k]: s(v)
                    for k, v in lfm2_weights.layer_shapes(cfg, i).items()}
                   for i in range(cfg["num_hidden_layers"]))
    h = cfg["hidden_size"]
    params = {"tok": s((cfg["vocab_size"], h)), "fnw": s((h,)),
              "layers": layers}
    pages = eng["kv_arena_bytes"] // closed_lfm2.page_bytes(
        cfg, eng["page_size"])
    arena = s((pages + 1, len(net_cfg.attn_layers), eng["page_size"],
               cfg["num_key_value_heads"], 2 * net_cfg.head_dim))
    slots = eng["num_slots"]
    state = s((slots, len(net_cfg.conv_layers), cfg["conv_L_cache"] - 1, h))
    per_slot = {
        "tables": s((slots, eng["max_seq"] // eng["page_size"]), jnp.int32),
        "lengths": s((slots,), jnp.int32), "finished": s((slots,), bool),
        "last": s((slots,), jnp.int32), "temperature": s((slots,)),
        "top_k": s((slots,), jnp.int32), "do_sample": s((slots,), bool),
        "eos": s((slots,), jnp.int32), "key": s((2,), jnp.uint32)}
    return net_cfg, eng, params, arena, state, per_slot, s


def _record(name, compiled, record_property):
    m = compiled.memory_analysis()
    found = {"argument_bytes": m.argument_size_in_bytes,
             "output_bytes": m.output_size_in_bytes,
             "temp_bytes": m.temp_size_in_bytes,
             "alias_bytes": m.alias_size_in_bytes}
    record_property(name, json.dumps(found))
    print(name, found)
    hbm = 15.75 * 2 ** 30
    assert found["argument_bytes"] + found["output_bytes"] \
        + found["temp_bytes"] - found["alias_bytes"] < hbm
    return found


def test_decode_step_of_the_lfm2_cell_compiles_for_v5e(
        one_chip, no_persistent_cache, mosaic_kernels, record_property):
    from paddle_tpu.serving.llm.paged.lfm2 import (
        build_lfm2_paged_decode_step)
    cfg, eng, params, arena, state, p, _ = _shapes(one_chip)
    step = build_lfm2_paged_decode_step(cfg, eng["max_top_k"],
                                        eng["page_size"], "kernel")
    compiled = jax.jit(step, donate_argnums=(1, 2)).lower(
        params, arena, state, p["tables"], p["lengths"],
        p["finished"], p["last"], p["temperature"], p["top_k"],
        p["do_sample"], p["eos"], p["key"]).compile()
    # two kernels an expert layer and one an attention layer
    wanted = 2 * cfg.num_expert_layers + len(cfg.attn_layers)
    assert compiled.as_text().count(
        'custom_call_target="tpu_custom_call"') >= wanted
    found = _record("decode_step_lfm2", compiled, record_property)
    # the arenas and the state are updated in place
    assert found["alias_bytes"] >= np.prod(arena.shape) * 4
    # ... and not copied: a minor axis under the lane width would be (the
    # device lays such an array out in another order than the kernel reads)
    assert "copy(" not in "".join(
        line for line in compiled.as_text().splitlines()
        if f"f32[{arena.shape[0]}," in line.split(" = ")[-1][:20])


def test_prefill_512_of_the_lfm2_cell_compiles_for_v5e(
        one_chip, no_persistent_cache, mosaic_kernels, record_property):
    from paddle_tpu.serving.llm.paged.lfm2 import build_lfm2_paged_prefill_fn
    cfg, eng, params, arena, state, p, s = _shapes(one_chip)
    bucket = eng["prefill_buckets"][-1]
    one = {k: s((1,), v.dtype) for k, v in p.items()
           if k in ("temperature", "top_k", "do_sample", "eos")}
    prefill = build_lfm2_paged_prefill_fn(cfg, eng["max_top_k"],
                                          eng["page_size"])
    compiled = jax.jit(prefill, donate_argnums=(3, 4)).lower(
        params, s((1, bucket), jnp.int32), s((1,), jnp.int32), arena,
        state, p["tables"], p["lengths"], p["finished"], s((1,), jnp.int32),
        one["temperature"], one["top_k"], one["do_sample"], one["eos"],
        p["key"]).compile()
    _record("prefill_512_lfm2", compiled, record_property)


# -- the comparison -------------------------------------------------------------------

@pytest.mark.parametrize("low_at,want", [
    (None, 6),      # no near-tie: every served token is compared
    (2, 0),         # inside the prompt: nothing after it can be trusted
    (4, 0),         # at the position that predicts the first served token
    (7, 3),         # tokens 0..2 are predicted from positions 4..6
    (9, 5),
    (10, 6),        # the last token is predicted from position 9
    (15, 6)])       # in the padding behind the request: ignored
def test_near_tie_rule_leaves_out_what_it_should_and_nothing_else(low_at,
                                                                   want):
    margin = np.full(32, 0.01)
    if low_at is not None:
        margin[low_at] = 1e-7
    assert closed_lfm2.compared_tokens(margin, plen=5, n=6, tau=1e-4) == want


@pytest.fixture(scope="module")
def toy():
    cfg = _cell(rehearsal=True)["config_data"]
    return cfg, ref.arch_of(cfg), lfm2_weights.make_lfm2_weights(cfg, 5)


@functools.partial(jax.jit, static_argnums=(1, 4))
def _next_token(w, arch, seq, at, mode):
    hid, _ = ref.hidden_states(w, arch, seq, mode)
    return jnp.argmax(ref.logits_of(w, hid[at][None], mode)[0])


def _greedy(w, arch, prompt, n, mode="highest"):
    """What a program computing in ``mode`` would serve, a token at a time
    (one padded row: the forward is causal, so the padding is not seen)."""
    seq = np.zeros(32, np.int32)
    seq[:len(prompt)] = prompt
    for at in range(len(prompt) - 1, len(prompt) - 1 + n):
        seq[at + 1] = int(_next_token(w, arch, jnp.asarray(seq), at, mode))
    return [int(t) for t in seq[len(prompt):len(prompt) + n]]


@functools.lru_cache(maxsize=None)
def _served(mode):
    """Three requests as a program computing in ``mode`` would serve them."""
    cfg = _cell(rehearsal=True)["config_data"]
    arch, w = ref.arch_of(cfg), lfm2_weights.make_lfm2_weights(cfg, 5)
    rng = np.random.default_rng(3)
    out = []
    for plen in (3, 9, 14):
        prompt = rng.integers(0, cfg["vocab_size"], plen).astype(np.int32)
        out.append({"prompt": prompt, "finished": True,
                    "tokens": _greedy(w, arch, prompt, 12, mode)})
    return out


def _records(w, arch, cfg, mode="highest"):
    return [dict(r, tokens=list(r["tokens"])) for r in _served(mode)]


#: at toy width on the CPU a sound float32 program serves the reference's
#: own first token, or its pair partner where the two logits lie within
#: rounding (a gap under 1e-6); anything wider than 1e-4 is a fault
TOY_LIMITS = {"served_token_gap": 1e-4, "left_out_share": 0.5}


def _judge(w, arch, records, tau=0.0, **kw):
    from benchmark import check
    numbers = closed_lfm2.serve_gaps(w, arch, records, tau, pad_len=32,
                                     max_new=12, **kw)
    return check.judge(numbers, TOY_LIMITS), numbers


def test_a_sound_program_is_correct_and_the_controls_are_not(toy):
    cfg, arch, w = toy
    ok, numbers = _judge(w, arch, _records(w, arch, cfg),
                         control_modes=("bfloat16",))
    assert ok and numbers["tokens_compared"] == 36 == numbers["tokens_sampled"]
    assert numbers["control_bfloat16_token_gap"] > TOY_LIMITS[
        "served_token_gap"]


def test_a_lower_precision_engine_fails_the_comparison(toy):
    cfg, arch, w = toy
    ok, numbers = _judge(w, arch, _records(w, arch, cfg, "bfloat16"))
    assert not ok and numbers["served_token_gap"] > 1e-4


def test_a_token_altered_after_serving_fails_the_comparison(toy):
    cfg, arch, w = toy
    records = _records(w, arch, cfg)
    records[1]["tokens"][7] = (records[1]["tokens"][7] + 2) % cfg["vocab_size"]
    ok, numbers = _judge(w, arch, records)
    assert not ok and numbers["served_token_gap"] > 1e-2


def test_too_few_positions_compared_is_not_correct(toy):
    cfg, arch, w = toy
    records = _records(w, arch, cfg)
    ok, numbers = _judge(w, arch, records, tau=1.0)     # every margin is under
    assert not ok and numbers["left_out_share"] == 1.0
    assert numbers["served_token_gap"] == 0.0           # nothing was compared
    # an altered token behind a near-tie is left out, as the rule says, and
    # one before it is not
    margin = ref.hidden_states(w, arch, jnp.asarray(np.concatenate(
        [records[0]["prompt"], records[0]["tokens"][:-1]]), jnp.int32))[1]
    tau = float(np.sort(np.asarray(margin))[1]) * 1.0001   # two positions low
    kept = closed_lfm2.compared_tokens(margin, 3, 12, tau)
    assert kept < 12
    late = [dict(records[0], tokens=list(records[0]["tokens"]))]
    late[0]["tokens"][-1] = (late[0]["tokens"][-1] + 2) % cfg["vocab_size"]
    assert closed_lfm2.serve_gaps(w, arch, late, tau, 32, 12)[
        "served_token_gap"] <= 1e-4
    if kept:
        early = [dict(records[0], tokens=list(records[0]["tokens"]))]
        early[0]["tokens"][0] = (early[0]["tokens"][0] + 2) % cfg["vocab_size"]
        assert closed_lfm2.serve_gaps(w, arch, early, tau, 32, 12)[
            "served_token_gap"] > 1e-2
