"""The Moonlight-16B-A3B configuration and its cell
``serve-moonlight-longgen``: the published sizes against the catalog row, the
cut and the share, the cell's entries and files, the rehearsal of the cell on
the CPU (correct) and its faults (a served token altered where it is
produced, the rotary part left out of the decode scores and the softmax scale
over the position-free part alone, each through the whole run; the bfloat16
control through the comparison itself, held to the toy width's own limit:
each not correct), the readers on an empty run and on a made one, and the
decode step and the chunk program compiled for a described (not attached) TPU
v5e at the cell's sizes, their memory recorded.

``test_benchmark_spec.py::test_every_file_the_benchmark_names_exists`` holds
every cell's driver to ``("fit", "closed", "open")`` and so fails on this
cell's ``closed_moonlight`` as it does on ``closed_lfm2``, ``closed_sala`` and
``closed_trinity``, at that line alone; the test of the entries below asserts
the same things with the drivers read from ``benchmark/drivers/``.

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

from benchmark import (check, costs_moonlight, moonlight_adapter,
                       moonlight_weights, spec as bench_spec)
from benchmark.drivers import closed_moonlight
from benchmark.reference import moonlight_ref as ref

pytestmark = pytest.mark.timeout_s(1200)
CELL = "serve-moonlight-longgen"
BENCH = bench_spec.load_benchmark()
CATALOG = "/opt/skills/guides/model-configs/architectures.jsonl"
REDUCED = ["num_hidden_layers", "n_routed_experts", "vocab_size"]


def _cell(rehearsal=False):
    return bench_spec.load_cell(BENCH, CELL, rehearsal=rehearsal)


# -- the configuration ------------------------------------------------------------

#: the catalog row's ``config`` but for what is reduced, as this PR read it
#: (the catalog itself is compared where it is present)
PUBLISHED = {
    "attention_bias": False, "ep_size": 1, "first_k_dense_replace": 1,
    "hidden_act": "silu", "hidden_size": 2048, "intermediate_size": 11264,
    "kv_lora_rank": 512, "max_position_embeddings": 8192,
    "model_type": "deepseek_v3", "moe_intermediate_size": 1408,
    "moe_layer_freq": 1, "n_group": 1, "n_shared_experts": 2,
    "norm_topk_prob": True, "num_attention_heads": 16,
    "num_experts_per_tok": 6, "num_key_value_heads": 16,
    "num_nextn_predict_layers": 0, "q_lora_rank": None,
    "qk_nope_head_dim": 128, "qk_rope_head_dim": 64, "rms_norm_eps": 1e-05,
    "rope_theta": 50000, "routed_scaling_factor": 2.446,
    "scoring_func": "sigmoid", "seq_aux": True, "tie_word_embeddings": False,
    "topk_group": 1, "topk_method": "noaux_tc", "v_head_dim": 128}


@pytest.mark.parametrize("key", sorted(PUBLISHED))
def test_published_size_is_unchanged(key):
    assert _cell()["config_data"][key] == PUBLISHED[key]


def test_the_file_holds_the_catalogs_row_but_for_what_is_reduced():
    if not os.path.isfile(CATALOG):
        pytest.skip("no catalog beside the guide here")
    with open(CATALOG) as f:
        (row,) = [r for r in map(json.loads, f)
                  if r["name"] == "Moonlight-16B-A3B"]
    cfg = _cell()["config_data"]
    (entry,) = [c for c in BENCH["configs"]
                if c["name"] == "moonlight-16b-a3b"]
    assert entry["source"] == row["source_url"] == cfg["source"]
    differs = sorted(k for k, v in row["config"].items() if cfg.get(k) != v)
    assert differs == sorted(REDUCED) == sorted(entry["reduced"])
    assert sorted(cfg["changed"]) == sorted(REDUCED)
    assert PUBLISHED == {k: row["config"][k] for k in PUBLISHED}
    # the published values stand beside the cut ones
    assert "published 27" in cfg["changed"]["num_hidden_layers"]
    assert "published 64" in cfg["changed"]["n_routed_experts"]
    assert "published 163,840" in cfg["changed"]["vocab_size"]


@pytest.mark.parametrize("item", ref.ASSUMED)
def test_assumed_item_is_stated_with_its_source(item):
    assumed = _cell()["config_data"]["assumed"]
    assert item in assumed
    assert "modeling_deepseek_v3" in assumed["source of every item"]
    assert "2405.04434" in assumed["source of every item"]


def test_the_cut_and_the_share_are_what_the_files_say():
    cfg = _cell()["config_data"]
    share = cfg["share"]
    assert (cfg["num_hidden_layers"], cfg["first_k_dense_replace"]) == (9, 1)
    assert share == {"chips_sharing_a_layer": 8, "num_experts_published": 64,
                     "experts_held": [0, 8], "vocab_size_published": 163840,
                     "vocab_rows": [0, 20480]}
    # the guide's floors: 8 experts, an eighth of the vocabulary, four layers
    assert cfg["n_routed_experts"] == 8
    assert cfg["vocab_size"] == 20480 == 163840 // 8
    assert "8 chips share each layer" in cfg["deployment"]
    assert "float32" in cfg["precision"]
    net = moonlight_adapter.config_of(cfg)
    assert (net.n_routed_experts, net.experts_held) == (64, (0, 8))
    assert (net.vocab_size, net.vocab_held) == (163840, 20480)
    assert net.route_eps == 1e-20 and net.latent_row == 576
    # 970.1 M parameters, 3.88 GB
    count = 2 * 20480 * 2048 + 2048 + sum(
        int(np.prod(shape)) for i in range(9)
        for shape in moonlight_weights.layer_shapes(cfg, i).values())
    assert abs(count - 970.1e6) < 0.3e6
    assert "3.88 GB" in cfg["parameters"]
    # attention 13.76 M a layer
    att = sum(int(np.prod(moonlight_weights.layer_shapes(cfg, 3)[k]))
              for k in ("q_w", "dkv_w", "ukv_w", "o_w"))
    assert abs(att - 13.76e6) < 0.01e6


def test_the_traffic_file_holds_the_issues_numbers():
    tr = _cell()["traffic_data"]
    assert tr["prompt_lens"] == [2048, 6144, 3072, 4096, 1024, 5120, 3072,
                                 4096]
    assert tr["output_lens"] == [1536, 768, 1280, 1536, 2048, 1024, 1536,
                                 1280]
    assert np.mean(tr["prompt_lens"]) == 3584
    assert np.mean(tr["output_lens"]) == 1376
    assert (tr["clients"], tr["client_stagger_s"]) == (48, 0.5)
    assert (tr["warm_seconds"], tr["drain_seconds"],
            tr["request_timeout_s"], tr["check_requests"],
            tr["trace_from_s"], tr["trace_seconds"]) == (75, 10, 300, 8, 4, 3)
    eng = tr["engine"]
    assert eng["num_slots"] == 48 and eng["max_seq"] == 7168 == 112 * 64
    assert (eng["page_size"], eng["prefill_chunk"], eng["max_top_k"],
            eng["max_queue"]) == (64, 1024, 8, 96)
    assert eng["num_pages"] == 48 * 112 + 64 == 5440
    cfg = _cell()["config_data"]
    # a page of 64 rows of 640 floats in 9 layers; 8.02 GB of pages beside
    # 3.88 GB of weights
    page = closed_moonlight.page_bytes(cfg, 64, 640)
    assert page == 1474560
    assert abs(5441 * page / 1e9 - 8.023) < 0.001
    assert closed_moonlight.page_bytes(cfg, 64, 576) == 1327104
    requests = closed_moonlight.replayed_requests(tr, 2**31 + 7,
                                                  cfg["vocab_size"])
    assert [len(r["prompt"]) for r in requests[3][:3]] == [4096, 1024, 5120]
    assert max(r["prompt"].max() for r in requests[0]) < cfg["vocab_size"]
    ends = [len(r["prompt"]) + r["max_new_tokens"]
            for c in requests for r in c]
    assert max(ends) == 6912 < eng["max_seq"] <= cfg[
        "max_position_embeddings"]


def test_the_cells_entries_are_legal_and_name_files_that_exist():
    """What ``test_benchmark_spec.py`` asserts of every cell, of this one,
    with the drivers read from ``benchmark/drivers/``."""
    name = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.\-]{0,63}$")
    unit = re.compile(r"^[A-Za-z0-9_/%.\-]{1,16}$")
    (config,) = [c for c in BENCH["configs"]
                 if c["name"] == "moonlight-16b-a3b"]
    (cell,) = [w for w in BENCH["workloads"] if w["config"] == config["name"]]
    ours = [m for m in BENCH["per_layer"] if CELL in m.get("workloads", ())]
    # appended behind what the benchmark had (a later PR appends behind
    # these, so "last" is not asserted)
    assert BENCH["configs"].index(config) == 5 \
        and BENCH["workloads"].index(cell) == 6
    first = BENCH["per_layer"].index(ours[0])
    assert BENCH["per_layer"][first:first + 20] == ours and first == 94
    assert os.path.isfile(os.path.join(bench_spec.ROOT, config["file"]))
    assert config["file"].startswith(tuple(BENCH["paths"]))
    assert config["reduced"] == REDUCED and len(config["why"]) <= 200
    assert (cell["name"], cell["config"], cell["chips"], cell["traffic"]) \
        == (CELL, config["name"], 1, "closed-48-mid-in-long-out")
    assert cell["why"] == (
        "closed loop, 48 slots, prompts 1k-6k replayed, outputs 768-2,048, "
        "contexts to 6.9k: a 576-wide latent row a token and layer, absorbed "
        "decode, expanded chunks; 8 of 64 experts held, 1/8 vocabulary")
    assert len(cell["why"]) <= 200
    assert all(name.match(n) for n in
               [cell["name"], cell["traffic"], config["name"],
                *config["reduced"], *(m["name"] for m in ours)])
    loaded = _cell()
    drivers = {f[:-3] for f in os.listdir(
        os.path.join(bench_spec.HERE, "drivers"))
        if f.endswith(".py") and f not in ("__init__.py", "serving.py")}
    assert loaded["traffic_data"]["driver"] == "closed_moonlight" in drivers
    assert callable(bench_spec.load_driver("closed_moonlight"))
    assert set(loaded["limits"]) == {
        "served_token_gap", "left_out_share", "served_not_first_share",
        "first_token_gap"}
    with open(os.path.join(bench_spec.ROOT, "PERF.md")) as f:
        perf = f.read()
    for m in ours:
        assert unit.match(m["unit"]) and m["better"] in ("lower", "higher")
        assert (m["moves"], m["workloads"]) == ("serve_tok_s", [CELL])
        assert set(m) == {"name", "unit", "better", "source", "layer",
                          "moves", "workloads"}
        assert m["layer"] in perf, m["layer"]
        assert m["name"] in perf, m["name"]
        assert callable(bench_spec.load_reader(m["name"]))
    e2e = {m["name"]: m for m in bench_spec.metrics_for(BENCH, "end_to_end",
                                                        CELL)}
    assert sorted(e2e) == ["serve_tok_s", "setup_s"]
    assert e2e["serve_tok_s"]["workloads"].index(CELL) == 4
    assert len(json.dumps(BENCH, indent=1)) < 64 * 1024
    # everything this PR adds under the benchmark's paths is named legally
    for folder, _, files in os.walk(bench_spec.HERE):
        for f in files:
            if "moonlight" in f or "latent" in f or f.startswith("mla_"):
                assert re.match(r"^[A-Za-z0-9_.\-]+$", f), f


OURS = [m["name"] for m in bench_spec.metrics_for(BENCH, "per_layer", CELL)
        if CELL in m.get("workloads", ())]
NEW_READERS = ("latent_paged_attn_roofline", "mla_ms_per_tick",
               "mla_absorb_ms_per_tick", "mla_expand_ms_per_chunk",
               "latent_cache_row_share", "held_experts_roofline.moonlight",
               "held_experts_ms_per_tick.moonlight",
               "moe_experts_active_mean.moonlight")


@pytest.mark.parametrize("name", OURS)
def test_a_reader_of_the_cell_that_finds_nothing_to_read_returns_nothing(name):
    """As ``test_benchmark_spec.py`` asks of every accepted reader: on the
    parent, which has no such span or counter, the line leaves it out, and
    in a cell of another configuration the new readers find nothing."""
    assert set(NEW_READERS) <= set(OURS)
    empty = {"records": [], "hist": {}, "cell": _cell()}
    assert bench_spec.load_reader(name)(empty) is None
    other = bench_spec.load_cell(BENCH, "serve-trinity-mixedctx")
    if name in NEW_READERS:
        assert bench_spec.load_reader(name)(
            {"records": [], "hist": {}, "cell": other, "counters": {
                "moe_experts_active": 5.0},
             "trace_counters": {"latent_attn.rows_live": 7,
                                "paged_attn.pages_live": 7}}) is None


def test_the_new_readers_read_what_the_engine_counts():
    cell = _cell()
    cfg = cell["config_data"]
    run = {"cell": cell, "records": [],
           "hist": {"decode_tick_ms": {"count": 10, "p50": 18.0}},
           "gauges": {"kv_row_bytes": 2560, "kv_row_bytes_expanded": 20480,
                      "latent_cache_row_share": 0.125},
           "counters": {"moe_experts_active": 10 * 8 * 7,
                        "moe_load_max": 400.0}}
    assert bench_spec.load_reader("latent_cache_row_share")(run) == 0.125
    assert bench_spec.load_reader("moe_experts_active_mean.moonlight")(
        run) == 7
    assert bench_spec.load_reader("moe_load_max_mean.moonlight")(run) == 40.0
    # a tick of 48 sequences at 3,950 rows, 9 layers: every row once, as
    # held; one dot of 576 and a sum over 512 a head and row
    rows = 48 * 3950 * 9
    cost = costs_moonlight.latent_walk_cost(cfg, 2560, rows, 48 * 9)
    assert cost["bytes"] == rows * 2560 + 48 * 9 * 16 * 1088 * 4
    assert cost["flops"] == rows * 16 * 1088 * 2
    assert abs(cost["bytes"] / 1e9 - 4.40) < 0.01       # 3.96 GB unpadded
    assert abs(cost["flops"] / 1e9 - 59.4) < 0.1
    # 7 of 8 held experts a layer, 8 layers; an eighth of the 2,304 pairs
    held = costs_moonlight.held_tick_cost(cfg, 7 * 8, 48 * 6 * 8)
    assert held["bytes"] == 7 * 8 * 3 * 2048 * 1408 * 4
    assert held["flops"] == 6.0 * 2048 * 1408 * 288
    view = costs_moonlight.as_lfm2(run)["cell"]["config_data"]
    assert (view["num_experts"], view["num_dense_layers"]) == (8, 1)
    assert "num_experts" not in cfg


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
    assert "28 pages of 8 latent rows of 128 numbers" in stdout
    assert "in ONE arena" in stdout
    found = set(last["readers_that_found_something"])
    assert {"compiles_in_window.moonlight", "tick_batch_mean.moonlight",
            "decode_tick_ms_p50.moonlight", "prefill_chunk_ms_p50.moonlight",
            "prefill_chunk_share_pct.moonlight", "worker_host_pct.moonlight",
            "tick_host_ms_mean.moonlight",
            "moe_experts_active_mean.moonlight",
            "moe_load_max_mean.moonlight",
            "latent_cache_row_share"} <= found
    # (the readers of the device trace find something when a whole tick
    # falls inside the half second traced, which a loaded machine may not
    # grant: tests/test_serving_moonlight.py holds the scopes they read)
    # one request of each prompt length went through the reference
    assert ("prompts and outputs [(70, 8), (60, 10), (48, 14), (40, 12), "
            "(33, 12), (32, 14), (20, 14), (12, 18)]") in stdout


#: served tokens changed where the engine hands them to its clients
ALTERED = (
    "from paddle_tpu.serving.llm.scheduler import GenerationRequest as G; "
    "emit = G._emit; "
    "G._emit = lambda self, tok: emit(self, (tok + 2) % 512 "
    "if len(self.tokens) == 3 else tok); ")


@pytest.mark.parametrize("fault,modes", [
    (ALTERED, ()), (None, (closed_moonlight.PROGRAM_NO_ROPE_PART,)),
    (None, (closed_moonlight.PROGRAM_SCALE_NOPE_ONLY,))],
    ids=["altered-token", "no-rotary-part", "scale-over-128"])
def test_a_faulty_engine_rehearses_not_correct(tmp_path, fault, modes):
    last, stdout = _rehearse(tmp_path, *modes, fault=fault)
    assert last["correct"] is False, stdout[-1500:]
    assert "check: served_token_gap" in stdout and "FAILED" in stdout


def test_a_faulty_program_is_put_back():
    """The calibration modes change the program inside a ``with`` and leave
    it as it was."""
    from paddle_tpu.models.moonlight import MoonlightConfig
    from paddle_tpu.serving.llm.paged import moonlight as paged
    sound = paged.PagedStep
    modes = (closed_moonlight.PROGRAM_NO_ROPE_PART,
             closed_moonlight.PROGRAM_SCALE_NOPE_ONLY)
    with closed_moonlight.faulty_program(modes) as config_cls:
        assert paged.PagedStep is not sound
        assert issubclass(paged.PagedStep, sound)
        assert config_cls().softmax_scale == 128 ** -0.5
    assert paged.PagedStep is sound
    assert MoonlightConfig().softmax_scale == 192 ** -0.5
    with closed_moonlight.faulty_program(()) as config_cls:
        assert config_cls is MoonlightConfig and paged.PagedStep is sound


def test_the_bfloat16_control_of_the_reference_is_not_correct():
    """The control the chip runs read beside the program: the tokens a
    bfloat16 pass of the reference puts first, under the reference; and the
    rule of this cell on the reference's own risk."""
    cfg = _cell(rehearsal=True)["config_data"]
    rng = np.random.default_rng(3)
    arch = ref.arch_of(cfg)
    top = moonlight_weights.make_top(cfg, 5)

    def layer(i):
        return moonlight_weights.make_layer(cfg, 5, i)

    records = []
    for plen in (12, 40, 70):
        seq = np.zeros(96, np.int32)
        seq[:plen] = rng.integers(0, cfg["vocab_size"], plen)
        for at in range(plen - 1, plen + 11):   # greedy under the reference
            hid, _, _ = ref.hidden_states(top, layer, arch, jnp.asarray(seq))
            seq[at + 1] = int(jnp.argmax(ref.logits_of(top, hid[at][None])))
        records.append({"prompt": seq[:plen].copy(), "finished": True,
                        "tokens": [int(t) for t in seq[plen:plen + 12]]})
    numbers = closed_moonlight.serve_gaps(
        cfg, 5, records, 0.0, 1.0, pad_len=96, max_new=12,
        control_modes=("bfloat16",))
    limits = {"served_token_gap": 1e-4, "left_out_share": 0.5}
    assert check.judge(numbers, limits)
    assert numbers["tokens_compared"] == 36 == numbers["tokens_sampled"]
    assert numbers["control_bfloat16_token_gap"] > 1e-4
    # greedy under the reference: every served token is its first choice,
    # and bfloat16 puts another first at some positions
    assert numbers["served_not_first_share"] == 0.0 == numbers[
        "first_token_gap"] == numbers["widest_token_gap"]
    assert numbers["control_bfloat16_not_first_share"] >= 1 / 36
    assert numbers["tokens_spared"] == 0 == numbers["tokens_not_first"]
    # no margin is under 0, so nothing is at risk; all are under 1, so from
    # the first expert layer on everything is; between, a position is at
    # risk by its own margin or by what it attends to
    seq = np.zeros(96, np.int32)
    seq[:70] = records[2]["prompt"]
    _, margin, none = ref.hidden_states(top, layer, arch, jnp.asarray(seq))
    tau = float(np.sort(np.asarray(margin))[7]) * 1.0001
    _, _, some = ref.hidden_states(top, layer, arch, jnp.asarray(seq),
                                   tau=tau)
    some, low = np.asarray(some), np.asarray(margin) < tau
    assert not np.asarray(none).any()
    source = some == 1.0
    assert low.sum() == 8 and 1 <= source.sum() <= 8 and not (
        source & ~low).any()
    first = int(np.flatnonzero(source)[0])
    assert (some[:first] == 0).all() and (some <= 1.0).all()
    assert ((some[first:] > 0) & (some[first:] < 1)).sum() > 10
    strict = closed_moonlight.serve_gaps(cfg, 5, records, 1.0, 1e-9, 96, 12)
    assert strict["left_out_share"] == 1.0 and not check.judge(strict, limits)
    assert strict["smallest_margin"] >= strict["smallest_margin_anywhere"]
    # the own half of the rule: a token whose own predicting position's
    # margin is under own_tau is left out whatever its risk
    own = closed_moonlight.serve_gaps(cfg, 5, records, 0.0, 1.0, 96, 12,
                                      own_tau=1.0)
    assert own["left_out_share"] == 1.0
    half = closed_moonlight.serve_gaps(
        cfg, 5, records, 0.0, 1.0, 96, 12,
        own_tau=float(np.median(np.asarray(margin)[69:81])))
    assert 0.0 < half["left_out_share"] < 1.0
    on_file = json.load(open(os.path.join(bench_spec.HERE, "limits",
                                          CELL + ".json")))
    assert on_file["own_margin_tau"] == 1e-4
    assert on_file["spared_share"] == 2e-3
    assert set(_cell()["limits"]) == {
        "served_token_gap", "left_out_share", "served_not_first_share",
        "first_token_gap"}
    # a served token that is not the reference's first: one of 36 is over
    # the share a run may have, whatever is spared; a FIRST token that is
    # wide is spared by nothing
    wrong = [dict(r, tokens=list(r["tokens"])) for r in records]
    wrong[1]["tokens"] = [(wrong[1]["tokens"][0] + 2) % cfg["vocab_size"]]
    off = closed_moonlight.serve_gaps(cfg, 5, wrong, 0.0, 1.0, 96, 12,
                                      spared_share=0.5)
    assert off["tokens_spared"] == 12 and off["tokens_not_first"] == 1
    assert off["served_not_first_share"] == 1 / 25
    assert off["first_token_gap"] == off["widest_token_gap"] > 0.015
    assert off["served_token_gap"] < off["widest_token_gap"]
    assert not check.judge(off, _cell()["limits"])
    # one finished request of each ENTRY of the lists, the longest first:
    # two entries may share a prompt length and differ in their outputs
    short = dict(records[1], tokens=records[1]["tokens"][:8])
    picked = closed_moonlight.sample_by_entry(
        records + [short, dict(records[0], finished=False)], 9,
        [12, 70, 40, 40, 33], [12, 12, 12, 8, 12])
    assert [(len(r["prompt"]), len(r["tokens"])) for r in picked] == [
        (70, 12), (40, 12), (40, 8), (12, 12)]


@pytest.mark.parametrize("gaps,share,widest,spared", [
    ([], 0.5, 0.0, 0), ([0.3], 0.999, 0.3, 0), ([0.1, 0.4, 0.2], 0.0, 0.4, 0),
    ([0.1, 0.4, 0.2], 0.34, 0.2, 1), ([0.5] + [0.0] * 1999, 1e-3, 0.0, 2),
    ([0.5, 0.4, 0.3] + [0.0] * 1997, 1e-3, 0.3, 2)],
    ids=["none", "one", "share-0", "a-third", "an-event", "three-events"])
def test_the_spared_are_the_widest_few(gaps, share, widest, spared):
    """``spared_widest``: the floor of the share times the tokens compared
    are set aside, the widest first; none where the tokens are few."""
    assert closed_moonlight.spared_widest(gaps, share) == (widest, spared)


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


def _shapes(one_chip):
    from paddle_tpu.serving.llm.paged.moonlight import latent_row_width
    cell = _cell()
    cfg, eng = cell["config_data"], cell["traffic_data"]["engine"]
    net_cfg = moonlight_adapter.config_of(cfg)

    def s(shape, dtype=jnp.float32):
        return jax.ShapeDtypeStruct(tuple(shape), dtype, sharding=one_chip)

    names = {"n1": "n1", "n2": "n2", "q_w": "qw", "dkv_w": "dkv",
             "kv_norm": "kvn", "ukv_w": "ukv", "o_w": "ow", "w1": "w1",
             "w3": "w3", "w2": "w2", "router": "gate", "expert_bias": "bias",
             "s1": "s1", "s3": "s3", "s2": "s2"}
    layers = tuple({names[k]: s(v) for k, v in
                    moonlight_weights.layer_shapes(cfg, i).items()}
                   for i in range(cfg["num_hidden_layers"]))
    h, vocab = cfg["hidden_size"], cfg["vocab_size"]
    params = {"tok": s((vocab, h)), "fnw": s((h,)), "head": s((h, vocab)),
              "layers": layers}
    page = eng["page_size"]
    arena = s((eng["num_pages"] + 1, cfg["num_hidden_layers"], page,
               latent_row_width(net_cfg)))
    slots = eng["num_slots"]
    per_slot = {
        "tables": s((slots, eng["max_seq"] // page), jnp.int32),
        "lengths": s((slots,), jnp.int32), "finished": s((slots,), bool),
        "last": s((slots,), jnp.int32), "temperature": s((slots,)),
        "top_k": s((slots,), jnp.int32), "do_sample": s((slots,), bool),
        "eos": s((slots,), jnp.int32), "key": s((2,), jnp.uint32)}
    return net_cfg, eng, params, arena, per_slot, s


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
    (3.88 GB of weights, the one arena of latent rows), aliased outputs and
    temporaries; both programs are loaded at once, so the sum holds the
    arguments once and both programs' temporaries."""
    from paddle_tpu.serving.llm.paged.moonlight import (
        build_moonlight_paged_chunk_fn, build_moonlight_paged_decode_step)
    cfg, eng, params, arena, p, s = _shapes(one_chip)
    step = build_moonlight_paged_decode_step(cfg, eng["max_top_k"], "kernel")
    compiled = jax.jit(step, donate_argnums=(1,)).lower(
        params, arena, p["tables"], p["lengths"], p["finished"], p["last"],
        p["temperature"], p["top_k"], p["do_sample"], p["eos"],
        p["key"]).compile()
    text = compiled.as_text()
    # one walk of paged_attn a layer, and two expert kernels an expert layer
    assert text.count('custom_call_target="tpu_custom_call"') \
        == cfg.num_hidden_layers + 2 * cfg.num_expert_layers
    decode = _record("decode_step_moonlight", compiled, record_property)
    held = int(np.prod(arena.shape)) * 4
    assert held == 5441 * 1474560
    # the arena is updated in place, and not copied
    assert decode["alias_bytes"] >= held
    assert "copy(" not in "".join(
        line for line in text.splitlines()
        if f"f32[{arena.shape[0]}," in line.split(" = ")[-1][:20])

    def one(dtype=jnp.float32):
        return s((1,), dtype)

    chunk = build_moonlight_paged_chunk_fn(cfg, eng["max_top_k"])
    compiled = jax.jit(chunk, donate_argnums=(5,)).lower(
        params, s((1, eng["prefill_chunk"]), jnp.int32), s((), jnp.int32),
        s((), jnp.int32), s((), bool), arena, p["tables"], p["lengths"],
        p["finished"], s((), jnp.int32), one(), one(jnp.int32), one(bool),
        one(jnp.int32), p["key"]).compile()
    prefill = _record("prefill_chunk_moonlight", compiled, record_property)
    assert prefill["alias_bytes"] >= held
    # no temporary of a chunk passes 0.8 GB
    assert prefill["temp_bytes"] < 0.8e9
    both = (decode["argument_bytes"] + decode["temp_bytes"]
            + prefill["temp_bytes"])
    record_property("both_programs_bytes", int(both))
    assert 3.88e9 + held < both < PEAK_LIMIT
