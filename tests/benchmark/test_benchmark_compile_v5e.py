"""The 1.3B decode step and the 1024-token prefill, compiled for a described
(not attached) TPU v5e at the cells' arena: what the chip's compiler would
refuse is refused here, and ``memory_analysis()`` says what the programs
need beside the weights. Nothing runs; no number here is a measurement.

All in one file, the topology described inside a fixture (see the
``on-chip-measurement`` guide): only the worker that is given this file
loads the TPU's library.
"""
import json
import os

import pytest

import jax
import jax.numpy as jnp
from jax.sharding import SingleDeviceSharding

from benchmark import spec as bench_spec
from benchmark.drivers.serving import page_bytes

pytestmark = pytest.mark.timeout_s(900)


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
    programs compiled here must hold the Mosaic kernel, so the test (not a
    program option) answers the compile-or-interpret question."""
    from paddle_tpu.ops import paged_attention
    monkeypatch.setattr(paged_attention, "resolve_interpret",
                        lambda kernel, requested=None: False)


def _cell(name):
    return bench_spec.load_cell(bench_spec.load_benchmark(), name)


def _shapes(cell, one_chip):
    """ShapeDtypeStructs of the engine's arguments at the cell's sizes."""
    from paddle_tpu.serving.llm.decode import GPTDecodeSpec
    cfg, eng = cell["config_data"], cell["traffic_data"]["engine"]
    spec = GPTDecodeSpec(
        vocab_size=cfg["vocab_size"], hidden_size=cfg["n_embd"],
        num_layers=cfg["n_layer"], num_heads=cfg["n_head"],
        max_position_embeddings=cfg["n_positions"])

    def s(shape, dtype=jnp.float32):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)

    e, f = cfg["n_embd"], cfg["n_inner"]
    layer = {"qw": s((e, e)), "qb": s((e,)), "kw": s((e, e)), "kb": s((e,)),
             "vw": s((e, e)), "vb": s((e,)), "ow": s((e, e)), "ob": s((e,)),
             "w1": s((e, f)), "b1": s((f,)), "w2": s((f, e)), "b2": s((e,)),
             "n1w": s((e,)), "n1b": s((e,)), "n2w": s((e,)), "n2b": s((e,))}
    params = {"tok": s((cfg["vocab_size"], e)),
              "pos": s((cfg["n_positions"], e)), "fnw": s((e,)),
              "fnb": s((e,)), "layers": (layer,) * cfg["n_layer"]}
    pages = eng["kv_arena_bytes"] // page_bytes(cfg, eng["page_size"])
    arena = s((pages + 1, cfg["n_layer"], eng["page_size"], cfg["n_head"],
               e // cfg["n_head"]))
    slots = eng["num_slots"]
    per_slot = {
        "tables": s((slots, eng["max_seq"] // eng["page_size"]), jnp.int32),
        "lengths": s((slots,), jnp.int32), "finished": s((slots,), bool),
        "last": s((slots,), jnp.int32), "temperature": s((slots,)),
        "top_k": s((slots,), jnp.int32), "do_sample": s((slots,), bool),
        "eos": s((slots,), jnp.int32), "key": s((2,), jnp.uint32)}
    return spec, eng, params, arena, per_slot, s


def _record(name, compiled, record_property):
    m = compiled.memory_analysis()
    found = {"argument_bytes": m.argument_size_in_bytes,
             "output_bytes": m.output_size_in_bytes,
             "temp_bytes": m.temp_size_in_bytes,
             "alias_bytes": m.alias_size_in_bytes}
    record_property(name, json.dumps(found))
    print(name, found)
    return found


def test_decode_step_of_the_1p3b_cells_compiles_for_v5e(
        one_chip, no_persistent_cache, mosaic_kernels, record_property):
    from paddle_tpu.serving.llm.paged.decode import build_paged_decode_step
    cell = _cell("serve-gpt1p3b-decode")
    spec, eng, params, arena, p, _ = _shapes(cell, one_chip)
    step = build_paged_decode_step(spec, eng["max_top_k"], eng["page_size"],
                                   "kernel")
    lowered = jax.jit(step).lower(
        params, arena, arena, p["tables"], p["lengths"], p["finished"],
        p["last"], p["temperature"], p["top_k"], p["do_sample"], p["eos"],
        p["key"])
    compiled = lowered.compile()
    text = compiled.as_text()
    assert text.count('custom_call_target="tpu_custom_call"') \
        >= spec.num_layers, "the paged kernel is not in the compiled step"
    found = _record("decode_step_1p3b", compiled, record_property)
    # weights + both arenas in, both arenas out: the step fits the chip
    hbm = 15.75 * 2 ** 30
    assert found["argument_bytes"] + found["output_bytes"] \
        + found["temp_bytes"] - found["alias_bytes"] < hbm


def test_prefill_1024_of_the_longprompt_cell_compiles_for_v5e(
        one_chip, no_persistent_cache, record_property):
    from paddle_tpu.serving.llm.paged.decode import build_paged_prefill_fn
    cell = _cell("serve-gpt1p3b-longprompt")
    spec, eng, params, arena, p, s = _shapes(cell, one_chip)
    bucket = eng["prefill_buckets"][-1]
    one = {k: s((1,), v.dtype) for k, v in p.items()
           if k in ("temperature", "top_k", "do_sample", "eos")}
    prefill = build_paged_prefill_fn(spec, eng["max_top_k"],
                                     eng["page_size"])
    compiled = jax.jit(prefill).lower(
        params, s((1, bucket), jnp.int32), s((1,), jnp.int32), arena, arena,
        p["tables"], p["lengths"], p["finished"], s((1,), jnp.int32),
        one["temperature"], one["top_k"], one["do_sample"], one["eos"],
        p["key"]).compile()
    found = _record("prefill_1024_1p3b", compiled, record_property)
    hbm = 15.75 * 2 ** 30
    assert found["argument_bytes"] + found["output_bytes"] \
        + found["temp_bytes"] - found["alias_bytes"] < hbm
