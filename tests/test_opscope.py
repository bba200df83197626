"""observability/opscope.py: the program notes what it traces, reads the
compiled text back when asked and maps a device event to the scope its
instruction came from.

The device lines here are written by hand from the toy program's own
instruction names (what a chip's trace gives, as the instruction's text, and
what the CPU's gives, as the bare name), so every time is known.
"""
import re

import numpy as np
import pytest

import jax
import jax.numpy as jnp

import paddle_tpu as paddle
from paddle_tpu.models import GPTConfig, GPTForCausalLM
from paddle_tpu.observability import opscope
from paddle_tpu.serving.llm import LLMEngine, LLMEngineConfig
from paddle_tpu.serving.llm.decode import jit_program

US = 1_000


@pytest.fixture()
def fresh(monkeypatch):
    """An opscope that has noted nothing (other tests of this process note
    their programs too, and two programs may share an instruction name)."""
    for name in ("_NOTED", "_ROWS", "_INDEX"):
        monkeypatch.setattr(opscope, name, {})
    monkeypatch.setattr(opscope, "_SPENT", [0.0])


def _toy():
    """Two scopes, a ``fori_loop``, a ``value_and_grad`` and an update."""
    def step(w, x):
        def loss(w):
            with jax.named_scope("train/forward"):
                with jax.named_scope("toy/mlp"):
                    h = jnp.tanh(x @ w)
                with jax.named_scope("toy/walk"):
                    h = jax.lax.fori_loop(
                        0, 3, lambda i, c: c * 1.01 + jnp.sin(c @ w), h)
                return jnp.sum(h ** 2)
        value, grad = jax.value_and_grad(loss)(w)
        with jax.named_scope("train/optimizer"):
            return value, w - 0.1 * grad

    def body(*args):
        opscope.note("jit_step", jitted, args)
        return step(*args)

    jitted = jax.jit(body)
    return jitted, (jnp.ones((64, 64)), jnp.ones((32, 64)))


def _pick(table, scope, phase, needle=None, opcode=None):
    """An instruction of the toy's table in ``scope`` and ``phase`` whose
    ``op_name`` holds ``needle``, as ``(name, shape)``."""
    for (_, name, shape), op_name in sorted(table.items()):
        if opscope.scope_of(op_name) == (scope, phase) \
                and (needle is None or needle in op_name) \
                and (opcode is None or name.startswith(opcode)):
            return name, shape
    raise AssertionError(f"no instruction in {scope!r} / {phase!r} holding "
                         f"{needle!r}")


def _text(name, shape):
    """An event's name as the chip's trace gives it."""
    return f"%{name} = {shape}{{1,0:T(8,128)}} fusion(f32[8]{{0}} %p), kind=kLoop"


# -- op_name -> (scope, phase) ---------------------------------------------------

@pytest.mark.parametrize("op_name, want", [
    ("jit(_step)/gpt/mlp/dot_general", ("gpt/mlp", "-")),
    ("jit(_step)/gpt/attn/jit(_paged_attention)/paged_attn/pallas_call",
     ("gpt/attn/paged_attn", "-")),
    ("jit(_chunk)/trinity/attn_window/trinity/chunk_walk/while/body/"
     "closed_call/dot_general",
     ("trinity/attn_window/trinity/chunk_walk", "-")),
    ("jit(_chunk)/trinity/attn_full/trinity/chunk_walk/while",
     ("trinity/attn_full/trinity/chunk_walk", "-")),
    ("jit(step)/jvp(train/forward)/gpt/loss_head/reduce_sum",
     ("gpt/loss_head", "forward")),
    ("jit(step)/transpose(jvp(train/forward))/gpt/norm/div",
     ("gpt/norm", "backward")),
    ("jit(step)/transpose(jvp(train/forward))/jvp(train/forward)/checkpoint/"
     "rematted_computation/gpt/qkv/dot_general", ("gpt/qkv", "recompute")),
    ("jit(step)/transpose(jvp(train/forward))/jvp(train/forward)/checkpoint/"
     "gpt/mlp/mul", ("gpt/mlp", "backward")),
    ("jit(step)/train/optimizer/sub", ("train/optimizer", "optimizer")),
    ("jit(step)/jvp(train/forward)/jit(jitted)", ("train/forward", "forward")),
    ("jit(_prefill)/gpt/attn/transpose;jit(_prefill)/gpt/proj/dot_general",
     ("gpt/attn", "-")),
    ("jit(_chunk)/trinity/moe_route/jit(searchsorted)/jit(_chunk)/trinity/"
     "moe_route/jit(searchsorted)/jit(_chunk)/trinity/moe_route/"
     "jit(searchsorted)/vmap()/while/body/add", ("trinity/moe_route", "-")),
    ("params['layers'][0]['w1']", (opscope.UNSCOPED, "-")),
    ("", (opscope.UNSCOPED, "-")),
])
def test_the_scope_and_the_phase_are_read_from_an_op_name(op_name, want):
    assert opscope.scope_of(op_name) == want


def test_an_instruction_without_a_name_of_its_own_takes_its_users():
    text = '''HloModule jit__step, is_scheduled=true

%fused_computation.1 (param_0: f32[8,64]) -> f32[8,64] {
  %param_0 = f32[8,64]{1,0} parameter(0)
  ROOT %add.1 = f32[8,64]{1,0} add(%param_0, %param_0), metadata={op_name="jit(_step)/gpt/norm/add"}
}

ENTRY %main.9 (w: f32[64,64], x: f32[8,64]) -> f32[8,64] {
  %w = f32[64,64]{1,0} parameter(0), metadata={op_name="w"}
  %x = f32[8,64]{1,0} parameter(1), metadata={op_name="x"}
  %copy-start = (f32[64,64]{1,0:S(1)}, f32[64,64]{1,0}, u32[]) copy-start(%w)
  %copy-done = f32[64,64]{1,0:S(1)} copy-done(%copy-start)
  %fusion.7 = f32[8,64]{1,0} fusion(%x), kind=kLoop, calls=%fused_computation.1
  ROOT %fusion.8 = f32[8,64]{1,0} fusion(%fusion.7, %copy-done), kind=kOutput, calls=%fused_computation.2, metadata={op_name="jit(_step)/gpt/mlp/dot_general"}
}
'''
    rows = opscope.parse_hlo(text)
    assert rows["fusion.8"] == ("f32[8,64]", "jit(_step)/gpt/mlp/dot_general")
    # a fusion with no metadata: its computation's root
    assert rows["fusion.7"] == ("f32[8,64]", "jit(_step)/gpt/norm/add")
    # the compiler's prefetch of the weight: start -> done -> the product
    assert rows["copy-done"] == ("f32[64,64]",
                                 "jit(_step)/gpt/mlp/dot_general")
    assert rows["copy-start"] == ("f32[64,64]",
                                  "jit(_step)/gpt/mlp/dot_general")


# -- the toy program, end to end --------------------------------------------------

def test_a_toy_program_is_noted_run_and_read_scope_by_scope(fresh):
    jitted, args = _toy()
    value, _ = jitted(*args)
    assert np.isfinite(float(value))
    assert opscope.noted() == ["jit_step"]
    assert opscope._ROWS == {} and opscope.table_seconds() == 0.0
    table = opscope.table()
    assert opscope.table_seconds() > 0.0
    assert {p for p, _, _ in table} == {"jit_step"}

    loop = _pick(table, "toy/walk", "forward", opcode="while")
    inside = _pick(table, "toy/walk", "forward", needle="while/body")
    back = _pick(table, "toy/walk", "backward", needle="while/body")
    update = _pick(table, "train/optimizer", "optimizer")
    assert loop != inside
    line = [
        # the forward loop: 100 us, of which its body's two runs take 70
        [_text(*loop), 0, 100 * US],
        [_text(*inside), 10 * US, 30 * US],
        [_text(*inside), 50 * US, 40 * US],
        [_text(*back), 200 * US, 25 * US],
        [_text(*update), 300 * US, 7 * US],
        # the CPU's trace names an event by the instruction alone
        [update[0], 310 * US, 3 * US],
        ["%fusion.99999 = f32[3]{0} fusion()", 400 * US, 11 * US],
        ["an event that is no instruction", 500 * US, 2 * US],
    ]
    got = opscope.by_scope(line)
    assert got == pytest.approx({
        ("jit_step", "toy/walk", "forward"): 100e-6,    # not 170
        ("jit_step", "toy/walk", "backward"): 25e-6,
        ("jit_step", "train/optimizer", "optimizer"): 10e-6,
        (opscope.NO_PROGRAM, opscope.UNSCOPED, "-"): 13e-6,
    })
    own = {n: t for n, _, t in opscope.exclusive(line[:3])}
    assert own[_text(*loop)] == 30 * US and own[_text(*inside)] == 40 * US
    # an instruction name under another result shape is another program's
    wrong = re.sub(r"\[[0-9,]*\]", "[7,7,7]", inside[1], count=1)
    assert opscope.by_scope([[_text(inside[0], wrong), 0, US]]) == {
        (opscope.NO_PROGRAM, opscope.UNSCOPED, "-"): pytest.approx(1e-6)}
    text = opscope.format_table(got, unit="us")
    assert text.splitlines()[1].split()[:4] == [
        "jit_step", "toy/walk", "forward", "100.000"]


def test_an_instruction_two_programs_hold_is_left_unscoped(fresh):
    rows = {"fusion.1": ("f32[8]", "jit(_step)/gpt/mlp/dot_general"),
            "fusion.2": ("f32[8]", "jit(_step)/gpt/norm/add")}
    opscope._index("jit__step", rows)
    opscope._index("jit__prefill", {
        "fusion.1": ("f32[8]", "jit(_prefill)/gpt/mlp/dot_general"),
        "fusion.2": ("f32[64]", "jit(_prefill)/gpt/norm/add")})
    got = opscope.by_scope([["%fusion.1 = f32[8]{0} fusion()", 0, 5 * US],
                            ["%fusion.2 = f32[8]{0} fusion()", 9 * US, US],
                            ["%fusion.2 = f32[64]{0} fusion()", 20 * US, US]])
    assert got == pytest.approx({
        (opscope.NO_PROGRAM, opscope.UNSCOPED, "-"): 5e-6,
        ("jit__step", "gpt/norm", "-"): 1e-6,
        ("jit__prefill", "gpt/norm", "-"): 1e-6})


def test_a_program_is_noted_once_a_signature_and_lowered_once(fresh):
    jitted, (w, x) = _toy()
    jitted(w, x)
    jitted(w, x)
    jitted(w, x[:16])                   # another signature, another trace
    assert opscope.noted() == ["jit_step", "jit_step"]
    first = opscope.table()
    spent = opscope.table_seconds()
    assert all(v is None for v in opscope._NOTED.values())   # callables gone
    assert opscope.table() == first                           # nothing anew
    assert opscope.table_seconds() == pytest.approx(spent, abs=0.05)


def test_the_scope_words_of_a_whole_program_are_read_path_by_path():
    """A train step's text holds 34,000 ``op_name``s: walked into one list,
    the repeat check of ``_walk`` looked back over all of them (412 s on
    the chip, PR 36); a path's names are its own."""
    import time
    paths = [f"jit(step)/jvp(train/forward)/gpt/part{i % 97}/op{i}"
             for i in range(30_000)]
    began = time.perf_counter()
    words = opscope._scope_words(paths)
    assert time.perf_counter() - began < 5.0
    assert {"train", "forward", "gpt", "part3", "op29999"} <= words
    # a stack written twice through a nested jit is still kept once
    assert opscope._scope_words(
        ["jit(f)/a/b/jit(g)/a/b/jit(g)/mul"]) == {"a", "b", "mul"}


_ANOTHER_TREES_EXECUTABLE = """
import contextlib, jax, jax.numpy as jnp
from paddle_tpu.observability import opscope
jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
jax.config.update("jax_persistent_cache_min_entry_size_bytes", -1)

def program(scoped):
    def step(x, w):
        with jax.named_scope("toy/mlp") if scoped else contextlib.nullcontext():
            return jnp.tanh(x @ w).sum()
    def body(*args):
        opscope.note("jit_step", jitted, args)
        return step(*args)
    body.__name__ = "step"
    jitted = jax.jit(body)
    return jitted

def scopes(rows):
    return sorted({opscope.scope_of(op)[0] for op in rows})

args = (jnp.ones((8, 16)), jnp.ones((16, 16)))
program(False)(*args)       # another tree's: the same code under no scope
opscope._NOTED.clear()
mine = program(True)
mine(*args)                 # the persistent cache answers with the other's
print(scopes(op for _, op in opscope.parse_hlo(
    mine.lower(*args).compile().as_text()).values()))
print(scopes(opscope.table().values()))
"""


def test_an_executable_another_tree_cached_is_compiled_anew(tmp_path):
    """JAX keys its persistent cache without the metadata: a program that
    differs from the parent's by its scopes alone is answered with the
    parent's executable, whose text names no scope of this tree's."""
    import os
    import subprocess
    import sys
    root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    out = subprocess.run(
        [sys.executable, "-c", _ANOTHER_TREES_EXECUTABLE], cwd=root,
        env=dict(os.environ, JAX_PLATFORMS="cpu", PYTHONPATH=root,
                 JAX_COMPILATION_CACHE_DIR=str(tmp_path)),
        capture_output=True, text=True, timeout=300)
    assert out.returncode == 0, out.stderr[-2000:]
    cached, read = out.stdout.strip().splitlines()[-2:]
    assert "toy/mlp" not in cached          # what the cache gave
    assert "toy/mlp" in read                # what table() made of it


# -- the serving programs -----------------------------------------------------------

def test_jit_program_notes_a_trace_and_a_call_pays_nothing(fresh,
                                                           monkeypatch):
    calls = []
    note = opscope.note
    monkeypatch.setattr(opscope, "note",
                        lambda *a: (calls.append(a[0]), note(*a))[1])

    def _double(x, y):
        with jax.named_scope("toy/double"):
            return x * 2 + y

    program = jit_program(_double)
    x = jnp.ones((4,))
    program(x, x)
    assert calls == ["jit__double"]
    assert program.trace_counter["traces"] == 1
    for _ in range(3):
        program(x, x)
    assert calls == ["jit__double"]             # no Python ran on a call
    assert program.trace_counter["traces"] == 1
    program(jnp.ones((8,)), jnp.ones((8,)))     # a new shape traces again
    assert calls == ["jit__double"] * 2
    # asking for the table lowers each program once more, after the fact;
    # the body runs again only where JAX has dropped the trace it kept
    table = opscope.table()
    assert program.trace_counter["traces"] in (2, 4)
    assert any(opscope.scope_of(op)[0] == "toy/double"
               for op in table.values())


@pytest.mark.timeout_s(600)
def test_nothing_is_lowered_by_importing_enabling_or_serving(fresh,
                                                            monkeypatch):
    lowered, parse = [], opscope.parse_hlo
    monkeypatch.setattr(opscope, "parse_hlo",
                        lambda text: lowered.append(len(text)) or parse(text))
    paddle.seed(3)
    net = GPTForCausalLM(GPTConfig(
        vocab_size=64, hidden_size=32, num_layers=2, num_heads=4,
        max_position_embeddings=64, hidden_dropout_prob=0.0,
        attention_dropout_prob=0.0))
    net.eval()
    from paddle_tpu import observability
    observability.enable()
    try:
        engine = LLMEngine(net, LLMEngineConfig(
            kv_layout="paged", num_slots=2, max_seq=32, page_size=8,
            num_pages=16, prefill_buckets=(8, 16)))
        try:
            out = engine.submit([1, 2, 3, 4, 5], max_new_tokens=6).result(120)
        finally:
            engine.drain(timeout=10.0)
    finally:
        observability.disable()
    assert len(out["tokens"]) == 6
    assert {"jit__step", "jit__prefill"} <= set(opscope.noted())
    assert lowered == [] and opscope._ROWS == {}
    assert opscope.table_seconds() == 0.0
    opscope.table()
    assert len(lowered) == len(opscope.noted())


# -- the train step -----------------------------------------------------------------

@pytest.mark.timeout_s(600)
def test_the_train_step_is_noted_and_its_phases_are_told_apart(fresh):
    from paddle_tpu.distributed.fleet.utils import recompute
    from paddle_tpu.models import GPTPretrainingCriterion
    paddle.seed(5)
    net = GPTForCausalLM(GPTConfig(
        vocab_size=128, hidden_size=32, num_layers=2, num_heads=2,
        max_position_embeddings=32, hidden_dropout_prob=0.0,
        attention_dropout_prob=0.0))
    for blk in net.gpt.decoder.layers:
        blk.forward = (lambda *a, __f=blk.forward, **k: recompute(__f, *a, **k))
    model = paddle.Model(net)
    model.prepare(paddle.optimizer.AdamW(
        learning_rate=1e-3, parameters=net.parameters(), weight_decay=0.1),
        GPTPretrainingCriterion())
    x = np.random.randint(0, 128, (2, 32)).astype("int64")
    with paddle.amp.auto_cast(enable=True, dtype="bfloat16"):
        model.train_batch([x], [x])
        assert opscope.noted() == ["jit_step"]
        model.train_batch([x], [x])              # a call notes nothing more
    assert opscope.noted() == ["jit_step"]
    # the table is asked for outside the autocast the step was traced in,
    # and after JAX has dropped the trace: it is the bfloat16 program still
    jax.clear_caches()
    table = opscope.table()
    assert any((shape or "").startswith("bf16[") for _, _, shape in table)
    seen = {opscope.scope_of(op) for op in table.values()}
    for scope in ("gpt/norm", "gpt/qkv", "gpt/attn", "gpt/proj", "gpt/mlp"):
        for phase in ("forward", "recompute", "backward"):
            assert (scope, phase) in seen, (scope, phase)
    assert ("gpt/embed", "forward") in seen
    assert ("gpt/loss_head", "forward") in seen
    assert ("gpt/loss_head", "backward") in seen
    assert ("train/optimizer", "optimizer") in seen
    assert not any(phase == "recompute" for scope, phase in seen
                   if scope in ("gpt/loss_head", "gpt/embed"))


# -- the operator's table ---------------------------------------------------------------

@pytest.mark.timeout_s(600)
def test_profiler_summary_prints_device_time_by_scope(fresh, tmp_path,
                                                      capsys):
    jitted, args = _toy()
    jitted(*args)[0].block_until_ready()
    prof = paddle.profiler.Profiler(log_dir=str(tmp_path))
    prof.start()
    assert prof.device_time_by_scope() == {}     # nothing while it runs
    for _ in range(3):
        jitted(*args)[0].block_until_ready()
        prof.step()
    prof.stop()
    seconds = prof.device_time_by_scope()
    named = {(p, s): t for (p, s, _), t in seconds.items()
             if s != opscope.UNSCOPED}
    assert ("jit_step", "toy/walk") in named and all(
        t > 0 for t in named.values())
    prof.summary()
    out = capsys.readouterr().out
    assert "steps=3" in out
    assert re.search(r"jit_step +toy/walk +(forward|backward) +\d", out)
    timer = paddle.profiler.Profiler(timer_only=True)
    timer.start()
    timer.stop()
    assert timer.device_time_by_scope() == {}
