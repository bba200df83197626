"""The reduction from a device trace to busy time, kernel time and idle
gaps, on a small trace recorded on the chip and kept under benchmark/; the
functions that count a kernel's operations and bytes, against hand counts."""
import json
import os

import numpy as np
import pytest

from benchmark import costs, peaks, spec, trace

RECORDED = os.path.join(spec.HERE, "data", "recorded_trace.json")


@pytest.fixture(scope="module")
def flat():
    with open(RECORDED) as f:
        data = json.load(f)
    return {"device": data["device"], "host": data["host"]}


def _brute_busy(flat, lo, hi, step=100):
    """Busy nanoseconds by marking a grid: an independent union."""
    grid = np.zeros((hi - lo) // step + 1, bool)
    for _, s, d in flat["device"][0]:
        a, b = max(s, lo), min(s + d, hi)
        if b > a:
            grid[(a - lo) // step:(b - lo + step - 1) // step] = True
    return grid.sum() * step


def test_busy_is_the_union_of_the_device_intervals(flat):
    lo, hi = trace.window_of(flat)
    assert (lo, hi) == (78_000_000, 100_000_000)   # the bench/trace_window span
    got = trace.busy_seconds(flat)
    assert got["window_s"] == pytest.approx(0.022)
    assert got["busy_s"] * 1e9 == pytest.approx(_brute_busy(flat, lo, hi),
                                                 rel=5e-3)
    # overlapping and nested operations are not counted twice
    plain_sum = sum(d for _, s, d in flat["device"][0])
    assert got["busy_s"] * 1e9 < plain_sum


def test_kernel_time_is_found_by_name(flat):
    seconds, calls = trace.kernel_seconds(flat, "paged_attn")
    hand = [d for n, s, d in flat["device"][0] if "paged_attn" in n]
    assert calls == len(hand) == 7
    assert seconds == pytest.approx(sum(hand) / 1e9)
    assert trace.kernel_seconds(flat, "flash_fwd") == (0.0, 0)


def test_idle_gaps_are_charged_to_the_open_span(flat):
    gaps = dict(trace.idle_gaps(flat))
    busy = trace.busy_seconds(flat)
    assert sum(gaps.values()) == pytest.approx(
        busy["window_s"] - busy["busy_s"], rel=1e-6)
    # the recorded stretch: the host gap between two ticks (no span open)
    # and then the start of a tick
    assert gaps[trace.NO_SPAN] > gaps["serving.llm/decode_tick"] > 0
    # a span opened by hand over the whole window takes every gap
    spanned = dict(flat, host=flat["host"] + [["bench/all", 0, 10**12]])
    only = dict(trace.idle_gaps(spanned))
    assert trace.NO_SPAN not in only


def test_top_operations_merge_instances_and_keep_the_shape(flat):
    top = trace.top_device_ops(flat)
    assert len(top) <= 10 and top == sorted(top, key=lambda kv: -kv[1])
    names = [n for n, _ in top]
    assert "paged_attn f32[8,16,128]" in names
    assert trace.short_name(
        "%copy.148 = f32[401,24,16,16,128]{4,3,2,1,0:T(8,128)} copy(f32[") \
        == "copy f32[401,24,16,16,128]"
    assert trace.short_name("flash_bwd_dkv.22") == "flash_bwd_dkv"


def test_a_trace_without_device_operations_is_refused():
    with pytest.raises(ValueError):
        trace.busy_seconds({"device": [], "host": []})


# -- operations and bytes ------------------------------------------------------

def test_flash_counts_against_a_hand_count():
    # batch 1, 1 head, S=4, D=2, 2-byte elements: one full S x S x D matmul
    # is 2*4*4*2 = 64 operations, the causal half 32
    fwd = costs.flash_call_cost("flash_fwd", 1, 1, 4, 2, 2)
    dq = costs.flash_call_cost("flash_bwd_dq", 1, 1, 4, 2, 2)
    dkv = costs.flash_call_cost("flash_bwd_dkv", 1, 1, 4, 2, 2)
    assert (fwd["flops"], dq["flops"], dkv["flops"]) == (64, 96, 128)
    # q, k, v, o of 4*2 elements of 2 bytes
    assert fwd["bytes"] == 4 * 8 * 2
    assert dq["bytes"] == 6 * 8 * 2 and dkv["bytes"] == 7 * 8 * 2


def test_paged_attention_counts_against_a_hand_count():
    # 10 cached rows, 2 heads of 4, f32, one query: QK^T and PV are
    # 2*10*2*4 operations each; K and V rows read once, q and o once
    c = costs.paged_attn_cost(10, 2, 4, 4, queries=1)
    assert c["flops"] == 2 * (2 * 10 * 2 * 4)
    assert c["bytes"] == (2 * 10 + 2) * 2 * 4 * 4
    t, bound = costs.least_seconds(c, peaks.peaks_for("TPU v5 lite"))
    assert bound == "memory" and t == pytest.approx(c["bytes"] / 819e9)


def test_model_flops_per_token_against_a_hand_count():
    cfg = {"n_embd": 4, "n_inner": 16, "n_layer": 2, "vocab_size": 10}
    n = 2 * (4 * 16 + 2 * 4 * 16) + 10 * 4
    assert costs.gpt_matmul_params(cfg) == n
    assert costs.gpt_train_flops_per_token(cfg, 8) == 6 * n + 6 * 2 * 8 * 4


def test_a_share_over_100_percent_raises():
    assert costs.share_pct(1.0, 4.0, "x") == 25.0
    with pytest.raises(ValueError, match="counted too high"):
        costs.share_pct(1.01, 1.0, "x")
    with pytest.raises(ValueError):
        costs.share_pct(1.0, 0.0, "x")


def test_an_unlisted_device_has_no_peak():
    assert peaks.peaks_for("TPU v5 lite")["flops_per_s"] == 197e12
    with pytest.raises(KeyError, match="no published peaks"):
        peaks.peaks_for("cpu")
