"""The yardstick's arithmetic: shape functions against the numbers in the
issue that defined the benchmark, and the statistics of the training rate
against a hand-made list of slices."""

import json
import os

import pytest

from benchmark import peaks, shapes, stats

REPO = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))


def config(name):
    with open(os.path.join(REPO, "benchmark", "configs", name + ".json")) as f:
        return json.load(f)


@pytest.mark.parametrize("name, gflop", [("gpt2-124m", 0.798),
                                         ("gpt2-xl-1.5b", 9.80)])
def test_required_operations_per_token(name, gflop):
    c = config(name)
    got = shapes.train_flops_per_token(
        c["n_layer"], c["n_embd"], c["vocab_size"], c["n_positions"])
    assert got / 1e9 == pytest.approx(gflop, abs=0.005)


def test_required_operations_by_hand():
    # L=1, d=2, V=3, T=4: 6 * (12*1*4 + 3*2) + 6*1*2*4 = 324 + 48
    assert shapes.train_flops_per_token(1, 2, 3, 4) == 372.0


def test_cache_bytes_per_slot():
    c = config("gpt2-xl-1.5b")
    got = shapes.kv_bytes_per_slot(c["n_layer"], c["n_embd"], 1024)
    assert got == 2 * 48 * 1024 * 1600 * 2
    assert got / 1e6 == pytest.approx(315, abs=1)


def test_flash_operations_and_bytes():
    # One head, T=4, hd=2: the full square of QK^T is 2*4*4*2 = 64
    # operations, PV as many; causal halves the sum: 64.
    ops, io = shapes.flash_forward(1, 1, 4, 2)
    assert ops == 64.0
    assert io == 4 * 4 * 2 * 2 + 4 * 4   # q,k,v,o in bf16 + fp32 stats
    ops_b, io_b = shapes.flash_backward(1, 1, 4, 2)
    assert ops_b == 160.0 and ops_b / ops == 2.5
    assert io_b == 8 * 4 * 2 * 2 + 8 * 4
    # GPT-2 small's step on one chip: 16 rows, 12 heads of 64, T=1024.
    ops, io = shapes.flash_forward(16, 12, 1024, 64)
    assert ops == pytest.approx(25.77e9, rel=1e-3)
    peak = peaks.peak("TPU v5 lite")
    least, by = shapes.roofline_seconds(ops, io, peak)
    assert by == "compute" and least == pytest.approx(ops / 197e12)


def test_decode_bytes_per_step():
    # 10 bytes of weights, 2 slots at 3 rows of context, L=1, d=4, bf16:
    # a token holds 2*1*4*2 = 16 bytes -> 10 + 2*3*16
    assert shapes.decode_step_bytes(10, 2, 3, 1, 4) == 106.0


def test_param_count_matches_the_published_sizes():
    assert shapes.gpt2_param_count(12, 768, 50257, 1024) == 124_439_808
    assert shapes.gpt2_param_count(48, 1600, 50257, 1024) == 1_557_611_200


def test_unknown_device_kind_is_an_error():
    with pytest.raises(KeyError, match="no published peak"):
        peaks.peak("cpu")
    assert peaks.peak("TPU v5 lite")["bytes_per_s"] == 819e9


def test_window_rate_counts_a_slow_slice_and_the_median_pace_does_not():
    # Nine slices of 1.0 s and one of 3.0 s, 1000 tokens a slice, 2 chips.
    slices = [1.0] * 9 + [3.0]
    # End to end: all 10,000 tokens over all 12 s, over 2 chips.
    assert stats.window_rate(slices, 1000, 2) == pytest.approx(
        1000 * 10 / 12 / 2)
    # Beside it, the pace between hiccups: a slice over the median time.
    assert stats.slice_rate(slices, 1000, 2) == 500.0
    # At the median pace the slices would have taken 10 s of the 12: the
    # rest is the distance between the two rates.
    assert stats.stall_pct(slices, 12.0) == pytest.approx(100 * 2 / 12)
    assert stats.window_rate(slices, 1000, 2) == pytest.approx(
        stats.slice_rate(slices, 1000, 2) * (1 - 2 / 12))
    assert stats.stall_pct([1.0] * 10, 10.0) == pytest.approx(0.0)
    assert stats.window_rate([1.0] * 10, 1000, 1) == stats.slice_rate(
        [1.0] * 10, 1000, 1) == 1000.0
    assert stats.slice_rate([], 1000, 1) is None
    assert stats.window_rate([], 1000, 1) is None


def test_percentile_and_intervals():
    assert stats.percentile([1, 2, 3, 4, 5], 50) == 3.0
    assert stats.percentile(range(1, 101), 90) == pytest.approx(90.1)
    assert stats.percentile([], 90) is None
    assert stats.union_length([(0, 2), (1, 3), (5, 6)]) == 4.0
    assert stats.merge([(5, 6), (0, 2), (1, 3)]) == [(0, 3), (5, 6)]
    # [0,10] minus ([2,3] u [5,7]) = 7; a hole outside changes nothing
    assert stats.subtract_length([(0, 10)], [(2, 3), (5, 7), (12, 13)]) == 7.0
    assert stats.subtract_length([(0, 1), (4, 6)], [(0.5, 5)]) == 1.5
