"""Every trace reducer against a small hand-made trace kept beside it
(``benchmark/metrics/fixtures``), with the expected numbers worked here by
hand. These run on any machine: they say nothing about a device."""

import os
import types

import pytest

from benchmark import trace
from benchmark.loading import load_json, load_module

REPO = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
METRICS = os.path.join(REPO, "benchmark", "metrics")
TOY = os.path.join(os.path.dirname(os.path.abspath(__file__)), "toy")


def reader(name):
    return load_module(os.path.join(METRICS, name + ".py"))


def stub(fixture, **more):
    said = []
    run = types.SimpleNamespace(
        trace=trace.from_json(os.path.join(METRICS, "fixtures", fixture)),
        params={"device_programs": {"step": "jit_step",
                                    "decode": "jit_step_fn"}},
        raw={}, counters={}, chips=1, device_kind="TPU v5 lite",
        config=load_json(os.path.join(TOY, "configs", "gpt2-toy.json")),
        family=load_module(os.path.join(REPO, "benchmark", "families",
                                        "gpt2.py")),
        window_ns=None, said=said,
        say=lambda event, **f: said.append((event, f)))
    for k, v in more.items():
        setattr(run, k, v)
    return run


def test_idle_busy_and_step_metrics_on_three_steps():
    run = stub("train_three_steps.json")
    # Each step is busy 0.10 + 0.04 + 0.05 = 0.19 s; three of them in a
    # 1 s window: busy 0.57, idle 43 %.
    assert trace.busy_seconds(run.trace) == pytest.approx(0.57)
    assert reader("train_device_idle_pct").read(run) == pytest.approx(43.0)
    assert reader("train_step_device_ms_p50").read(run) == pytest.approx(190)
    # Step 1 ends 0.30, step 2 starts 0.32 (gap 0.02); step 2 ends 0.52,
    # step 3 starts 0.60 (gap 0.08): the median of two is 0.05 s.
    assert reader("train_host_gap_ms_p50").read(run) == pytest.approx(50.0)
    # Mosaic calls: 3 * 0.04 of 0.57 busy.
    assert reader("flash_time_pct").read(run) == pytest.approx(
        100 * 0.12 / 0.57)


def test_flash_roofline_on_three_steps():
    # Toy shape: 2 layers, 4 heads of 16, T=64, 4 rows on this chip.
    # Forward: 4*4*2*64*64*16 = 2,097,152 operations -> 1.0645e-8 s at
    # 197 TFLOP/s; bytes 4*4*4*64*16*2 + 4*4*4*64 = 135,168 ->
    # 1.650e-7 s at 819 GB/s: memory bounds it. Backward: bytes
    # 270,336 -> 3.3008e-7 s (operations 2.66e-8 s). Two layers:
    # 2 * (1.650e-7 + 3.3008e-7) = 9.902e-7 s, against 0.04 s measured.
    run = stub("train_three_steps.json", raw={"rows_per_step": 4})
    need = 2 * (135168 + 270336) / 819e9
    assert reader("flash_attention_roofline").read(run) == pytest.approx(
        100 * need / 0.04)
    assert run.said[0][1]["bound_by"] == {"forward": "memory",
                                          "backward": "memory"}


def test_breakdown_names_the_host_span_behind_each_gap():
    run = stub("train_three_steps.json")
    gaps = dict(trace.idle_gaps_by_span(run.trace))
    # 0.30-0.32 is covered by bench.next_batch, 0.52-0.60 by
    # bench.loss_to_host; the lead-in (0.1), the tail (0.2) and the three
    # 0.01 s pauses inside the steps have no span.
    assert gaps["bench.next_batch"] == pytest.approx(0.02)
    assert gaps["bench.loss_to_host"] == pytest.approx(0.08)
    assert gaps["unattributed"] == pytest.approx(0.33)
    ops = dict(trace.top_ops(run.trace))
    assert ops["opcode:fusion"] == pytest.approx(0.45)
    assert ops["opcode:custom-call"] == pytest.approx(0.12)


def test_exposed_collective_time_on_two_chips():
    run = stub("collectives_two_chips.json")
    # 0.15 s exposed on chip 0, 0.05 + 0.02 on chip 1, of a 1 s window.
    assert reader("train_collective_exposed_pct").read(run) == \
        pytest.approx(100 * (0.15 + 0.07) / 2)
    # busy counts the core's own line only: chip 0 runs 0-0.95 without a
    # pause; chip 1 runs 0-0.45, 0.5-0.9 and 0.9-0.92 (its all-gather is
    # in flight on the asynchronous line, which is not the core working).
    assert trace.busy_seconds(run.trace) == pytest.approx((0.95 + 0.87) / 2)


def test_decode_step_roofline_on_three_steps():
    ms = 1_000_000
    requests = [{"prompt_len": 100, "chunk_tokens": [1, 1, 1, 1, 1],
                 "chunk_ns": [10 * ms, 20 * ms, 30 * ms, 40 * ms, 50 * ms]}]
    run = stub(
        "decode_three_steps.json", window_ns=(0, 600 * ms),
        raw={"requests": requests, "weight_bytes": 4e9},
        counters={"open": {"steps": 0, "occupancy_sum": 0},
                  "close": {"steps": 10, "occupancy_sum": 30}})
    # Decoded tokens 1..4 of the stream attended 101..104 rows: mean
    # 102.5. Three slots occupied. Toy shape: a token holds
    # 2*2*64*2 = 512 bytes. Bytes a step: 4e9 + 3*102.5*512.
    # The decode program is busy 0.05 + 0.03 = 0.08 s an execution; the
    # prefill program's execution is not counted.
    need = (4e9 + 3 * 102.5 * 512) / 819e9
    assert reader("decode_step_roofline").read(run) == pytest.approx(
        100 * need / 0.08)
    assert reader("serve_device_idle_pct.decode").read(run) == \
        pytest.approx(100 * (1 - 0.34 / 0.6))


def test_a_reader_with_nothing_to_read_returns_nothing():
    run = stub("train_three_steps.json", trace=None)
    for name in ("train_device_idle_pct", "train_host_gap_ms_p50",
                 "flash_time_pct", "train_collective_exposed_pct",
                 "decode_step_roofline", "flash_attention_roofline",
                 "train_tokens_per_s_chip", "serve_queue_ms_p50"):
        assert reader(name).read(run) is None, name


def test_opcode_from_names_and_stats():
    assert trace.opcode_of("fusion.12", {}) == "fusion"
    assert trace.opcode_of("all-gather-start.3", {}) == "all-gather-start"
    assert trace.opcode_of("x", {"hlo_category": "convolution"}) == \
        "convolution"
    assert trace.opcode_of(
        "%checkpoint.15 = bf16[192,1024,64]{2,1,0} custom-call(a, b)", {}
    ) == "custom-call"
    assert trace.is_collective("all-reduce", "all-reduce.1")
    assert not trace.is_collective("fusion", "fusion.3")
    assert trace.is_custom_call("custom-call", "checkpoint.15")
