"""``benchmark/tools/simulate_open_loop.py``: the loop's arithmetic on
schedules small enough to work by hand, and one sweep over the prompt
cell's own files. No device, no engine."""

import os

import pytest

from benchmark.loading import load_module

REPO = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
sim = load_module(os.path.join(
    REPO, "benchmark", "tools", "simulate_open_loop.py"))

ENGINE = {"max_batch": 2, "prefill_rows": 1}


@pytest.mark.parametrize("offsets,asked,engine,expected", [
    # an idle engine: the wait is one prefill and the client's share
    ([1.0], [4], ENGINE, [101.0]),
    # one row a prefill: the second request waits out the first one's
    # prefill and the decode step that follows it
    ([1.0, 1.0], [4, 4], ENGINE, [101.0, 251.0]),
    # two rows a prefill: both leave in one batch
    ([1.0, 1.0], [4, 4], {"max_batch": 2, "prefill_rows": 2},
     [101.0, 101.0]),
    # two-token requests free their slot in the step after their prefill,
    # so the queue moves one prefill and one step at a time
    ([1.0, 1.0, 1.0], [2, 2, 2], ENGINE, [101.0, 251.0, 401.0]),
    # the one slot is taken: the second waits out both decode steps of the
    # first before its own prefill
    ([1.0, 1.0], [3, 2], {"max_batch": 1, "prefill_rows": 1},
     [101.0, 301.0]),
    # a request that asks for one token never takes a slot
    ([1.0, 1.0], [1, 4], {"max_batch": 1, "prefill_rows": 1},
     [101.0, 201.0]),
])
def test_waits_worked_by_hand(offsets, asked, engine, expected):
    got = sim.waits_ms(offsets, asked, engine, prefill_s=0.1, step_s=0.05,
                       lead_s=0.5, window_s=5.0)
    assert got == pytest.approx(expected)


def test_requests_outside_the_window_are_served_but_not_reported():
    got = sim.waits_ms([0.1, 1.0, 9.0], [4, 4, 4], ENGINE, 0.1, 0.05,
                       lead_s=0.5, window_s=5.0)
    assert got == pytest.approx([101.0])


def test_sweep_reads_the_prompt_cell_from_its_files():
    rows = sim.sweep("serve_gpt2xl_prompt_rate", 170.3, 86.4,
                     [0.99, 1.0, 1.03])
    assert [r["speed"] for r in rows] == [0.99, 1.0, 1.03]
    for r in rows:
        assert r["requests"] > 50
        assert 170.3 * r["speed"] < r["ttft_p90_ms"] <= r["ttft_max_ms"]
