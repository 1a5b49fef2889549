"""``decode_ring_rows_read_pct`` (PR 48): the reader on hand-made windows of
``llm_stats()`` counters, in the manner of ``moe_experts_hit_pct``'s, and its
entry in BENCHMARK.json. The counters themselves are the decode steps'
(``ops/attention.ring_rows_counted``), held in
``tests/test_merged_row_attention.py`` and by the engines' tests."""

import os
import types

import pytest

import benchmark_toy
from benchmark.loading import load_json, load_module

REPO = benchmark_toy.REPO
NAME = "decode_ring_rows_read_pct"
CELLS = ["serve_gpt2xl_decode_sat", "serve_falconh1_longgen_sat",
         "serve_qwen3next_mixedctx_sat"]


def fake_run(counters):
    return types.SimpleNamespace(counters=counters, raw={},
                                 say=lambda *a, **k: None)


@pytest.fixture(scope="module")
def reader():
    return load_module(os.path.join(REPO, "benchmark", "metrics",
                                    NAME + ".py"))


# layers x slots x ring rows a step, and the rows of the blocks a step's
# slots reach into: (held a step, read a step, steps before, steps within)
WINDOWS = {
    # GPT-2 XL: 48 layers, 9 slots of 1024; eight contexts of 600 rows in
    # blocks of 256 and the scratch slot's one block
    "gpt2-xl": (48 * 9 * 1024, 48 * (8 * 768 + 256), 40, 2_500),
    # Falcon-H1: 9 layers, 33 slots of 5120; contexts of 2,085 rows
    "falcon-h1": (9 * 33 * 5120, 9 * (32 * 2304 + 256), 10, 1_400),
    # Qwen3-Next: 2 layers, 65 slots of 18432; contexts of 5,080 rows
    "qwen3-next": (2 * 65 * 18432, 2 * (64 * 5120 + 256), 5, 1_100),
    # a program that reads every ring whole
    "whole-rings": (9 * 33 * 5120, 9 * 33 * 5120, 0, 100),
}


@pytest.mark.parametrize("window", list(WINDOWS))
def test_the_reader_on_a_hand_made_window(reader, window):
    held, read, before, steps = WINDOWS[window]
    a = {"steps": before, "ring_rows_read": before * read,
         "ring_rows_held": before * held}
    b = {"steps": before + steps, "ring_rows_read": (before + steps) * read,
         "ring_rows_held": (before + steps) * held}
    got = reader.read(fake_run({"open": a, "close": b}))
    assert got == pytest.approx(100.0 * read / held)
    assert 0 < got <= 100.0
    if window == "whole-rings":
        assert got == 100.0


@pytest.mark.parametrize("counters", [
    {},                                                    # no window
    {"open": {"steps": 10}, "close": {"steps": 110}},      # the parent
    {"open": {"steps": 10, "ring_rows_read": 5, "ring_rows_held": 9},
     "close": {"steps": 10, "ring_rows_read": 5, "ring_rows_held": 9}},
], ids=["no-window", "a-program-without-the-counter", "no-step-in-window"])
def test_the_reader_gives_none_and_does_not_raise(reader, counters):
    assert reader.read(fake_run(counters)) is None


def test_the_spec_lists_it_for_the_cells_that_hold_merged_rings():
    """Looked up by name, the cells IN its list: a later PR may append."""
    spec = load_json(os.path.join(REPO, "BENCHMARK.json"))
    m = {m["name"]: m for m in spec["per_layer"]}[NAME]
    assert (m["unit"], m["better"], m["source"]) == (
        "%", "lower", "program_counter")
    assert m["moves"] == "serve_out_tokens_per_s"
    assert set(CELLS) <= set(m["workloads"])
    attention = {x["name"]: x for x in spec["per_layer"]}[
        "decode_attention_time_pct"]
    assert m["layer"] == attention["layer"]  # one layer, letter for letter
    assert "bound" not in m
