"""``serve_steps_ahead_pct`` (PR 55): the reader on hand-made windows of
``llm_stats()`` counters, in the manner of ``decode_ring_rows_read_pct``'s,
and its entry in BENCHMARK.json. The counters themselves are the engine
loop's (``steps_ahead``, ``rows_dropped``), held by
``tests/test_llm_serving.py`` for every served family."""

import os
import types

import pytest

import benchmark_toy
from benchmark.loading import load_json, load_module

REPO = benchmark_toy.REPO
NAME = "serve_steps_ahead_pct"
CLOSED = ["serve_gpt2xl_decode_sat", "serve_nemotron3s_decode_sat",
          "serve_granite4hs_longdoc_sat", "serve_dsv2_longctx_sat",
          "serve_falconh1_longgen_sat", "serve_qwen3next_mixedctx_sat",
          "serve_smallthinker_mixedwin_sat", "serve_kexaone_selfdraft_sat"]


def fake_run(counters):
    said = []
    run = types.SimpleNamespace(
        counters=counters, raw={},
        say=lambda event, **fields: said.append((event, fields)))
    return run, said


@pytest.fixture(scope="module")
def reader():
    return load_module(os.path.join(REPO, "benchmark", "metrics",
                                    NAME + ".py"))


# (steps before the window, steps within, of them ahead, slots, rows a
# step dropped in all)
WINDOWS = {
    # 8 slots, a request ends every ten steps and sits out one
    "gpt2-xl": (40, 2_500, 2_499, 8, 250),
    # 64 slots, an end every fourth step
    "nemotron": (10, 1_400, 1_400, 64, 350),
    # an engine that reads each step before it enqueues the next
    "reads-first": (5, 900, 0, 8, 0),
    # a window of idle waits between bursts: half the steps found none unread
    "bursts": (0, 600, 300, 8, 12),
}


@pytest.mark.parametrize("window", list(WINDOWS))
def test_the_reader_on_a_hand_made_window(reader, window):
    before, steps, ahead, slots, dropped = WINDOWS[window]
    served = steps * slots - dropped
    a = {"steps": before, "steps_ahead": before, "rows_dropped": 3,
         "occupancy_sum": before * slots}
    b = {"steps": before + steps, "steps_ahead": before + ahead,
         "rows_dropped": 3 + dropped,
         "occupancy_sum": before * slots + served}
    run, said = fake_run({"open": a, "close": b})
    got = reader.read(run)
    assert got == pytest.approx(100.0 * ahead / steps)
    assert 0 <= got <= 100.0
    [(event, fields)] = said
    assert event == "steps_ahead"
    assert (fields["steps"], fields["ahead"]) == (steps, ahead)
    assert fields["slot_rows"] == steps * slots
    assert fields["rows_dropped_pct"] == pytest.approx(
        100.0 * dropped / (steps * slots))


@pytest.mark.parametrize("counters", [
    {},                                                    # no window
    {"open": {"steps": 10, "occupancy_sum": 80},           # the parent
     "close": {"steps": 110, "occupancy_sum": 880}},
    {"open": {"steps": 10, "steps_ahead": 9, "rows_dropped": 0,
              "occupancy_sum": 80},
     "close": {"steps": 10, "steps_ahead": 9, "rows_dropped": 0,
               "occupancy_sum": 80}},
], ids=["no-window", "a-program-without-the-counter", "no-step-in-window"])
def test_the_reader_gives_none_and_does_not_raise(reader, counters):
    run, said = fake_run(counters)
    assert reader.read(run) is None and not said


def test_the_spec_lists_it_for_the_closed_serving_cells():
    """Looked up by name, the cells IN its list: a later PR may append."""
    spec = load_json(os.path.join(REPO, "BENCHMARK.json"))
    m = {m["name"]: m for m in spec["per_layer"]}[NAME]
    assert (m["unit"], m["better"], m["source"]) == (
        "%", "higher", "program_counter")
    assert m["moves"] == "serve_out_tokens_per_s"
    assert set(CLOSED) <= set(m["workloads"])
    reports = {x["name"]: x for x in spec["end_to_end"]}[m["moves"]]
    assert set(m["workloads"]) <= set(reports["workloads"])
    host = {x["name"]: x for x in spec["per_layer"]}[
        "serve_step_host_ms_p50"]
    assert m["layer"] == host["layer"]  # one layer, letter for letter
    assert "bound" not in m


def test_the_engine_keeps_the_counters_the_reader_reads():
    """The names, where the reader looks for them: the engine's own table
    of counters (no engine is built: ``tests/test_llm_serving.py`` runs
    them)."""
    import inspect

    from ray_tpu.serve import llm_engine

    source = inspect.getsource(llm_engine.LLMEngine.__init__)
    for key in ("steps_ahead", "rows_dropped", "occupancy_sum", "steps"):
        assert f'"{key}": 0' in source, key
