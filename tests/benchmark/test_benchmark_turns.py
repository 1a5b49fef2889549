"""The three readers of the engine loop's own record of its turns (PR 57:
``serve_stall_pct``, ``serve_turn_ms_max``, ``serve_loop_host_pct``) on
hand-made ``llm_stats()`` snapshots kept beside the metrics
(``turn_counters_two_stalls.json``, after the two stalled runs of 3 Oct),
with the expected numbers worked here by hand; on a parent's counters,
which lack the keys; against a hand-made profile; and in the CPU rehearsal
of a closed cell, where the engine's real record feeds them. The record
itself is held by ``tests/test_llm_turns.py``. These run on any machine:
they say nothing about a device."""

import copy
import json
import os
import types

import pytest

import benchmark_toy
from benchmark.loading import load_json, load_module
from ray_tpu.serve import llm_engine

REPO = benchmark_toy.REPO
METRICS = os.path.join(REPO, "benchmark", "metrics")
NAMES = ["serve_stall_pct", "serve_turn_ms_max", "serve_loop_host_pct"]
FIXTURE = load_json(os.path.join(METRICS, "fixtures",
                                 "turn_counters_two_stalls.json"))
CLOSED = ["serve_gpt2xl_decode_sat", "serve_nemotron3s_decode_sat",
          "serve_granite4hs_longdoc_sat", "serve_dsv2_longctx_sat",
          "serve_falconh1_longgen_sat", "serve_qwen3next_mixedctx_sat",
          "serve_smallthinker_mixedwin_sat", "serve_kexaone_selfdraft_sat"]
RECORD = ("turns", "turn_ns", "turn_phase_ns", "turn_hist_plain",
          "turn_hist_plain_ns", "turn_hist_prefill", "turn_hist_prefill_ns",
          "slow_turns")


def reader(name):
    return load_module(os.path.join(METRICS, name + ".py"))


def stub(which="no_stall", **more):
    """A run as the readers see one, untraced: the two snapshots and the
    window of one of the fixture's runs."""
    fx = copy.deepcopy(FIXTURE["runs"][which])
    said = []
    run = types.SimpleNamespace(
        counters={"open": fx["open"], "close": fx["close"]},
        window_ns=tuple(fx["window_ns"]), epoch_offset_ns=0, raw={},
        program_trace=None, trace_path=None, trace=None, said=said,
        say=lambda event, **f: said.append((event, f)))
    for k, v in more.items():
        setattr(run, k, v)
    return run


def said(run, event):
    return next(f for e, f in run.said if e == event)


def test_the_fixture_is_laid_out_as_the_engine_lays_its_record_out():
    assert FIXTURE["fields"] == list(llm_engine.SLOW_TURN_FIELDS)
    assert FIXTURE["phases"] == list(llm_engine.TURN_PHASES)
    for fx in FIXTURE["runs"].values():
        for snap in (fx["open"], fx["close"]):
            assert len(snap["slow_turns"]) % len(FIXTURE["fields"]) == 0
            assert len(snap["slow_turns"]) <= \
                llm_engine.SLOW_TURNS * len(FIXTURE["fields"])
            assert sum(snap["turn_phase_ns"]) == snap["turn_ns"]
            assert len(snap["turn_hist_plain"]) == \
                len(llm_engine.TURN_EDGES_MS) + 1


# which run -> (serve_stall_pct: the one stalled turn over the 30 s;
# serve_turn_ms_max: the stall, or the longest admission (31 chunks: 0.05 +
# 27.9 + 0.04 + 3.6 + 573.0 + 0.6 + 0.11 ms); the window's ordinary plain
# turns, 14 ms each)
READ = {"stall_2981_handover": (100 * 2981.2 / 30_000, 2981.2, 1290),
        "stall_2257_device": (100 * 2257.0 / 30_000, 2257.0, 1342),
        "stall_3325_admission": (100 * 3325.21 / 30_000, 3325.21, 1266),
        "no_stall": (0.0, 605.3, 1473)}
# the prefill turns' time less a plain turn each, over the chunks they
# dispatched: (500 + 5,580 + the kept admissions' ms - 14 a turn) / chunks
CHUNK_MS = {"stall_2981_handover": 19.2693, "stall_2257_device": 19.2693,
            "stall_3325_admission": 26.6092, "no_stall": 19.2839}


@pytest.mark.parametrize("which", list(READ))
def test_stall_share_and_longest_turn_of_the_window(which):
    stall_pct, turn_ms, ordinary = READ[which]
    run = stub(which)
    assert reader("serve_stall_pct").read(run) == pytest.approx(stall_pct)
    assert reader("serve_turn_ms_max").read(run) == pytest.approx(turn_ms)
    if which != "no_stall":
        assert round(stall_pct, 1) == {2981.2: 9.9, 2257.0: 7.5,
                                       3325.21: 11.1}[turn_ms]
    line = said(run, "stalls")
    plain_stall = which in ("stall_2981_handover", "stall_2257_device")
    assert line["plain_turn_ms_typical"] == pytest.approx(14.0)
    assert line["chunk_ms_mean"] == pytest.approx(CHUNK_MS[which], abs=1e-3)
    assert line["stalled_from_ms"] == pytest.approx(8 * 14.0)
    assert line["stalled_turns"] == (which != "no_stall")
    assert line["stalled_plain_turns"] == plain_stall
    # An admission is held to its chunks: the five chunks that took
    # 3,325 ms should have taken 14 + 5 x 26.6, while no admission of 19
    # to 31 chunks and 400 to 600 ms is a stall. The buckets hold the
    # harness's collection before the window too: a plain turn of 253 ms
    # that is no stall of the window.
    assert line["beyond_ms_between_snapshots"] == pytest.approx(
        253.0 + (turn_ms if plain_stall else 0.0))
    assert line["plain_turns"] == ordinary + 1 + plain_stall


def test_the_line_slow_turns_says_what_each_kept_turn_did_and_what_came_next():
    run = stub("stall_2981_handover")
    reader("serve_turn_ms_max").read(run)
    line = said(run, "slow_turns")
    # eight are kept; the one that began 20 s before the window is not its
    assert line["kept_in_window"] == 7 == len(line["turns"])
    assert [t["at_s"] for t in line["turns"]] == sorted(
        t["at_s"] for t in line["turns"])
    assert line["plain_turn_ms_typical"] == pytest.approx(14.0)
    # a plain turn's sync: the turn less the loop's own phases a turn
    host = sum(y - x for p, x, y in zip(
        FIXTURE["phases"], run.counters["open"]["turn_phase_ns"],
        run.counters["close"]["turn_phase_ns"]) if p in (
            "llm.admit", "llm.step.select", "llm.step.dispatch",
            "llm.step.fanout", "other")) / line["turns_in_window"] * 1e-6
    assert line["plain_sync_ms_typical"] == pytest.approx(14.0 - host)
    [stall] = [t for t in line["turns"] if t["class"] == "plain"]
    assert stall["at_s"] == pytest.approx(0.912)
    assert stall["ms"] == pytest.approx(2981.2)
    assert stall["phases_ms"]["llm.step.sync"] == pytest.approx(2976.6)
    assert stall["phases_ms"]["llm.step.dispatch"] == pytest.approx(3.9)
    assert stall["phases_ms"]["llm.step.fanout"] == pytest.approx(0.5)
    assert "llm.admit" not in stall["phases_ms"]     # 0.02 ms: not said
    assert stall["did"] == {"chunks": 0, "admitted": 0, "rows": 64,
                            "steps_read": 1, "firsts_read": 0,
                            "outstanding": 1}
    # the step after it had ended when the long sync returned
    assert stall["next_sync_ms"] == pytest.approx(0.04)
    assert stall["next_sync_ms"] < line["plain_sync_ms_typical"] / 10
    assert stall["memory"]["bytes_in_use"] == 13_080_000_000
    assert stall["memory_since_reap"] == {
        "num_allocs": 431, "bytes_in_use": 0, "bytes_reserved": 0,
        "largest_free_block_bytes": 0}
    assert "profile" not in stall                    # an untraced run
    admissions = [t for t in line["turns"] if t["class"] == "prefill"]
    assert all(19 <= t["did"]["chunks"] <= 31 for t in admissions)
    # in the other run the next sync took a step: the device stood still
    run = stub("stall_2257_device")
    reader("serve_turn_ms_max").read(run)
    line = said(run, "slow_turns")
    [stall] = [t for t in line["turns"] if t["class"] == "plain"]
    assert stall["next_sync_ms"] == pytest.approx(11.9)
    assert stall["next_sync_ms"] > 0.8 * line["plain_sync_ms_typical"]


@pytest.mark.parametrize("which", list(READ))
def test_the_hosts_share_of_the_loops_time(which):
    """Everything but ``llm.step.sync`` and ``llm.loop.wait``: 0.06 + 2.15
    + 0.24 + 14.0 + 2.1 + 0.01 + 0.85 % of the loop's time."""
    run = stub(which)
    assert reader("serve_loop_host_pct").read(run) == pytest.approx(
        19.41, abs=1e-4)
    line = said(run, "loop_phases")
    assert set(line["pct"]) == set(FIXTURE["phases"])
    assert sum(line["pct"].values()) == pytest.approx(100.0)
    assert line["pct"]["llm.step.dispatch"] == pytest.approx(14.0, abs=1e-4)
    assert line["turns"] == run.counters["close"]["turns"] \
        - run.counters["open"]["turns"]
    # the snapshots lie just outside the window (the harness's collection)
    assert 100.0 < line["covers_window_pct"] < 102.0


@pytest.mark.parametrize("name", NAMES)
@pytest.mark.parametrize("counters", ["parent", "no-window", "one-end"])
def test_a_program_without_the_record_reads_none(name, counters):
    """The parent of the PR that added the record keeps none of its keys:
    every reader says nothing and raises nothing."""
    run = stub()
    if counters == "no-window":
        run.counters = {}
    for snap in list(run.counters.values())[:2 if counters == "parent"
                                            else 1]:
        for key in RECORD:
            del snap[key]
    assert reader(name).read(run) is None
    assert not run.said


def test_a_window_whose_turns_the_list_does_not_hold_reads_its_top_bucket():
    """Eight longer turns of the minute before the window hold the list:
    the longest turn is then the mean of the window's highest bucket."""
    run = stub("no_stall")
    n = len(FIXTURE["fields"])
    close = run.counters["close"]
    for i in range(0, len(close["slow_turns"]), n):
        close["slow_turns"][i] = run.window_ns[0] - 1 - i   # all before it
    # the highest bucket, 512 ms to 1,024, holds 2: 605.3 and 538.7 ms
    assert reader("serve_turn_ms_max").read(run) == pytest.approx(
        (605.3 + 538.7) / 2)
    assert said(run, "slow_turns")["kept_in_window"] == 0
    assert reader("serve_stall_pct").read(run) == 0.0


def test_a_program_that_counts_no_chunks_holds_plain_turns_alone():
    """Without ``prefill_chunks`` there is nothing to hold an admission
    to: the turns that dispatched no chunk are judged, the plain stall is
    there as before, and a stalled admission is not seen."""
    for which, pct in (("stall_2981_handover", 100 * 2981.2 / 30_000),
                       ("stall_3325_admission", 0.0)):
        run = stub(which)
        for snap in run.counters.values():
            del snap["prefill_chunks"]
        assert reader("serve_stall_pct").read(run) == pytest.approx(pct)
        line = said(run, "stalls")
        assert line["chunk_ms_mean"] is None
        assert line["stalled_turns"] == line["stalled_plain_turns"] \
            == (pct > 0)


def test_in_a_traced_run_a_kept_turn_is_placed_in_the_profile():
    """The profile's clock is the window's less 4,990 s here (an anchor,
    ``llm.step.dispatch`` with ``epoch_ns``, says so): the profile ran
    from 10 to 15 s of the window. The admission at 13.9 s lies inside it
    and the device was busy for 3 of every 4 ms of it; the one at 4.21 s
    ended before it, the one at 17.3 s began after it, and one moved to
    9.7 s lies across its start."""
    run = stub("no_stall")
    n = len(FIXTURE["fields"])
    at = FIXTURE["fields"].index
    flat = run.counters["close"]["slow_turns"]
    # the second kept turn of the window (481.5 ms), moved across 10 s
    flat[2 * n + at("start_ns")] = run.window_ns[0] + 9_700_000_000
    shift = 4_990.0
    lo = run.window_ns[0] * 1e-9 - shift
    run.program_trace = {
        "host": [("llm.step.dispatch", lo + 10.5, lo + 10.504,
                  {"epoch_ns": str(run.window_ns[0] + 10_500_000_000 + 77)},
                  "loop#0")],
        "ops": [], "modules": [], "window": (lo + 10.0, lo + 15.0)}
    run.epoch_offset_ns = 77      # time.time_ns() less perf_counter_ns()
    run.trace = {"devices": [{"ops": [
        ("fusion", lo + 10.0 + k * 0.004, lo + 10.0 + k * 0.004 + 0.003,
         "fusion") for k in range(1250)]}], "window": (lo + 10.0, lo + 15.0)}
    reader("serve_turn_ms_max").read(run)
    by_at = {round(t["at_s"], 1): t for t in said(run, "slow_turns")["turns"]}
    assert by_at[13.9]["profile"] == "inside"
    assert by_at[13.9]["device_busy_pct"] == pytest.approx(75.0, abs=0.5)
    assert by_at[9.7]["profile"] == "across_start"
    assert by_at[4.2]["profile"] == "before"
    assert by_at[17.3]["profile"] == "after"
    assert all("device_busy_pct" not in t for t in by_at.values()
               if t["profile"] != "inside")


def test_the_spec_lists_the_three_for_the_closed_cells():
    """Looked up by name, the cells IN their lists: a later PR may append.
    They are the last entries the benchmark was given, after everything it
    had."""
    spec = load_json(os.path.join(REPO, "BENCHMARK.json"))
    by_name = {m["name"]: m for m in spec["per_layer"]}
    units = dict(zip(NAMES, ["%", "ms", "%"]))
    host = by_name["serve_step_host_ms_p50"]
    reports = {x["name"]: x for x in spec["end_to_end"]}[
        "serve_out_tokens_per_s"]
    for name in NAMES:
        m = by_name[name]
        assert (m["unit"], m["better"], m["source"]) == (
            units[name], "lower", "program_counter")
        assert m["moves"] == "serve_out_tokens_per_s"
        assert m["layer"] == host["layer"]      # one layer, letter for letter
        assert set(CLOSED) <= set(m["workloads"]) <= set(
            reports["workloads"])
        assert "bound" not in m
        assert os.path.exists(os.path.join(METRICS, name + ".py"))
    listed = [m["name"] for m in spec["per_layer"]]
    assert listed.index("serve_steps_ahead_pct") < min(
        listed.index(n) for n in NAMES)


def test_the_rehearsal_of_a_closed_cell_reads_the_engines_own_record(
        tmp_path, capsys):
    """The toy closed cell on the CPU: the engine's record is the real one,
    so the three metrics are read (names, no value) and their lines hold
    what the loop counted."""
    from benchmark import run as bench_run

    root = benchmark_toy.make_root(str(tmp_path))
    code = bench_run.main(["--workload", "toy_closed", "--seed", "5",
                           "--seconds", "3", "--trace", "1", "--root", root,
                           "--rehearsal"])
    out = capsys.readouterr().out.strip().splitlines()
    assert code == 0
    last = json.loads(out[-1])
    assert last["correct"] is True
    assert set(NAMES) <= set(last["rehearsal"]["metric_names"])
    lines = {}
    for line in out[:-1]:
        if line.startswith("[bench] "):
            rec = json.loads(line[len("[bench] "):])
            lines[rec["event"]] = rec
    lp = lines["loop_phases"]
    assert lp["turns"] > 0 and sum(lp["pct"].values()) == pytest.approx(100)
    assert 90.0 < lp["covers_window_pct"] < 130.0
    st = lines["slow_turns"]
    assert 1 <= st["kept_in_window"] <= llm_engine.SLOW_TURNS
    assert st["turns_in_window"] == lp["turns"]
    assert all(t["ms"] > 0 and sum(t["phases_ms"].values()) <= t["ms"] + 1e-6
               for t in st["turns"])
    assert lines["stalls"]["plain_turns"] > 0
    # the record rides on the engine_counters line of every run, traced or not
    ec = lines["engine_counters"]
    for key in RECORD:
        assert key in ec["open"] and key in ec["close"], key
