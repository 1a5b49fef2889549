"""The four delivery metrics' readers (``benchmark/delivery.py``) against
hand-made fixtures kept beside the metrics, with the expected numbers
worked here by hand: a profile of four decode turns with the pollers'
``llm.next.drain`` annotations (``program_delivery_four_turns.json``), and
one run's ``llm_stats()`` snapshots and ``serve.stream`` spans
(``delivery_counters_and_spans.json``). Then the same four in the CPU
rehearsal of a closed cell, where the engine's real counters and spans
feed them. These run on any machine: they say nothing about a device."""

import json
import os
import types

import pytest

from benchmark import delivery, program_trace
from benchmark.loading import load_json, load_module

from benchmark_toy import make_root

REPO = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
METRICS = os.path.join(REPO, "benchmark", "metrics")
NAMES = ["serve_sync_overshoot_ms_p50", "serve_deliver_lag_ms_mean",
         "serve_polls_per_chunk", "serve_poll_rpc_ms_p50"]
COUNTERS = "delivery_counters_and_spans.json"
CLOSED = ["serve_gpt2xl_decode_sat", "serve_nemotron3s_decode_sat",
          "serve_granite4hs_longdoc_sat", "serve_dsv2_longctx_sat"]


def reader(name):
    return load_module(os.path.join(METRICS, name + ".py"))


def stub(trace=None, fixture=None, **more):
    """A run as the readers see one: a profile from a fixture, and the
    counters, spans and window of the counters fixture."""
    said = []
    run = types.SimpleNamespace(
        program_trace=None if trace is None else program_trace.from_json(
            os.path.join(METRICS, "fixtures", trace)),
        trace_path=None,
        params={"device_programs": {"decode": "jit_step_fn",
                                    "prefill": "jit_prefill_fn"}},
        raw={}, counters={}, window_ns=None, program_spans=[], said=said,
        say=lambda event, **f: said.append((event, f)))
    if fixture is not None:
        fx = load_json(os.path.join(METRICS, "fixtures", fixture))
        run.counters = {"open": fx["open"], "close": fx["close"]}
        run.program_spans = fx["spans"]
        run.window_ns = tuple(fx["window_ns"])
        run.window_s = (run.window_ns[1] - run.window_ns[0]) * 1e-9
        run.epoch_offset_ns = fx["epoch_offset_ns"]
    for k, v in more.items():
        setattr(run, k, v)
    return run


def said(run, event):
    return next(f for e, f in run.said if e == event)


def test_overshoot_is_the_syncs_end_past_the_devices_per_turn():
    run = stub("program_delivery_four_turns.json")
    # Turn 1: sync ends 0.130, the execution's last operation 0.120: 10 ms.
    # Turn 2: the device ends (0.227) after the sync (0.225): 0, not -2.
    # Turn 3: the prefill execution between is not the decode program's;
    # sync 0.330, device 0.324: 6 ms. Turn 4's execution is not in the
    # profile: no turn. Median of 10, 0, 6.
    assert reader("serve_sync_overshoot_ms_p50").read(run) \
        == pytest.approx(6.0)
    line = said(run, "sync_overshoot")
    assert line["turns"] == 3
    assert line["ms_p90"] == pytest.approx(6.0 + 0.8 * 4.0)
    assert line["ms_p99"] == pytest.approx(6.0 + 0.98 * 4.0)
    # drains that BEGAN inside the overshoot: two (2 + 3 ms; the one at
    # 0.106 is in the sync, before the device ended), none (0.210 is
    # before the device's end, 0.226 past the sync's), one (1 ms)
    assert line["drains_inside_p50"] == 1
    assert line["drains_inside_ms_p50"] == pytest.approx(1.0)
    assert set(line) == {"turns", "ms_p50", "ms_p90", "ms_p99",
                         "drains_inside_p50", "drains_inside_ms_p50"}


def test_turns_keep_their_edges_and_executions_end_with_their_last_op():
    pt = program_trace.from_json(os.path.join(
        METRICS, "fixtures", "program_delivery_four_turns.json"))
    turns = delivery.turns(pt)
    assert [t["dispatch"] for t in turns] == pytest.approx(
        [0.100, 0.202, 0.302, 0.902])
    assert turns[0]["sync"] == pytest.approx((0.104, 0.130))
    # the same turns as the reader of the phases' lengths counts
    assert len(turns) == len(program_trace.step_turns(pt))
    assert delivery.executions(pt, "jit_step_fn") == [
        pytest.approx(x) for x in
        [(0.102, 0.120), (0.204, 0.227), (0.304, 0.324)]]
    # a profile that holds an execution and none of its operations
    assert delivery.executions({**pt, "ops": []}, "jit_prefill_fn") \
        == [pytest.approx((0.250, 0.280))]


def test_a_program_without_the_drain_annotation_still_reads_an_overshoot():
    """The parent annotates the loop's phases and no poller: the overshoot
    reads (each of that fixture's two syncs ends with its execution), and
    no drain is counted."""
    run = stub("program_decode_two_turns.json")
    assert reader("serve_sync_overshoot_ms_p50").read(run) \
        == pytest.approx(0.0, abs=1e-9)
    line = said(run, "sync_overshoot")
    assert (line["turns"], line["drains_inside_p50"],
            line["drains_inside_ms_p50"]) == (2, 0, 0)


def test_deliver_lag_and_its_line_from_the_window_delta():
    run = stub(fixture=COUNTERS)
    # 1,100 chunks lay 1,650 ms in all: 1.5 ms each
    assert reader("serve_deliver_lag_ms_mean").read(run) \
        == pytest.approx(1.5)
    line = said(run, "delivery")
    assert line["chunks"] == 1100
    assert line["lag_hist"] == [100, 200, 300, 300, 100, 50, 40, 10]
    assert sum(line["lag_hist"]) == line["chunks"]
    assert len(line["lag_hist"]) == len(line["lag_hist_edges_ms"]) + 1
    assert line["deferred_share"] == pytest.approx(0.4)
    assert line["next_empty_share"] == pytest.approx(100 / 1100)


@pytest.mark.parametrize("batched_lane_counted", [True, False])
def test_polls_per_chunk_counts_the_lanes_the_program_counts(
        batched_lane_counted):
    """``poll_calls`` is a later program's to keep (no cell drives
    ``llm_poll`` yet): where it is there it is counted, where it is not
    the long-polls alone are."""
    run = stub(fixture=COUNTERS)
    if not batched_lane_counted:
        for snap in run.counters.values():
            del snap["poll_calls"]
    assert reader("serve_polls_per_chunk").read(run) \
        == pytest.approx((1100 + 5 * batched_lane_counted) / 1100)
    assert not run.said


def test_poll_rpc_is_the_round_trips_less_what_the_engine_held():
    run = stub(fixture=COUNTERS)
    # 0.2, 0.3 and 0.5 ms a poll; the stream that ended before the window,
    # the one with no tally, the one with no poll and the span of another
    # name are left out
    assert reader("serve_poll_rpc_ms_p50").read(run) == pytest.approx(0.3)
    line = said(run, "stream_polls")
    assert line["streams"] == 3
    assert line["polls_per_stream"] == pytest.approx(100 / 3)
    assert line["polls_per_s"] == pytest.approx(100 / 30)
    assert line["rpc_ms_p90"] == pytest.approx(0.3 + 0.8 * 0.2)


@pytest.mark.parametrize("name", NAMES)
@pytest.mark.parametrize("trace", ["program_parent_no_annotations.json",
                                   None])
def test_a_program_that_keeps_none_of_it_reads_none(name, trace):
    """The parent of the PR that added the counters, the span's tally and
    the annotations: every reader says nothing and raises nothing."""
    run = stub(trace, counters={"open": {"steps": 1, "errors": 0},
                                "close": {"steps": 9, "errors": 0}},
               window_ns=(0, 30_000_000_000), window_s=30.0,
               epoch_offset_ns=0,
               program_spans=[{"name": "serve.stream:llm", "trace_id": "p",
                               "start_ns": 1, "end_ns": 2_000_000_000,
                               "attributes": {"ttft_s": 0.05}}])
    assert reader(name).read(run) is None
    assert not run.said


def test_a_window_with_no_chunk_reads_none():
    fx = load_json(os.path.join(METRICS, "fixtures", COUNTERS))
    run = stub(counters={"open": fx["open"], "close": fx["open"]})
    assert reader("serve_deliver_lag_ms_mean").read(run) is None
    assert reader("serve_polls_per_chunk").read(run) is None


def test_the_spec_lists_the_four_for_the_closed_cells():
    """Looked up by name, and the cells IN their lists: a later PR may
    append metrics after these and cells to their lists."""
    spec = load_json(os.path.join(REPO, "BENCHMARK.json"))
    by_name = {m["name"]: m for m in spec["per_layer"]}
    step, router = "engine decode step (_step_once)", \
        "router and handle (serve/_private.py, stream_call)"
    want = dict(zip(NAMES, [("ms", "lower", "device_trace", step),
                            ("ms", "lower", "program_counter", router),
                            ("calls", "lower", "program_counter", router),
                            ("ms", "lower", "program_span", router)]))
    for name, fields in want.items():
        m = by_name[name]
        assert (m["unit"], m["better"], m["source"], m["layer"]) == fields
        assert m["moves"] == "serve_out_tokens_per_s"
        assert set(CLOSED) <= set(m["workloads"])
        assert "bound" not in m


def test_the_rehearsal_of_a_closed_cell_reads_the_engines_own(
        tmp_path, capsys):
    """The toy closed cell under the profiler on the CPU: the engine's
    counters and the streams' spans are the real ones, so the three
    metrics that need no device are read (their names are printed, no
    value), and their lines hold what the engine counted."""
    from benchmark import run as bench_run

    root = make_root(str(tmp_path))
    code = bench_run.main(["--workload", "toy_closed", "--seed", "3",
                           "--seconds", "3", "--trace", "1", "--root", root,
                           "--rehearsal"])
    out = capsys.readouterr().out.strip().splitlines()
    assert code == 0
    last = json.loads(out[-1])
    assert last["correct"] is True
    assert set(NAMES[1:]) <= set(last["rehearsal"]["metric_names"])
    lines = {}
    for line in out[:-1]:
        if line.startswith("[bench] "):
            rec = json.loads(line[len("[bench] "):])
            lines[rec["event"]] = rec
    d = lines["delivery"]
    assert d["chunks"] > 0 and sum(d["lag_hist"]) == d["chunks"]
    assert 0 <= d["deferred_share"] <= 1
    assert 0 <= d["next_empty_share"] < 1
    sp = lines["stream_polls"]
    assert sp["streams"] > 0 and sp["polls_per_stream"] >= 1
    assert sp["rpc_ms_p50"] > 0
    # the counters ride on the engine_counters line, open and close
    ec = lines["engine_counters"]
    for key in ("next_calls", "next_empty", "deliver_chunks",
                "deliver_lag_ns", "deliver_lag_hist", "wake_defer_ns"):
        assert key in ec["open"] and key in ec["close"], key
    # a poll is empty, or it took at least one chunk
    polls = ec["close"]["next_calls"] - ec["open"]["next_calls"]
    assert polls * (1 - d["next_empty_share"]) <= d["chunks"] + 1e-6
