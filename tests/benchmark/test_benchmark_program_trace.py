"""The readers of the program's own annotations, scopes and counters
(``benchmark/program_trace.py``, ``benchmark/program_counters.py``)
against small hand-made traces kept beside the metrics
(``benchmark/metrics/fixtures/program_*.json``), with the expected numbers
worked here by hand; and the wire-format reader against a profile encoded
here byte by byte. These run on any machine: they say nothing about a
device."""

import os
import struct
import types

import pytest

from benchmark import program_counters, program_trace
from benchmark.loading import load_module

REPO = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
METRICS = os.path.join(REPO, "benchmark", "metrics")


def reader(name):
    return load_module(os.path.join(METRICS, name + ".py"))


def stub(fixture, **more):
    said = []
    run = types.SimpleNamespace(
        program_trace=None if fixture is None else program_trace.from_json(
            os.path.join(METRICS, "fixtures", fixture)),
        trace_path=None,
        params={"device_programs": {"step": "jit_step",
                                    "decode": "jit_step_fn",
                                    "prefill": "jit_prefill_fn"}},
        raw={}, counters={}, window_ns=None, said=said,
        say=lambda event, **f: said.append((event, f)))
    for k, v in more.items():
        setattr(run, k, v)
    return run


def said(run, event):
    return next(f for e, f in run.said if e == event)


def test_step_host_time_is_the_loop_turns_three_host_phases():
    run = stub("program_decode_two_turns.json")
    # Turn 1: select 2 + dispatch 10 + fanout 10 = 22 ms (sync 200);
    # turn 2: 2 + 18 + 6 = 26 ms (sync 270). The third select found no
    # active slot: no dispatch follows, so it is no turn.
    assert reader("serve_step_host_ms_p50").read(run) == pytest.approx(24.0)
    phases = said(run, "engine_step_phases_ms_p50")
    assert phases["turns"] == 2
    assert phases["llm.step.sync"] == pytest.approx(235.0)
    assert phases["llm.step.dispatch"] == pytest.approx(14.0)
    # Anchors: 5,090,000,400 - 90,000,000 and 5,312,000,000 - 312,000,000
    # (one attribute came as a number, one as text): 400 ns apart, the
    # median between them.
    anchors = phases["clock_anchors"]
    assert anchors["anchors"] == 2
    assert anchors["offset_ns"] == 5_000_000_200
    assert anchors["spread_max_us"] == pytest.approx(0.2)


@pytest.mark.parametrize("name", ["serve_idle_attributed_pct.decode",
                                  "serve_idle_attributed_pct.prompt"])
def test_idle_seconds_go_to_the_span_over_them(name):
    run = stub("program_decode_two_turns.json")
    # Idle: [0, .10], [.30, .40], [.60, .70], [.90, 1.0] = 0.40 s.
    # No llm.* span lies over [.085, .088] and [.990, 1.0]: 0.013 s.
    # bench.first_chunk (a client thread) covers much and counts for
    # nothing here.
    assert reader(name).read(run) == pytest.approx(100 * 0.387 / 0.40)
    by = said(run, "idle_by_program_span")
    assert by["idle_s"] == pytest.approx(0.40)
    assert by["seconds"]["llm.loop.wait"] == pytest.approx(0.080 + 0.084)
    assert by["seconds"]["llm.step.sync"] == pytest.approx(0.070)
    assert by["seconds"]["llm.prefill.sync"] == pytest.approx(0.060)
    assert by["seconds"]["llm.step.dispatch"] == pytest.approx(0.028)
    assert by["seconds"]["llm.step.fanout"] == pytest.approx(0.016)
    assert by["seconds"]["unattributed"] == pytest.approx(0.013)


def test_decode_attention_share_and_the_table_by_scope():
    run = stub("program_decode_two_turns.json")
    # Two executions of the decode program: attn .05, cache_write .05,
    # mlp .06, one convert with no path .04 each; the while that holds
    # them and the prefill program's operation are left out.
    assert reader("decode_attention_time_pct").read(run) == pytest.approx(50.0)
    table = said(run, "decode_by_scope")
    assert table["busy_s"] == pytest.approx(0.40)
    assert table["by_scope_pct"] == pytest.approx(
        {"mlp": 30.0, "attn": 25.0, "cache_write": 25.0,
         program_trace.NO_PATH: 20.0})
    assert table["unscoped_ops"] == [["%convert.3", pytest.approx(0.08)]]
    # the prefill program has no metric of its own: its table rides here
    assert said(run, "prefill_by_scope")["by_scope_pct"] == pytest.approx(
        {"attn": 100.0})


@pytest.mark.parametrize("name", ["serve_idle_attributed_pct.decode",
                                  "serve_idle_attributed_pct.prompt",
                                  "serve_step_host_ms_p50"])
def test_a_reader_says_its_own_lines_only(name):
    run = stub("program_decode_two_turns.json")
    reader(name).read(run)
    assert not [e for e, _ in run.said if e.endswith("_by_scope")]


def test_training_scopes_forward_and_backward_and_the_report():
    run = stub("program_train_two_steps.json")
    # A step is busy 0.40 s: head_loss .05 forward (jvp(head_loss)) and
    # .05 backward (transpose(jvp(head_loss))), adamw .04.
    assert reader("train_head_loss_time_pct").read(run) == pytest.approx(25.0)
    assert reader("train_optimizer_time_pct").read(run) == pytest.approx(10.0)
    # two metrics, one table, said once
    assert [e for e, _ in run.said].count("train_by_scope") == 1
    assert said(run, "train_by_scope")["by_scope_pct"]["attn"] \
        == pytest.approx(25.0)
    assert reader("train_report_ms_p50").read(run) == pytest.approx(3.5)


@pytest.mark.parametrize("path,scope", [
    ("jit(step)/jit(main)/transpose(jvp(head_loss))/dot_general", "head_loss"),
    ("jit(step_fn)/jit(main)/while/body/attn_proj/dot_general", "attn_proj"),
    ("jit(step_fn)/jit(main)/while/body/attn/reduce_max", "attn"),
    ("jit(step)/jit(main)/jvp(jit(checkpoint))/ln/rsqrt", "ln"),
    ("jit(step_fn)/jit(main)/head/argmax", "head"),
    ("jit(step)/jit(main)/convert_element_type",
     "(no scope) convert_element_type"),
    ("jit(step_fn)/while/body/dynamic_slice:", "(no scope) dynamic_slice"),
    ("jit(step)/jit(main)/mlp_extra/add", "(no scope) add"),
    (None, program_trace.NO_PATH)])
def test_scope_of_a_path(path, scope):
    assert program_trace.scope_of(path) == scope


@pytest.mark.parametrize("name", [
    "serve_step_host_ms_p50", "serve_idle_attributed_pct.decode",
    "serve_idle_attributed_pct.prompt", "decode_attention_time_pct",
    "train_head_loss_time_pct", "train_optimizer_time_pct",
    "train_report_ms_p50", "serve_prefill_fill_pct.decode",
    "serve_prefill_fill_pct.prompt", "setup_program_compile_s"])
@pytest.mark.parametrize("fixture", [
    "program_parent_no_annotations.json", None])
def test_a_reader_with_nothing_to_read_returns_none(name, fixture):
    """The parent of the PR that added the annotations has none of them
    (and a run whose profile was not written has no trace at all): every
    reader says nothing and raises nothing."""
    run = stub(fixture)
    assert reader(name).read(run) is None
    assert not run.said


@pytest.mark.parametrize("name", ["serve_prefill_fill_pct.decode",
                                  "serve_prefill_fill_pct.prompt"])
def test_prefill_fill_from_the_window_delta_of_the_counters(name):
    before = {"prefill_batches": 2, "prefill_rows_real": 5,
              "prefill_tokens_real": 900, "prefill_tokens_lane": 6144}
    after = {"prefill_batches": 12, "prefill_rows_real": 17,
             "prefill_tokens_real": 900 + 5530,
             "prefill_tokens_lane": 6144 + 10 * 3072}
    run = stub(None, counters={"open": before, "close": after})
    assert reader(name).read(run) == pytest.approx(100 * 5530 / 30720)
    lane = said(run, "prefill_lane")
    assert lane["batches"] == 10 and lane["rows_per_batch"] == 1.2
    # an engine that keeps no such counter (the parent)
    run = stub(None, counters={"open": {"steps": 1}, "close": {"steps": 9}})
    assert reader(name).read(run) is None


def test_setup_compiles_counts_what_ended_before_the_window(monkeypatch):
    from ray_tpu.util import device_telemetry

    log = [{"epoch_ns": 1_000, "seconds": 2.0, "cache": "hit",
            "fun_name": "step_fn"},
           {"epoch_ns": 2_000, "seconds": 30.0, "cache": "miss",
            "fun_name": "prefill_fn"},
           {"epoch_ns": 3_000, "seconds": 0.5, "cache": None,
            "fun_name": "iota"},
           {"epoch_ns": 9_000, "seconds": 7.0, "cache": "miss",
            "fun_name": "in_the_window"}]
    monkeypatch.setattr(device_telemetry, "compile_log", lambda: log)
    run = stub(None, window_ns=(500, 900), epoch_offset_ns=4_000)
    assert reader("setup_program_compile_s").read(run) \
        == pytest.approx(32.5)
    seen = said(run, "setup_compiles")
    assert (seen["compiles"], seen["hits"], seen["misses"],
            seen["uncached"]) == (3, 1, 1, 1)
    assert seen["slowest"][0] == ["prefill_fn", "miss", 30.0]
    # a program without the log (the parent)
    monkeypatch.delattr(device_telemetry, "compile_log")
    assert program_counters.setup_compiles(run) is None


# -- the wire format -----------------------------------------------------------


def _varint(n: int) -> bytes:
    out = bytearray()
    while True:
        b = n & 0x7F
        n >>= 7
        out.append(b | (0x80 if n else 0))
        if not n:
            return bytes(out)


def _f(no: int, value) -> bytes:
    """One field: an int as a varint, bytes or str length-delimited."""
    if isinstance(value, int):
        return _varint(no << 3) + _varint(value)
    if isinstance(value, str):
        value = value.encode()
    return _varint(no << 3 | 2) + _varint(len(value)) + value


def _stat(sid: int, value) -> bytes:
    if isinstance(value, float):
        body = _varint(2 << 3 | 1) + struct.pack("<d", value)
    elif isinstance(value, int):
        body = _f(4, value)
    else:
        body = _f(5, value)
    return _f(1, sid) + body


def _plane(name, stat_names, event_meta, lines) -> bytes:
    """``event_meta``: id -> (name, [stats]); ``lines``: (name,
    timestamp ns, [(metadata id, offset ps, duration ps, [stats])])."""
    out = _f(2, name)
    for lname, t0, events in lines:
        body = _f(2, lname) + _f(3, t0)
        for mid, off, dur, stats in events:
            body += _f(4, _f(1, mid) + _f(2, off) + _f(3, dur)
                       + b"".join(_f(4, s) for s in stats))
        out += _f(3, body)
    for mid, (ename, stats) in event_meta.items():
        meta = _f(1, mid) + _f(2, ename) + b"".join(_f(5, s) for s in stats)
        out += _f(4, _f(1, mid) + _f(2, meta))
    for sid, sname in stat_names.items():
        out += _f(5, _f(1, sid) + _f(2, _f(1, sid) + _f(2, sname)))
    return out


def test_load_reads_annotations_attributes_and_scope_paths(tmp_path):
    host = _plane(
        "/host:CPU", {1: "epoch_ns", 2: "_pt"},
        {1: ("bench.window", []), 2: ("llm.step.dispatch", []),
         3: ("llm.admit#queued=3,free=1#", []), 4: ("XlaLinearize", [])},
        [("python3", 1_000_000_000, [(1, 0, 2_000_000_000_000, [])]),
         ("python3", 1_000_000_000, [
             (2, 500_000_000_000, 10_000_000_000,
              [_stat(1, 7_000_000_000), _stat(2, 1)]),
             (3, 400_000_000_000, 1_000_000_000, []),
             (4, 100_000_000_000, 1_000_000_000, [])])])
    dev = _plane(
        "/device:TPU:0", {1: "tf_op", 2: "hlo_category", 3: "flops"},
        {1: ("%fusion.1 = bf16[8] fusion(%p0)", [
            _stat(1, "jit(step_fn)/jit(main)/while/body/attn/dot_general"),
            _stat(2, "fusion"), _stat(3, 4.0)]),
         2: ("%copy.2 = bf16[8] copy(%p1)", [_stat(2, "copy")]),
         3: ("jit_step_fn(99)", [])},
        [("XLA Ops", 1_000_000_000, [
            (1, 600_000_000_000, 50_000_000_000, []),
            (2, 700_000_000_000, 50_000_000_000, [])]),
         ("XLA Modules", 1_000_000_000, [
             (3, 600_000_000_000, 200_000_000_000, [])])])
    other = _plane(
        "/device:TPU:1", {}, {1: ("%fusion.7", [])},
        [("XLA Ops", 1_000_000_000, [(1, 600_000_000_000, 1_000_000, [])])])
    path = tmp_path / "t.xplane.pb"
    path.write_bytes(_f(1, host) + _f(1, dev) + _f(1, other))

    pt = program_trace.load(str(path))
    assert pt["window"] == pytest.approx((1.0, 3.0))
    by_name = {h[0]: h for h in pt["host"]}
    assert set(by_name) == {"bench.window", "llm.step.dispatch", "llm.admit"}
    name, s, e, attrs, thread = by_name["llm.step.dispatch"]
    assert (s, e) == pytest.approx((1.5, 1.51))
    assert attrs == {"epoch_ns": 7_000_000_000}       # "_pt" is dropped
    assert by_name["llm.admit"][3] == {"queued": "3", "free": "1"}
    assert by_name["llm.admit"][4] == thread != by_name["bench.window"][4]
    # the first device only, each operation with its metadata's path
    assert [(o[0][:9], o[3]) for o in pt["ops"]] == [
        ("%fusion.1", "jit(step_fn)/jit(main)/while/body/attn/dot_general"),
        ("%copy.2 =", None)]
    assert pt["modules"][0][0] == "jit_step_fn(99)"
    assert program_trace.busy_by_scope(pt, "jit_step_fn") == pytest.approx(
        {"attn": 0.05, program_trace.NO_PATH: 0.05})
    described = program_trace.describe(str(path), limit=1)
    first = described["planes"][1]["lines"][0]["first"][0]
    assert first["metadata_stats"]["tf_op"].endswith("attn/dot_general")


def test_load_steps_over_the_planes_it_does_not_read(tmp_path):
    """Only host planes and the first device plane are decoded: a later
    chip's plane (here one whose metadata map is not even well-formed)
    costs the walk over its top-level fields and nothing else."""
    dev = _plane(
        "/device:TPU:0", {1: "tf_op"},
        {1: ("%fusion.1", [_stat(1, "jit(step)/jit(main)/adamw/mul")])},
        [("XLA Ops", 0, [(1, 0, 50_000_000_000, [])])])
    broken = _f(2, "/device:TPU:1") + _f(4, b"\x0f") + _f(5, b"\x0f")
    path = tmp_path / "t.xplane.pb"
    path.write_bytes(_f(1, dev) + _f(1, broken)
                     + _f(1, _f(2, "/host:metadata") + _f(4, b"\x0f")))
    pt = program_trace.load(str(path))
    assert [program_trace.scope_of(o[3]) for o in pt["ops"]] == ["adamw"]
    with pytest.raises(ValueError):
        program_trace._plane_head(memoryview(broken))


def test_of_run_says_how_long_the_second_read_took(tmp_path):
    dev = _plane("/device:TPU:0", {}, {1: ("%fusion.1", [])},
                 [("XLA Ops", 0, [(1, 0, 50_000_000_000, [])])])
    path = tmp_path / "t.xplane.pb"
    path.write_bytes(_f(1, dev))
    run = stub(None, trace_path=str(path))
    assert program_trace.of_run(run) is program_trace.of_run(run)
    (read,) = [f for e, f in run.said if e == "program_trace_read"]
    assert read["seconds"] >= 0 and read["ops"] == 1
