"""Evidence-gap lint (``bench_log --check``): the committed on-chip
evidence trail must always validate — every BENCH_TPU_SESSIONS.jsonl
line is either the schema header or a named-bench record, with the
fields a later reader needs (ts/bench/device and the bench's own).
VERDICT r5 item 1, "the cheapest high-value fix"."""

import json
import subprocess
import sys

from ray_tpu.scripts import bench_log


def test_committed_evidence_file_passes_check():
    """Tier-1 gate: the file in the repo root validates. If this fails,
    a writer appended a line the schema can't describe — fix the writer
    (or teach check_line the new shape), don't hand-edit the trail."""
    assert bench_log.check_file(bench_log.default_path()) == []


def test_check_accepts_real_writer_shapes(tmp_path):
    """Lines exactly as the record_* helpers produce them."""
    dest = tmp_path / "trail.jsonl"
    lines = [
        {"schema": "one JSON line per on-chip run of a harness"},
        {"ts": 3.0, "iso": "2026-08-03T00:00:00Z", "bench": "chaos_soak",
         "script": "chaos_soak", "device": "TPU v5 lite", "seed": 7,
         "duration_s": 30.0, "faults": {}, "violations": []},
        {"ts": 4.0, "bench": "drain_recovery_ms", "device": "TPU v5 lite",
         "proactive_drain_ms": 100.0, "crash_detection_ms": 210.0},
        {"ts": 5.0, "bench": "streaming_dataflow", "device": "TPU v5 lite",
         "rows_s": 84000.0, "client": {"stall_fraction": 0.03},
         "server": {"stall_fraction": 0.04},
         "agreement": {"ok": True},
         "spill": {"spilled_objects": 50, "restores": 55},
         "pool": {"pool_peak": 4}},
    ]
    dest.write_text("".join(json.dumps(ln) + "\n" for ln in lines))
    assert bench_log.check_file(str(dest)) == []


def test_check_flags_malformed_lines(tmp_path):
    dest = tmp_path / "trail.jsonl"
    dest.write_text("\n".join([
        "not json at all",
        json.dumps({"bench": "chaos_soak"}),  # no ts/device
        json.dumps({"ts": 1.0, "device": "cpu", "bench": "chaos_soak"}),
        # A rate under a 'script' key is no shape of this trail: speeds
        # come from the benchmark and live in the ledger.
        json.dumps({"ts": 1.0, "device": "TPU v5 lite", "script": "bench",
                    "config": "base", "tok_s": 1.0}),
        json.dumps({"ts": 1.0, "device": "TPU v5 lite",
                    "bench": "not_a_bench"}),
        # A 'schema' key can't smuggle a malformed line past the lint:
        # the header shape is only valid on line 1.
        json.dumps({"schema": "x", "bench": "chaos_soak", "device": "cpu"}),
    ]) + "\n")
    problems = bench_log.check_file(str(dest))
    assert any("invalid JSON" in p and p.startswith("line 1") for p in problems)
    assert any(p.startswith("line 2") and "'ts'" in p for p in problems)
    assert any(p.startswith("line 3") and "cpu" in p for p in problems)
    assert any(p.startswith("line 4") and "neither" in p for p in problems)
    assert any(p.startswith("line 5") and "unknown bench" in p
               for p in problems)
    assert any(p.startswith("line 6") and "only valid on line 1" in p
               for p in problems)


def test_check_flags_gutted_streaming_dataflow_line(tmp_path):
    """A streaming_dataflow line without both stall views, the agreement
    verdict, and the spill/restore churn proof is an unverified claim —
    every missing piece is flagged."""
    dest = tmp_path / "trail.jsonl"
    dest.write_text(json.dumps({
        "ts": 1.0, "bench": "streaming_dataflow",
        "device": "TPU v5 lite"}) + "\n")
    problems = "\n".join(bench_log.check_file(str(dest)))
    assert "rows_s/tokens_s" in problems
    assert "client.stall_fraction" in problems
    assert "server.stall_fraction" in problems
    assert "agreement.ok" in problems
    assert "spill.spilled_objects/restores" in problems


def test_check_cli_exit_codes(tmp_path):
    ok = tmp_path / "ok.jsonl"
    ok.write_text(json.dumps({"schema": "v1"}) + "\n")
    bad = tmp_path / "bad.jsonl"
    bad.write_text("{broken\n")
    r = subprocess.run(
        [sys.executable, "-m", "ray_tpu.scripts.bench_log", "--check",
         str(ok)], capture_output=True, text=True, timeout=60)
    assert r.returncode == 0, r.stdout + r.stderr
    r = subprocess.run(
        [sys.executable, "-m", "ray_tpu.scripts.bench_log", "--check",
         str(bad)], capture_output=True, text=True, timeout=60)
    assert r.returncode == 1
    assert "invalid JSON" in r.stdout


def test_recorded_entries_validate(tmp_path, monkeypatch):
    """What record_if_on_chip writes, check_line accepts — the writer
    and the lint can't drift apart."""
    dest = tmp_path / "trail.jsonl"
    monkeypatch.setenv(bench_log.ENV_VAR, str(dest))
    bench_log.record_if_on_chip({
        "bench": "chaos_soak", "script": "chaos_soak", "seed": 7,
        "device": "TPU v5 lite"})
    bench_log.record_drain_recovery(100.0, 200.0, device="TPU v5 lite")
    assert bench_log.check_file(str(dest)) == []
