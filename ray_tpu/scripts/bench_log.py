"""Persist on-chip evidence from the control-plane harnesses.

Each ``record_*`` helper appends one JSON line to a committed
``BENCH_TPU_SESSIONS.jsonl`` at the repo root when its harness ran on an
accelerator. Speeds are not kept here: those come from ``python3 -m
benchmark.run`` and live in ``PERF_LEDGER.jsonl``. Override the
destination with ``RAY_TPU_BENCH_LOG`` (tests point it at a tmp file; CI
containers without a writable checkout can point it at /tmp or set it
empty to disable).

Appending is best-effort by design: a benchmark must never fail because
the evidence file is unwritable.
"""

from __future__ import annotations

import json
import os
import time

ENV_VAR = "RAY_TPU_BENCH_LOG"
FILENAME = "BENCH_TPU_SESSIONS.jsonl"

# Named benches that append via the record_* helpers below (lines keyed
# by "bench" rather than "script"+"config").
KNOWN_BENCHES = frozenset({
    "task_overhead", "memory_pressure", "chaos_soak", "scalebench",
    "drain_recovery_ms", "serve_latency", "input_pipeline", "goodput",
    "analyze", "gang_recovery", "streaming_dataflow", "signal_plane",
    "fleet_scaling",
})


def device_kind() -> str:
    """Platform of the first visible accelerator ("" when jax is absent
    or broken) — the shared probe every bench stamps its evidence lines
    with, so 'device' can never disagree across harnesses."""
    try:
        import jax

        return jax.devices()[0].platform
    except Exception:
        return ""


def default_path() -> str:
    """Repo-root BENCH_TPU_SESSIONS.jsonl (this file lives in
    ray_tpu/scripts/, two levels below the root)."""
    root = os.path.dirname(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))))
    return os.path.join(root, FILENAME)


def record(entry: dict, path: str | None = None) -> str | None:
    """Append one measurement line; returns the path written, or None if
    persistence was disabled/unwritable."""
    if path is None:
        path = os.environ.get(ENV_VAR)
        if path == "":
            return None  # explicitly disabled
        if path is None:
            path = default_path()
    line = dict(entry)
    line.setdefault("ts", round(time.time(), 3))
    line.setdefault(
        "iso", time.strftime("%Y-%m-%dT%H:%M:%SZ", time.gmtime()))
    try:
        with open(path, "a") as f:
            f.write(json.dumps(line, default=str) + "\n")
    except OSError:
        return None
    return path


def record_if_on_chip(entry: dict, path: str | None = None) -> str | None:
    """Record only measurements taken on an accelerator: a CPU fallback
    number is not TPU perf evidence and must not pollute the trail."""
    device = str(entry.get("device", "")).lower()
    if not device or device == "cpu":
        return None
    return record(entry, path)


def record_task_overhead(task_records: list, *, device: str = "",
                         path: str | None = None, **extra) -> dict:
    """Framework task-overhead evidence (``scripts/overhead_bench.py``):
    p50/p99 submit→start latency and per-phase (get_args / execute /
    put_outputs) wall time, computed from state-API task records that
    carry the worker-side phase breakdown. Committed to the evidence
    trail only on an accelerator; returns the entry (with
    ``committed_to``) either way."""
    from ray_tpu.util.metrics import latency_dist_ms

    submit_ms = []
    phase_samples: dict[str, list] = {}
    n = 0
    for rec in task_records:
        if rec.get("start_time") is None:
            continue
        n += 1
        if rec.get("submitted_at") is not None:
            submit_ms.append(
                max(0.0, (rec["start_time"] - rec["submitted_at"]) * 1e3))
        for phase, ns in (rec.get("phases") or {}).items():
            phase_samples.setdefault(phase, []).append(ns / 1e6)
    entry: dict = {"bench": "task_overhead", "device": device, "n_tasks": n}
    if submit_ms:
        entry["submit_to_start"] = latency_dist_ms(submit_ms)
    if phase_samples:
        entry["phases"] = {
            phase: latency_dist_ms(vals)
            for phase, vals in phase_samples.items()
        }
    entry.update(extra)
    entry["committed_to"] = record_if_on_chip(dict(entry), path)
    return entry


def record_memory_pressure(samples: list, *, device: str = "",
                           path: str | None = None, **extra) -> dict:
    """Object-store pressure evidence (``scripts/memory_bench.py``):
    peak/mean occupancy, evictions, and spill denials over a churn
    workload, computed from per-round ``stats()`` samples (dicts with
    used/capacity/num_evictions[/spill_denied]). Committed to the
    evidence trail only on an accelerator; returns the entry (with
    ``committed_to``) either way."""
    entry: dict = {"bench": "memory_pressure", "device": device,
                   "n_samples": len(samples)}
    if samples:
        used = [int(s.get("used", 0)) for s in samples]
        capacity = max(int(s.get("capacity", 0)) for s in samples)
        evictions = [int(s.get("num_evictions", 0)) for s in samples]
        denied = [int(s.get("spill_denied", 0)) for s in samples]
        entry["capacity_bytes"] = capacity
        entry["peak_used_bytes"] = max(used)
        entry["mean_used_bytes"] = round(sum(used) / len(used))
        if capacity:
            entry["peak_occupancy"] = round(max(used) / capacity, 4)
        entry["evictions"] = max(evictions) - min(evictions)
        if any("spill_denied" in s for s in samples):
            # Only samples that actually carry the stat (agent store
            # stats do; ad-hoc sample dicts may not) — a fabricated 0
            # would misreport a pressure run as denial-free.
            entry["spill_denied"] = max(denied) - min(denied)
    entry.update(extra)
    entry["committed_to"] = record_if_on_chip(dict(entry), path)
    return entry


def record_chaos_soak(*, seed, duration_s: float, faults: dict,
                      violations: list, mttr_ms: list,
                      tasks_ok: int, actor_calls_ok: int, puts_ok: int,
                      device: str = "", path: str | None = None,
                      **extra) -> dict:
    """Chaos-soak evidence (``scripts/chaos_soak.py``): the seeded fault
    schedule's class counts, invariant violations (must be [] for a
    passing soak), and per-fault MTTR (fault injection -> first
    successful probe round-trip). Committed to the evidence trail only
    on an accelerator; returns the entry (with ``committed_to``) either
    way. The seed makes any line replayable:
    ``RAY_TPU_CHAOS_SEED=<seed> python -m ray_tpu.scripts.chaos_soak``."""
    entry: dict = {
        "bench": "chaos_soak",
        "device": device,
        "seed": seed,
        "duration_s": round(float(duration_s), 1),
        "faults": dict(faults),
        "faults_injected": sum(faults.values()),
        "violations": list(violations),
        "n_violations": len(violations),
        "tasks_ok": tasks_ok,
        "actor_calls_ok": actor_calls_ok,
        "puts_ok": puts_ok,
    }
    if mttr_ms:
        entry["mttr_ms"] = {
            "mean": round(sum(mttr_ms) / len(mttr_ms), 1),
            "max": round(max(mttr_ms), 1),
            "n": len(mttr_ms),
        }
    entry.update(extra)
    entry["committed_to"] = record_if_on_chip(dict(entry), path)
    return entry


def record_serve_latency(*, client: dict, server: dict, agreement: dict,
                         mode: str = "http", connections: int = 0,
                         n_requests: int = 0, device: str = "",
                         path: str | None = None, **extra) -> dict:
    """Serve SLO latency evidence (``scripts/serve_bench.py``):
    client-side p50/p99/QPS over N concurrent streams, the server-side
    histogram view of the same requests, and the agreement verdict
    between them (the two must match or the serve metrics are lying).
    Committed to the evidence trail only on an accelerator; returns the
    entry (with ``committed_to``) either way."""
    entry: dict = {
        "bench": "serve_latency",
        "device": device,
        "mode": mode,
        "connections": int(connections),
        "n_requests": int(n_requests),
        "client": dict(client),
        "server": dict(server),
        "agreement": dict(agreement),
    }
    entry.update(extra)
    entry["committed_to"] = record_if_on_chip(dict(entry), path)
    return entry


def record_input_pipeline(*, client: dict, server: dict,
                          agreement: dict, n_batches: int = 0,
                          device: str = "", path: str | None = None,
                          **extra) -> dict:
    """Input-pipeline stall evidence (``scripts/input_bench.py``): the
    client-measured stall fraction of a dataset->iterator->train-step
    loop, the metrics-derived view of the same loop, and the agreement
    verdict between them (count-exact per phase, stall within
    tolerance — disagreement means the goodput metrics are lying).
    Committed to the evidence trail only on an accelerator; returns the
    entry (with ``committed_to``) either way."""
    entry: dict = {
        "bench": "input_pipeline",
        "device": device,
        "n_batches": int(n_batches),
        "client": dict(client),
        "server": dict(server),
        "agreement": dict(agreement),
    }
    entry.update(extra)
    entry["committed_to"] = record_if_on_chip(dict(entry), path)
    return entry


def record_streaming_dataflow(*, client: dict, server: dict,
                              agreement: dict, rows_s: float,
                              spill: dict, pool: dict,
                              device: str = "", path: str | None = None,
                              **extra) -> dict:
    """Streaming-dataflow evidence (``scripts/dataflow_bench.py``): a
    generation->training pipeline driven past store capacity — the
    client-measured consumer stall fraction, the metrics-derived view
    of the same loop, the agreement verdict, the throughput headline
    (rows/s through the consumer), the spill/restore counts that prove
    the store actually churned, and the actor-pool scale events. A
    stall claim without the spill counts is just a small-data run.
    Committed to the evidence trail only on an accelerator; returns the
    entry (with ``committed_to``) either way."""
    entry: dict = {
        "bench": "streaming_dataflow",
        "device": device,
        "rows_s": float(rows_s),
        "client": dict(client),
        "server": dict(server),
        "agreement": dict(agreement),
        "spill": dict(spill),
        "pool": dict(pool),
    }
    entry.update(extra)
    entry["committed_to"] = record_if_on_chip(dict(entry), path)
    return entry


def record_signal_plane(*, agreement: dict, query_p50_ms: float,
                        series: int, ring: dict | None = None,
                        slo: dict | None = None,
                        device: str = "", path: str | None = None,
                        **extra) -> dict:
    """Signal-plane evidence (``scripts/signal_bench.py``): the
    windowed-query-vs-client agreement verdict (history-derived QPS and
    TTFT p50 must match client-side measurement within bucket
    resolution — a query engine that disagrees with the traffic it
    summarizes is worse than none), the query path's p50 latency (the
    zero-sleeps claim, measured), the ring's series count, the
    bounded-memory section (64-node-shaped scrape: growth + eviction
    counts), and the seeded SLO burn section (exactly one burning and
    one recovery event). Committed to the evidence trail only on an
    accelerator; returns the entry (with ``committed_to``) either
    way."""
    entry: dict = {
        "bench": "signal_plane",
        "device": device,
        "agreement": dict(agreement),
        "query_p50_ms": float(query_p50_ms),
        "series": int(series),
    }
    if ring is not None:
        entry["ring"] = dict(ring)
    if slo is not None:
        entry["slo"] = dict(slo)
    entry.update(extra)
    entry["committed_to"] = record_if_on_chip(dict(entry), path)
    return entry


def record_fleet_scaling(*, scale_up_ms: dict, bin_pack_efficiency: float,
                         scale_down: dict, waves: int, seed: int,
                         device: str = "", path: str | None = None,
                         **extra) -> dict:
    """Fleet autoscaling evidence (``scalebench --demand-burst``): the
    seeded arrival-wave envelope — scale-up latency p50/p99 (submit to
    demand-served, capacity provisioned by the bin-packer), bin-pack
    efficiency (requested / provisioned resources; launching a node per
    demand would read as waste here), and the zero-goodput-loss
    scale-down section (every terminated node drained first, every
    removal cause-attributed ``drain:*`` — an unplanned termination is
    exactly the goodput loss this bench exists to rule out). Committed
    to the evidence trail only on an accelerator; returns the entry
    (with ``committed_to``) either way."""
    entry: dict = {
        "bench": "fleet_scaling",
        "device": device,
        "waves": int(waves),
        "seed": int(seed),
        "scale_up_ms": dict(scale_up_ms),
        "bin_pack_efficiency": float(bin_pack_efficiency),
        "scale_down": dict(scale_down),
    }
    entry.update(extra)
    entry["committed_to"] = record_if_on_chip(dict(entry), path)
    return entry


def record_goodput(*, trial: str, goodput_pct: float, wall_s: float,
                   downtime_s: float, by_cause: dict,
                   device: str = "", path: str | None = None,
                   **extra) -> dict:
    """Training goodput evidence (``scripts/input_bench.py --drain``,
    chaos soak train probe): a trial's goodput %% with its downtime
    ledger — every non-productive second must carry a cause
    (drain:<reason> / preemption / failure), never unaccounted wall
    time. Committed to the evidence trail only on an accelerator;
    returns the entry (with ``committed_to``) either way."""
    entry: dict = {
        "bench": "goodput",
        "device": device,
        "trial": str(trial),
        "goodput_pct": float(goodput_pct),
        "wall_s": round(float(wall_s), 3),
        "downtime_s": round(float(downtime_s), 3),
        "by_cause": dict(by_cause),
    }
    entry.update(extra)
    entry["committed_to"] = record_if_on_chip(dict(entry), path)
    return entry


def record_analyze(*, rule_counts: dict, new: int, baselined: int,
                   ok: bool, stale_baseline: int = 0,
                   passes: list | None = None,
                   device: str = "", path: str | None = None,
                   **extra) -> dict:
    """Static-analysis gate evidence (``scripts/analyze.py --out``, the
    perfsuite `analyze` stage): per-rule finding counts, how many are
    baselined vs NEW, and the gate verdict — so an on-chip perf session
    also records that its tree passed the concurrency/contract gate
    (rule-count trends live in MICROBENCH.json's `analyze` section;
    this line is the timestamped trail). Committed to the evidence
    trail only on an accelerator; returns the entry (with
    ``committed_to``) either way."""
    if passes is None:
        # Default to the live registry: the evidence line must say
        # WHICH pass families were active — "analyze ran" from a build
        # where half the passes didn't load is a weaker claim.
        try:
            from ray_tpu.util import analyze as _analyze

            passes = sorted(_analyze.PASSES)
        except Exception:
            passes = []
    entry: dict = {
        "bench": "analyze",
        "device": device,
        "rule_counts": dict(rule_counts),
        "new": int(new),
        "baselined": int(baselined),
        "stale_baseline": int(stale_baseline),
        "passes": list(passes),
        "ok": bool(ok),
    }
    entry.update(extra)
    entry["committed_to"] = record_if_on_chip(dict(entry), path)
    return entry


def record_scalebench(*, scalability: dict | None = None,
                      head_scale: dict | None = None,
                      device: str = "", path: str | None = None,
                      **extra) -> dict:
    """Control-plane envelope evidence (``scripts/scalebench.py``): the
    real-cluster section's rates and the head-at-scale section's
    machine-independent per-RPC accounting, flattened to the headline
    numbers (the full artifact lives in MICROBENCH.json — this line is
    the timestamped when/at-what-shape trail). Committed to
    BENCH_TPU_SESSIONS.jsonl only on an accelerator; returns the entry
    (with ``committed_to``) either way."""

    def headline(section: dict | None, keys: tuple) -> dict:
        if not section:
            return {}
        out = {}
        for k in keys:
            e = section.get(k)
            if isinstance(e, dict) and "value" in e:
                out[k] = e["value"]
            elif e is not None and not isinstance(e, dict):
                out[k] = e
        return out

    entry: dict = {"bench": "scalebench", "device": device}
    sc = headline(scalability, (
        "nodes", "cpus_per_node", "cluster_boot_s", "burst_tasks_per_s",
        "burst_submit_per_s", "actor_create_call_per_s",
        "broadcast_agg_gib_per_s", "queued_pending",
        "queued_sched_rpcs_per_s", "queued_probe_latency_s",
        "queued_shutdown_s", "queued_rss_growth_mb"))
    if sc:
        entry["scalability"] = sc
    hs = headline(head_scale, (
        "nodes", "queued", "actors", "subscribers", "spans",
        "heartbeats_per_s", "status_polls_per_s", "sched_feasible_per_s",
        "sched_infeasible_per_s", "ref_begin_per_s", "add_location_per_s",
        "actor_register_per_s", "actor_updates_per_s",
        "pubsub_coalesced", "pubsub_dropped", "span_dropped",
        "persist_coalesced", "rss_growth_mb", "head_handler_total_s"))
    if hs:
        entry["head_scale"] = hs
    entry.update(extra)
    entry["committed_to"] = record_if_on_chip(dict(entry), path)
    return entry


# --------------------------------------------------------------------------
# Evidence-gap lint (VERDICT r5 item 1, "the cheapest high-value fix"):
# every line of the committed trail must parse and carry the fields a
# later reader needs to reconstruct when/where/what was measured. Runs
# in tier-1 against the committed file and as
# ``python -m ray_tpu.scripts.bench_log --check [path]``.
# --------------------------------------------------------------------------


def _is_num(v) -> bool:
    return isinstance(v, (int, float)) and not isinstance(v, bool)


def check_line(obj: object, *, allow_header: bool = False) -> list[str]:
    """Schema errors for one parsed JSONL entry ([] = valid).

    Two valid shapes:
    * header — ``{"schema": <str>, ...}``; ONLY the first line of the
      file (``allow_header=True``) may take this shape, so a 'schema'
      key on a data line can't smuggle it past validation;
    * named bench — ``bench`` lines from the record_* helpers: need ts
      and a non-CPU device.
    """
    if not isinstance(obj, dict):
        return ["not a JSON object"]
    if "schema" in obj:
        if not allow_header:
            return ["'schema' header shape only valid on line 1"]
        return [] if isinstance(obj["schema"], str) else [
            "header 'schema' must be a string"]
    errs = []
    if not _is_num(obj.get("ts")):
        errs.append("missing/non-numeric 'ts'")
    iso = obj.get("iso")
    if iso is not None and not isinstance(iso, str):
        errs.append("'iso' must be a string")
    device = obj.get("device")
    if not isinstance(device, str) or not device:
        errs.append("missing/empty 'device'")
    elif device.lower() == "cpu":
        errs.append("'device' is cpu — CPU numbers must not enter the "
                    "on-chip evidence trail")
    if "bench" in obj:
        if obj["bench"] not in KNOWN_BENCHES:
            errs.append(f"unknown bench {obj['bench']!r}")
        elif obj["bench"] == "input_pipeline":
            # The whole point of the line is the CROSS-CHECKED stall
            # fraction: client AND server views plus the agreement flag
            # — a one-sided stall number is exactly the unverified
            # claim this bench exists to prevent.
            client = obj.get("client")
            server = obj.get("server")
            if not (isinstance(client, dict)
                    and _is_num(client.get("stall_fraction"))):
                errs.append("input_pipeline line missing numeric "
                            "client.stall_fraction")
            if not (isinstance(server, dict)
                    and _is_num(server.get("stall_fraction"))):
                errs.append("input_pipeline line missing numeric "
                            "server.stall_fraction")
            agreement = obj.get("agreement")
            if not (isinstance(agreement, dict)
                    and isinstance(agreement.get("ok"), bool)):
                errs.append("input_pipeline line missing boolean "
                            "agreement.ok")
        elif obj["bench"] == "streaming_dataflow":
            # The claim is "stall stayed bounded WHILE the store
            # churned": both stall views, the agreement verdict, a
            # numeric throughput, and the spill/restore counts that
            # prove churn are all load-bearing — drop any one and the
            # line is an unverified (or unloaded) claim.
            if not any(_is_num(obj.get(k))
                       for k in ("rows_s", "tokens_s")):
                errs.append("streaming_dataflow line missing numeric "
                            "rows_s/tokens_s throughput")
            client = obj.get("client")
            server = obj.get("server")
            if not (isinstance(client, dict)
                    and _is_num(client.get("stall_fraction"))):
                errs.append("streaming_dataflow line missing numeric "
                            "client.stall_fraction")
            if not (isinstance(server, dict)
                    and _is_num(server.get("stall_fraction"))):
                errs.append("streaming_dataflow line missing numeric "
                            "server.stall_fraction")
            agreement = obj.get("agreement")
            if not (isinstance(agreement, dict)
                    and isinstance(agreement.get("ok"), bool)):
                errs.append("streaming_dataflow line missing boolean "
                            "agreement.ok")
            spill = obj.get("spill")
            if not (isinstance(spill, dict)
                    and _is_num(spill.get("spilled_objects"))
                    and _is_num(spill.get("restores"))):
                errs.append("streaming_dataflow line missing numeric "
                            "spill.spilled_objects/restores counts")
        elif obj["bench"] == "fleet_scaling":
            # The claim is "the fleet sizes itself and shrinks without
            # losing goodput": the latency percentiles, the packing
            # efficiency, and the fully cause-attributed scale-down
            # ledger are each load-bearing — a line without them is an
            # unverified autoscaling claim.
            su = obj.get("scale_up_ms")
            if not (isinstance(su, dict) and _is_num(su.get("p50"))
                    and _is_num(su.get("p99"))):
                errs.append("fleet_scaling line missing numeric "
                            "scale_up_ms.p50/p99")
            if not _is_num(obj.get("bin_pack_efficiency")):
                errs.append("fleet_scaling line missing numeric "
                            "bin_pack_efficiency")
            sd = obj.get("scale_down")
            if not (isinstance(sd, dict) and _is_num(sd.get("nodes"))
                    and isinstance(sd.get("causes"), dict)):
                errs.append("fleet_scaling line missing scale_down "
                            "dict with numeric 'nodes' + 'causes' "
                            "attribution")
        elif obj["bench"] == "goodput":
            if not _is_num(obj.get("goodput_pct")):
                errs.append("goodput line missing numeric goodput_pct")
            if not _is_num(obj.get("downtime_s")):
                errs.append("goodput line missing numeric downtime_s")
            if not isinstance(obj.get("by_cause"), dict):
                errs.append("goodput line missing by_cause attribution "
                            "dict")
        elif obj["bench"] == "analyze":
            # The gate line must carry the verdict AND the per-rule
            # breakdown: a bare "analyze ran" claim with no counts is
            # exactly the unreviewable evidence this lint exists to
            # prevent.
            if not isinstance(obj.get("rule_counts"), dict):
                errs.append("analyze line missing rule_counts dict")
            if not _is_num(obj.get("new")):
                errs.append("analyze line missing numeric 'new' "
                            "finding count")
            if not isinstance(obj.get("ok"), bool):
                errs.append("analyze line missing boolean 'ok' gate "
                            "verdict")
            required = {"lock-order", "blocking", "finalizer",
                        "async-lock", "contracts", "retry",
                        "daemon-loop", "timeout-order", "jax-hotpath",
                        "lifecycle"}
            passes = obj.get("passes")
            if not isinstance(passes, list) \
                    or not required <= set(passes):
                missing = sorted(required - set(passes or ()))
                errs.append(f"analyze line missing active pass "
                            f"families {missing} — the gate claim must "
                            f"name every family that ran")
        elif obj["bench"] == "gang_recovery":
            # The MTTR line IS the number: a gang-recovery claim with
            # no reschedule latency is unreviewable.
            if not _is_num(obj.get("pg_reschedule_ms")):
                errs.append("gang_recovery line missing numeric "
                            "pg_reschedule_ms")
            if not isinstance(obj.get("trigger"), str) \
                    or not obj.get("trigger"):
                errs.append("gang_recovery line missing 'trigger' "
                            "(drain | node_death)")
        elif obj["bench"] == "signal_plane":
            # The line's claim is "the history ring answers truthfully
            # and cheaply": the windowed-vs-client agreement verdict,
            # the measured query latency (zero-sleeps, proven not
            # asserted), and the series count are all load-bearing.
            agreement = obj.get("agreement")
            if not (isinstance(agreement, dict)
                    and isinstance(agreement.get("ok"), bool)):
                errs.append("signal_plane line missing boolean "
                            "agreement.ok")
            if not _is_num(obj.get("query_p50_ms")):
                errs.append("signal_plane line missing numeric "
                            "query_p50_ms")
            if not _is_num(obj.get("series")):
                errs.append("signal_plane line missing numeric "
                            "series count")
        elif obj["bench"] == "serve_latency":
            # A serve latency line must carry both views AND the
            # agreement verdict — a client-only (or server-only) number
            # is exactly the uncross-checked claim this bench exists to
            # prevent.
            client = obj.get("client")
            server = obj.get("server")
            if not (isinstance(client, dict)
                    and _is_num(client.get("p50_ms"))
                    and _is_num(client.get("p99_ms"))):
                errs.append("serve_latency line missing numeric "
                            "client.p50_ms/p99_ms")
            if not (isinstance(server, dict)
                    and _is_num(server.get("count"))):
                errs.append("serve_latency line missing server.count")
            agreement = obj.get("agreement")
            if not (isinstance(agreement, dict)
                    and isinstance(agreement.get("ok"), bool)):
                errs.append("serve_latency line missing boolean "
                            "agreement.ok")
    else:
        errs.append("neither a header ('schema') nor a named bench "
                    "('bench')")
    return errs


def check_file(path: str) -> list[str]:
    """All schema violations in an evidence file, as 'line N: why'
    strings ([] = the file passes)."""
    problems: list[str] = []
    with open(path) as f:
        for n, line in enumerate(f, 1):
            if not line.strip():
                problems.append(f"line {n}: blank line")
                continue
            try:
                obj = json.loads(line)
            except ValueError as e:
                problems.append(f"line {n}: invalid JSON ({e})")
                continue
            problems.extend(
                f"line {n}: {err}"
                for err in check_line(obj, allow_header=n == 1))
    return problems


def main(argv: list[str] | None = None) -> int:
    import argparse

    ap = argparse.ArgumentParser(
        description="On-chip benchmark evidence trail tools")
    ap.add_argument("--check", action="store_true",
                    help="validate every line of the evidence file "
                         "against the expected schema; exit 1 on any "
                         "malformed line")
    ap.add_argument("path", nargs="?", default=None,
                    help=f"evidence file (default: committed {FILENAME})")
    args = ap.parse_args(argv)
    if not args.check:
        ap.error("nothing to do (pass --check)")
    path = args.path or default_path()
    try:
        problems = check_file(path)
    except OSError as e:
        print(f"bench_log check: cannot read {path}: {e}")
        return 1
    if problems:
        for p in problems:
            print(f"bench_log check: {p}")
        print(f"bench_log check: FAIL ({len(problems)} problem(s) in "
              f"{path})")
        return 1
    with open(path) as f:
        n_lines = sum(1 for _ in f)
    print(f"bench_log check: OK ({n_lines} line(s) in {path})")
    return 0


def record_gang_recovery(pg_reschedule_ms: float, *,
                         trigger: str = "drain",
                         bundles: int = 0, bundles_lost: int = 0,
                         device: str = "", path: str | None = None,
                         **extra) -> dict:
    """Gang-recovery MTTR evidence (``scripts/drain_bench.py`` gang
    probe): wall milliseconds from a gang bundle losing its node (drain
    initiated / node killed) to the placement group's reservation being
    whole again on healthy nodes — the reschedule coordinator's
    end-to-end latency, the number the elastic-fleet goodput envelope
    stands on. Committed to the evidence trail only on a real
    (accelerator) cluster; returns the entry (with ``committed_to``)
    either way."""
    entry = {
        "bench": "gang_recovery",
        "device": device,
        "trigger": str(trigger),
        "pg_reschedule_ms": round(float(pg_reschedule_ms), 1),
        "bundles": int(bundles),
        "bundles_lost": int(bundles_lost),
    }
    entry.update(extra)
    entry["committed_to"] = record_if_on_chip(dict(entry), path)
    return entry


def record_drain_recovery(proactive_drain_ms: float,
                          crash_detection_ms: float, *,
                          device: str = "", path: str | None = None,
                          **extra) -> dict:
    """Drain-vs-crash actor recovery latency evidence
    (``scripts/drain_bench.py``): how long until an actor lost from a
    departing node is ALIVE on another node, proactive drain vs
    heartbeat-timeout crash detection. Committed to the evidence trail
    only when run on a real (accelerator) cluster; returns the entry
    (with ``committed_to``) either way so callers print the same record
    that lands in the trail."""
    entry = {
        "bench": "drain_recovery_ms",
        "device": device,
        "proactive_drain_ms": round(float(proactive_drain_ms), 1),
        "crash_detection_ms": round(float(crash_detection_ms), 1),
        "speedup": round(
            float(crash_detection_ms) / max(float(proactive_drain_ms),
                                            1e-9), 2),
    }
    entry.update(extra)
    entry["committed_to"] = record_if_on_chip(dict(entry), path)
    return entry


if __name__ == "__main__":
    import sys

    sys.exit(main())
