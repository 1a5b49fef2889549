"""Input-pipeline / training-goodput harness with client/server cross-check.

Drives the full training ingest path — dataset -> ``iter_batches`` /
``iter_device_batches`` -> train-step loop — and REQUIRES the metrics
plane to agree with an independent client-side measurement (the
serve_bench discipline: the telemetry itself is under test, not just
the workload):

* **pipeline**: a consumer loop with a known per-batch cost measures
  its own stall fraction (time starved in ``next()`` vs total loop
  wall); the bench then derives the same number from the
  ``ray_tpu_data_iter_seconds`` histograms and requires exact batch
  counts and agreement within tolerance — disagreement exits non-zero.
* **train**: a real ``DataParallelTrainer`` run whose per-step phase
  histograms (``ray_tpu_train_step_phase_seconds``) must count exactly
  ``workers x steps`` steps, with data_wait / checkpoint phases
  observed.
* **goodput under drain** (``--drain``): a checkpointing trial on a
  multi-node cluster is gracefully drained mid-run (the drain_bench
  scenario composed with the goodput ledger); the trial must finish
  with no error, its goodput %% computed, and the downtime attributed
  to the drain/preemption cause — never unaccounted wall time.

What it checks is machine-independent (counts, phase coverage,
agreement booleans, attribution); the latency and stall numbers it
prints ride along for context and are written nowhere.

Run: python -m ray_tpu.scripts.input_bench
     [--device] [--drain] [--blocks 8] [--batch-size 64]
"""

from __future__ import annotations

import argparse
import json
import sys
import threading
import time


def _obs():
    from ray_tpu.serve import _observability as serve_obs
    from ray_tpu.train import _observability as train_obs

    return serve_obs, train_obs


def _poll_until(fn, deadline_s: float = 20.0, interval: float = 0.25):
    """Re-evaluate ``fn`` until truthy or the deadline; returns the last
    value either way (cluster events ship on a 0.25s cadence)."""
    deadline = time.monotonic() + deadline_s
    val = fn()
    while not val and time.monotonic() < deadline:
        time.sleep(interval)
        val = fn()
    return val


# -- pipeline stage ---------------------------------------------------------


def run_pipeline(n_blocks: int = 8, rows_per_block: int = 256,
                 batch_size: int = 64, consume_ms: float = 3.0,
                 produce_ms: float = 1.0, device: bool = False) -> dict:
    """Dataset -> iterator consumer loop; cross-check the stall
    fraction. Requires an initialized runtime."""
    import numpy as np

    from ray_tpu import data
    from ray_tpu.train import _observability as tob

    serve_obs, _ = _obs()
    before = serve_obs.parse_prometheus(tob.scrape_text())

    n_rows = n_blocks * rows_per_block

    def slow_ident(batch):
        time.sleep(produce_ms / 1e3)
        return batch

    ds = data.from_numpy(
        np.arange(n_rows * 4, dtype=np.float32).reshape(n_rows, 4),
        parallelism=n_blocks,
    ).map_batches(slow_ident, batch_size=rows_per_block)
    # Execute the plan BEFORE the timed loop: stage execution is its
    # own instrument (ray_tpu_data_stage_seconds); the stall fraction
    # is about the steady-state consumer loop, and lumping plan
    # execution into the client's first next() would compare two
    # different quantities.
    ds.materialize()

    # Client-side measurement: wall time inside next() (starved) vs the
    # consumer's own time — measured OUTSIDE the dataset code, so it is
    # an independent view of the same loop the iterator instruments.
    if device:
        # Warm the jax backend BEFORE the timed loop: the first
        # device_put pays platform init, which is startup cost, not
        # input-pipeline stall.
        import jax

        jax.device_put(np.zeros(1)).block_until_ready()

    waits: list = []
    n_batches = 0
    t_loop0 = time.perf_counter()
    if device:
        it = iter(ds.iter_device_batches(batch_size=batch_size,
                                         drop_last=True))
    else:
        it = iter(ds.iter_batches(batch_size=batch_size, drop_last=True))
    while True:
        t0 = time.perf_counter()
        try:
            _batch = next(it)
        except StopIteration:
            waits.append(time.perf_counter() - t0)  # final starved probe
            break
        waits.append(time.perf_counter() - t0)
        n_batches += 1
        time.sleep(consume_ms / 1e3)  # the "train step"
    loop_wall = time.perf_counter() - t_loop0
    client_wait = sum(waits)
    client_stall = client_wait / loop_wall if loop_wall > 0 else 0.0

    expected = n_batches

    def settled():
        parsed = serve_obs.parse_prometheus(tob.scrape_text())
        delta = serve_obs.diff_parsed(before, parsed)
        d = serve_obs.histogram_dist(delta, "ray_tpu_data_iter_seconds",
                                     phase="user")
        return delta if d and d["count"] >= expected else None

    delta = _poll_until(settled) or serve_obs.diff_parsed(
        before, serve_obs.parse_prometheus(tob.scrape_text()))

    wait_d = serve_obs.histogram_dist(delta, "ray_tpu_data_iter_seconds",
                                      phase="wait")
    user_d = serve_obs.histogram_dist(delta, "ray_tpu_data_iter_seconds",
                                      phase="user")
    xfer_d = serve_obs.histogram_dist(delta, "ray_tpu_data_iter_seconds",
                                      phase="transfer")
    occ_d = serve_obs.histogram_dist(delta,
                                     "ray_tpu_data_prefetch_occupancy")
    stage_names = sorted(set(serve_obs.sum_counter(
        delta, "ray_tpu_data_stage_seconds_count", "stage")))
    server_stall = tob.stall_fraction_from(delta)

    # Quantile agreement (serve_bench discipline): the per-batch wait
    # p50 seen by the client must sit within the histogram's bucket
    # resolution of the server's estimate.
    from ray_tpu.util.metrics import percentile

    client_p50_ms = round(percentile(sorted(waits), 0.5) * 1e3, 3) \
        if waits else None
    server_p50 = serve_obs.quantile_from_buckets(wait_d, 0.50)
    server_p50_ms = round(server_p50 * 1e3, 3) \
        if server_p50 is not None else None
    p50_within = False
    if client_p50_ms is not None and server_p50_ms is not None:
        tol_ms = max(
            serve_obs.bucket_width_at(wait_d, client_p50_ms / 1e3) * 1e3,
            0.35 * client_p50_ms, 2.0)
        p50_within = abs(client_p50_ms - server_p50_ms) <= tol_ms

    counts = {
        "wait": int(wait_d["count"]) if wait_d else 0,
        "user": int(user_d["count"]) if user_d else 0,
        "transfer": int(xfer_d["count"]) if xfer_d else 0,
        "occupancy": int(occ_d["count"]) if occ_d else 0,
    }
    agreement = {
        # One extra wait sample is the final starved next() that raised
        # StopIteration client-side; the iterator records waits only for
        # yielded batches, so both views count exactly n_batches.
        "wait_count_exact": counts["wait"] == expected,
        "user_count_exact": counts["user"] == expected,
        "occupancy_sampled": counts["occupancy"] == expected,
        "transfer_count_exact": (not device
                                 or counts["transfer"] == expected),
        "stall_within_tol": (
            server_stall is not None
            and abs(client_stall - server_stall) <= 0.10),
        "server_not_exceeding": (
            wait_d is not None
            and wait_d["sum"] <= client_wait * 1.1 + 0.05),
        "p50_within_tol": p50_within,
        "stage_recorded": any("map_batches" in s for s in stage_names),
    }
    agreement["ok"] = all(agreement.values())
    return {
        "n_batches": expected,
        "batch_size": batch_size,
        "n_blocks": n_blocks,
        "device": device,
        "client": {
            "stall_fraction": round(client_stall, 4),
            "wait_s": round(client_wait, 4),
            "loop_wall_s": round(loop_wall, 4),
            "wait_p50_ms": client_p50_ms,
        },
        "server": {
            "stall_fraction": round(server_stall, 4)
            if server_stall is not None else None,
            "wait_s": round(wait_d["sum"], 4) if wait_d else None,
            "wait_p50_ms": server_p50_ms,
            "counts": counts,
        },
        "stages_recorded": stage_names,
        "agreement": agreement,
    }


# -- train stage ------------------------------------------------------------


def run_train(steps: int = 6, workers: int = 2,
              step_ms: float = 5.0) -> dict:
    """A real trainer run; the per-step phase histograms must count
    exactly workers x steps."""
    import numpy as np

    from ray_tpu import data, train
    from ray_tpu.train import _observability as tob
    from ray_tpu.train import session
    from ray_tpu.train.checkpoint import Checkpoint

    serve_obs, _ = _obs()
    before = serve_obs.parse_prometheus(tob.scrape_text())

    ds = data.from_numpy(
        np.arange(workers * steps * 32, dtype=np.float32).reshape(-1, 1),
        parallelism=workers * 2)

    sleep_s = step_ms / 1e3

    def train_fn(config):
        shard = session.get_dataset_shard("train")
        it = iter(shard.iter_batches(batch_size=16)) \
            if shard is not None else None
        for i in range(config["steps"]):
            if it is not None:
                try:
                    next(it)
                except StopIteration:
                    it = None
            time.sleep(sleep_s)
            ckpt = None
            if session.get_world_rank() == 0:
                ckpt = Checkpoint.from_dict({"step": i})
            session.report({"step": i, "loss": 1.0 / (i + 1)},
                           checkpoint=ckpt)

    trainer = train.DataParallelTrainer(
        train_fn,
        train_loop_config={"steps": steps},
        scaling_config=train.ScalingConfig(num_workers=workers),
        datasets={"train": ds},
    )
    result = trainer.fit()
    if result.error is not None:
        raise RuntimeError(f"train stage failed: {result.error!r}")

    expected = workers * steps

    def settled():
        parsed = serve_obs.parse_prometheus(tob.scrape_text())
        delta = serve_obs.diff_parsed(before, parsed)
        d = serve_obs.histogram_dist(
            delta, "ray_tpu_train_step_phase_seconds",
            trial="train", phase="step")
        return delta if d and d["count"] >= expected else None

    delta = _poll_until(settled) or serve_obs.diff_parsed(
        before, serve_obs.parse_prometheus(tob.scrape_text()))

    phase_counts = {}
    for phase in ("data_wait", "step", "report", "checkpoint_save",
                  "checkpoint_restore"):
        d = serve_obs.histogram_dist(
            delta, "ray_tpu_train_step_phase_seconds",
            trial="train", phase=phase)
        if d:
            phase_counts[phase] = int(d["count"])
    reports = sum(serve_obs.sum_counter(
        delta, "ray_tpu_train_reports_total", "trial",
        trial="train").values())
    agreement = {
        "step_count_exact": phase_counts.get("step") == expected,
        "reports_exact": int(reports) == expected,
        # Every step consumed the shard iterator -> a data_wait sample
        # per step; rank 0 attached a checkpoint per step.
        "data_wait_observed": phase_counts.get("data_wait", 0) > 0,
        "checkpoint_save_counted":
            phase_counts.get("checkpoint_save") == steps,
    }
    agreement["ok"] = all(agreement.values())
    return {
        "workers": workers,
        "steps": steps,
        "phase_counts": phase_counts,
        "phases_observed": sorted(phase_counts),
        "reports": int(reports),
        "client_reports": len(result.metrics_history),
        "goodput": result.goodput,
        "agreement": agreement,
    }


# -- goodput-under-drain stage (drain_bench composed with the ledger) ------


def run_goodput_drain(steps: int = 12, step_ms: float = 250.0) -> dict:
    """Checkpointing trial on a real cluster, gracefully drained
    mid-run: the trial must complete, and every second of downtime must
    be attributed to the drain/preemption cause."""
    import ray_tpu
    from ray_tpu import train
    from ray_tpu.cluster.cluster_utils import Cluster
    from ray_tpu.train import session
    from ray_tpu.train.checkpoint import Checkpoint

    ray_tpu.shutdown()
    cluster = Cluster()
    cluster.add_node(num_cpus=1)   # driver node: survives
    victim = cluster.add_node(num_cpus=4)  # the trial's capacity
    cluster.wait_for_nodes()
    ray_tpu.init(cluster.address)
    sleep_s = step_ms / 1e3

    def train_fn(config):
        start = 0
        ckpt = session.get_checkpoint()
        if ckpt is not None:
            start = ckpt.to_dict().get("step", -1) + 1
        for i in range(start, config["steps"]):
            time.sleep(sleep_s)
            session.report(
                {"step": i},
                checkpoint=Checkpoint.from_dict({"step": i})
                if session.get_world_rank() == 0 else None)

    try:
        trainer = train.DataParallelTrainer(
            train_fn,
            train_loop_config={"steps": steps},
            scaling_config=train.ScalingConfig(num_workers=2),
            run_config=train.RunConfig(
                failure_config=train.FailureConfig(max_failures=3)),
        )

        drained = threading.Event()

        def drain_mid_trial():
            # Let a few steps land, then gracefully drain the node the
            # workers run on (the drain_bench scenario) and add
            # replacement capacity for the elastic restart.
            time.sleep(steps * sleep_s / 3.0)
            try:
                cluster.head.rpc_drain_node(
                    victim.node_id, "input_bench-drain", 5.0)
                if victim in cluster.nodes:
                    cluster.nodes.remove(victim)
                cluster.add_node(num_cpus=4)
                drained.set()
            except Exception:
                pass

        t = threading.Thread(target=drain_mid_trial, daemon=True)
        t.start()
        result = trainer.fit()
        t.join(timeout=60.0)

        goodput = result.goodput or {}
        by_cause = goodput.get("by_cause") or {}
        attributed = sum(by_cause.values())
        downtime = goodput.get("downtime_s", 0.0)
        planned = {c: s for c, s in by_cause.items()
                   if c.startswith(("drain", "preemption"))}
        agreement = {
            "completed_without_error": result.error is None,
            "all_steps_reported": bool(
                result.metrics and
                result.metrics.get("step") == steps - 1),
            "drain_injected": drained.is_set(),
            "downtime_recorded": downtime > 0,
            # Attribution closes the books: the ledger's by_cause sums
            # to the downtime it reports (nothing unaccounted), and the
            # cause is the injected drain, not a generic failure.
            "downtime_fully_attributed":
                abs(attributed - downtime) < 1e-6,
            "attributed_to_drain":
                sum(planned.values()) >= downtime * 0.99 > 0,
        }
        agreement["ok"] = all(agreement.values())
        return {
            "steps": steps,
            "goodput_pct": goodput.get("goodput_pct"),
            "wall_s": goodput.get("wall_s"),
            "downtime_s": downtime,
            "by_cause": by_cause,
            "restarts": goodput.get("restarts"),
            "agreement": agreement,
        }
    finally:
        ray_tpu.shutdown()
        cluster.shutdown()


# -- driver -----------------------------------------------------------------


def run(blocks: int = 8, batch_size: int = 64, device: bool = False,
        drain: bool = False, steps: int = 6, workers: int = 2,
        cluster: bool = False) -> dict:
    import ray_tpu

    ray_tpu.shutdown()
    cluster_obj = None
    if cluster:
        from ray_tpu.cluster.cluster_utils import Cluster

        cluster_obj = Cluster()
        cluster_obj.add_node(num_cpus=8)
        cluster_obj.wait_for_nodes()
        ray_tpu.init(cluster_obj.address)
    else:
        ray_tpu.init(num_cpus=8)
    try:
        pipeline = run_pipeline(n_blocks=blocks, batch_size=batch_size,
                                device=device)
        train_res = run_train(steps=steps, workers=workers)
    finally:
        ray_tpu.shutdown()
        if cluster_obj is not None:
            cluster_obj.shutdown()

    result = {
        "backend": "cluster" if cluster else "local",
        "pipeline": pipeline,
        "train": train_res,
    }
    if drain:
        result["goodput_drain"] = run_goodput_drain()
    result["agreement"] = {
        "pipeline_ok": pipeline["agreement"]["ok"],
        "train_ok": train_res["agreement"]["ok"],
        "goodput_ok": (not drain
                       or result["goodput_drain"]["agreement"]["ok"]),
    }
    result["agreement"]["ok"] = all(result["agreement"].values())
    return result


def main() -> None:
    ap = argparse.ArgumentParser(
        description="Input-pipeline / training-goodput harness with "
                    "client/server stall-fraction cross-check")
    ap.add_argument("--blocks", type=int, default=8)
    ap.add_argument("--batch-size", type=int, default=64)
    ap.add_argument("--steps", type=int, default=6)
    ap.add_argument("--workers", type=int, default=2)
    ap.add_argument("--device", action="store_true",
                    help="drive iter_device_batches (requires jax; "
                         "JAX_PLATFORMS=cpu works)")
    ap.add_argument("--drain", action="store_true",
                    help="add the goodput-under-drain probe (multi-node "
                         "cluster, graceful drain mid-trial)")
    ap.add_argument("--cluster", action="store_true",
                    help="run pipeline+train against a real "
                         "multiprocess cluster backend")
    args = ap.parse_args()

    res = run(blocks=args.blocks, batch_size=args.batch_size,
              device=args.device, drain=args.drain, steps=args.steps,
              workers=args.workers, cluster=args.cluster)

    print(json.dumps(res, indent=1, default=str))
    if not res["agreement"]["ok"]:
        print("input_bench: CLIENT/SERVER DISAGREE — the goodput "
              "metrics are lying; see 'agreement'", file=sys.stderr)
        sys.exit(1)


if __name__ == "__main__":
    main()
