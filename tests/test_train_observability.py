"""Training goodput plane (PR 10): structured DatasetStats v2 with
lineage-correct child stats, iterator stall instrumentation with exact
histogram counts, session-driven per-step phase accounting, the
trainer's downtime ledger, metrics federation with dead-rank gauge
retraction, and the input_bench client/server stall cross-check.

Test order matters (``-p no:randomly`` keeps definition order): the
cluster-federation test tears down the module's local runtime, so it
runs last.
"""

import json
import os
import time

import numpy as np
import pytest

import ray_tpu
from ray_tpu import data, state, train
from ray_tpu.data.dataset import DatasetStats
from ray_tpu.serve import _observability as obs
from ray_tpu.train import _observability as tob
from ray_tpu.train import session
from ray_tpu.train.checkpoint import Checkpoint
from ray_tpu.util import metrics, tracing


@pytest.fixture(autouse=True, scope="module")
def _runtime():
    ray_tpu.shutdown()
    ray_tpu.init(num_cpus=8)
    yield
    ray_tpu.shutdown()


@pytest.fixture(autouse=True)
def _clean_between_tests():
    yield
    tracing.disable()


def _snapshot():
    return obs.parse_prometheus(metrics.prometheus_text())


def _delta_since(before):
    return obs.diff_parsed(before, _snapshot())


# -- DatasetStats v2 --------------------------------------------------------


def test_dataset_stats_structured_keeps_old_string():
    ds = (data.from_items(list(range(200)), parallelism=4)
          .map(lambda x: x + 1)
          .filter(lambda x: x % 2 == 0))
    ds.materialize()
    st = ds.stats()
    assert isinstance(st, DatasetStats)
    # Old contracts: substring membership and str() keep working.
    assert "map+filter" in st
    assert "map+filter" in str(st)
    line = st.summary().splitlines()[0]
    assert line.startswith("stage 0: map+filter") and "4 blocks" in line

    stages = st.lineage()
    assert len(stages) == 1
    sg = stages[0]
    assert sg.name == "map+filter"
    assert sg.n_blocks == 4
    assert len(sg.block_seconds) == 4
    assert sg.rows_total == 100  # evens of range(1, 201)
    assert sg.bytes_total > 0
    assert sg.wall_s > 0
    d = st.to_dict()
    assert d["stages"][0]["rows_total"] == 100
    assert d["stages"][0]["rows_per_s"] > 0


def test_dataset_stats_lineage_isolated_between_siblings():
    base = data.range(64, parallelism=4)
    a = base.map(lambda x: x + 1)
    b = base.map(lambda x: x * 2)
    a.materialize()
    b.materialize()
    # Sibling stage records must not pollute each other (pre-v2 they
    # aliased ONE stats object).
    assert len(a.stats().lineage()) == 1
    assert len(b.stats().lineage()) == 1
    # Re-materializing records nothing new (the plan is cached).
    a.materialize()
    assert len(a.stats().lineage()) == 1

    r = base.repartition(2)
    assert "repartition" in r.stats()
    assert "repartition" not in str(base.stats())

    shards = base.split(2)
    assert shards[0]._stats is not shards[1]._stats
    sh = shards[0].map(lambda x: x).materialize()
    assert "map" in sh.stats()
    assert "map" not in str(shards[1].stats())

    # union lineage covers both branches, diamond root deduped.
    u = a.union(b)
    names = [s.name for s in u.stats().lineage()]
    assert names.count("map") == 2


def test_dataset_stats_bounded_samples_and_stages():
    st = DatasetStats()
    st.record("big", 0.5, 1000,
              blocks=[(0.001, 2, 16)] * 1000)
    sg = st.stages[0]
    assert sg.n_blocks == 1000
    assert len(sg.block_seconds) == DatasetStats.MAX_BLOCK_SAMPLES
    assert sg.rows_total == 2000  # totals exact despite sampling
    for i in range(DatasetStats.MAX_STAGES + 10):
        st.record(f"s{i}", 0.001, 1)
    assert len(st.stages) <= DatasetStats.MAX_STAGES
    assert "dropped" in st.summary()


# -- iterator instrumentation ----------------------------------------------


def test_iter_batches_stall_metrics_exact_counts():
    before = _snapshot()
    ds = data.from_numpy(
        np.arange(512, dtype=np.float32).reshape(-1, 1), parallelism=4)
    n = 0
    for _b in ds.iter_batches(batch_size=32, drop_last=True):
        n += 1
        time.sleep(0.002)
    assert n == 16
    delta = _delta_since(before)
    for phase in ("wait", "user"):
        d = obs.histogram_dist(delta, "ray_tpu_data_iter_seconds",
                               phase=phase)
        assert d and int(d["count"]) == n, (phase, d)
    occ = obs.histogram_dist(delta, "ray_tpu_data_prefetch_occupancy")
    assert occ and int(occ["count"]) == n
    sf = tob.stall_fraction_from(delta)
    assert sf is not None and 0.0 <= sf < 1.0
    # The consumer slept 2ms/batch: user time dominates, so the loop
    # must not read as mostly starved.
    assert sf < 0.9

    ds_stats = state.data_stats()
    assert "iterator" in ds_stats and "stall_fraction" in ds_stats
    assert ds_stats["iterator"]["wait"]["count"] >= n


def test_iter_batches_stats_object_records_iteration():
    ds = data.range(128, parallelism=2)
    list(ds.iter_batches(batch_size=64))
    st = ds.stats()
    it = st.iterations[-1]
    assert it.batches == 2
    assert it.wait_s >= 0 and it.user_s >= 0
    assert 0.0 <= it.stall_fraction <= 1.0
    assert "stall" in st.summary()


def test_iter_device_batches_transfer_metrics():
    jax = pytest.importorskip("jax")
    before = _snapshot()
    ds = data.from_numpy(
        np.arange(256, dtype=np.float32).reshape(-1, 1), parallelism=2)
    n = 0
    for b in ds.iter_device_batches(batch_size=64, drop_last=True):
        arr = b["data"] if isinstance(b, dict) else b
        assert isinstance(arr, jax.Array)
        n += 1
    assert n == 4
    delta = _delta_since(before)
    d = obs.histogram_dist(delta, "ray_tpu_data_iter_seconds",
                           phase="transfer")
    assert d and int(d["count"]) == n


def test_data_stage_metrics_recorded():
    before = _snapshot()
    ds = data.range(100, parallelism=4).map(lambda x: x + 1)
    ds.materialize()
    delta = _delta_since(before)
    d = obs.histogram_dist(delta, "ray_tpu_data_stage_seconds",
                           stage="map")
    assert d and int(d["count"]) == 1
    blk = obs.histogram_dist(delta, "ray_tpu_data_block_seconds",
                             stage="map")
    assert blk and int(blk["count"]) == 4
    rows = obs.histogram_dist(delta, "ray_tpu_data_block_rows",
                              stage="map")
    assert rows and int(rows["sum"]) == 100
    st = state.data_stats()
    assert "map" in st["stages"]


# -- session-driven step phases --------------------------------------------


def _run_small_trainer(steps=3, workers=2, with_data=True,
                       fail_first_attempt_flag=None):
    ds = data.from_numpy(
        np.arange(workers * steps * 32, dtype=np.float32).reshape(-1, 1),
        parallelism=workers * 2)

    def train_fn(config):
        if fail_first_attempt_flag is not None \
                and not os.path.exists(fail_first_attempt_flag):
            with open(fail_first_attempt_flag, "w") as f:
                f.write("attempted")
            raise RuntimeError("injected first-attempt failure")
        shard = session.get_dataset_shard("train")
        it = iter(shard.iter_batches(batch_size=16)) if shard else None
        for i in range(config["steps"]):
            if it is not None:
                try:
                    next(it)
                except StopIteration:
                    it = None
            time.sleep(0.005)
            ckpt = None
            if session.get_world_rank() == 0:
                ckpt = Checkpoint.from_dict({"step": i})
            session.report({"step": i}, checkpoint=ckpt)

    trainer = train.DataParallelTrainer(
        train_fn,
        train_loop_config={"steps": steps},
        scaling_config=train.ScalingConfig(num_workers=workers),
        run_config=train.RunConfig(
            failure_config=train.FailureConfig(max_failures=2)),
        datasets={"train": ds} if with_data else None,
    )
    return trainer.fit()


def test_session_step_phases_exact_counts():
    before = _snapshot()
    result = _run_small_trainer(steps=3, workers=2)
    assert result.error is None
    delta = _delta_since(before)
    step = obs.histogram_dist(delta, "ray_tpu_train_step_phase_seconds",
                              trial="train", phase="step")
    assert step and int(step["count"]) == 6
    dwait = obs.histogram_dist(delta, "ray_tpu_train_step_phase_seconds",
                               trial="train", phase="data_wait")
    assert dwait and int(dwait["count"]) == 6
    save = obs.histogram_dist(delta, "ray_tpu_train_step_phase_seconds",
                              trial="train", phase="checkpoint_save")
    assert save and int(save["count"]) == 3  # rank 0 only
    rep = obs.histogram_dist(delta, "ray_tpu_train_step_phase_seconds",
                             trial="train", phase="report")
    assert rep and int(rep["count"]) == 3  # the other rank
    reports = sum(obs.sum_counter(
        delta, "ray_tpu_train_reports_total", "trial",
        trial="train").values())
    assert int(reports) == 6
    # Straggler gauge: per-rank children live only while the trial
    # runs — fit() retracts them at session stop (round-19 LC001
    # discipline; the cluster backend's agent sweep covers worker
    # death), so a finished trial leaves no stale rank series.
    parsed = _snapshot()
    ranks = {dict(labels).get("rank")
             for labels in (parsed.get(
                 "ray_tpu_train_rank_step_seconds") or {})
             if dict(labels).get("trial") == "train"}
    assert ranks == set()

    # Goodput: clean run => no downtime, 100%.
    assert result.goodput is not None
    assert result.goodput["downtime_s"] == 0
    assert result.goodput["goodput_pct"] == 100.0
    assert result.goodput["wall_s"] > 0

    ts = state.train_stats()
    entry = ts["trials"]["train"]
    assert entry["reports"] >= 6
    assert "step" in entry["phases"]
    # rank_step_s is derived from the per-rank gauges retracted above,
    # so a finished trial no longer carries it.
    assert "rank_step_s" not in entry


def test_checkpoint_restore_phase_observed():
    before = _snapshot()

    def train_fn(config):
        ckpt = session.get_checkpoint()
        start = 0
        if ckpt is not None:
            start = ckpt.to_dict()["step"] + 1  # timed as restore
        for i in range(start, 2):
            session.report({"step": i})

    trainer = train.DataParallelTrainer(
        train_fn,
        scaling_config=train.ScalingConfig(num_workers=1),
        resume_from_checkpoint=Checkpoint.from_dict({"step": 0}),
    )
    result = trainer.fit()
    assert result.error is None
    delta = _delta_since(before)
    d = obs.histogram_dist(delta, "ray_tpu_train_step_phase_seconds",
                           trial="train", phase="checkpoint_restore")
    assert d and int(d["count"]) == 1


def test_goodput_ledger_attributes_failure(tmp_path):
    flag = str(tmp_path / "attempted")
    before = _snapshot()
    result = _run_small_trainer(steps=2, workers=1,
                                fail_first_attempt_flag=flag)
    assert result.error is None
    gp = result.goodput
    assert gp is not None
    assert gp["restarts"] == 1
    assert gp["downtime_s"] > 0
    assert gp["by_cause"].get("failure", 0) == pytest.approx(
        gp["downtime_s"])
    assert gp["goodput_pct"] < 100.0
    # The ledger's downtime also lands on the metrics plane.
    delta = _delta_since(before)
    down = obs.sum_counter(delta, "ray_tpu_train_downtime_seconds_total",
                           "cause", trial="train")
    assert down.get("failure", 0) > 0
    ts = state.train_stats()
    assert ts["trials"]["train"]["downtime_s"]["failure"] > 0


# -- surfaces ---------------------------------------------------------------


def test_cli_data_and_train_stats(capsys):
    from ray_tpu.scripts import cli

    cli.main(["data", "stats"])
    out = capsys.readouterr().out
    assert "stall" in out.lower()

    cli.main(["train", "stats"])
    out = capsys.readouterr().out
    assert "train" in out

    cli.main(["data", "stats", "--json"])
    parsed = json.loads(capsys.readouterr().out)
    assert "stages" in parsed

    cli.main(["train", "stats", "--json"])
    parsed = json.loads(capsys.readouterr().out)
    assert "trials" in parsed


def test_grafana_dashboard_has_goodput_panels():
    from ray_tpu.util.grafana import generate_dashboard

    titles = [p["title"] for p in generate_dashboard()["panels"]]
    for family in ("ray_tpu_data_iter_seconds",
                   "ray_tpu_data_stage_seconds",
                   "ray_tpu_train_step_phase_seconds",
                   "ray_tpu_train_rank_step_seconds",
                   "ray_tpu_train_downtime_seconds_total"):
        assert any(family in t for t in titles), family


def test_timeline_contains_data_and_train_spans():
    tracing.enable()
    data.range(32, parallelism=2).map(lambda x: x).materialize()
    result = _run_small_trainer(steps=1, workers=1, with_data=False)
    assert result.error is None
    events = state.timeline()
    cats = {e.get("cat") for e in events}
    assert "data" in cats
    assert "train" in cats


# -- evidence lint ----------------------------------------------------------


# -- cluster backend: federation + dead-rank retraction ---------------------


def test_cluster_federation_and_rank_gauge_retraction():
    """Cluster backend: goodput observations ship over the worker-events
    plane into the agent registry, federate on ONE /metrics/cluster
    scrape, and a finished trial's per-rank gauges are retracted when
    its workers die."""
    from ray_tpu.cluster.cluster_utils import Cluster
    from ray_tpu.cluster.gcs_client import GcsClient

    ray_tpu.shutdown()
    c = Cluster()
    c.add_node(num_cpus=8)
    c.wait_for_nodes()
    ray_tpu.init(c.address)
    gcs = GcsClient(c.address)
    try:
        result = _run_small_trainer(steps=2, workers=2)
        assert result.error is None

        deadline = time.monotonic() + 30
        dist = None
        parsed = {}
        while time.monotonic() < deadline:
            parsed = obs.parse_prometheus(gcs.metrics.cluster_text())
            dist = obs.histogram_dist(
                parsed, "ray_tpu_train_step_phase_seconds",
                trial="train", phase="step")
            if dist and dist["count"] >= 4:
                break
            time.sleep(0.5)
        assert dist and dist["count"] >= 4
        # Iterator instrumentation from inside the workers federates too.
        assert obs.histogram_dist(parsed, "ray_tpu_data_iter_seconds",
                                  phase="wait")
        # state readers see the federated plane from the driver.
        assert state.train_stats()["trials"]["train"]["reports"] >= 4

        def rank_series(p):
            # The in-process Cluster shares this pytest process's
            # registry, so earlier LOCAL-backend tests' node_id="local"
            # children show in the federated body too; the agent owns
            # (and must retract) only its own node's series.
            return [labels for labels in
                    (p.get("ray_tpu_train_rank_step_seconds") or {})
                    if dict(labels).get("trial") == "train"
                    and dict(labels).get("node_id") != "local"]

        # The workers are killed at group shutdown; the agent must
        # retract their rank gauges from the federated scrape.
        deadline = time.monotonic() + 60
        leftover = rank_series(parsed)
        while time.monotonic() < deadline:
            parsed = obs.parse_prometheus(gcs.metrics.cluster_text())
            leftover = rank_series(parsed)
            if not leftover:
                break
            time.sleep(1.0)
        assert not leftover, f"dead rank series survived: {leftover}"
    finally:
        gcs.close()
        ray_tpu.shutdown()
        c.shutdown()


@pytest.mark.slow
def test_input_bench_smoke_slow():
    """Standing harness gate: the full input_bench shape — pipeline
    stall cross-check, exact train phase counts, goodput-under-drain
    with cause attribution — runs end to end and agrees."""
    from ray_tpu.scripts import input_bench

    res = input_bench.run(blocks=4, batch_size=32, steps=3, workers=2,
                          drain=True)
    assert res["agreement"]["ok"], res["agreement"]
    gd = res["goodput_drain"]
    assert gd["agreement"]["attributed_to_drain"], gd
