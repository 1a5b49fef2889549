"""Step anatomy plane (round 19): exact per-rank step decomposition in
seconds, head-side straggler attribution, the ``timing`` (TH) analyze
family, and the gauge-retraction discipline for the per-rank family.

Test order matters (``-p no:randomly`` keeps definition order): the
cluster-federation test tears down the module's local runtime, so it
runs last.
"""

import ast
import os
import queue
import time

import pytest

import ray_tpu
from ray_tpu import state, train
from ray_tpu.serve import _observability as obs
from ray_tpu.train import _observability as tob
from ray_tpu.train import session
from ray_tpu.util import metrics


@pytest.fixture(autouse=True, scope="module")
def _runtime():
    ray_tpu.shutdown()
    ray_tpu.init(num_cpus=8)
    yield
    ray_tpu.shutdown()


def _snapshot():
    return obs.parse_prometheus(metrics.prometheus_text())


# -- session: exact partition -----------------------------------------------


def test_anatomy_phases_partition_step_wall_exactly():
    tob.drain_events()
    session.init_session(
        world_rank=0, world_size=1, local_rank=0, node_rank=0,
        results_queue=queue.Queue(), checkpoint=None,
        dataset_shards=None, trial_info={"trial_id": "anat-t"})
    try:
        for _ in range(3):
            session.add_data_wait(0.002)
            time.sleep(0.002)
            session.timed_step(time.sleep, 0.003)
            session.report({})
    finally:
        session.shutdown_session()
    events = tob.drain_events()
    walls = [ev["p"].get("data_wait", 0.0) + ev["p"]["step"]
             for ev in events
             if ev.get("k") == "step" and ev.get("t") == "anat-t"]
    anats = [ev for ev in events
             if ev.get("k") == "anat" and ev.get("t") == "anat-t"]
    assert len(anats) == 3 and len(walls) == 3
    for ev, wall in zip(anats, walls):
        assert set(ev["p"]) == {"data_wait", "host", "compute", "sync"}
        assert sum(ev["p"].values()) == pytest.approx(wall, abs=1e-9)
        assert set(ev) == {"k", "t", "r", "p"}  # seconds, nothing else
    tob.retract_trial("anat-t")


def test_plain_train_fn_emits_no_anatomy():
    tob.drain_events()
    session.init_session(
        world_rank=0, world_size=1, local_rank=0, node_rank=0,
        results_queue=queue.Queue(), checkpoint=None,
        dataset_shards=None, trial_info={"trial_id": "plain-t"})
    try:
        time.sleep(0.002)
        session.report({})
    finally:
        session.shutdown_session()
    kinds = {ev.get("k") for ev in tob.drain_events()}
    assert "anat" not in kinds  # uninstrumented steps stay classic
    tob.retract_trial("plain-t")


# -- straggler attribution ---------------------------------------------------


def test_straggler_attribution_classifies_causes():
    base = {"data_wait": 0.01, "host": 0.02, "compute": 0.1,
            "sync": 0.05}
    slow_compute = dict(base, compute=0.3, sync=0.0)
    v = tob.straggler_attribution(
        {0: base, 1: slow_compute, 2: dict(base)})
    assert v["rank"] == 1 and v["cause"] == "compute-bound"
    assert v["phase"] == "compute"
    assert v["excess_s"] == pytest.approx(0.2, abs=1e-6)

    slow_input = dict(base, data_wait=0.25, sync=0.0)
    v = tob.straggler_attribution({0: base, 1: slow_input})
    assert v["rank"] == 1 and v["cause"] == "input-bound"

    # Balanced gang: nobody named, no phase blamed.
    v = tob.straggler_attribution({0: base, 1: dict(base)})
    assert v["cause"] == "balanced" and "phase" not in v
    # A single rank has no gang to lag behind.
    assert tob.straggler_attribution({0: base}) is None
    assert tob.straggler_attribution({}) is None


def test_seeded_straggler_attributed_through_local_trainer():
    def train_fn(config):
        rank = session.get_world_rank()
        for _ in range(2):
            slow = 0.04 if rank == 1 else 0.0
            session.timed_step(time.sleep, 0.005 + slow)
            session.report({})

    tob.drain_events()
    trainer = train.DataParallelTrainer(
        train_fn,
        scaling_config=train.ScalingConfig(num_workers=2),
    )
    result = trainer.fit()
    assert result.error is None
    rank_phases = {}
    for ev in tob.drain_events():
        if ev.get("k") != "anat":
            continue
        acc = rank_phases.setdefault(ev["r"], {})
        for p, s in ev["p"].items():
            acc[p] = acc.get(p, 0.0) + s
    v = tob.straggler_attribution(rank_phases)
    assert v is not None
    assert v["rank"] == 1 and v["cause"] == "compute-bound"

    # Session-stop discipline (LC001): fit()'s finally retracted the
    # trial's per-rank gauges from the local registry.
    parsed = _snapshot()
    for fam in ("ray_tpu_step_phase_seconds",
                "ray_tpu_train_rank_step_seconds"):
        leftover = [dict(lb) for lb in (parsed.get(fam) or {})
                    if dict(lb).get("trial") == "train"]
        assert not leftover, (fam, leftover)


def test_retract_trial_clears_anatomy_gauges():
    tob.record_anatomy("rt-t", 0, {"data_wait": 0.01, "host": 0.01,
                                   "compute": 0.05, "sync": 0.0})
    tob.record_step("rt-t", 0, {"step": 0.07})
    parsed = _snapshot()
    assert any(dict(lb).get("trial") == "rt-t"
               for lb in parsed.get("ray_tpu_step_phase_seconds") or {})
    assert any(dict(lb).get("trial") == "rt-t"
               for lb in parsed.get("ray_tpu_train_rank_step_seconds")
               or {})
    tob.retract_trial("rt-t")
    parsed = _snapshot()
    for fam in ("ray_tpu_step_phase_seconds",
                "ray_tpu_train_rank_step_seconds"):
        assert not any(dict(lb).get("trial") == "rt-t"
                       for lb in parsed.get(fam) or {}), fam
    tob.drain_events()


def test_train_stats_carries_anatomy_and_straggler():
    tob.record_anatomy("ts-t", 0, {"data_wait": 0.01, "host": 0.01,
                                   "compute": 0.05, "sync": 0.05})
    tob.record_anatomy("ts-t", 1, {"data_wait": 0.01, "host": 0.01,
                                   "compute": 0.11, "sync": 0.0})
    try:
        entry = state.train_stats()["trials"]["ts-t"]
        anat = entry["anatomy"]
        assert set(anat["ranks"]) == {"0", "1"}
        assert set(anat) == {"ranks", "straggler"}
        assert anat["ranks"]["1"]["compute"] == pytest.approx(0.11)
        assert anat["straggler"]["rank"] == "1"
        assert anat["straggler"]["cause"] == "compute-bound"
    finally:
        tob.retract_trial("ts-t")
        tob.drain_events()


def test_timing_family_is_a_registered_pass():
    from ray_tpu.util import analyze as _analyze

    assert "timing" in _analyze.PASSES


# -- timing-honesty analyze family (TH) -------------------------------------


def _th_findings(src):
    from ray_tpu.util.analyze.core import PASSES, ParsedModule

    mod = ParsedModule("x.py", "x.py", src, ast.parse(src))
    return PASSES["timing"](mod)


def test_timing_pass_flags_unsynced_wall_and_stale_marker():
    src = (
        "import time\n"
        "\n"
        "def unsynced(step_fn, batch):  # step-timed\n"
        "    t0 = time.perf_counter()\n"
        "    for _ in range(10):\n"
        "        out = step_fn(batch)\n"
        "    return time.perf_counter() - t0\n"
        "\n"
        "def stale():  # step-timed\n"
        "    return 1\n"
    )
    rules = {f.rule for f in _th_findings(src)}
    assert rules == {"TH001", "TH002"}


def test_timing_pass_accepts_synced_walls():
    src = (
        "import time\n"
        "import jax\n"
        "import numpy as np\n"
        "\n"
        "def blocked(step_fn, batch):  # step-timed\n"
        "    t0 = time.perf_counter()\n"
        "    out = step_fn(batch)\n"
        "    jax.block_until_ready(out)\n"
        "    return time.perf_counter() - t0\n"
        "\n"
        "def floated(step_fn, batch):  # step-timed\n"
        "    t0 = time.perf_counter()\n"
        "    loss = step_fn(batch)\n"
        "    v = float(loss)\n"
        "    return time.perf_counter() - t0, v\n"
        "\n"
        "def helper_sync(step_fn, batch):  # step-timed\n"
        "    t0 = time.perf_counter()\n"
        "    out = step_fn(batch)\n"
        "    host = time.perf_counter() - t0\n"
        "    _block_sync(out)\n"
        "    return host, time.perf_counter() - t0\n"
        "\n"
        "def unmarked_untimed(step_fn, batch):\n"
        "    t0 = time.perf_counter()\n"
        "    return step_fn(batch), time.perf_counter() - t0\n"
    )
    assert _th_findings(src) == []


def test_timing_pass_repo_instrumented_regions_clean():
    """The live `# step-timed` regions (session.timed_step, the engine
    step) must satisfy their own pass."""
    from ray_tpu.util.analyze.core import PASSES, ParsedModule

    root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    marked = []
    for rel in ("ray_tpu/train/session.py",
                "ray_tpu/serve/llm_engine.py"):
        path = os.path.join(root, rel)
        src = open(path).read()
        if "# step-timed" in src:
            marked.append(rel)
            mod = ParsedModule(path, rel, src, ast.parse(src))
            assert PASSES["timing"](mod) == [], rel
    assert len(marked) == 2  # the annotations exist and stay


# -- named signals + grafana -------------------------------------------------


def test_named_signals_parse_with_percent_semantics():
    from ray_tpu.cluster.signals import parse_slo

    s = parse_slo("gauge_avg(ray_tpu_worker_cpu_percent) > 90% over 120s")
    # Percent against a *_percent family stays in gauge units (90, not
    # 0.9) — the threshold the grammar promises.
    assert s["threshold"] == pytest.approx(90.0)
    assert s["signal"][0] == "gauge_avg"
    assert s["window_s"] == 120.0
    with pytest.raises(ValueError):
        parse_slo('mfu{trial="x"} < 40% over 120s')  # no such signal
    assert parse_slo("sync_ratio < 25% over 60s")["threshold"] == \
        pytest.approx(0.25)
    assert parse_slo("step_p99 < 500ms")["threshold"] == \
        pytest.approx(0.5)


def test_signal_plane_evaluates_sync_ratio():
    from ray_tpu.cluster.signals import SignalPlane

    plane = SignalPlane(history_s=600.0, scrape_interval_s=1.0,
                        burn_evals=1)

    def lbl(**kv):
        return tuple(sorted(kv.items()))

    for t in range(5):
        plane.ring.ingest(float(t), {
            "ray_tpu_step_phase_seconds": {
                lbl(node_id="n", trial="x", phase="sync",
                    rank="0"): 0.03,
                lbl(node_id="n", trial="x", phase="compute",
                    rank="0"): 0.07,
            },
        })
    plane.register_slo("sync-share", "sync_ratio < 20% over 60s")
    plane.evaluate_slos(5.0)
    st = plane.slo_status()["slos"]
    assert st["sync-share"]["value"] == pytest.approx(0.3)
    assert st["sync-share"]["state"] == "burning"


def test_grafana_registry_covers_new_families():
    from ray_tpu.util.grafana import generate_dashboard

    titles = [p["title"] for p in generate_dashboard()["panels"]]
    assert any("ray_tpu_step_phase_seconds" in t for t in titles)
    assert not any("mfu" in t.lower() for t in titles)


# -- cluster backend: anatomy federation + dead-rank retraction --------------


def test_cluster_anatomy_federates_and_retracts_on_worker_death():
    """Cluster backend: anat events ship over the worker-events plane,
    the agent's replay exports the per-rank phase gauges on the
    federated scrape, and a dead worker's series are retracted by the
    agent's sweep (the family rides the same gauge_keys ledger as
    rank_step)."""
    from ray_tpu.cluster.cluster_utils import Cluster
    from ray_tpu.cluster.gcs_client import GcsClient

    ray_tpu.shutdown()
    c = Cluster()
    c.add_node(num_cpus=8)
    c.wait_for_nodes()
    ray_tpu.init(c.address)
    gcs = GcsClient(c.address)
    try:
        def train_fn(config):
            for _ in range(120):
                session.timed_step(time.sleep, 0.05)
                session.report({})
                # In-process Cluster: every rank shares the test's
                # filesystem, so the stop file reaches them all.
                if os.path.exists(config["stop_file"]):
                    break

        import tempfile
        import threading

        stop_file = os.path.join(tempfile.mkdtemp(), "stop")
        trainer = train.DataParallelTrainer(
            train_fn,
            train_loop_config={"stop_file": stop_file},
            scaling_config=train.ScalingConfig(num_workers=2),
        )
        box = {}
        th = threading.Thread(
            target=lambda: box.update(result=trainer.fit()))
        th.start()

        def anat_series(p):
            # Earlier LOCAL-backend tests share this pytest process's
            # registry; the agent owns only its own node's series.
            return [dict(lb) for lb in
                    (p.get("ray_tpu_step_phase_seconds") or {})
                    if dict(lb).get("trial") == "train"
                    and dict(lb).get("node_id") != "local"]

        # The gauges federate while the gang is training — the agent
        # replays the workers' shipped anat events live...
        try:
            deadline = time.monotonic() + 60
            seen = []
            while time.monotonic() < deadline:
                parsed = obs.parse_prometheus(
                    gcs.metrics.cluster_text())
                seen = anat_series(parsed)
                if {lb.get("rank") for lb in seen} >= {"0", "1"}:
                    break
                time.sleep(0.5)
            assert {lb.get("rank") for lb in seen} >= {"0", "1"}, seen
            assert any("phase" in lb for lb in seen)
        finally:
            open(stop_file, "w").close()
            th.join(timeout=120)
        assert not th.is_alive()
        assert box["result"].error is None

        # ...then the group shutdown kills the workers and the agent
        # sweep must retract every one of them.
        deadline = time.monotonic() + 60
        leftover = seen
        while time.monotonic() < deadline:
            parsed = obs.parse_prometheus(gcs.metrics.cluster_text())
            leftover = anat_series(parsed)
            if not leftover:
                break
            time.sleep(1.0)
        assert not leftover, f"dead rank anatomy survived: {leftover}"
    finally:
        gcs.close()
        ray_tpu.shutdown()
        c.shutdown()
