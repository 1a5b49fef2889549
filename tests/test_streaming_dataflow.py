"""Memory-safe streaming dataflow (round 14): dynamic block splitting,
autoscaling actor pools, remote spill with restore-from-URI recovery,
and the stale-shm sweeper.

The acceptance claims under test:

* a dataset whose blocks exceed store capacity completes end-to-end via
  split+spill (no OOM kill / StoreFullError);
* a node death mid-pipeline restores its spilled objects from the spill
  URI — NOT by recomputing them (the creating task's side effect runs
  exactly once);
* an ``ActorPoolStrategy(min, max)`` pool observably grows under queue
  depth and shrinks back on idle, on both the direct pool API and the
  ``map_batches`` stats surface.
"""

import gc
import os
import subprocess
import sys
import time

import numpy as np
import pytest

import ray_tpu
from ray_tpu.core.config import config
from ray_tpu.data import block as B


def wait_for(cond, timeout=30.0, msg="condition"):
    deadline = time.monotonic() + timeout
    while time.monotonic() < deadline:
        if cond():
            return
        time.sleep(0.05)
    raise TimeoutError(f"timed out waiting for {msg}")


# -- dynamic block splitting (pure block layer) ----------------------------


def test_split_block_passthrough_and_split():
    arr = {"x": np.zeros((1024, 8), np.float32)}  # 32 KiB
    # At/under target (or disabled): identity, no copies.
    assert B.split_block(arr, 1 << 20) == [arr]
    assert B.split_block(arr, 0) == [arr]
    parts = B.split_block(arr, 8 << 10)  # 32 KiB / 8 KiB -> 4 pieces
    assert len(parts) == 4
    assert all(B.size_bytes(p) <= (8 << 10) + 512 for p in parts)
    merged = B.concat_blocks(parts)
    assert np.array_equal(merged["x"], arr["x"])


def test_split_block_single_row_never_splits():
    one = {"x": np.zeros((1, 65536), np.float32)}  # one fat row
    assert B.split_block(one, 1024) == [one]


def test_split_block_list_blocks():
    rows = list(range(100))
    parts = B.split_block(rows, B.size_bytes(rows) // 4)
    assert len(parts) >= 2
    assert [r for p in parts for r in p] == rows


# -- spill storage backends ------------------------------------------------


def test_file_spill_backend_roundtrip(tmp_path):
    from ray_tpu.cluster import spill_storage

    be = spill_storage.backend_for(f"file://{tmp_path}/spill")
    assert be.remote
    meta, data = b"meta-bytes", os.urandom(4096)
    n = be.write("oid1", meta, data)
    assert n == 8 + len(meta) + len(data)
    assert be.read("oid1") == (meta, data)
    assert be.read_range("oid1", 100, 16) == data[100:116]
    assert be.stats() == {"objects": 1, "bytes": n}
    assert be.read("missing") is None
    assert be.delete("oid1") and not be.delete("oid1")
    assert be.stats() == {"objects": 0, "bytes": 0}


def test_spill_uri_scheme_registry(tmp_path):
    from ray_tpu.cluster import spill_storage

    with pytest.raises(ValueError, match="no registered backend"):
        spill_storage.backend_for("s3-not-registered://bucket/x")
    with pytest.raises(ValueError, match="not a .*URI"):
        spill_storage.backend_for("/just/a/path")
    with pytest.raises(ValueError, match="absolute"):
        spill_storage.backend_for("file://relative/dir")

    class _Mem(spill_storage.SpillBackend):
        remote = True

        def __init__(self, uri):
            self.uri = uri
            self.objs = {}

        def write(self, oid, meta, data):
            self.objs[oid] = (meta, data)
            return len(meta) + len(data)

        def read(self, oid):
            return self.objs.get(oid)

    spill_storage.register_scheme("memtest", _Mem)
    try:
        be = spill_storage.backend_for("memtest://pool")
        be.write("a", b"m", b"d")
        assert be.read("a") == (b"m", b"d")
        assert "memtest" in spill_storage.registered_schemes()
    finally:
        spill_storage._SCHEMES.pop("memtest", None)


# -- stale-shm sweeper -----------------------------------------------------


def test_shm_sweep_removes_only_dead_owners(tmp_path):
    from ray_tpu.util.shm_sweep import sweep_stale_shm

    # A pid that is certainly dead: a subprocess we already reaped.
    proc = subprocess.Popen([sys.executable, "-c", "pass"])
    proc.wait()
    dead_pid = proc.pid
    (tmp_path / f"ray_tpu_s{dead_pid}_deadbeef").write_bytes(b"x" * 1024)
    (tmp_path / f"ray_tpu_c{dead_pid}_ab_cdef").write_bytes(b"y" * 2048)
    (tmp_path / f"ray_tpu_s{os.getpid()}_alive").write_bytes(b"z")
    (tmp_path / "ray_tpu_nopid_name").write_bytes(b"k")
    (tmp_path / "unrelated_segment").write_bytes(b"u")

    removed, freed = sweep_stale_shm(str(tmp_path))
    assert removed == 2 and freed == 3072
    left = sorted(p.name for p in tmp_path.iterdir())
    assert left == sorted([
        f"ray_tpu_s{os.getpid()}_alive", "ray_tpu_nopid_name",
        "unrelated_segment",
    ])
    # Idempotent: a second sweep finds nothing.
    assert sweep_stale_shm(str(tmp_path)) == (0, 0)


def test_shm_sweep_missing_dir_is_noop(tmp_path):
    from ray_tpu.util.shm_sweep import sweep_stale_shm

    assert sweep_stale_shm(str(tmp_path / "nope")) == (0, 0)


# -- autoscaling actor pool (local backend) --------------------------------


@pytest.fixture(scope="module")
def local_runtime():
    ray_tpu.shutdown()
    ray_tpu.init(num_cpus=8)
    yield
    ray_tpu.shutdown()


def test_autoscaling_pool_grows_and_shrinks(local_runtime):
    from ray_tpu.util.actor_pool import AutoscalingActorPool

    @ray_tpu.remote
    class Worker:
        def work(self, x):
            time.sleep(0.02)
            return x * 2

    pool = AutoscalingActorPool(
        Worker.remote, min_size=1, max_size=3,
        scale_up_queue_depth=1, name="t-pool")
    assert pool.size == 1
    for i in range(8):
        pool.submit(lambda a, v: a.work.remote(v), i)
    out = []
    while pool.has_next():
        out.append(ray_tpu.get(pool.get_next_ref()))
    assert out == [i * 2 for i in range(8)]  # submission order held
    assert pool.peak_size == 3  # grew to max under the backlog
    downs = [s for d, s in pool.scale_events if d == "down"]
    assert downs and downs[-1] == 1  # drained back to min on idle
    pool.shutdown()
    assert pool.size == 0


def test_map_batches_pool_stats_expose_scaling(local_runtime):
    from ray_tpu import data as rtd

    ds = rtd.range(64, parallelism=16).map_batches(
        lambda b: np.asarray(b) + 1,
        compute=rtd.ActorPoolStrategy(
            min_size=1, max_size=4, scale_up_queue_depth=1),
    )
    assert sorted(ds.take_all()) == list(range(1, 65))
    stage = next(s for s in ds.stats().lineage()
                 if s.name == "map_batches(actors)")
    assert stage.extra["pool_peak"] > 1
    assert stage.extra["pool_scale_ups"] >= 1
    assert stage.extra["pool_scale_downs"] >= 1
    # The stats surface prints the shape facts.
    assert "pool_peak" in ds.stats().summary()


def test_pool_scale_failpoint_vetoes_but_completes(local_runtime):
    from ray_tpu import data as rtd
    from ray_tpu.util import failpoints

    failpoints.set_failpoints({"data.pool.before_scale": "raise"})
    try:
        ds = rtd.range(32, parallelism=8).map_batches(
            lambda b: np.asarray(b) * 3,
            compute=rtd.ActorPoolStrategy(
                min_size=1, max_size=4, scale_up_queue_depth=1),
        )
        assert sorted(ds.take_all()) == [i * 3 for i in range(32)]
        stage = next(s for s in ds.stats().lineage()
                     if s.name == "map_batches(actors)")
        # Every scale decision was vetoed: the pool never moved.
        assert stage.extra["pool_peak"] == 1
        assert stage.extra["pool_scale_ups"] == 0
    finally:
        failpoints.reset()


def test_dynamic_split_local_backend(local_runtime):
    from ray_tpu import data as rtd

    config.override("target_block_size_bytes", 64 << 10)
    try:
        ds = rtd.from_numpy(np.arange(262144.0), parallelism=8) \
            .map_batches(lambda b: {"data": b["data"] * 2})
        out = ds.take_all()
        assert len(out) == 262144
        assert ds.num_blocks > 8  # oversized outputs split
        stage = next(s for s in ds.stats().lineage()
                     if "map_batches" in s.name)
        assert stage.extra.get("splits", 0) > 0
        # Downstream ops handle the finer granularity.
        assert ds.repartition(4).count() == 262144
    finally:
        config.reset("target_block_size_bytes")


def test_block_splits_counter_agrees_with_stage_stats(local_runtime):
    """The metrics plane counts the splits the stage's stats count:
    ``ray_tpu_block_splits_total`` grows by exactly the extra blocks the
    run made."""
    from ray_tpu import data as rtd
    from ray_tpu.serve import _observability as obs
    from ray_tpu.train import _observability as tob

    config.override("target_block_size_bytes", 64 << 10)
    try:
        before = obs.parse_prometheus(tob.scrape_text())
        ds = rtd.from_numpy(np.arange(131072.0), parallelism=4) \
            .map_batches(lambda b: {"data": b["data"] + 1})
        assert ds.count() == 131072
        stage = next(s for s in ds.stats().lineage()
                     if "map_batches" in s.name)
        splits = stage.extra["splits"]
        assert splits == ds.num_blocks - 4 > 0

        def counted():
            delta = obs.diff_parsed(
                before, obs.parse_prometheus(tob.scrape_text()))
            return sum(obs.sum_counter(
                delta, "ray_tpu_block_splits_total", "stage").values())

        wait_for(lambda: counted() >= splits, msg="splits counted")
        assert counted() == splits
    finally:
        config.reset("target_block_size_bytes")


# -- split + spill + restore on the cluster backend ------------------------


@pytest.fixture()
def spill_cluster(tmp_path):
    """Two-node cluster spilling to a shared file:// URI; the victim
    node has a tiny store so the pipeline runs past capacity."""
    from ray_tpu.cluster.cluster_utils import Cluster

    ray_tpu.shutdown()
    spill_dir = tmp_path / "spill"
    config.override("spill_uri", f"file://{spill_dir}")
    config.override("target_block_size_bytes", 256 << 10)
    c = Cluster()
    c.add_node(num_cpus=2)  # driver node: survives
    victim = c.add_node(num_cpus=2, store_capacity=8 << 20,
                        resources={"victim": 8})
    c.wait_for_nodes()
    ray_tpu.init(c.address)
    yield c, victim, str(spill_dir)
    ray_tpu.shutdown()
    c.shutdown()
    config.reset("spill_uri")
    config.reset("target_block_size_bytes")
    gc.collect()


def test_dataset_past_capacity_completes_via_split_spill(spill_cluster):
    """~16 MiB of 1-MiB generation blocks through an 8 MiB store: the
    map stage splits outputs to the 256 KiB target, the store spills to
    the URI instead of OOM-killing, and every row survives the trip."""
    from ray_tpu import data as rtd

    c, victim, _ = spill_cluster

    @ray_tpu.remote(resources={"victim": 1})
    def gen(i):
        return {"t": np.full((4096, 64), float(i), np.float32)}  # 1 MiB

    refs = [gen.remote(i) for i in range(16)]
    ray_tpu.wait(refs, num_returns=len(refs), timeout=120.0)
    ds = rtd.Dataset(list(refs)).map_batches(
        lambda b: {"t": b["t"] + 1.0})
    rows = 0
    seen = set()
    for batch in ds.iter_batches(batch_size=1024):
        rows += batch["t"].shape[0]
        seen.update(np.unique(batch["t"][:, 0]).tolist())
    assert rows == 16 * 4096
    assert seen == {float(i) + 1.0 for i in range(16)}
    assert ds.num_blocks > 16  # splitting engaged
    stats = victim.rpc_store_stats()
    assert stats["spilled_objects"] > 0 or stats["spill_restores"] > 0, \
        "store never spilled: the run did not actually exceed capacity"


def test_node_death_restores_spilled_from_uri(spill_cluster):
    """Kill the node whose store spilled to the shared URI: its spilled
    objects come back via restore-from-URI on a surviving node — the
    creating tasks do NOT re-execute (their side-effect marker is
    written exactly once)."""
    c, victim, spill_dir = spill_cluster
    marker_dir = os.path.join(spill_dir, os.pardir, "exec_markers")
    os.makedirs(marker_dir, exist_ok=True)

    @ray_tpu.remote(resources={"victim": 1}, max_retries=3)
    def make(i, marker_dir):
        with open(os.path.join(marker_dir, f"m{i}"), "a") as f:
            f.write("x")
        return np.full(1 << 20, i % 251, np.uint8)

    # 14 MiB through the 8 MiB store: some objects must spill. NOT
    # waited/fetched on the driver — a driver-side get would replicate
    # the value into the survivor's store and the death below would
    # never need the URI.
    refs = [make.remote(i, marker_dir) for i in range(14)]
    wait_for(lambda: len(c.head.rpc_spilled_objects()) >= 4,
             timeout=120.0, msg="head records remote-spilled objects")
    spilled = c.head.rpc_spilled_objects()
    spilled_refs = [(i, r) for i, r in enumerate(refs) if r.id in spilled]
    assert spilled_refs, "nothing was recorded as remote-spilled"
    # A spilled record means the creating task completed: its marker
    # exists exactly once before the kill.
    for i, _ in spilled_refs:
        assert os.path.getsize(os.path.join(marker_dir, f"m{i}")) == 1

    survivor = c.nodes[0]
    restores_before = survivor.rpc_store_stats()["spill_restores"]
    c.kill_node(victim)

    # Spilled objects read back correct — restored from the URI onto a
    # live node, not recomputed.
    for i, ref in spilled_refs:
        arr = ray_tpu.get(ref, timeout=120.0)
        assert arr[0] == i % 251 and arr.nbytes == 1 << 20
        del arr
    assert survivor.rpc_store_stats()["spill_restores"] > restores_before
    for i, _ in spilled_refs:
        assert os.path.getsize(os.path.join(marker_dir, f"m{i}")) == 1, \
            f"task {i} re-executed: restore fell back to recompute"


def test_freed_spilled_objects_leave_the_uri(spill_cluster):
    """Free-on-zero reaches the remote target: dropping the last ref to
    a spilled object deletes its URI copy (no one-file-per-free leak)."""
    _c, victim, spill_dir = spill_cluster

    @ray_tpu.remote(resources={"victim": 1})
    def blob(i):
        return np.full(1 << 20, i, np.uint8)

    refs = [blob.remote(i) for i in range(14)]
    ray_tpu.wait(refs, num_returns=len(refs), timeout=120.0)
    wait_for(lambda: victim.rpc_store_stats()["spilled_objects"] > 0,
             msg="spill to the shared URI")
    del refs
    gc.collect()
    wait_for(lambda: victim.rpc_store_stats()["spilled_bytes"] == 0,
             msg="URI copies removed after refs dropped", timeout=30.0)
