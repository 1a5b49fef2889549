"""Cluster-wide object & memory observability tests: put-side
attribution (both backends), ``state.memory_summary`` agreeing with the
per-node shm ``stats()``, size-sorted/truncation-reporting
``list_objects``, the head's leak sweeper, OOM forensics (report +
death cause + counter + structured event), the ``ray-tpu memory`` CLI,
and dead-node series pruning from the federated scrape."""

import json
import os
import re
import sys
import time

import cloudpickle
import numpy as np
import pytest

import ray_tpu
from ray_tpu import state
from ray_tpu.core.config import config

# Cluster workers unpickle test functions by value.
cloudpickle.register_pickle_by_value(sys.modules[__name__])

THIS_FILE = os.path.basename(__file__)


def _wait_for(cond, timeout=15.0, interval=0.2):
    deadline = time.time() + timeout
    while time.time() < deadline:
        if cond():
            return True
        time.sleep(interval)
    return cond()


# -- pure-unit pieces ------------------------------------------------------


def test_callsite_helper_points_here():
    from ray_tpu.core import attribution

    site = attribution.callsite()
    assert THIS_FILE in site
    assert "test_callsite_helper_points_here" in site


def test_attr_rides_serialization_meta():
    from ray_tpu.core import serialization as ser

    attr = {"owner": "o", "task": "t", "created_at": 1.0}
    meta, chunks = ser.serialize({"x": 1}, extra_meta={"attr": attr})
    assert ser.meta_field(meta, "attr") == attr
    # Consumers that don't know the key still round-trip the value.
    assert ser.deserialize(meta, b"".join(bytes(c) for c in chunks)) == {
        "x": 1}
    assert ser.meta_field(b"not-msgpack", "attr", {}) == {}


def test_shm_stats_info_raise_on_unlinked_segment(tmp_path):
    """A live handle whose segment another process unlinked must fail
    LOUD from stats()/info(), not return recycled-memory garbage
    (closed handles keep returning the empty defaults)."""
    from ray_tpu._native.shm_store import ShmStore

    path = str(tmp_path / "segment")
    s = ShmStore(path, capacity=1 << 20, create=True)
    s.put("obj1", b"x" * 128)
    assert s.stats()["num_objects"] == 1
    assert s.info("obj1")["data_size"] == 128
    os.unlink(path)
    with pytest.raises(RuntimeError, match="unlinked"):
        s.stats()
    with pytest.raises(RuntimeError, match="unlinked"):
        s.info("obj1")
    s.close()
    # Closed handle: back to the quiet defaults, never a raise.
    assert s.stats()["num_objects"] == 0
    assert s.info("obj1") is None


# -- local backend ---------------------------------------------------------


@pytest.fixture
def local_runtime():
    ray_tpu.shutdown()
    config.override("record_callsite", True)
    ray_tpu.init(num_cpus=4)
    yield
    ray_tpu.shutdown()
    config.reset("record_callsite")


def test_local_put_and_task_attribution(local_runtime):
    ref = ray_tpu.put(np.zeros(4096, dtype=np.uint8))

    @ray_tpu.remote
    def maker_local():
        return np.ones(2048, dtype=np.uint8)

    ref2 = maker_local.remote()
    ray_tpu.get(ref2)
    objs = state.list_objects()
    rec = next(r for r in objs if r["object_id"] == ref.id)
    assert rec["owner"] == "local"
    assert rec["task"] == "driver"
    assert THIS_FILE in rec["callsite"]
    assert rec["size"] == 4096
    assert rec["age_s"] is not None
    rec2 = next(r for r in objs if r["object_id"] == ref2.id)
    assert rec2["task"] == "maker_local"
    # Return objects fall back to the submit-time (.remote()) callsite.
    assert THIS_FILE in rec2["callsite"]
    del ref, ref2


def test_local_list_objects_sorted_and_truncated(local_runtime):
    refs = [ray_tpu.put(np.zeros(n, dtype=np.uint8))
            for n in (1 << 12, 1 << 14, 1 << 13)]
    objs = state.list_objects()
    sizes = [r["size"] for r in objs]
    assert sizes == sorted(sizes, reverse=True)
    assert objs.truncated is False
    clipped = state.list_objects(limit=1)
    assert len(clipped) == 1
    assert clipped[0]["size"] == max(sizes)
    assert clipped.truncated is True
    assert clipped.total == len(objs)
    del refs


def test_local_memory_summary_groups(local_runtime):
    ref = ray_tpu.put(np.zeros(1 << 13, dtype=np.uint8))
    summary = state.memory_summary(group_by="task")
    assert summary["totals"]["objects"] >= 1
    assert summary["totals"]["bytes_used"] >= 1 << 13
    keys = [g["key"] for g in summary["groups"]]
    assert "driver" in keys
    assert state.memory_leaks() == []
    del ref


# -- cluster ---------------------------------------------------------------


@pytest.fixture(scope="module")
def cluster():
    ray_tpu.shutdown()
    # Workers are separate processes: callsite recording must arrive by
    # env; the sweeper knobs only matter in the head (this process).
    os.environ["RAY_TPU_RECORD_CALLSITE"] = "1"
    config.override("record_callsite", True)
    config.override("leak_age_threshold_s", 0.5)
    config.override("leak_sweep_interval_s", 0.3)
    from ray_tpu.cluster.cluster_utils import Cluster

    c = Cluster()
    c.add_node(num_cpus=2)
    c.add_node(num_cpus=2)
    c.wait_for_nodes()
    ray_tpu.init(c.address)
    yield c
    ray_tpu.shutdown()
    c.shutdown()
    os.environ.pop("RAY_TPU_RECORD_CALLSITE", None)
    for knob in ("record_callsite", "leak_age_threshold_s",
                 "leak_sweep_interval_s"):
        config.reset(knob)


def _find_object(oid, timeout=10.0):
    rec = None

    def seen():
        nonlocal rec
        rec = next((r for r in state.list_objects(limit=100_000)
                    if r["object_id"] == oid), None)
        return rec is not None

    _wait_for(seen, timeout)
    return rec


def test_cluster_put_attribution(cluster):
    ref = ray_tpu.put(np.zeros(1 << 16, dtype=np.uint8))
    rec = _find_object(ref.id)
    assert rec is not None
    assert rec["owner"].startswith("d:")  # the driver owns its puts
    assert rec["task"] == "driver"
    assert THIS_FILE in rec["callsite"]
    assert rec["size"] > 1 << 16 - 1
    del ref


def test_cluster_task_return_attribution(cluster):
    @ray_tpu.remote
    def maker_cluster():
        return np.ones(1 << 16, dtype=np.uint8)

    ref = maker_cluster.remote()
    ray_tpu.get(ref, timeout=60)
    rec = _find_object(ref.id)
    assert rec is not None
    assert rec["owner"].startswith("w:")  # stored by the worker
    assert rec["task"] == "maker_cluster"
    # Submit-time callsite fallback: the .remote() line above.
    assert THIS_FILE in rec["callsite"]
    del ref


def test_cluster_nested_put_attribution(cluster):
    @ray_tpu.remote
    def putter_cluster():
        inner = ray_tpu.put(np.full(1 << 15, 7, dtype=np.uint8))
        return inner

    outer = putter_cluster.remote()
    inner = ray_tpu.get(outer, timeout=60)
    rec = _find_object(inner.id)
    assert rec is not None
    assert rec["task"] == "putter_cluster"
    assert "putter_cluster" in rec["callsite"]  # the in-task put line
    del outer, inner


def test_cluster_async_actor_put_attribution(cluster):
    """Nested puts inside ASYNC actor methods attribute to the method
    (the contextvar rides the asyncio task, not the loop thread)."""
    @ray_tpu.remote
    class AsyncPutter:
        async def makeref(self):
            return ray_tpu.put(np.ones(1 << 14, dtype=np.uint8))

    a = AsyncPutter.remote()
    inner = ray_tpu.get(a.makeref.remote(), timeout=60)
    rec = _find_object(inner.id)
    assert rec is not None
    assert rec["task"] == "makeref"
    del inner


def test_memory_summary_matches_shm_stats(cluster):
    refs = [ray_tpu.put(np.ones(1 << 17, dtype=np.uint8))
            for _ in range(4)]

    def agree():
        summary = state.memory_summary()
        used = sum(n.store.stats()["used"] for n in cluster.nodes)
        objs = sum(n.store.stats()["num_objects"] for n in cluster.nodes)
        return (summary["totals"]["bytes_used"] == used
                and summary["totals"]["objects"] == objs
                and summary["totals"]["bytes_used"] >= 4 * (1 << 17))

    assert _wait_for(agree), (state.memory_summary()["totals"],
                              [n.store.stats() for n in cluster.nodes])
    summary = state.memory_summary(group_by="node")
    assert summary["totals"]["bytes_capacity"] == sum(
        n.store.stats()["capacity"] for n in cluster.nodes)
    assert set(summary["nodes"]) == {n.node_id for n in cluster.nodes}
    # Grouping by node covers every replica-holding node.
    keys = {g["key"] for g in summary["groups"]}
    assert keys <= {n.node_id for n in cluster.nodes}
    del refs


def test_cluster_list_objects_sorted_and_truncated(cluster):
    big = ray_tpu.put(np.zeros(1 << 20, dtype=np.uint8))
    small = ray_tpu.put(np.zeros(1 << 10, dtype=np.uint8))
    assert _find_object(big.id) is not None
    assert _find_object(small.id) is not None
    objs = state.list_objects(limit=100_000)
    sizes = [r["size"] for r in objs]
    assert sizes == sorted(sizes, reverse=True)
    clipped = state.list_objects(limit=1)
    assert clipped.truncated is True
    assert clipped.total == len(objs)
    assert clipped[0]["size"] == sizes[0]
    del big, small


def test_object_store_stats_per_key_join(cluster):
    ref = ray_tpu.put(np.full(1 << 16, 3, dtype=np.uint8))
    assert _find_object(ref.id) is not None

    def joined():
        for rep in state.object_store_stats():
            for rec in rep.get("objects", []):
                if rec["object_id"] == ref.id:
                    return (rec["pinned"] and rec["sealed"]
                            and rec["size"] > 1 << 16 - 1
                            and rec["task"] == "driver"
                            and rec["ref_holders"] >= 1)
        return False

    assert _wait_for(joined), state.object_store_stats()
    del ref


def test_leak_sweeper_flags_and_clears(cluster, capsys):
    """The acceptance leak: a pinned primary copy whose owner died
    before registering any hold (directly injected into an agent's
    store + the head directory, exactly what a crashed owner leaves
    behind). The sweeper must flag it WITH its creation callsite, the
    CLI must print it, and a registered holder must clear the flag;
    releasing the holder then frees the object entirely."""
    from ray_tpu._private import worker as worker_mod
    from ray_tpu.core import ids
    from ray_tpu.core import serialization as ser

    backend = worker_mod.backend()
    agent = cluster.nodes[0]
    oid = ids.new_object_id()
    attr = {"owner": "w:dead-node:9999:feed", "task": "leaky_task",
            "created_at": round(time.time(), 3),
            "callsite": "leaky_test.py:42 in leaker"}
    meta, chunks = ser.serialize(b"z" * 4096, extra_meta={"attr": attr})
    agent.store.put(oid, chunks, b"V" + meta)
    agent.store.pin(oid)  # the owner's primary-copy pin, never released
    backend.head.call("add_location", oid, agent.node_id, False,
                      ser.total_size(chunks), None, "dead-owner", attr)

    def flagged():
        return any(r["object_id"] == oid for r in state.memory_leaks())

    assert _wait_for(flagged), state.memory_leaks()
    rec = next(r for r in state.memory_leaks() if r["object_id"] == oid)
    assert rec["kind"] == "no_reachable_refs"
    assert rec["task"] == "leaky_task"
    assert rec["callsite"] == "leaky_test.py:42 in leaker"
    assert rec["age_s"] >= 0.5

    from ray_tpu.scripts.cli import main as cli_main

    cli_main(["memory", "--leaks"])
    out = capsys.readouterr().out
    assert oid[:20] in out
    assert "leaky_test.py:42 in leaker" in out
    assert "no_reachable_refs" in out

    # A holder appearing clears the flag...
    backend.head.call("ref_update", "c:leak-test-holder", [oid], [])
    assert _wait_for(lambda: not flagged()), state.memory_leaks()
    # ...and releasing it frees the object cluster-wide.
    backend.head.call("ref_update", "c:leak-test-holder", [], [oid])
    assert _wait_for(
        lambda: not any(r["object_id"] == oid
                        for r in state.list_objects(limit=100_000)))
    assert _wait_for(lambda: not agent.store.contains(oid))


def test_cli_memory_summary_and_groups(cluster, capsys):
    ref = ray_tpu.put(np.zeros(1 << 18, dtype=np.uint8))
    assert _find_object(ref.id) is not None
    from ray_tpu.scripts.cli import main as cli_main

    cli_main(["memory"])
    out = capsys.readouterr().out
    assert "object store:" in out
    assert "node " in out
    assert "top objects by size:" in out
    assert "by callsite:" in out

    cli_main(["memory", "--group-by", "task"])
    out = capsys.readouterr().out
    assert "by task:" in out
    assert "driver" in out

    cli_main(["memory", "--stats-only"])
    out = capsys.readouterr().out
    stats = json.loads(out)
    assert len(stats) == len(cluster.nodes)
    assert all("stats" in rep and "objects" not in rep for rep in stats)
    del ref


def test_store_gauges_in_federated_metrics(cluster):
    from ray_tpu._private import worker as worker_mod

    backend = worker_mod.backend()
    text = backend.cluster_metrics_text()
    for nid in (n.node_id for n in cluster.nodes):
        assert f'ray_tpu_object_store_bytes_used{{node_id="{nid}"}}' \
            in text
        assert f'ray_tpu_object_store_bytes_capacity{{node_id="{nid}"}}' \
            in text


def test_dead_node_store_series_pruned(cluster):
    """A removed node's object-store series must vanish from the
    federated scrape (same lifecycle as the worker/device gauges)."""
    from ray_tpu._private import worker as worker_mod

    backend = worker_mod.backend()
    extra = cluster.add_node(num_cpus=1)
    cluster.wait_for_nodes()
    nid = extra.node_id
    series = f'ray_tpu_object_store_bytes_used{{node_id="{nid}"}}'
    assert _wait_for(
        lambda: series in backend.cluster_metrics_text()), \
        backend.cluster_metrics_text()
    cluster.remove_node(extra, graceful=True)
    assert _wait_for(
        lambda: series not in backend.cluster_metrics_text(),
        timeout=30.0)
    # Survivors keep reporting.
    text = backend.cluster_metrics_text()
    assert any(
        f'ray_tpu_object_store_bytes_used{{node_id="{n.node_id}"}}'
        in text for n in cluster.nodes if n.node_id != nid)


def test_dashboard_memory_routes(cluster):
    import urllib.request

    from ray_tpu.dashboard import Dashboard

    dash = Dashboard(cluster.address, port=0)
    try:
        ref = ray_tpu.put(np.zeros(1 << 14, dtype=np.uint8))
        ref2 = ray_tpu.put(np.zeros(1 << 12, dtype=np.uint8))
        assert _find_object(ref.id) is not None
        assert _find_object(ref2.id) is not None

        def get_json(path):
            with urllib.request.urlopen(dash.url + path, timeout=15) as r:
                return json.loads(r.read())

        d = get_json("/api/memory_summary?group_by=task")
        assert "totals" in d and "groups" in d and "nodes" in d
        o = get_json("/api/objects?limit=1")
        assert isinstance(o["objects"], list) and o["truncated"] is True
        leaks = get_json("/api/memory_leaks")
        assert "leaks" in leaks
        del ref, ref2
    finally:
        dash.shutdown()


def test_memory_summary_shows_leak_count_field(cluster):
    summary = state.memory_summary()
    assert "leaks" in summary
    assert isinstance(summary["leaks"], int)


# -- OOM forensics (own cluster: a memory limit that a hog crosses) --------


@pytest.fixture
def oom_cluster():
    ray_tpu.shutdown()
    from ray_tpu.cluster.cluster_utils import Cluster

    c = Cluster()
    c.add_node(num_cpus=2, memory_limit_bytes=600 << 20,
               memory_usage_threshold=1.0)
    c.wait_for_nodes()
    ray_tpu.init(address=c.address)
    yield c
    ray_tpu.shutdown()
    c.shutdown()


def test_oom_kill_writes_forensic_report(oom_cluster):
    from ray_tpu._private import worker as worker_mod

    backend = worker_mod.backend()
    sub = "test-oom-events"
    backend.head.call("pubsub_subscribe", sub, "NODES")

    @ray_tpu.remote(num_cpus=1)
    def hog():
        blobs = []
        for _ in range(40):
            blobs.append(np.ones(64 << 20, dtype=np.uint8))
            time.sleep(0.05)
        return len(blobs)

    with pytest.raises(ray_tpu.OutOfMemoryError) as ei:
        ray_tpu.get(hog.remote(), timeout=90)
    msg = str(ei.value)
    # Death cause carries the report path.
    m = re.search(r"memory report: (\S+\.json)", msg)
    assert m is not None, msg
    path = m.group(1)
    assert os.path.exists(path)
    report = json.load(open(path))
    assert report["victim"]["task"] == "hog"
    assert report["reason"]
    assert report["system_memory"]["total_bytes"] > 0
    assert isinstance(report["workers"], list)
    assert "object_store" in report and "top_objects" in report

    # The report is indexed on the node for post-mortem discovery.
    agent = oom_cluster.nodes[0]
    reports = state.object_store_stats(node_id=agent.node_id)
    assert any(r["path"] == path
               for rep in reports for r in rep["oom_reports"])
    summary = state.memory_summary()
    assert path in summary["nodes"][agent.node_id]["oom_reports"]

    # Counter federated; structured event in the drain-event shape.
    assert _wait_for(lambda: (
        f'ray_tpu_oom_kills_total{{node_id="{agent.node_id}"}}'
        in backend.cluster_metrics_text()))

    def got_event():
        got = backend.head.call("pubsub_poll", sub, 1.0, timeout=10.0)
        if got is None:
            return False
        msgs, _dropped = got
        return any(
            m["data"].get("state") == "OOM_KILL"
            and m["data"].get("report_path") == path
            and m["data"].get("task") == "hog"
            for m in msgs)

    assert _wait_for(got_event, timeout=15.0)
    backend.head.call("pubsub_unsubscribe", sub)
    assert agent.memory_monitor.kills >= 1

    # The node survived the kill.
    @ray_tpu.remote(num_cpus=1)
    def ping():
        return "pong"

    assert ray_tpu.get(ping.remote(), timeout=30) == "pong"
