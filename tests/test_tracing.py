"""Distributed tracing: spans follow a request across processes.

Reference: ``python/ray/util/tracing/tracing_helper.py`` — enabled
tracing records a submit-side span per task, injects its context into
the spec, and the worker parents the execution span under it; spans
aggregate centrally (here: head span store via worker-event batches).
"""

import sys
import time

import cloudpickle
import pytest

import ray_tpu
from ray_tpu._private import worker as worker_mod
from ray_tpu.cluster import Cluster
from ray_tpu.util import tracing

cloudpickle.register_pickle_by_value(sys.modules[__name__])


def test_span_nesting_and_status():
    tracing.enable()
    try:
        with tracing.span("outer", {"k": "v"}) as outer:
            with tracing.span("inner") as inner:
                pass
        assert inner["trace_id"] == outer["trace_id"]
        assert inner["parent_id"] == outer["span_id"]
        spans = tracing.collect(clear=True)
        names = {s["name"] for s in spans}
        assert {"outer", "inner"} <= names
        with pytest.raises(ValueError):
            with tracing.span("boom"):
                raise ValueError("x")
        boom = [s for s in tracing.collect(clear=True)
                if s["name"] == "boom"][0]
        assert boom["status"].startswith("ERROR")
    finally:
        tracing.disable()


def test_chrome_export(tmp_path):
    tracing.enable()
    try:
        with tracing.span("step"):
            time.sleep(0.01)
        path = str(tmp_path / "trace.json")
        n = tracing.export_chrome_trace(path)
        assert n >= 1
        import json

        events = json.load(open(path))
        assert any(e["name"] == "step" and e["dur"] > 0 for e in events)
        tracing.collect(clear=True)
    finally:
        tracing.disable()


@pytest.fixture(scope="module")
def cluster():
    ray_tpu.shutdown()
    c = Cluster()
    c.add_node(num_cpus=2)
    c.wait_for_nodes()
    ray_tpu.init(address=c.address)
    yield c
    ray_tpu.shutdown()
    c.shutdown()


def test_trace_crosses_task_boundary(cluster):
    """submit-span (driver) and run-span (worker) share one trace id,
    and the run span reaches the head's span store."""
    tracing.enable()
    try:
        @ray_tpu.remote
        def traced_work():
            time.sleep(0.05)
            return "done"

        with tracing.span("request") as root:
            assert ray_tpu.get(traced_work.remote(), timeout=30) == "done"

        # The driver's own client ships finished spans to the head every
        # half second (its span flusher), so the submit span is either
        # still here or already there; the worker's run span comes over
        # the worker-events plane.
        local = tracing.collect(clear=True)
        head = worker_mod.backend().head
        deadline = time.monotonic() + 15
        submit_spans, run_spans = [], []
        while time.monotonic() < deadline and not (submit_spans
                                                   and run_spans):
            seen = local + head.call("list_spans", root["trace_id"])
            submit_spans = [s for s in seen
                            if s["name"].startswith("submit:")]
            run_spans = [s for s in seen if s["name"].startswith("run:")]
            time.sleep(0.2)
        assert submit_spans, "submit span neither local nor at the head"
        submit = submit_spans[0]
        assert submit["trace_id"] == root["trace_id"]
        assert submit["parent_id"] == root["span_id"]
        assert run_spans, "worker span never reached the head"
        assert run_spans[0]["parent_id"] == submit["span_id"]
        assert run_spans[0]["pid"] != submit["pid"]
    finally:
        tracing.disable()
