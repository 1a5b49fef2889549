"""Distributed tracing: OTel-shaped spans across task/actor boundaries.

Reference: ``python/ray/util/tracing/tracing_helper.py`` — when tracing is
enabled, every task submission records a client-side span and injects its
context into the task spec; the worker continues the trace around
execution, so one trace follows a request through submit → schedule →
run, across processes. The environment ships only the OpenTelemetry API
(no SDK), so the span model here is self-contained but OTel-shaped:
trace_id/span_id/parent_id hex ids, name, start/end ns, attributes,
status — exportable as a Chrome trace.

Usage:
    from ray_tpu.util import tracing
    tracing.enable()                  # or RAY_TPU_TRACING_ENABLED=1
    with tracing.span("my-step", {"k": "v"}):
        ref = f.remote()              # submit/execute spans attach under it
    spans = tracing.collect()         # this process's finished spans
    tracing.export_chrome_trace("/tmp/trace.json")

Two sinks. The span store above stamps ``time.time_ns()`` and follows a
request across threads and processes. The second sink is the JAX
profiler's own trace: :func:`device_span` (always) and :func:`span`
(while a span is being recorded) enter a ``jax.profiler.TraceAnnotation``
on the calling thread, so that whoever captures a profile
(``device_telemetry.capture``, XProf) sees the program's phases on the
device's clock, with no switch to find. This module never imports jax:
the annotation exists only where something else already loaded it.

The process-wide switch (``enable``/``disable``) gates ROOT spans only.
A span with a parent — an explicit ``parent`` context that came in on a
request, or the thread's current span — is recorded whether or not the
switch is on: the caller made the sampling decision, and what nests
under a recorded span belongs to its trace. ``suppressed()`` still wins.

Worker-side spans ride the existing worker-events batching to the node
agent and head (``rpc_worker_events`` → LOGS-style aggregation), queryable
via ``head.call("list_spans")``.
"""

from __future__ import annotations

import json
import os
import random
import sys
import threading
import time
from contextlib import contextmanager
from typing import Any, Dict, List, Optional

_lock = threading.Lock()
_enabled = os.environ.get("RAY_TPU_TRACING_ENABLED", "").lower() in (
    "1", "true", "yes", "on")
_finished: List[dict] = []
_MAX_SPANS = 100_000
_dropped = 0  # guarded-by: _lock — spans lost to the _MAX_SPANS cap
_current = threading.local()  # .span = active span dict


def enable() -> None:
    global _enabled
    _enabled = True


def disable() -> None:
    global _enabled
    _enabled = False


def is_enabled() -> bool:
    return _enabled


# Ids come from a generator seeded from the OS once per process (and
# again in a forked child). ``os.urandom`` per id releases the GIL, and
# on a process whose other threads are waiting for it (an engine's
# pollers, just woken) every span then costs a GIL handoff: 1.0 ms a
# decode step between two of the loop's phases on the chip (PR 24).
_ids = random.Random(os.urandom(16))
os.register_at_fork(after_in_child=lambda: _ids.seed(os.urandom(16)))


def _new_id(nbytes: int) -> str:
    return "%0*x" % (2 * nbytes, _ids.getrandbits(8 * nbytes))


def _record(span: dict) -> None:
    global _dropped
    overflow = 0
    with _lock:
        _finished.append(span)
        if len(_finished) > _MAX_SPANS:
            overflow = len(_finished) - _MAX_SPANS
            del _finished[:overflow]
            _dropped += overflow
    if overflow:
        # No silent caps: the truncation that used to vanish here is a
        # counter on the scrape (and rides the worker-events batch to
        # the head, node-attributed, via drain_dropped).
        try:
            from ray_tpu.util import metrics as _metrics

            _metrics.TRACING_DROPPED_SPANS.inc(overflow, tags={
                "node_id": os.environ.get("RAY_TPU_NODE_ID", "local")})
        except Exception:
            pass


def dropped_spans() -> int:
    """Spans this process dropped to the ``_MAX_SPANS`` ring cap."""
    with _lock:
        return _dropped


def drain_dropped() -> int:
    """Pop the drop count accumulated since the last drain (the worker
    event flusher ships this alongside the span batch so the head's
    scrape sees worker-side truncation, not just its own ring's)."""
    global _dropped
    with _lock:
        n = _dropped
        _dropped = 0
    return n


def requeue_dropped(n: int) -> None:
    """Give a drained drop count back (a shipped batch that was itself
    evicted from the resend queue must not silently lose its count)."""
    global _dropped
    if n:
        with _lock:
            _dropped += n


def current_span() -> Optional[dict]:
    return getattr(_current, "span", None)


@contextmanager
def suppressed():
    """Suppress span creation on THIS thread (``span`` yields None).

    Control-plane housekeeping — serve controller health probes,
    autoscaling reconcile passes, routing-table long-polls — submits
    actor calls on its own cadence; without suppression an enabled
    tracer records a ``submit:get_num_ongoing`` span every 250ms
    forever, drowning the request traces the operator actually wants."""
    prev = getattr(_current, "suppress", False)
    _current.suppress = True
    try:
        yield
    finally:
        _current.suppress = prev


def is_suppressed() -> bool:
    return bool(getattr(_current, "suppress", False))


def current_context() -> Optional[dict]:
    """Injectable context of the active span (what task specs carry)."""
    s = current_span()
    if s is None:
        return None
    return {"trace_id": s["trace_id"], "span_id": s["span_id"]}


class _NoSpan:
    """What :func:`device_span` returns where jax is not loaded."""

    __slots__ = ()

    def __enter__(self):
        return self

    def __exit__(self, *exc):
        return False

    def set_metadata(self, **attrs):
        pass


_NO_SPAN = _NoSpan()


def device_span(name: str, **attrs):
    """A span on the device's clock: a ``jax.profiler.TraceAnnotation``
    where jax is already in ``sys.modules``, nothing otherwise. Always
    on: an inactive annotation costs well under a microsecond and the
    profiler decides whether anything is recorded (``attrs`` are only
    formatted while a profile is being taken). Same-thread only.
    ``with device_span(...) as ds`` gives an object whose
    ``ds.set_metadata(**attrs)`` adds what is known only inside."""
    jax = sys.modules.get("jax")
    if jax is None:
        return _NO_SPAN
    return jax.profiler.TraceAnnotation(name, **attrs)


_profiler_on = None  # jax's own switch, bound at the first call with jax


def profiling() -> bool:
    """Is a profile being taken, so that a :func:`device_span` would be
    recorded? For a path that runs thousands of times a second, where
    even an inactive annotation's context manager shows (a poll of
    ``llm_next``): a branch on this costs a tenth of a microsecond."""
    global _profiler_on
    if _profiler_on is None:
        jax = sys.modules.get("jax")
        if jax is None:
            return False
        _profiler_on = jax.profiler.TraceAnnotation.is_enabled
    return _profiler_on()


def _sampled(parent: Optional[dict]) -> bool:
    """Is a span with this explicit ``parent`` recorded? Roots obey the
    switch; a span under a parent follows its parent's trace."""
    if is_suppressed():
        return False
    if parent is None:
        parent = current_context()
    return bool(parent) or _enabled


def _make_span(name: str, attributes: Optional[Dict[str, Any]],
               parent: Optional[dict], cat: Optional[str]) -> dict:
    s = {
        "trace_id": (parent or {}).get("trace_id") or _new_id(16),
        "span_id": _new_id(8),
        "parent_id": (parent or {}).get("span_id"),
        "name": name,
        "start_ns": time.time_ns(),
        "end_ns": None,
        "attributes": dict(attributes or {}),
        "status": "OK",
        "pid": os.getpid(),
    }
    if cat:
        s["cat"] = cat
    return s


def start_span(name: str, attributes: Optional[Dict[str, Any]] = None,
               parent: Optional[dict] = None,
               cat: Optional[str] = None) -> Optional[dict]:
    """Manually-managed span: never touches the thread-local current-
    span stack, so it is safe to hold OPEN across ``await`` points in
    async code (where interleaved coroutines on one thread would
    corrupt a context-manager span's restore order). Pass ``parent={}``
    to force a fresh root. Close with :func:`finish_span`."""
    if not _sampled(parent):
        return None
    if parent is None:
        parent = current_context()
    return _make_span(name, attributes, parent, cat)


def finish_span(s: Optional[dict], status: str = "OK") -> None:
    """End and record a :func:`start_span` span."""
    if s is None:
        return
    s["end_ns"] = time.time_ns()
    if status != "OK":
        s["status"] = status
    _record(s)


@contextmanager
def span(name: str, attributes: Optional[Dict[str, Any]] = None,
         parent: Optional[dict] = None, cat: Optional[str] = None):
    """Start a span; ``parent`` is an injected context from another
    process (or None to nest under this thread's active span).
    ``cat`` labels the span's Chrome-trace category (default "span");
    the Serve request path uses ``cat="serve"`` so request traces are
    filterable from task spans in one merged timeline. A recorded span
    is also written into the profiler's trace (:func:`device_span`)."""
    if not _sampled(parent):
        yield None
        return
    if parent is None:
        parent = current_context()
    s = _make_span(name, attributes, parent, cat)
    prev = getattr(_current, "span", None)
    _current.span = s
    try:
        with device_span(name):
            yield s
    except BaseException as e:
        s["status"] = f"ERROR: {type(e).__name__}"
        raise
    finally:
        s["end_ns"] = time.time_ns()
        _current.span = prev
        _record(s)


# -- W3C Trace Context (the HTTP proxy's wire format) ----------------------
#
# ``traceparent: 00-<32 hex trace id>-<16 hex parent span id>-<2 hex flags>``
# — the standard header external clients/gateways already emit, so an
# ingress request joins its caller's distributed trace.


def parse_traceparent(header: Optional[str]) -> Optional[dict]:
    """W3C ``traceparent`` header -> injectable span context (or None on
    anything malformed — a bad header must never fail the request)."""
    if not header or not isinstance(header, str):
        return None
    parts = header.strip().split("-")
    if len(parts) < 4 or parts[0] == "ff":
        return None
    trace_id, span_id = parts[1], parts[2]
    if len(trace_id) != 32 or len(span_id) != 16:
        return None
    try:
        if int(trace_id, 16) == 0 or int(span_id, 16) == 0:
            return None
    except ValueError:
        return None
    return {"trace_id": trace_id.lower(), "span_id": span_id.lower()}


def format_traceparent(ctx: Optional[dict]) -> Optional[str]:
    """Span context -> W3C ``traceparent`` header value."""
    if not ctx or not ctx.get("trace_id") or not ctx.get("span_id"):
        return None
    return f"00-{ctx['trace_id']}-{ctx['span_id']}-01"


def collect(clear: bool = False) -> List[dict]:
    with _lock:
        out = list(_finished)
        if clear:
            del _finished[:]
    return out


def drain() -> List[dict]:
    """Pop this process's finished spans (used by the worker's event
    flusher to ship spans to the node agent in batches)."""
    return collect(clear=True)


def chrome_events(spans: List[dict]) -> List[dict]:
    """Chrome trace 'X' events, mergeable with ``state.timeline()``'s
    task/phase slices into one trace (distinct ``cat`` so a merged view
    can filter spans vs task slices)."""
    return [
        {
            "name": s["name"],
            "cat": s.get("cat") or "span",
            "ph": "X",
            "ts": s["start_ns"] / 1e3,
            "dur": ((s["end_ns"] or s["start_ns"]) - s["start_ns"]) / 1e3,
            "pid": s.get("pid", 0),
            "tid": s["trace_id"][:8],
            "args": {**s["attributes"], "status": s["status"],
                     "span_id": s["span_id"],
                     "parent_id": s.get("parent_id")},
        }
        for s in spans
    ]


def export_chrome_trace(path: str, spans: Optional[List[dict]] = None) -> int:
    spans = collect() if spans is None else spans
    with open(path, "w") as f:
        json.dump(chrome_events(spans), f)
    return len(spans)
