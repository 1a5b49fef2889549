"""JAX/XLA device telemetry: HBM gauges, compile counters, trace capture.

The host-side observability plane (node reporter /proc stats, task
events) sees *that* a task ran; this module sees what it did to the
device. Three surfaces:

* ``snapshot()`` — per-device view from ``jax.local_devices()`` +
  ``device.memory_stats()`` (HBM bytes in use / peak / limit on TPU;
  CPU devices report no memory stats) plus process-wide JAX compile
  counters, as a plain dict that rides the worker-events RPC batch.
* compile counters — ``jax.monitoring`` listeners counting backend
  compiles / compile seconds and (persistent) compilation-cache
  hits/misses, installed once per process where the main path first
  touches JAX (``ensure_compile_cache``, ``ray_tpu.init``) or on first
  snapshot; ``compile_log()`` keeps the last compiles one by one.
* ``capture(duration_s)`` — a timed ``jax.profiler.trace()`` window
  returning the trace directory as ``{relpath: bytes}``, falling back
  to the pure-Python stack sampler (``util/stack_sampler``) when
  ``jax.profiler`` is unavailable or fails.

Everything degrades to a stub until the process has initialised a JAX
backend itself: this module NEVER imports jax and never makes the first
backend touch (workers fork fast precisely because jax loads lazily; a
node agent, or a worker that merely imported jax, must never take the
chip from the process that needs it). ``snapshot(force=True)`` opts a
process in explicitly.
"""

from __future__ import annotations

import collections
import json
import os
import sys
import threading
import time
from typing import Any, Dict, List, Optional

_lock = threading.Lock()
_listeners_installed = False
_listeners_installing = False
# Per-listener success flags: a partial failure must retry ONLY the
# listener that failed — re-registering the one that succeeded would
# double-count every event (jax.monitoring has no unregister).
_event_registered = False
_duration_registered = False
_install_failures = 0
_MAX_INSTALL_FAILURES = 5  # then give up: API is genuinely absent
# Process-wide compile counters, fed by jax.monitoring listeners.
_counts = {
    "backend_compiles": 0,
    "compile_seconds": 0.0,
    "cache_hits": 0,
    "cache_misses": 0,
    "compile_requests": 0,
}
# The last compiles one by one (guarded-by: _lock), oldest first:
# {"epoch_ns", "seconds", "cache", "fun_name"}. ``cache`` is "hit" (the
# program came from the persistent cache; ``seconds`` is then the load),
# "miss" (the cache was asked and the program compiled) or None (the
# cache was not asked).
_COMPILE_LOG_MAX = 512
_compile_log: collections.deque = collections.deque(maxlen=_COMPILE_LOG_MAX)
# What the persistent cache said about the compile in flight on THIS
# thread: jax reports the request and the hit as events before the
# compile's duration, on the compiling thread.
_pending = threading.local()

# Keys copied out of device.memory_stats() when present (TPU/GPU
# backends; CPU returns None).
_MEM_KEYS = ("bytes_in_use", "peak_bytes_in_use", "bytes_limit",
             "largest_alloc_size", "num_allocs")


def jax_loaded() -> bool:
    """Has something in this process already imported jax? (We piggyback
    on their import; we never trigger one.)"""
    return "jax" in sys.modules


def backend_initialized() -> bool:
    """Has this process's OWN code already initialised a JAX backend?

    ``jax.local_devices()`` initialises one if none is: on a TPU host
    that takes the chip in whichever process asked first, and in a
    trainer rank still waiting for its coordinator it happens before
    ``jax.distributed.initialize``, which that call forbids. Telemetry
    must never be that first touch, so an unforced snapshot waits until
    the backend exists — an imported jax is not enough."""
    xla_bridge = sys.modules.get("jax._src.xla_bridge")
    return (jax_loaded() and xla_bridge is not None
            and xla_bridge.backends_are_initialized())


def _install_listeners() -> None:
    """Register jax.monitoring hooks once per process. Retry-safe: the
    installed flag is only set after a successful registration, so a
    failed attempt (e.g. racing a partially-finished jax import) is
    retried on the next call instead of silently disabling counting.
    Caller guarantees ``sys.modules`` has jax (possibly mid-import —
    the submodule import below then just blocks on the import lock)."""
    global _listeners_installed, _listeners_installing
    global _event_registered, _duration_registered, _install_failures
    with _lock:
        if _listeners_installed or _listeners_installing or \
                _install_failures >= _MAX_INSTALL_FAILURES:
            return
        _listeners_installing = True
    try:
        try:
            from jax import monitoring
        except Exception:
            with _lock:
                _install_failures += 1
            return  # retried on the next ensure_listeners/snapshot

        def on_event(name: str, **kw):
            if name.endswith("/cache_hits"):
                key = "cache_hits"
                _pending.cache = "hit"
            elif name.endswith("/cache_misses"):
                key = "cache_misses"
            elif name.endswith("/compile_requests_use_cache"):
                key = "compile_requests"
                _pending.cache = "miss"  # until a hit says otherwise
            else:
                return
            with _lock:
                _counts[key] += 1

        def on_duration(name: str, secs: float, **kw):
            if name.endswith("/backend_compile_duration"):
                entry = {"epoch_ns": time.time_ns(),
                         "seconds": float(secs),
                         "cache": getattr(_pending, "cache", None),
                         "fun_name": kw.get("fun_name")}
                _pending.cache = None
                with _lock:
                    _counts["backend_compiles"] += 1
                    _counts["compile_seconds"] += float(secs)
                    _compile_log.append(entry)

        ok = True
        if not _event_registered:
            try:
                monitoring.register_event_listener(on_event)
                _event_registered = True
            except Exception:
                ok = False
        if not _duration_registered:
            try:
                monitoring.register_event_duration_secs_listener(
                    on_duration)
                _duration_registered = True
            except Exception:
                ok = False
        with _lock:
            if ok:
                _listeners_installed = True
            else:
                _install_failures += 1  # bounded retries of the FAILED half
    finally:
        with _lock:
            _listeners_installing = False


def ensure_listeners() -> bool:
    """Attach the compile-counter listeners as soon as jax is importable
    in this process (idempotent, never imports jax itself).
    ``ensure_compile_cache`` and ``ray_tpu.init`` call this, so on the
    main paths counting starts before the first program compiles;
    workers also call it from their event-flush tick, within ~250ms of
    jax appearing — compiles issued before the attach are not
    retroactively countable."""
    if not jax_loaded():
        return False
    _install_listeners()
    return True


def compile_counts() -> Dict[str, Any]:
    with _lock:
        out = dict(_counts)
    out["compile_seconds"] = round(out["compile_seconds"], 4)
    return out


def compile_log() -> List[Dict[str, Any]]:
    """The last compiles of this process, oldest first (at most
    ``_COMPILE_LOG_MAX``): when each ended (epoch ns), how long it took,
    whether the persistent cache was hit or missed, and the function's
    name where ``jax.monitoring`` passes one."""
    with _lock:
        return [dict(e) for e in _compile_log]


def _stub(ts: float, error: str | None = None) -> Dict[str, Any]:
    snap: Dict[str, Any] = {
        "available": False,
        "platform": None,
        "devices": [],
        "compile": compile_counts(),
        "ts": ts,
        "pid": os.getpid(),
    }
    if error:
        snap["error"] = error
    return snap


def snapshot(force: bool = False) -> Dict[str, Any]:
    """Current device view of THIS process. A stub (``available: False``)
    until the process's own code has initialised a JAX backend (see
    ``backend_initialized``) — pass ``force=True`` to import jax and
    initialise one (drivers/benchmarks that own their chip)."""
    ts = time.time()
    if not force and not backend_initialized():
        return _stub(ts)
    try:
        import jax
    except Exception as e:  # forced on a box without jax
        return _stub(ts, error=repr(e))
    _install_listeners()
    try:
        devices = jax.local_devices()
    except Exception as e:  # backend init failed (no TPU, bad plugin...)
        return _stub(ts, error=repr(e))
    out = []
    for d in devices:
        rec: Dict[str, Any] = {
            "id": getattr(d, "id", -1),
            "platform": getattr(d, "platform", "?"),
            "device_kind": getattr(d, "device_kind", "?"),
            "process_index": getattr(d, "process_index", 0),
            "memory_stats": False,
        }
        try:
            stats = d.memory_stats()
        except Exception:
            stats = None
        if stats:
            rec["memory_stats"] = True
            for k in _MEM_KEYS:
                if k in stats:
                    rec[k] = stats[k]
        out.append(rec)
    return {
        "available": True,
        "platform": out[0]["platform"] if out else None,
        "devices": out,
        "compile": compile_counts(),
        "ts": ts,
        "pid": os.getpid(),
    }


# -- remote profiler capture ---------------------------------------------


def _read_dir(root: str) -> Dict[str, bytes]:
    files: Dict[str, bytes] = {}
    for dirpath, _dirs, names in os.walk(root):
        for name in names:
            path = os.path.join(dirpath, name)
            rel = os.path.relpath(path, root)
            try:
                with open(path, "rb") as f:
                    files[rel] = f.read()
            except OSError:
                continue
    return files


def capture_to_dir(out_dir: str, duration_s: float = 1.0,
                   interval_s: float = 0.01, force_stack: bool = False,
                   worker_id: Optional[str] = None) -> Dict[str, Any]:
    """Profile THIS process for ``duration_s``, writing the trace files
    straight into ``out_dir`` (no bytes held in memory — a TPU trace
    window routinely reaches hundreds of MB, and on a node the agent
    and its workers share the filesystem, so the capture RPC only needs
    to carry the manifest).

    With jax loaded (and ``jax.profiler`` working) this opens a
    ``jax.profiler.trace(out_dir)`` window — XLA host+device activity
    lands there as a TensorBoard-compatible trace directory. Otherwise
    (or on any profiler failure) it degrades to the PR-1 stack sampler.
    Returns ``{kind, files: {relpath: size}, ...}``.
    """
    duration_s = max(0.05, float(duration_s))
    os.makedirs(out_dir, exist_ok=True)
    kind = None
    if not force_stack and jax_loaded():
        try:
            import jax.profiler

            with jax.profiler.trace(out_dir):
                time.sleep(duration_s)
            if any(files for _, _, files in os.walk(out_dir)):
                kind = "jax_profiler"
        except Exception:
            kind = None  # fall through to the stack sampler
    if kind is None:
        from ray_tpu.util import stack_sampler

        prof = stack_sampler.sample(duration_s, interval_s)
        prof["worker_id"] = worker_id
        for name, blob in (
            ("stack_trace.json",
             json.dumps(stack_sampler.chrome_trace(prof)).encode()),
            ("stack_collapsed.txt", stack_sampler.collapsed(prof).encode()),
            ("stack_report.txt", stack_sampler.text_report(prof).encode()),
        ):
            with open(os.path.join(out_dir, name), "wb") as f:
                f.write(blob)
        kind = "stack_sampler"
    files: Dict[str, int] = {}
    for dirpath, _dirs, names in os.walk(out_dir):
        for name in names:
            path = os.path.join(dirpath, name)
            try:
                files[os.path.relpath(path, out_dir)] = \
                    os.path.getsize(path)
            except OSError:
                continue
    return {
        "kind": kind,
        "worker_id": worker_id,
        "pid": os.getpid(),
        "duration_s": duration_s,
        "dir": out_dir,
        "files": files,
    }


def capture(duration_s: float = 1.0, interval_s: float = 0.01,
            force_stack: bool = False,
            worker_id: Optional[str] = None) -> Dict[str, Any]:
    """In-memory variant of :func:`capture_to_dir` — same result shape
    but ``files`` maps relpath to BYTES (callers that can't share a
    filesystem with this process). Prefer capture_to_dir for anything
    that may run on a TPU: traces there don't fit comfortably in one
    in-memory dict."""
    import shutil
    import tempfile

    root = tempfile.mkdtemp(prefix="ray_tpu_tprof_")
    try:
        res = capture_to_dir(root, duration_s, interval_s, force_stack,
                             worker_id)
        res["files"] = _read_dir(root)
        del res["dir"]
        return res
    finally:
        shutil.rmtree(root, ignore_errors=True)


def resolve_capture_path(out_dir: str, name: str) -> Optional[str]:
    """Resolve a capture-relative file name under ``out_dir`` (creating
    parent dirs), or None if the name would escape it. The ONE
    sanitization point for every consumer that writes remote-supplied
    capture names to local disk (write_capture, the client's chunked
    download)."""
    rel = os.path.normpath(name)
    if rel.startswith("..") or os.path.isabs(rel):
        return None
    path = os.path.join(out_dir, rel)
    os.makedirs(os.path.dirname(path) or out_dir, exist_ok=True)
    return path


def write_capture(result: Dict[str, Any], out_dir: str) -> list[str]:
    """Materialize a capture's files under ``out_dir``; returns the
    written paths (capture consumers: CLI, state API)."""
    written = []
    for rel, blob in (result.get("files") or {}).items():
        path = resolve_capture_path(out_dir, rel)
        if path is None:
            continue  # never let a remote path escape out_dir
        with open(path, "wb") as f:
            f.write(blob)
        written.append(path)
    return written
