"""From a profiler trace to intervals the metric readers can reduce.

``load_xplane`` reads the ``.xplane.pb`` that ``jax.profiler`` writes, with
nothing but JAX (``jax.profiler.ProfileData``). ``from_json`` builds the
same structure from a small hand-made file, which is how every reducer is
checked (``benchmark/metrics/fixtures``). All times are seconds on the
profile's own clock; host spans (``jax.profiler.TraceAnnotation``) are on
that clock too, so a device gap can be attributed to what the host did.

Structure::

    {"devices": [{"name": "/device:TPU:0",
                  "ops":     [(name, start, end, category), ...],
                  "async":   [(name, start, end, category), ...],
                  "modules": [(name, start, end), ...]}, ...],
     "host": [(name, start, end), ...],          # bench.* annotations
     "window": (start, end)}                     # the traced window
"""

from __future__ import annotations

import glob
import json
import os
import re

from benchmark import stats

COLLECTIVE_OPCODES = (
    "all-gather", "all-reduce", "reduce-scatter", "all-to-all",
    "collective-permute", "collective-broadcast")
# Gaps shorter than this are the device's own pauses between operations,
# not something the host did.
SHORT_GAP_S = 20e-6
_OPCODE_RE = re.compile(r"=\s*[^=]*?\s([a-z][a-z0-9\-]*)\(")


def opcode_of(name: str, event_stats: dict | None = None) -> str:
    """HLO opcode of a device op event: the profiler's own category where
    it gives one, else parsed from the HLO text or the instruction name
    (``fusion.12`` -> ``fusion``, ``all-gather-start.3`` ->
    ``all-gather-start``)."""
    event_stats = event_stats or {}
    for key in ("hlo_category", "category"):
        if event_stats.get(key):
            return str(event_stats[key])
    text = event_stats.get("hlo_op") or name
    m = _OPCODE_RE.search(str(text))
    if m:
        return m.group(1)
    base = str(text).lstrip("%").split(" ", 1)[0]
    return re.sub(r"[._]\d+$", "", base)


def own_name(name: str) -> str:
    """The instruction's own name: an event's name may be its whole HLO
    text, whose operands must not be matched."""
    return str(name).split(" = ", 1)[0]


def is_collective(category: str, name: str) -> bool:
    text = f"{category} {own_name(name)}".lower()
    return any(op in text for op in COLLECTIVE_OPCODES)


def is_custom_call(category: str, name: str) -> bool:
    text = str(category).lower()
    return "custom-call" in text or "custom_call" in text


def newest_xplane(log_dir: str) -> str | None:
    found = sorted(glob.glob(os.path.join(
        log_dir, "plugins", "profile", "*", "*.xplane.pb")))
    return found[-1] if found else None


def load_xplane(path: str, window_name: str = "bench.window",
                host_prefix: str = "bench.") -> dict:
    from jax.profiler import ProfileData

    data = ProfileData.from_file(path)
    devices, host = [], []
    for plane in data.planes:
        if plane.name.startswith("/device:TPU:"):
            dev = {"name": plane.name, "ops": [], "async": [],
                   "modules": []}
            for line in plane.lines:
                # "XLA Ops" is the core's own sequence of operations;
                # "Async XLA Ops" holds copies and collectives in flight
                # beside it, from their start to their done.
                if line.name in ("XLA Ops", "Async XLA Ops"):
                    into = dev["ops" if line.name == "XLA Ops" else "async"]
                    for ev in line.events:
                        s = ev.start_ns * 1e-9
                        into.append(
                            (ev.name, s, s + ev.duration_ns * 1e-9,
                             opcode_of(ev.name)))
                elif line.name == "XLA Modules":
                    for ev in line.events:
                        s = ev.start_ns * 1e-9
                        dev["modules"].append(
                            (ev.name, s, s + ev.duration_ns * 1e-9))
            if dev["ops"] or dev["modules"]:
                devices.append(dev)
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                for ev in line.events:
                    if ev.name.startswith(host_prefix):
                        s = ev.start_ns * 1e-9
                        host.append((ev.name, s, s + ev.duration_ns * 1e-9))
    return _finish(devices, host, window_name)


def from_json(path: str) -> dict:
    """A hand-made trace: ops as ``[name, start, duration, category]``,
    modules and host spans as ``[name, start, duration]``, seconds."""
    with open(path) as f:
        raw = json.load(f)
    devices = [{
        "name": d.get("name", f"/device:TPU:{i}"),
        "ops": [(n, s, s + dur, c) for n, s, dur, c in d.get("ops", [])],
        "async": [(n, s, s + dur, c) for n, s, dur, c in d.get("async", [])],
        "modules": [(n, s, s + dur) for n, s, dur in d.get("modules", [])],
    } for i, d in enumerate(raw["devices"])]
    host = [(n, s, s + dur) for n, s, dur in raw.get("host", [])]
    out = _finish(devices, host, "bench.window")
    if "window" in raw:
        out["window"] = tuple(raw["window"])
    return out


def _finish(devices: list, host: list, window_name: str) -> dict:
    for d in devices:
        d["ops"].sort(key=lambda o: o[1])
        d["async"].sort(key=lambda o: o[1])
        d["modules"].sort(key=lambda m: m[1])
    host.sort(key=lambda h: h[1])
    window = None
    for name, s, e in host:
        if name == window_name:
            window = (s, e)
    if window is None:
        starts = [d["ops"][0][1] for d in devices if d["ops"]]
        ends = [max(o[2] for o in d["ops"]) for d in devices if d["ops"]]
        if starts:
            window = (min(starts), max(ends))
    if window is not None:
        lo, hi = window
        for d in devices:
            d["ops"] = [o for o in d["ops"] if o[2] > lo and o[1] < hi]
            d["async"] = [o for o in d["async"] if o[2] > lo and o[1] < hi]
            d["modules"] = [m for m in d["modules"]
                            if m[2] > lo and m[1] < hi]
    return {"devices": devices, "host": host, "window": window}


# -- reductions the readers share -------------------------------------------


def clip(intervals, lo: float, hi: float) -> list:
    return [(max(s, lo), min(e, hi)) for s, e in intervals
            if e > lo and s < hi]


def window_length(trace: dict) -> float:
    lo, hi = trace["window"]
    return hi - lo


def busy_seconds(trace: dict) -> float | None:
    """Seconds in which an operation ran, averaged over devices."""
    if not trace["devices"] or trace["window"] is None:
        return None
    lo, hi = trace["window"]
    per_dev = [stats.union_length(clip(
        [(s, e) for _, s, e, _ in d["ops"]], lo, hi))
        for d in trace["devices"]]
    return sum(per_dev) / len(per_dev)


def idle_pct(trace: dict) -> float | None:
    busy = busy_seconds(trace)
    if busy is None or window_length(trace) <= 0:
        return None
    return 100.0 * (1.0 - busy / window_length(trace))


def read_idle_pct(run) -> float | None:
    """The reader behind every ``*_device_idle_pct*`` metric. The quantity
    is one; BENCHMARK.json lists it under a name per end-to-end metric it
    moves, because ``moves`` names exactly one."""
    return None if run.trace is None else idle_pct(run.trace)


def program_runs(dev: dict, program: str | None) -> list:
    """Executions ``(start, end)`` of the named program on one device,
    from the modules line; whole runs only (not cut by the window)."""
    return [(s, e) for name, s, e in dev["modules"]
            if program is None or program in name]


def ops_within(dev: dict, lo: float, hi: float) -> list:
    return [o for o in dev["ops"] if o[1] >= lo and o[2] <= hi]


def per_run_busy(trace: dict, program: str | None) -> list:
    """Busy seconds of each whole execution of ``program``, all devices."""
    out = []
    lo, hi = trace["window"]
    for dev in trace["devices"]:
        for s, e in program_runs(dev, program):
            if s < lo or e > hi:
                continue
            out.append(stats.union_length(
                [(a, b) for _, a, b, _ in ops_within(dev, s, e)]))
    return out


def run_gaps(trace: dict, program: str | None) -> list:
    """Seconds between one execution's last operation and the next one's
    first, on each device."""
    out = []
    for dev in trace["devices"]:
        runs = program_runs(dev, program)
        edges = []
        for s, e in runs:
            ops = ops_within(dev, s, e)
            if ops:
                edges.append((ops[0][1], max(o[2] for o in ops)))
        out.extend(b[0] - a[1] for a, b in zip(edges, edges[1:]))
    return out


def top_ops(trace: dict, n: int = 10) -> list:
    """The device operations that took most time (first device):
    totals by opcode, then single operations by name."""
    if not trace["devices"]:
        return []
    by_cat, by_name = {}, {}
    for name, s, e, cat in trace["devices"][0]["ops"]:
        by_cat[f"opcode:{cat}"] = by_cat.get(f"opcode:{cat}", 0.0) + e - s
        short = own_name(name)[:80]
        by_name[short] = by_name.get(short, 0.0) + e - s
    cats = sorted(by_cat.items(), key=lambda kv: -kv[1])[:4]
    names = sorted(by_name.items(), key=lambda kv: -kv[1])[:n - len(cats)]
    return [[k, v] for k, v in cats + names]


def idle_gaps_by_span(trace: dict, n: int = 10,
                      skip: tuple = ("bench.window",)) -> list:
    """Idle seconds of the first device, by the benchmark's host span
    that covers most of each gap (``unattributed`` where none does)."""
    if not trace["devices"] or trace["window"] is None:
        return []
    lo, hi = trace["window"]
    busy = stats.merge(clip(
        [(s, e) for _, s, e, _ in trace["devices"][0]["ops"]], lo, hi))
    gaps, cur = [], lo
    for s, e in busy:
        if s > cur:
            gaps.append((cur, s))
        cur = max(cur, e)
    if cur < hi:
        gaps.append((cur, hi))
    spans = [h for h in trace["host"] if h[0] not in skip]
    totals = {}
    for gs, ge in gaps:
        if ge - gs < SHORT_GAP_S:
            totals["between_ops"] = totals.get("between_ops", 0.0) + ge - gs
            continue
        best, best_cover = "unattributed", 0.0
        for name, s, e in spans:
            if e <= gs:
                continue
            if s >= ge:
                break
            cover = min(e, ge) - max(s, gs)
            if cover > best_cover:
                best, best_cover = name, cover
        totals[best] = totals.get(best, 0.0) + ge - gs
    return [[k, v] for k, v in
            sorted(totals.items(), key=lambda kv: -kv[1])[:n]]


def describe(path: str, limit: int = 12) -> dict:
    """What a trace file holds, for reading one by hand: every plane and
    line with its event count and its first events with their stats."""
    from jax.profiler import ProfileData

    out = []
    for plane in ProfileData.from_file(path).planes:
        lines = []
        for line in plane.lines:
            evs = list(line.events)
            lines.append({
                "line": line.name, "events": len(evs),
                "first": [{"name": str(e.name)[:300], "start_ns": e.start_ns,
                           "dur_ns": e.duration_ns,
                           "stats": {k: str(v)[:200] for k, v in
                                     (dict(e.stats) if e.stats else {}
                                      ).items()}}
                          for e in evs[:limit]]})
        out.append({"plane": plane.name, "lines": lines})
    return {"planes": out}
