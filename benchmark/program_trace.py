"""The program's own annotations and scopes in a profiler trace.

``benchmark/trace.py`` reads what the device did. This module reads, from
the same ``.xplane.pb``, what the PROGRAM said about it:

* host events whose names start with ``bench.``, ``llm.`` or ``train.``
  (``ray_tpu.util.tracing.device_span``: the engine loop's phases, the
  trainer's report), with their attributes, on the profile's clock;
* for the first device only (a four-chip profile takes long enough to
  read as it is), every operation with the ``jax.named_scope`` path the
  program traced it under. The profile's Python reader
  (``jax.profiler.ProfileData``) gives an event's own stats but not those
  of its metadata, where the path is, so this module decodes the
  protobuf itself: the wire format, the few fields it needs, nothing
  installed.

``load(path)`` gives::

    {"host":    [(name, start, end, attrs, thread), ...],
     "ops":     [(name, start, end, scope_path), ...],   # first device
     "modules": [(name, start, end), ...],               # first device
     "window":  (start, end)}                            # bench.window

in seconds on the profile's clock. ``from_json`` builds the same from a
small hand-made file (``benchmark/metrics/fixtures/program_*.json``),
which is how every reader of it is checked. A program that has no such
annotation or scope (the parent of the PR that added them) gives empty
lists and every reader returns ``None``.
"""

from __future__ import annotations

import json
import re
import struct
import time

from benchmark import stats, trace

HOST_PREFIXES = ("bench.", "llm.", "train.")
WINDOW_NAME = "bench.window"
# The scopes the program names (PERF.md section 3 lists them). An
# operation belongs to the innermost one on its path.
SCOPES = ("embed", "ln", "attn_proj", "attn", "cache_write", "mlp",
          "head_loss", "head", "adamw")
UNSCOPED = "(no scope)"     # a path, but none of SCOPES on it
NO_PATH = "(no path)"        # no metadata at all (compiler-made)
# The stat of an operation's metadata that carries its scope path on the
# v5e (found with tools/describe_program_trace.py, PR 24):
# ``jit(step_fn)/while/body/attn/dot_general:``.
SCOPE_STAT = "tf_op"
_PART = re.compile(r"[/()]")


# -- protobuf wire format ----------------------------------------------------


def _varint(buf, i: int):
    out = shift = 0
    while True:
        b = buf[i]
        i += 1
        out |= (b & 0x7F) << shift
        if b < 0x80:
            return out, i
        shift += 7


def fields(buf):
    """``(field number, value)`` of one message: an int for a varint or
    a fixed-width field, a memoryview for a length-delimited one."""
    i, n = 0, len(buf)
    while i < n:
        key, i = _varint(buf, i)
        wire = key & 7
        if wire == 0:
            val, i = _varint(buf, i)
        elif wire == 2:
            size, i = _varint(buf, i)
            val = buf[i:i + size]
            i += size
        elif wire == 1:
            val = int.from_bytes(buf[i:i + 8], "little")
            i += 8
        elif wire == 5:
            val = int.from_bytes(buf[i:i + 4], "little")
            i += 4
        else:
            raise ValueError(f"wire type {wire} at byte {i}")
        yield key >> 3, val


def _text(view) -> str:
    return bytes(view).decode("utf-8", "replace")


def _signed(v: int) -> int:
    return v - (1 << 64) if v >= 1 << 63 else v


def _stat(buf, stat_names: dict):
    """One XStat -> (name, value); a ``ref_value`` names another stat's
    metadata, whose name is the string meant."""
    name, value = None, None
    for no, v in fields(buf):
        if no == 1:
            name = stat_names.get(v, str(v))
        elif no == 2:
            value = struct.unpack("<d", v.to_bytes(8, "little"))[0]
        elif no == 3:
            value = v
        elif no == 4:
            value = _signed(v)
        elif no == 5:
            value = _text(v)
        elif no == 6:
            value = f"<{len(v)} bytes>"
        elif no == 7:
            value = stat_names.get(v, str(v))
    return name, value


def _plane_name(buf) -> str:
    """An XPlane's name alone: the length-delimited fields before it are
    stepped over, not decoded."""
    for no, v in fields(buf):
        if no == 2:
            return _text(v)
    return ""


def _plane_head(buf):
    """Name, raw lines, and the two metadata maps of one XPlane."""
    name, lines, event_meta, stat_names = "", [], {}, {}
    for no, v in fields(buf):
        if no == 2:
            name = _text(v)
        elif no == 3:
            lines.append(v)
        elif no == 4:      # map<int64, XEventMetadata> entry
            for k, entry in fields(v):
                if k == 2:
                    meta = {"name": "", "display": "", "stats": []}
                    for f, x in fields(entry):
                        if f == 1:
                            meta["id"] = x
                        elif f == 2:
                            meta["name"] = _text(x)
                        elif f == 4:
                            meta["display"] = _text(x)
                        elif f == 5:
                            meta["stats"].append(x)
                    event_meta[meta.get("id", 0)] = meta
        elif no == 5:      # map<int64, XStatMetadata> entry
            for k, entry in fields(v):
                if k == 2:
                    sid, sname = 0, ""
                    for f, x in fields(entry):
                        if f == 1:
                            sid = x
                        elif f == 2:
                            sname = _text(x)
                    stat_names[sid] = sname
    return name, lines, event_meta, stat_names


def _line(buf):
    """Name, timestamp (ns) and raw events of one XLine."""
    name, t0_ns, events = "", 0, []
    for no, v in fields(buf):
        if no == 2:
            name = _text(v)
        elif no == 3:
            t0_ns = _signed(v)
        elif no == 4:
            events.append(v)
    return name, t0_ns, events


def _event(buf):
    """(metadata id, offset ps, duration ps, raw stats) of one XEvent."""
    mid = off = dur = 0
    raw = []
    for no, v in fields(buf):
        if no == 1:
            mid = v
        elif no == 2:
            off = v
        elif no == 3:
            dur = v
        elif no == 4:
            raw.append(v)
    return mid, off, dur, raw


def _planes(path: str):
    with open(path, "rb") as f:
        data = memoryview(f.read())
    for no, v in fields(data):
        if no == 1:
            yield v


# -- from a profile to the structure ----------------------------------------


def split_attrs(name: str) -> tuple[str, dict]:
    """``name#k=v,k2=v2#`` (how a ``TraceAnnotation``'s attributes come
    where the collector did not turn them into stats) -> (name, attrs)."""
    if not name.endswith("#") or "#" not in name[:-1]:
        return name, {}
    base, _, tail = name[:-1].partition("#")
    attrs = {}
    for pair in tail.split(","):
        k, eq, v = pair.partition("=")
        if eq:
            attrs[k] = v
    return base, attrs


def scope_of(path: str | None) -> str:
    """The innermost known scope on an operation's path
    (``jit(step)/transpose(jvp(head_loss))/dot_general`` -> ``head_loss``).
    A path with none says what made the operation (``(no scope)
    dynamic_slice``: ``lax.scan`` slicing its stacked operands); an
    operation with no path at all is the compiler's own."""
    if not path:
        return NO_PATH
    parts = [p for p in _PART.split(path.rstrip(":")) if p]
    for part in reversed(parts):
        if part in SCOPES:
            return part
    return f"{UNSCOPED} {parts[-1]}" if parts else NO_PATH


def _scope_path(meta: dict, stat_names: dict) -> str | None:
    for raw in meta["stats"]:
        k, v = _stat(raw, stat_names)
        if k == SCOPE_STAT and isinstance(v, str) and v:
            return v
    return None


def load(path: str) -> dict:
    host, ops, modules = [], [], []
    device_done = False
    for plane in _planes(path):
        pname = _plane_name(plane)
        is_host = pname.startswith("/host:") and pname != "/host:metadata"
        is_device = pname.startswith("/device:TPU:") and not device_done
        if not (is_host or is_device):
            continue    # the later chips' metadata maps are never decoded
        _, lines, event_meta, stat_names = _plane_head(plane)
        if is_host:
            wanted = {mid: m for mid, m in event_meta.items()
                      if m["name"].startswith(HOST_PREFIXES)}
            if not wanted:
                continue
            for n, raw_line in enumerate(lines):
                lname, t0_ns, events = _line(raw_line)
                for raw_ev in events:
                    mid, off, dur, raw_stats = _event(raw_ev)
                    if mid not in wanted:
                        continue
                    name, attrs = split_attrs(wanted[mid]["name"])
                    for rs in raw_stats:
                        k, v = _stat(rs, stat_names)
                        if k is not None and not k.startswith("_"):
                            attrs[k] = v
                    s = t0_ns * 1e-9 + off * 1e-12
                    host.append((name, s, s + dur * 1e-12, attrs,
                                 f"{lname}#{n}"))
        else:
            paths = {}
            for raw_line in lines:
                lname, t0_ns, events = _line(raw_line)
                if lname not in ("XLA Ops", "XLA Modules"):
                    continue
                for raw_ev in events:
                    mid, off, dur, _ = _event(raw_ev)
                    meta = event_meta.get(mid)
                    if meta is None:
                        continue
                    s = t0_ns * 1e-9 + off * 1e-12
                    if lname == "XLA Modules":
                        modules.append((meta["name"], s, s + dur * 1e-12))
                        continue
                    if mid not in paths:
                        paths[mid] = _scope_path(meta, stat_names)
                    ops.append((meta["name"], s, s + dur * 1e-12,
                                paths[mid]))
            device_done = bool(ops or modules)
    return _finish(host, ops, modules)


def from_json(path: str) -> dict:
    """A hand-made trace: host events ``[name, start, duration, attrs,
    thread]``, ops ``[name, start, duration, scope_path]``, modules
    ``[name, start, duration]``; seconds."""
    with open(path) as f:
        raw = json.load(f)
    host = [(n, s, s + d, dict(a), t) for n, s, d, a, t in raw.get("host", [])]
    ops = [(n, s, s + d, p) for n, s, d, p in raw.get("ops", [])]
    modules = [(n, s, s + d) for n, s, d in raw.get("modules", [])]
    return _finish(host, ops, modules)


def _finish(host: list, ops: list, modules: list) -> dict:
    host.sort(key=lambda h: h[1])
    ops.sort(key=lambda o: o[1])
    modules.sort(key=lambda m: m[1])
    window = None
    for name, s, e, _, _ in host:
        if name == WINDOW_NAME:
            window = (s, e)
    if window is None and ops:
        window = (ops[0][1], max(o[2] for o in ops))
    if window is not None:
        lo, hi = window
        ops = [o for o in ops if o[2] > lo and o[1] < hi]
        modules = [m for m in modules if m[2] > lo and m[1] < hi]
    return {"host": host, "ops": ops, "modules": modules, "window": window}


def of_run(run) -> dict | None:
    """The run's profile, read once and kept on the run; how long this
    second reading of the file took goes to the line
    ``program_trace_read``."""
    if getattr(run, "program_trace", None) is None:
        path = getattr(run, "trace_path", None)
        if path is None:
            return None
        t0 = time.perf_counter()
        run.program_trace = pt = load(path)
        run.say("program_trace_read", seconds=time.perf_counter() - t0,
                host_events=len(pt["host"]), ops=len(pt["ops"]))
    return run.program_trace


# -- reductions the readers share ---------------------------------------------


def spans(pt: dict, name: str) -> list:
    """Host events of that name, whole inside the traced window."""
    if pt["window"] is None:
        return []
    lo, hi = pt["window"]
    return [h for h in pt["host"]
            if h[0] == name and h[1] >= lo and h[2] <= hi]


def span_ms(pt: dict, name: str) -> list:
    return [(h[2] - h[1]) * 1e3 for h in spans(pt, name)]


def step_turns(pt: dict) -> list:
    """One dict per turn of the engine loop that ran a decode step: the
    milliseconds of ``llm.step.select``, ``.dispatch``, ``.sync`` and
    ``.fanout``, in the order the loop opens them on its thread."""
    order = ("llm.step.select", "llm.step.dispatch", "llm.step.sync",
             "llm.step.fanout")
    by_thread: dict = {}
    for h in pt["host"]:
        if h[0] in order:
            by_thread.setdefault(h[4], []).append(h)
    lo, hi = pt["window"] if pt["window"] else (float("-inf"), float("inf"))
    turns = []
    for events in by_thread.values():
        cur: dict = {}
        for name, s, e, _, _ in events:
            if name == order[0]:
                cur = {"start": s}
            cur[name] = (e - s) * 1e3
            if name == order[-1]:
                if all(k in cur for k in order) and cur["start"] >= lo \
                        and e <= hi:
                    turns.append(cur)
                cur = {}
    return turns


def idle_gaps(pt: dict) -> list:
    """Gaps ``(start, end)`` of the first device inside the window, as
    ``trace.idle_gaps_by_span`` cuts them."""
    if pt["window"] is None or not pt["ops"]:
        return []
    lo, hi = pt["window"]
    busy = stats.merge(trace.clip([(s, e) for _, s, e, _ in pt["ops"]],
                                  lo, hi))
    gaps, cur = [], lo
    for s, e in busy:
        if s > cur:
            gaps.append((cur, s))
        cur = max(cur, e)
    if cur < hi:
        gaps.append((cur, hi))
    return gaps


def idle_by_span(pt: dict, prefix: str = "llm.") -> dict | None:
    """Idle seconds of the first device (gaps of ``trace.SHORT_GAP_S``
    and more) by the program span that lies over them. Spans nest only
    on other threads (the loop's own do not), so a stretch of a gap is
    given to the span that covers it and started last; what no span
    covers is ``unattributed``. None where there is no such span."""
    mine = [h for h in pt["host"] if h[0].startswith(prefix)]
    if not mine or pt["window"] is None:
        return None
    totals: dict = {}
    for gs, ge in idle_gaps(pt):
        if ge - gs < trace.SHORT_GAP_S:
            continue
        over = [h for h in mine if h[2] > gs and h[1] < ge]
        # cut the gap at every span edge; each piece goes to the span
        # over it that started last
        edges = sorted({gs, ge, *[min(max(x, gs), ge)
                                  for h in over for x in (h[1], h[2])]})
        for a, b in zip(edges, edges[1:]):
            if b <= a:
                continue
            cover = [h for h in over if h[1] <= a and h[2] >= b]
            name = max(cover, key=lambda h: h[1])[0] if cover \
                else "unattributed"
            totals[name] = totals.get(name, 0.0) + b - a
    return totals


def read_idle_attributed(run):
    """The reader behind every ``serve_idle_attributed_pct.*`` metric:
    percent of the first device's idle seconds (long gaps) that lie
    under an ``llm.*`` annotation; the seconds by span name go to the
    earlier line ``idle_by_program_span``."""
    pt = of_run(run)
    totals = None if pt is None else idle_by_span(pt)
    if not totals:
        return None
    idle = sum(totals.values())
    if idle <= 0:
        return None
    run.say("idle_by_program_span", idle_s=idle, seconds={
        k: v for k, v in sorted(totals.items(), key=lambda kv: -kv[1])})
    return 100.0 * (idle - totals.get("unattributed", 0.0)) / idle


_HOLDERS = ("while", "conditional", "call")


def program_ops(pt: dict, program: str | None):
    """The first device's operations that ran whole inside an execution
    of ``program`` (all of them where None), without the ``while`` /
    ``conditional`` / ``call`` operations that only hold others: their
    children are there themselves."""
    runs = None if program is None else [
        (s, e) for n, s, e in pt["modules"] if program in n]
    i = 0
    for op in pt["ops"]:           # ops and runs are both sorted by start
        name, s, e, _ = op
        if runs is not None:
            while i < len(runs) and runs[i][1] < s:
                i += 1
            if i == len(runs):
                return
            if not (runs[i][0] <= s and e <= runs[i][1]):
                continue
        if trace.opcode_of(name) not in _HOLDERS:
            yield op


def busy_by_scope(pt: dict, program: str | None = None) -> dict | None:
    """Busy seconds of the first device by named scope, for the
    operations of ``program``. None where no operation of the profile
    lies under any of ``SCOPES`` (a program that names none: its
    operations still carry paths, ``jit(step)/while/body/dot_general``)."""
    if "scoped" not in pt:
        pt["scoped"] = any(scope_of(o[3]) in SCOPES for o in pt["ops"])
    if not pt["scoped"]:
        return None
    totals: dict = {}
    for _, s, e, path in program_ops(pt, program):
        key = scope_of(path)
        totals[key] = totals.get(key, 0.0) + e - s
    return totals or None


def scope_share(run, scopes: tuple, program: str | None, event: str):
    """Percent of ``program``'s busy time under ``scopes``; says the
    whole table on an earlier line."""
    pt = of_run(run)
    table = None if pt is None else busy_by_scope(pt, program)
    if not table:
        return None
    total = sum(table.values())
    if total <= 0:
        return None
    said = run.raw.setdefault("said_by_scope", set())
    if event not in said:    # two metrics of one program share the table
        said.add(event)
        run.say(event, program=program, busy_s=total, by_scope_pct={
            k: 100.0 * v / total for k, v in
            sorted(table.items(), key=lambda kv: -kv[1])},
            unscoped_ops=top_unscoped(pt, program))
    return 100.0 * sum(table.get(s, 0.0) for s in scopes) / total


def top_unscoped(pt: dict, program: str | None, n: int = 6) -> list:
    """The operations that carry no scope and took most time."""
    totals: dict = {}
    for name, s, e, path in program_ops(pt, program):
        if scope_of(path) not in SCOPES:
            short = trace.own_name(name)[:60]
            totals[short] = totals.get(short, 0.0) + e - s
    return [[k, v] for k, v in
            sorted(totals.items(), key=lambda kv: -kv[1])[:n]]


def clock_anchors(pt: dict, name: str = "llm.step.dispatch") -> dict | None:
    """The offset between the span store's clock (``time.time_ns``) and
    the profile's, from every annotation that carries ``epoch_ns``: the
    median, and how far the anchors spread around it."""
    offs = []   # whole nanoseconds: a float of epoch seconds is too coarse
    for h in pt["host"]:
        if h[0] == name and "epoch_ns" in h[3]:
            offs.append(int(h[3]["epoch_ns"]) - round(h[1] * 1e9))
    if not offs:
        return None
    med = int(stats.median(offs))
    dev = sorted(abs(o - med) for o in offs)
    return {"anchors": len(offs), "offset_ns": med,
            "spread_p50_us": dev[len(dev) // 2] * 1e-3,
            "spread_p99_us": dev[min(len(dev) - 1,
                                     int(0.99 * len(dev)))] * 1e-3,
            "spread_max_us": dev[-1] * 1e-3}


def describe(path: str, limit: int = 8) -> dict:
    """What the metadata of a trace holds, for reading one by hand: for
    every plane its stat names, and for its first lines' first events the
    metadata's own stats (which ``trace.describe`` cannot see)."""
    out = []
    for plane in _planes(path):
        pname, lines, event_meta, stat_names = _plane_head(plane)
        entry = {"plane": pname, "event_metadata": len(event_meta),
                 "stat_names": sorted(set(stat_names.values()))[:80],
                 "lines": []}
        for raw_line in lines[:12]:
            lname, t0_ns, events = _line(raw_line)
            first = []
            for raw_ev in events[:limit]:
                mid, off, dur, raw_stats = _event(raw_ev)
                meta = event_meta.get(mid, {"name": "?", "display": "",
                                            "stats": []})
                first.append({
                    "name": meta["name"][:160], "display": meta["display"][:80],
                    "dur_ps": dur,
                    "stats": {str(k): str(v)[:200] for k, v in (
                        _stat(r, stat_names) for r in raw_stats)},
                    "metadata_stats": {str(k): str(v)[:300] for k, v in (
                        _stat(r, stat_names) for r in meta["stats"])}})
            entry["lines"].append({"line": lname, "events": len(events),
                                   "first": first})
        out.append(entry)
    return {"planes": out}
