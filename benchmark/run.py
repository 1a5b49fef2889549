"""Run one cell of the benchmark once.

    python -m benchmark.run --workload <name> --seed <n> --seconds <s> --trace <0|1>

One process holds the cell's chips. It finds everything by name, from
``BENCHMARK.json`` at the root of the checkout:

* the cell: its entry in ``workloads`` and ``benchmark/cells/<name>.json``
  (batch or engine settings; a cell that names a ``deployment`` takes the
  settings of ``benchmark/deployments/<that>.json``, which several cells
  can share, under its own);
* the configuration: the ``file`` its entry in ``configs`` names, whose
  ``family`` picks ``benchmark/families/<family>.py`` (how the system is
  built from the file, and every count of parameters, bytes and
  operations that is that architecture's) and
  ``benchmark/reference/<family>.py``;
* the traffic mix: ``benchmark/traffic/<name>.json``, whose ``kind`` picks
  ``benchmark/kinds/<kind>.py`` (the generator and the measuring loop);
* each metric: ``benchmark/metrics/<name>.py``, one ``read(run)`` from the
  run's spans, counters, trace and raw timings to one number, or None
  where there is nothing to read (the metric is then left out).

There is no ``if`` on a cell's, configuration's, metric's or family's name
anywhere in the harness, and no formula or configuration key of one family
in a kind or a reader: a later PR adds files and entries, of any family,
and edits nothing. ``benchmark/families/README.md`` lists what a family's
two files define and who calls each function.

The last line of standard output is one JSON object: ``correct``,
``attempted``, ``failed``, ``metrics``, ``device`` and, in a traced run,
``breakdown``. Without a TPU (or with fewer chips than the cell asks
for) nothing is printed to standard output and the exit code is 2.
``--rehearsal`` runs the same code on CPU devices to find faults before
chip time is spent: it says so, prints no metric value, and is not what
the driver runs.
"""

from __future__ import annotations

import argparse
import contextlib
import gc
import json
import os
import sys
import time

from benchmark.loading import load_json, load_module

PKG_DIR = os.path.dirname(os.path.abspath(__file__))
COMPILE_EVENT = "/jax/core/compile/backend_compile_duration"


class Run:
    """One run of one cell: what was asked, and what was seen."""

    def __init__(self, root: str, workload: str, seed: int, seconds: float,
                 trace: bool, rehearsal: bool = False):
        self.t_start = time.perf_counter()
        self.root = os.path.abspath(root)
        self.bench_dir = os.path.join(self.root, "benchmark")
        self.spec = load_json(os.path.join(self.root, "BENCHMARK.json"))
        cells = {w["name"]: w for w in self.spec["workloads"]}
        if workload not in cells:
            raise SystemExit(f"no workload {workload!r} in BENCHMARK.json "
                             f"(have {sorted(cells)})")
        self.cell = cells[workload]
        self.chips = int(self.cell["chips"])
        self.params = load_json(
            os.path.join(self.bench_dir, "cells", workload + ".json"))
        if "deployment" in self.params:
            self.params = {**load_json(os.path.join(
                self.bench_dir, "deployments",
                self.params["deployment"] + ".json")), **self.params}
        config_entry = {c["name"]: c for c in self.spec["configs"]}[
            self.cell["config"]]
        self.config = load_json(os.path.join(self.root, config_entry["file"]))
        self.traffic = load_json(os.path.join(
            self.bench_dir, "traffic", self.cell["traffic"] + ".json"))
        self.family = load_module(os.path.join(
            self.bench_dir, "families", self.config["family"] + ".py"))
        self.reference = load_module(os.path.join(
            self.bench_dir, "reference", self.config["family"] + ".py"))
        self.kind = load_module(os.path.join(
            self.bench_dir, "kinds", self.traffic["kind"] + ".py"))
        self.seed = int(seed)
        self.seconds = float(seconds)
        self.trace_on = bool(trace)
        self.rehearsal = rehearsal
        self.out_dir = os.path.join(self.root, ".bench_out")
        os.makedirs(self.out_dir, exist_ok=True)
        tag = f"{workload}.seed{self.seed}.trace{int(self.trace_on)}"
        self.log_path = os.path.join(self.out_dir, tag + ".jsonl")
        self.trace_dir = os.path.join(self.out_dir, tag + ".profile")
        self._log = open(self.log_path, "w")
        self.devices: list = []
        self.device_kind = None
        self.spans: list = []        # (name, start_ns, end_ns, attrs)
        self.counters: dict = {}     # program counters, by the kind
        self.raw: dict = {}          # the kind's own timings
        self.checks: list = []       # (name, ok, detail)
        self.attempted = 0
        self.failed = 0
        self.setup_s = None
        self.window_ns = None        # (open, close), perf_counter_ns
        self.compiles_in_window = 0
        self._in_window = False
        self._tracing = False
        self.trace = None            # benchmark.trace structure
        self.trace_window_s = None
        self.rest_bytes: list = []

    # -- what the kinds call -----------------------------------------------

    def say(self, event: str, **fields) -> None:
        """An earlier line of output, also kept in the run's file."""
        line = json.dumps({"event": event, "t": round(
            time.perf_counter() - self.t_start, 3), **fields}, default=str)
        print(f"[bench] {line}", flush=True)
        self._log.write(line + "\n")
        self._log.flush()

    @contextlib.contextmanager
    def span(self, name: str, **attrs):
        """A host span around one of the benchmark's own calls; while the
        profiler runs it is also written into the profiler's trace."""
        ann = None
        if self._tracing:
            import jax

            ann = jax.profiler.TraceAnnotation(name)
            ann.__enter__()
        t0 = time.perf_counter_ns()
        try:
            yield
        finally:
            t1 = time.perf_counter_ns()
            if ann is not None:
                ann.__exit__(None, None, None)
            self.spans.append((name, t0, t1, attrs))

    def check(self, name: str, ok: bool, detail="") -> bool:
        self.checks.append((name, bool(ok), detail))
        if not ok:
            self.say("check_failed", check=name, detail=detail)
        return bool(ok)

    def rng(self, *stream):
        """A numpy generator from ``--seed`` (any whole number) and a
        stream label, so that each use draws independently."""
        import numpy as np

        return np.random.default_rng([self.seed, *[
            int.from_bytes(str(s).encode()[:8], "little") for s in stream]])

    def open_window(self) -> None:
        """Set-up is over. Its garbage is put out of the collector's way
        (the collector itself stays on, as it is for users)."""
        gc.collect()
        gc.freeze()
        self.setup_s = time.perf_counter() - self.t_start
        self.say("window_open", setup_s=self.setup_s)
        self._in_window = True
        self.compiles_in_window = 0
        self._window_open_ns = time.perf_counter_ns()

    def close_window(self) -> None:
        self.window_ns = (self._window_open_ns, time.perf_counter_ns())
        self._in_window = False
        # what the cell's own state holds on each chip, for the peak
        self.rest_bytes = [int((d.memory_stats() or {}).get(
            "bytes_in_use", 0)) for d in self.devices]

    @property
    def window_s(self) -> float:
        return (self.window_ns[1] - self.window_ns[0]) * 1e-9

    def start_trace(self) -> None:
        import jax

        opts = jax.profiler.ProfileOptions()
        opts.python_tracer_level = 0   # the Python tracer slows the host
        opts.host_tracer_level = 2     # TraceAnnotation spans
        jax.profiler.start_trace(self.trace_dir, profiler_options=opts)
        self._tracing = True
        self._trace_ann = jax.profiler.TraceAnnotation("bench.window")
        self._trace_ann.__enter__()
        self._trace_t0 = time.perf_counter()

    def stop_trace(self) -> None:
        import jax

        self.trace_window_s = time.perf_counter() - self._trace_t0
        self._trace_ann.__exit__(None, None, None)
        self._tracing = False
        jax.profiler.stop_trace()

    # -- the harness's own -------------------------------------------------

    def _on_duration(self, event: str, duration: float, **_) -> None:
        if event == COMPILE_EVENT and self._in_window:
            self.compiles_in_window += 1
            self.say("compile_in_window", seconds=duration)

    def take_devices(self) -> bool:
        import jax

        if not self.rehearsal:
            # Every program goes to the persistent cache, however quick
            # its compile, so that only a checkout's first run compiles.
            jax.config.update(
                "jax_persistent_cache_min_compile_time_secs", 0)
            jax.config.update(
                "jax_persistent_cache_min_entry_size_bytes", 0)
        jax.monitoring.register_event_duration_secs_listener(
            self._on_duration)
        devices = jax.devices()
        platform = devices[0].platform
        wanted = "cpu" if self.rehearsal else "tpu"
        if platform != wanted or len(devices) < self.chips:
            print(f"[bench] need {self.chips} {wanted} device(s), found "
                  f"{len(devices)} of platform {platform!r}: nothing was "
                  f"run", file=sys.stderr)
            return False
        self.devices = devices[:self.chips]
        self.device_kind = devices[0].device_kind
        self.all_devices = len(devices)
        return True

    def read_trace(self) -> None:
        from benchmark import trace as tr

        path = tr.newest_xplane(self.trace_dir)
        if path is None:
            self.say("no_trace_file", dir=self.trace_dir)
            return
        self.trace_path = path
        self.trace = tr.load_xplane(path)

    def memory_peak_bytes(self) -> int:
        """Peak bytes on the fullest chip. The allocator's ``peak_bytes_in_
        use`` leaves a running program's temporary space out on this
        runtime (1.64 GB under a 9.09 GB-temp step, PR 21); that space is
        what it calls reserved. So the peak is the larger of the allocator's
        own peak and the bytes the cell's state held when the window
        closed plus the largest reservation any program made."""
        peak = 0
        for d, rest in zip(self.devices, self.rest_bytes):
            st = d.memory_stats() or {}
            peak = max(peak, int(st.get("peak_bytes_in_use", 0)),
                       rest + int(st.get("peak_bytes_reserved", 0)))
        self.say("memory", rest_bytes=self.rest_bytes,
                 device0=self.devices[0].memory_stats())
        return peak

    def metric_values(self) -> dict:
        listed = self.spec["per_layer" if self.trace_on else "end_to_end"]
        out = {}
        for m in listed:
            if "workloads" in m and self.cell["name"] not in m["workloads"]:
                continue
            reader = load_module(os.path.join(
                self.bench_dir, "metrics", m["name"] + ".py"))
            try:
                value = reader.read(self)
            except KeyError as e:
                if not self.rehearsal:
                    raise
                # e.g. no published peak for a CPU: names only, no values
                self.say("metric_unreadable_in_rehearsal", metric=m["name"],
                         error=str(e))
                continue
            if value is not None:
                out[m["name"]] = {"value": float(value), "unit": m["unit"]}
        return out

    def result(self) -> dict:
        from benchmark import trace as tr

        correct = all(ok for _, ok, _ in self.checks) and bool(self.checks)
        device = {"platform": self.devices[0].platform,
                  "kind": self.device_kind, "count": self.all_devices,
                  "memory_peak_bytes": self.memory_peak_bytes()}
        out = {"correct": correct, "attempted": self.attempted,
               "failed": self.failed, "metrics": self.metric_values(),
               "device": device}
        if self.trace_on and self.trace is not None \
                and self.trace["window"] is not None:
            device["busy_s"] = tr.busy_seconds(self.trace)
            device["window_s"] = tr.window_length(self.trace)
            out["breakdown"] = {
                "device_ops": tr.top_ops(self.trace),
                "idle_gaps": tr.idle_gaps_by_span(self.trace)}
        return out


def run_cell(args) -> tuple[int, Run | None, dict | None]:
    run = Run(args.root, args.workload, args.seed, args.seconds,
              bool(args.trace), args.rehearsal)
    if not run.take_devices():
        return 2, run, None
    run.say("start", workload=args.workload, seed=run.seed,
            seconds=run.seconds, trace=run.trace_on,
            rehearsal=run.rehearsal, device_kind=run.device_kind,
            devices=run.all_devices)
    try:
        run.kind.run(run)
        if run.trace_on:
            run.read_trace()
        run.check("no_compile_in_window", run.compiles_in_window == 0,
                  f"{run.compiles_in_window} compilations inside the window")
        result = run.result()
    finally:
        gc.unfreeze()
    run.say("checks", checks=[[n, ok, str(d)[:300]]
                              for n, ok, d in run.checks])
    run._log.close()
    return 0, run, result


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--seconds", type=float, default=None)
    ap.add_argument("--trace", type=int, default=0, choices=(0, 1))
    ap.add_argument("--root", default=os.path.dirname(PKG_DIR),
                    help="checkout that holds BENCHMARK.json (default: "
                         "the one this file is in)")
    ap.add_argument("--rehearsal", action="store_true",
                    help="run on CPU devices to find faults; prints no "
                         "metric value and is not evidence for chips")
    args = ap.parse_args(argv)
    if args.seconds is None:
        args.seconds = load_json(
            os.path.join(args.root, "BENCHMARK.json"))["run_seconds"]
    code, run, result = run_cell(args)
    if result is None:
        return code
    if run.rehearsal:
        # A CPU run says nothing about the device: names, not values.
        result["rehearsal"] = {"platform": "cpu",
                               "metric_names": sorted(result["metrics"])}
        result["metrics"] = {}
        result.pop("breakdown", None)
    print(json.dumps(result), flush=True)
    return code


if __name__ == "__main__":
    sys.exit(main())
