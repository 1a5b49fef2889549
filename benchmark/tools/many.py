"""Run one cell several times in one call and say how its metrics spread.

    python3 benchmark/tools/many.py --workload <name> --runs 6 --seed-base 1000 \
        [--seconds S] [--trace 0] [--out-dir DIR] [--set key=value ...]

Each run is its own process (``python3 -m benchmark.run``), one after the
other, each with another ``--seed``; this parent never touches JAX, so it
never holds the chip. For every metric it prints the values, the median and
the spread as the builder's contract defines it: the distance between the
first and third quartile (``statistics.quantiles(values, n=4)``) as a share
of the median. ``setup_s`` of the first run (which may compile) is shown
apart. ``--set`` is for sweeps by hand (for example ``--set rate_per_s=6``):
it runs a copy of the benchmark under ``.bench_out/`` whose traffic file for
this cell has that number changed, through the command's own ``--root``.
``--out-dir`` collects each run's own output file (every slice or wait).
"""

from __future__ import annotations

import argparse
import glob
import json
import os
import shutil
import statistics
import subprocess
import sys
import time

REPO = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
sys.path.insert(0, REPO)

from benchmark.stats import spread  # noqa: E402 - no JAX behind it


def root_with(workload: str, sets: list) -> str:
    """A copy of BENCHMARK.json and benchmark/ whose traffic file for
    ``workload`` has the numbers of ``sets`` (``key=number``) changed."""
    tag = "_".join(kv.replace("=", "-") for kv in sets)
    root = os.path.join(REPO, ".bench_out", f"root.{workload}.{tag}")
    shutil.rmtree(root, ignore_errors=True)
    shutil.copytree(os.path.join(REPO, "benchmark"),
                    os.path.join(root, "benchmark"),
                    ignore=shutil.ignore_patterns("__pycache__"))
    shutil.copy(os.path.join(REPO, "BENCHMARK.json"), root)
    with open(os.path.join(root, "BENCHMARK.json")) as f:
        cells = {w["name"]: w for w in json.load(f)["workloads"]}
    path = os.path.join(root, "benchmark", "traffic",
                        cells[workload]["traffic"] + ".json")
    with open(path) as f:
        traffic = json.load(f)
    for kv in sets:
        key, value = kv.split("=", 1)
        traffic[key] = float(value)
    with open(path, "w") as f:
        json.dump(traffic, f)
    return root


def main() -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--workload", required=True)
    ap.add_argument("--runs", type=int, default=6)
    ap.add_argument("--seed-base", type=int, default=2147480000)
    ap.add_argument("--seconds", type=float, default=None)
    ap.add_argument("--trace", type=int, default=0)
    ap.add_argument("--out-dir", default=None)
    ap.add_argument("--set", action="append", default=[])
    args = ap.parse_args()
    root = root_with(args.workload, args.set) if args.set else REPO
    lines, failures = [], 0
    for i in range(args.runs):
        cmd = [sys.executable, "-m", "benchmark.run", "--workload",
               args.workload, "--seed", str(args.seed_base + 7919 * i),
               "--trace", str(args.trace), "--root", root]
        if args.seconds is not None:
            cmd += ["--seconds", str(args.seconds)]
        t0 = time.time()
        proc = subprocess.run(cmd, capture_output=True, text=True, cwd=REPO)
        wall = time.time() - t0
        if args.out_dir:
            os.makedirs(args.out_dir, exist_ok=True)
            for f in glob.glob(os.path.join(
                    root, ".bench_out", f"{args.workload}.seed*.jsonl")):
                shutil.copy(f, args.out_dir)
        last = proc.stdout.strip().splitlines()[-1:] or [""]
        try:
            result = json.loads(last[0])
        except ValueError:
            result = None
        if proc.returncode != 0 or result is None:
            failures += 1
            print(f"run {i}: exit {proc.returncode}, no result; stderr "
                  f"tail:\n{proc.stderr[-3000:]}\nstdout tail:\n"
                  f"{proc.stdout[-1500:]}", flush=True)
            continue
        result["wall_s"] = wall
        lines.append(result)
        print(f"run {i} ({wall:.1f} s wall): {json.dumps(result)}",
              flush=True)
    if args.out_dir and lines:
        os.makedirs(args.out_dir, exist_ok=True)
        name = f"many.{args.workload}.trace{args.trace}.{int(time.time())}.json"
        with open(os.path.join(args.out_dir, name), "w") as f:
            json.dump(lines, f)
    names = sorted({n for r in lines for n in r["metrics"]})
    print(f"\n== {args.workload}: {len(lines)} runs, {failures} failed, "
          f"correct in {sum(r['correct'] for r in lines)}")
    for n in names:
        vals = [r["metrics"][n]["value"] for r in lines if n in r["metrics"]]
        shown = vals
        note = ""
        if n == "setup_s" and len(vals) > 1:
            shown, note = vals[1:], f" (first run {vals[0]:.3f})"
        sp = spread(shown)
        print(f"{n}: median {statistics.median(shown):.6g} spread "
              f"{'n/a' if sp is None else f'{100 * sp:.3f}%'}{note} values "
              f"{[round(v, 4) for v in vals]}")
    if lines:
        print("memory_peak_bytes:",
              [r["device"]["memory_peak_bytes"] for r in lines])
    return 1 if failures else 0


if __name__ == "__main__":
    sys.exit(main())
