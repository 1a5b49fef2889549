"""What an open-loop serving cell's ``ttft_p90_ms`` would read, from its
files alone: the engine's loop (admit up to ``prefill_rows`` queued requests
into free slots and prefill them, then one decode step for every live slot)
run on the cell's fixed schedule with a prefill batch of ``--prefill-ms``
and a decode step of ``--step-ms``, at every host speed of ``--speeds``.
No chip, no engine, no model: it tells how far the reading moves when the
host runs one per cent faster or slower, and where it jumps, before chip
time is spent on a schedule (PERF.md section 6, PR 24's third session: at
170.3 and 86.4 ms it gives the prompt cell's 88 waits within 15 ms).

    python3 benchmark/tools/simulate_open_loop.py serve_gpt2xl_prompt_rate \\
        --prefill-ms 170.3 --step-ms 86.4 --speeds 0.97:1.04:0.0025
"""

from __future__ import annotations

import argparse
import json
import os
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
sys.path.insert(0, ROOT)

from benchmark import stats  # noqa: E402
from benchmark.loading import load_json, load_module  # noqa: E402


def cell_files(workload: str, root: str = ROOT):
    """The traffic and engine settings ``run.py`` would give the cell."""
    bench = os.path.join(root, "benchmark")
    spec = load_json(os.path.join(root, "BENCHMARK.json"))
    cell = {w["name"]: w for w in spec["workloads"]}[workload]
    params = load_json(os.path.join(bench, "cells", workload + ".json"))
    if "deployment" in params:
        params = {**load_json(os.path.join(
            bench, "deployments", params["deployment"] + ".json")), **params}
    traffic = load_json(os.path.join(
        bench, "traffic", cell["traffic"] + ".json"))
    return traffic, params["engine"], spec["run_seconds"]


def waits_ms(offsets, asked, engine: dict, prefill_s: float, step_s: float,
             lead_s: float, window_s: float, handle_s: float = 0.001):
    """Due-to-first-chunk of every request due inside the window, in due
    order. ``handle_s`` is the client's share (send, wake-up)."""
    slots, rows = engine["max_batch"], engine["prefill_rows"]
    n, nxt, t = len(offsets), 0, 0.0
    queue, live, first = [], [], {}
    while (nxt < n or queue or live) and t < lead_s + window_s + 60:
        while nxt < n and offsets[nxt] <= t:
            queue.append(nxt)
            nxt += 1
        did = False
        take = min(slots - len(live), rows, len(queue))
        if take > 0:
            batch, queue = queue[:take], queue[take:]
            t += prefill_s
            for i in batch:
                first[i] = t + handle_s
                if asked[i] > 1:
                    live.append(asked[i] - 1)
            did = True
        if live:
            t += step_s
            live = [r - 1 for r in live if r > 1]
            did = True
        if not did:
            if nxt >= n:
                break
            t = max(t, offsets[nxt])
    return [(first[i] - offsets[i]) * 1e3 for i in range(n)
            if lead_s <= offsets[i] <= lead_s + window_s and i in first]


def sweep(workload: str, prefill_ms: float, step_ms: float, speeds,
          root: str = ROOT) -> list:
    traffic, engine, seconds = cell_files(workload, root)
    kinds = os.path.join(root, "benchmark", "kinds")
    common = load_module(os.path.join(kinds, "serve_common.py"))
    kind = load_module(os.path.join(kinds, traffic["kind"] + ".py"))
    lead = traffic["lead_seconds"]
    n = int(traffic["rate_per_s"] * (seconds + lead) * 1.5) + 32
    _, asked = common.draw_sizes(traffic, n)
    offsets = [float(x) for x in kind.arrival_offsets(traffic, n)]
    out = []
    for f in speeds:
        w = waits_ms(offsets, [int(a) for a in asked], engine,
                     prefill_ms * f * 1e-3, step_ms * f * 1e-3, lead, seconds)
        out.append({"speed": round(f, 6), "requests": len(w),
                    "ttft_p90_ms": round(stats.percentile(w, 90), 1),
                    "ttft_max_ms": round(max(w), 1)})
    return out


if __name__ == "__main__":
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("workload")
    ap.add_argument("--prefill-ms", type=float, required=True)
    ap.add_argument("--step-ms", type=float, required=True)
    ap.add_argument("--speeds", default="0.97:1.04:0.0025",
                    help="lo:hi:step, multipliers of both times")
    args = ap.parse_args()
    lo, hi, step = (float(x) for x in args.speeds.split(":"))
    speeds = [lo + k * step for k in range(int(round((hi - lo) / step)) + 1)]
    for row in sweep(args.workload, args.prefill_ms, args.step_ms, speeds):
        print(json.dumps(row))
