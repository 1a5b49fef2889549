"""CLI: ``python -m ray_tpu.scripts.cli <command>``.

Reference parity: ``python/ray/scripts/scripts.py`` (``ray start/stop/
status/list/summary/timeline/memory``) + the state CLI
(``experimental/state/state_cli.py``).
"""

from __future__ import annotations

import argparse
import json
import sys


def _connect(args):
    import ray_tpu

    ray_tpu.init(args.address)
    return ray_tpu


def cmd_start(args):
    """Start a head or worker node daemon (blocks until SIGTERM)."""
    if args.head:
        from ray_tpu.cluster.head import main as head_main

        sys.argv = ["head", "--port", str(args.port)]
        head_main()
    else:
        if not args.address:
            print("--address required for worker nodes", file=sys.stderr)
            sys.exit(2)
        from ray_tpu.cluster.node_agent import main as node_main

        sys.argv = ["node", "--head", args.address]
        if args.num_cpus is not None:
            sys.argv += ["--num-cpus", str(args.num_cpus)]
        node_main()


def cmd_status(args):
    ray_tpu = _connect(args)
    total = ray_tpu.cluster_resources()
    avail = ray_tpu.available_resources()
    nodes = ray_tpu.nodes()

    def node_state(n):
        return n.get("State") or ("ALIVE" if n["Alive"] else "DEAD")

    alive = sum(1 for n in nodes if node_state(n) == "ALIVE")
    draining = sum(1 for n in nodes if node_state(n) == "DRAINING")
    extra = f", {draining} draining" if draining else ""
    print(f"nodes: {alive} alive{extra} / {len(nodes)}")
    for n in nodes:
        state = node_state(n)
        why = n.get("DrainReason") if state == "DRAINING" \
            else n.get("DeathCause")
        labels = n.get("Labels") or {}
        kind = labels.get("node_type") or "-"
        if labels.get("spot"):
            kind += " (spot)"
        print(f"  {n['NodeID'][-12:]:<14} {state:<9} {kind:<16}"
              + (f" ({why})" if why else ""))
    for k in sorted(total):
        print(f"  {k}: {avail.get(k, 0.0):g} / {total[k]:g} available")
    from ray_tpu import state

    fleet = state.autoscaler_status() or {}
    if fleet.get("types"):
        print(f"autoscaler: max_workers {fleet.get('max_workers', '?')}"
              + (f", draining {len(fleet['draining'])}"
                 if fleet.get("draining") else "")
              + (f", SLO burns: {', '.join(fleet['slo_burns'])}"
                 if fleet.get("slo_burns") else ""))
        for name, t in sorted(fleet["types"].items()):
            flags = []
            if t.get("spot"):
                flags.append("spot")
            if t.get("quarantined"):
                flags.append(
                    f"QUARANTINED {t['quarantine_remaining_s']:g}s")
            elif t.get("backoff_remaining_s"):
                flags.append(f"backoff {t['backoff_remaining_s']:g}s")
            if t.get("failures"):
                flags.append(f"{t['failures']} boot failure(s)")
            suffix = f" [{', '.join(flags)}]" if flags else ""
            print(f"  {name:<16} nodes {t.get('nodes', 0)}{suffix}")

    pgs = state.placement_groups() or {}
    active = {pid: pg for pid, pg in pgs.items()
              if pg.get("state") not in ("REMOVED",)}
    if active:
        by_state: dict = {}
        for pg in active.values():
            by_state[pg["state"]] = by_state.get(pg["state"], 0) + 1
        states = ", ".join(f"{n} {s}" for s, n in sorted(by_state.items()))
        print(f"placement groups: {states}")
        for pid, pg in sorted(active.items()):
            n_live = len(pg.get("live_bundles", ()))
            n_all = len(pg.get("bundles", ()))
            extra = ""
            if pg.get("reschedules"):
                extra += f", {pg['reschedules']} reschedule(s)"
            if pg["state"] == "RESCHEDULING" and pg.get("reschedule_cause"):
                extra += f" ({pg['reschedule_cause']})"
            print(f"  {pid[-12:]:<14} {pg['state']:<12} "
                  f"bundles {n_live}/{n_all} live{extra}")

    snaps = [s for s in state.device_stats() if s.get("available")]
    if snaps:
        # One line per jax-loaded worker process: platform, device
        # count, HBM in use / limit where the backend reports it.
        for s in snaps:
            devs = s.get("devices") or []
            used = sum(d.get("bytes_in_use", 0) for d in devs)
            limit = sum(d.get("bytes_limit", 0) for d in devs)
            mem = (f" HBM {used / 2**30:.2f}/{limit / 2**30:.2f} GiB"
                   if limit else "")
            comp = (s.get("compile") or {}).get("backend_compiles", 0)
            print(f"  devices[{s.get('worker_id', '?')}]: "
                  f"{len(devs)}x {s.get('platform')}{mem}, "
                  f"{comp} compiles")
    else:
        print("  devices: none reported (no jax-loaded worker)")


def cmd_drain(args):
    """Gracefully drain a node: exclude it from scheduling, migrate
    restartable actors, let in-flight tasks finish to the deadline, then
    deregister (the ``ray drain-node`` analog)."""
    _connect(args)
    from ray_tpu._private import worker as worker_mod

    backend = worker_mod.backend()
    if not hasattr(backend, "head"):
        raise SystemExit("drain requires a cluster (--address <head>)")
    from ray_tpu.cluster.gcs_client import NodeInfoAccessor

    result = NodeInfoAccessor(backend.head).drain(
        args.node_id, reason=args.reason, deadline_s=args.deadline,
        wait=not args.no_wait)
    print(json.dumps(result, indent=2, default=str))


def cmd_list(args):
    from ray_tpu import state

    _connect(args)
    kind = args.kind
    rows = {
        "tasks": state.list_tasks,
        "actors": state.list_actors,
        "objects": state.list_objects,
    }[kind]()
    if getattr(rows, "truncated", False):
        # No silent caps: a clipped object listing says so.
        print(json.dumps({"truncated": True, "total": rows.total,
                          "objects": list(rows)}, indent=2, default=str))
        return
    print(json.dumps(list(rows), indent=2, default=str))


def cmd_summary(args):
    from ray_tpu import state

    _connect(args)
    print(json.dumps(
        {"tasks": state.summarize_tasks(), "actors": state.summarize_actors()},
        indent=2,
    ))


def cmd_timeline(args):
    from ray_tpu import state

    _connect(args)
    out = state.timeline(args.output)
    print(f"wrote chrome trace to {out}")


def _mib(n) -> str:
    return f"{(n or 0) / 1048576:.1f}"


def cmd_memory(args):
    """Object & memory observability (``ray memory`` analog): cluster
    totals + per-node shm occupancy + top objects with owner/task/
    callsite attribution; ``--group-by`` aggregates live bytes by
    creation site, ``--leaks`` prints the head sweeper's flags,
    ``--stats-only`` the raw per-node store stats."""
    from ray_tpu import state

    _connect(args)
    if args.stats_only:
        reports = state.object_store_stats(node_id=args.node,
                                           include_objects=False)
        print(json.dumps(reports, indent=2, default=str))
        return
    if args.leaks:
        leaks = state.memory_leaks()
        if not leaks:
            print("no leaked objects flagged")
            return
        print(f"{len(leaks)} leaked object(s) "
              f"(alive past the age threshold, unreachable):")
        for r in leaks:
            print(f"  {r['object_id'][:20]}…  {_mib(r.get('size'))} MiB  "
                  f"{r.get('kind')}  age {r.get('age_s')}s  "
                  f"task={r.get('task') or '?'}  "
                  f"owner={r.get('owner') or '?'}")
            if r.get("callsite"):
                print(f"    created at: {r['callsite']}")
        return
    summary = state.memory_summary(top_k=args.top,
                                   group_by=args.group_by or "callsite")
    t = summary["totals"]
    print(f"object store: {_mib(t['bytes_used'])}/"
          f"{_mib(t['bytes_capacity'])} MiB used across "
          f"{t['nodes']} node(s), {t['objects']} object(s), "
          f"{t['evictions']} eviction(s), "
          f"{_mib(t['spilled_bytes'])} MiB spilled, "
          f"{summary.get('leaks', 0)} leak(s)")
    for nid, n in sorted(summary["nodes"].items()):
        if args.node and nid != args.node:
            continue
        line = (f"  node {nid[-12:]:<14} {_mib(n['bytes_used'])}/"
                f"{_mib(n['bytes_capacity'])} MiB "
                f"({n['occupancy'] * 100:.0f}%)  "
                f"{n['objects']} obj  {n['evictions']} evict  "
                f"{_mib(n['spilled_bytes'])} MiB spilled")
        print(line)
        for path in n.get("oom_reports") or []:
            print(f"    oom report: {path}")
    top = summary.get("top_objects") or []
    if args.node:
        top = [r for r in top
               if args.node in (r.get("nodes") or [])]
    if top:
        print("top objects by size:")
        for r in top:
            # Holders (processes keeping the ref alive) over the shm
            # active-reader count: "who still references this" is the
            # question a full store asks.
            refs = r.get("ref_holders")
            if refs is None:
                refs = r.get("refcount", "?")
            print(f"  {r['object_id'][:20]}…  {_mib(r.get('size'))} MiB  "
                  f"refs={refs}  "
                  f"{'pinned' if r.get('pinned') else 'unpinned':<8}  "
                  f"task={r.get('task') or '?'}  "
                  f"age={r.get('age_s', '?')}s")
            if r.get("callsite"):
                print(f"    created at: {r['callsite']}")
    groups = summary.get("groups") or []
    if groups:
        print(f"by {summary.get('group_by', 'callsite')}:")
        for g in groups:
            print(f"  {_mib(g['bytes']):>9} MiB  {g['objects']:>5} obj  "
                  f"{g['key']}")


def cmd_serve(args):
    """Serve observability: ``ray-tpu serve stats`` prints the
    per-deployment SLO table (replicas, p50/p99, QPS over the sampling
    window, status/shed counts, live ongoing/queued gauges) from the
    request-path latency plane — the first stop before attributing
    serving latency to the model itself."""
    _connect(args)
    from ray_tpu import serve

    if args.action != "stats":
        raise SystemExit(f"unknown serve action {args.action!r}")
    stats = serve.stats(window_s=args.window)
    if args.json:
        print(json.dumps(stats, indent=2, default=str))
        return
    deployments = stats.get("deployments") or {}
    if not deployments:
        print("no deployments (or no serve traffic recorded yet)")
        return
    hdr = (f"{'deployment':<24} {'repl':>4} {'p50 ms':>8} {'p99 ms':>8} "
           f"{'qps':>7} {'ok':>8} {'err':>5} {'shed':>5} {'ongoing':>7} "
           f"{'queued':>6}")
    print(hdr)
    print("-" * len(hdr))
    for name, d in deployments.items():
        req = d.get("requests") or {}
        shed = sum((d.get("shed") or {}).values())
        qps = d.get("qps")
        print(f"{name:<24} {d.get('replicas', '?'):>4} "
              f"{d.get('p50_ms', '—'):>8} {d.get('p99_ms', '—'):>8} "
              f"{qps if qps is not None else '—':>7} "
              f"{req.get('ok', 0):>8} {req.get('error', 0):>5} "
              f"{shed:>5} {d.get('ongoing', 0):>7} "
              f"{d.get('queued', 0):>6}")
        phases = d.get("phases") or {}
        if args.phases and phases:
            for phase, ph in phases.items():
                print(f"    {phase:<12} p50 {ph.get('p50_ms', '—')} ms  "
                      f"mean {ph.get('mean_ms', '—')} ms  "
                      f"n={ph.get('count', 0)}")
        decode = d.get("decode") or {}
        if decode:
            print(f"    decode       streams {decode.get('streams', 0)}  "
                  f"ttft p50 {decode.get('ttft_p50_ms', '—')} ms  "
                  f"p99 {decode.get('ttft_p99_ms', '—')} ms  "
                  f"tokens {decode.get('tokens', 0)}  "
                  f"steps {decode.get('steps', 0)}  "
                  f"occ {decode.get('mean_occupancy', '—')}")
    if stats.get("reconcile_s") is not None:
        print(f"controller reconcile: {stats['reconcile_s'] * 1e3:.1f} ms")


def _print_top(top, window):
    slos = top.get("slos") or {}
    burning = sum(1 for s in slos.values() if s["state"] == "burning")
    print(f"window {window:g}s · {top.get('series', 0)} series"
          + (f" · {burning} SLO(s) BURNING" if burning else ""))
    nodes = top.get("nodes") or {}
    if nodes:
        hdr = (f"{'node':<16} {'cpu%':>6} {'rss MB':>8} {'store%':>7} "
               f"{'workers':>7}")
        print(hdr)
        print("-" * len(hdr))
        for nid, n in sorted(nodes.items()):
            occ = n.get("store_occupancy")
            print(f"{nid[-14:]:<16} {n.get('cpu_percent', 0):>6} "
                  f"{n.get('rss_bytes', 0) / 1e6:>8.1f} "
                  f"{(f'{occ:.1%}' if occ is not None else '—'):>7} "
                  f"{n.get('workers', 0):>7}")
    serve = top.get("serve") or {}
    if serve:
        hdr = (f"{'deployment':<24} {'qps':>7} {'shed%':>6} "
               f"{'ttft p50':>9} {'itl p50':>9} {'lat p50':>9}")
        print(hdr)
        print("-" * len(hdr))
        for dep, d in sorted(serve.items()):
            def ms(key):
                v = d.get(key)
                return f"{v * 1e3:.1f}ms" if v is not None else "—"
            shed = d.get("shed_ratio")
            print(f"{dep:<24} {d.get('qps', 0):>7} "
                  f"{(f'{shed:.1%}' if shed is not None else '—'):>6} "
                  f"{ms('ttft_p50_s'):>9} {ms('itl_p50_s'):>9} "
                  f"{ms('latency_p50_s'):>9}")
    fleet = top.get("fleet") or {}
    churn = fleet.get("types") or {}
    if churn:
        hdr = (f"{'node type':<16} {'launch':>7} {'fail':>6} "
               f"{'bench':>6} {'down':>6}")
        print(hdr)
        print("-" * len(hdr))
        for t, c in sorted(churn.items()):
            print(f"{t:<16} {c.get('launches', 0):>7} "
                  f"{c.get('launch_failures', 0):>6} "
                  f"{c.get('quarantines', 0):>6} "
                  f"{c.get('scale_downs', 0):>6}")
    pending = fleet.get("pending_demand") or {}
    if pending:
        print("pending demand: " + ", ".join(
            f"{k} {v}" for k, v in sorted(pending.items())))
    train = top.get("train") or {}
    for trial, t in sorted(train.items()):
        gp = t.get("goodput_pct")
        strag = t.get("straggler")
        strag_s = ""
        if strag and strag.get("cause") != "balanced":
            strag_s = (f", straggler r{strag.get('rank')} "
                       f"{strag.get('cause')}")
        print(f"trial {trial}: {t.get('reports_per_s', 0)} reports/s"
              + (f", goodput {gp}%" if gp is not None else "")
              + strag_s)
    for name, s in sorted(slos.items()):
        v = s.get("value")
        print(f"slo {name:<20} {s['state']:<8} "
              f"{v if v is not None else '—'} "
              f"{s['op']} {s['threshold']}  ({s['expr']})")
        ex = s.get("exemplar_trace_ids")
        if ex:
            print("    exemplars: " + " ".join(ex)
                  + "  (ray-tpu trace <id>)")
    traces = top.get("traces") or {}
    if traces.get("assembled_total") or traces.get("pending"):
        drops = traces.get("dropped") or {}
        drop_s = ", ".join(f"{k} {v}" for k, v in sorted(drops.items())
                           if v) or "none"
        span_drops = (traces.get("head_spans_dropped", 0)
                      + traces.get("worker_spans_dropped", 0))
        print(f"traces: {traces.get('kept', 0)} kept / "
              f"{traces.get('assembled_total', 0)} assembled "
              f"({traces.get('pending', 0)} pending) · drops: {drop_s}"
              + (f" · SPANS DROPPED: {span_drops}" if span_drops else ""))


def cmd_top(args):
    """Live cluster view from the head's metrics history ring — every
    number a windowed ring query, zero sleeps in the request path (the
    --watch cadence is the terminal's, not the data path's)."""
    _connect(args)
    from ray_tpu import state

    def once():
        top = state.signal_top(args.window)
        if not top.get("ok"):
            raise SystemExit(f"signal plane unavailable: "
                             f"{top.get('error')}")
        if args.json:
            print(json.dumps(top, indent=2, default=str))
        else:
            _print_top(top, args.window)

    if not args.watch:
        once()
        return
    import time as _time

    try:
        while True:
            print("\x1b[2J\x1b[H", end="")
            once()
            _time.sleep(args.interval)
    except KeyboardInterrupt:
        pass


def cmd_slo(args):
    """SLO registry: ``ray-tpu slo`` prints the burn-rate table;
    ``register <name> <expr>`` / ``remove <name>`` manage objectives
    (grammar: ``ttft_p50{deployment="d"} < 2s over 60s``,
    ``shed_ratio < 1% over 300s``, ``rate(family) < N over Ws``)."""
    _connect(args)
    from ray_tpu import state

    if args.op == "register":
        if not args.name or not args.expr:
            raise SystemExit("usage: ray-tpu slo register <name> <expr>")
        res = state.register_slo(args.name, " ".join(args.expr))
        if not res.get("ok"):
            raise SystemExit(f"register failed: {res.get('error')}")
        print(json.dumps(res["slo"], indent=2, default=str))
        return
    if args.op == "remove":
        if not args.name:
            raise SystemExit("usage: ray-tpu slo remove <name>")
        res = state.remove_slo(args.name)
        if not res.get("ok"):
            raise SystemExit(f"remove failed: {res.get('error')}")
        print("removed" if res.get("removed") else "not registered")
        return
    status = state.slo_status()
    if not status.get("ok"):
        raise SystemExit(f"signal plane unavailable: "
                         f"{status.get('error')}")
    if args.json:
        print(json.dumps(status, indent=2, default=str))
        return
    slos = status.get("slos") or {}
    if not slos:
        print("no SLOs registered "
              "(ray-tpu slo register <name> '<expr>')")
        return
    hdr = (f"{'name':<20} {'state':<8} {'value':>10} {'threshold':>10} "
           f"{'window':>7} {'breaches':>8}")
    print(hdr)
    print("-" * len(hdr))
    for name, s in sorted(slos.items()):
        v = s.get("value")
        print(f"{name:<20} {s['state']:<8} "
              f"{(round(v, 5) if v is not None else '—'):>10} "
              f"{s['op']}{s['threshold']:>9} "
              f"{s['window_s']:>6g}s {s['breach_streak']:>8}")
        print(f"    {s['expr']}")
        ex = s.get("exemplar_trace_ids")
        if ex:
            print("    exemplars: " + " ".join(ex)
                  + "  (ray-tpu trace <id>)")


def _print_ttft_decomp(out):
    n = out.get("traces", 0)
    if not n:
        print("no finalized traces in the window "
              "(is tracing enabled? RAY_TPU_TRACING_ENABLED=1)")
        return
    p50 = out.get("ttft_p50_s")
    p99 = out.get("ttft_p99_s")
    print(f"{n} trace(s) · ttft p50 "
          f"{p50 * 1e3:.1f}ms · p99 {p99 * 1e3:.1f}ms · dominant phase: "
          f"{out.get('dominant')}")
    hdr = f"{'phase':<12} {'p50':>10} {'p99':>10} {'mean':>10} {'n':>6}"
    print(hdr)
    print("-" * len(hdr))
    for phase, p in sorted((out.get("phases") or {}).items(),
                           key=lambda kv: -(kv[1].get("p50_s") or 0.0)):
        def ms(v):
            return f"{v * 1e3:.1f}ms" if v is not None else "—"
        print(f"{phase:<12} {ms(p.get('p50_s')):>10} "
              f"{ms(p.get('p99_s')):>10} {ms(p.get('mean_s')):>10} "
              f"{p.get('count', 0):>6}")
    ps = out.get("phase_sum_p50_s")
    if p50 and ps is not None:
        print(f"phase-sum p50 {ps * 1e3:.1f}ms "
              f"({ps / p50:.1%} of ttft p50)")


def cmd_trace(args):
    """Flight-recorder queries. ``ray-tpu trace`` lists kept traces;
    ``ray-tpu trace <id>`` renders the assembled cross-process span
    tree (``--chrome out.json`` exports Perfetto-loadable events,
    ``--path`` prints the critical-path segments); ``ray-tpu trace
    --ttft`` prints the windowed per-phase TTFT decomposition."""
    _connect(args)
    from ray_tpu import state

    if args.ttft:
        out = state.ttft_decomposition(
            window_s=args.window, deployment=args.deployment)
        if args.json:
            print(json.dumps(out, indent=2, default=str))
        else:
            _print_ttft_decomp(out)
        return
    if not args.trace_id:
        rows = state.list_traces(args.limit)
        if args.json:
            print(json.dumps(rows, indent=2, default=str))
            return
        if not rows:
            print("no traces kept (enable tracing and send traffic; "
                  "only errored/slow/sampled traces are retained)")
            return
        hdr = (f"{'trace_id':<34} {'root':<28} {'dur':>9} "
               f"{'spans':>5} {'kept':>10} {'dominant':>9}")
        print(hdr)
        print("-" * len(hdr))
        for r in rows:
            dur = r.get("duration_s") or 0.0
            mark = "!" if r.get("errored") else " "
            print(f"{r['trace_id']:<34} {(r.get('root') or '?')[:27]:<28} "
                  f"{dur * 1e3:>8.1f}ms{mark}{r.get('spans', 0):>5} "
                  f"{r.get('kept_because', ''):>10} "
                  f"{r.get('dominant') or '—':>9}")
        return
    tr = state.get_trace(args.trace_id)
    if tr is None:
        raise SystemExit(
            f"unknown trace {args.trace_id!r} — never reported, still "
            f"inside the assembly quiet window, or tail-sampled out "
            f"(kept: errored, >slow-threshold, or sampled-in)")
    if args.chrome:
        from ray_tpu.util import tracing

        n = tracing.export_chrome_trace(args.chrome, tr["spans"])
        print(f"wrote {n} span(s) to {args.chrome} "
              f"(load in Perfetto / chrome://tracing)")
        return
    if args.json:
        print(json.dumps(tr, indent=2, default=str))
        return
    from ray_tpu.cluster.traces import render_tree

    print(f"trace {tr['trace_id']}  "
          f"({tr['duration_s'] * 1e3:.1f}ms, kept: {tr['kept_because']}"
          + (f", deployment {tr['deployment']}" if tr.get("deployment")
             else "") + ")")
    print(render_tree(tr["spans"]))
    d = tr.get("decomposition")
    if d:
        parts = ", ".join(f"{k} {v * 1e3:.1f}ms"
                          for k, v in sorted(d["phases"].items(),
                                             key=lambda kv: -kv[1]))
        print(f"ttft {d['total_s'] * 1e3:.1f}ms = {parts} "
              f"(dominant: {d['dominant']})")
    if args.path:
        print("critical path:")
        for seg in tr.get("critical_path") or ():
            print(f"  {seg['self_s'] * 1e3:>8.1f}ms  {seg['phase']:<10} "
                  f"{seg['name']}")


def cmd_data(args):
    """Input-pipeline observability: ``ray-tpu data stats`` prints the
    per-stage execution rollup and the consumer-loop stall fraction —
    the input-pipeline gate in front of any kernel-level MFU work
    (a starved loop means the kernels are idle, not slow)."""
    _connect(args)
    from ray_tpu import state

    if args.action != "stats":
        raise SystemExit(f"unknown data action {args.action!r}")
    stats = state.data_stats()
    if args.json:
        print(json.dumps(stats, indent=2, default=str))
        return
    stages = stats.get("stages") or {}
    if stages:
        hdr = (f"{'stage':<28} {'execs':>5} {'blocks':>7} "
               f"{'rows':>10} {'wall ms':>9} {'MB/s':>8}")
        print(hdr)
        print("-" * len(hdr))
        for name, st in stages.items():
            mb_s = (st.get("bytes_per_s") or 0) / 1e6
            print(f"{name:<28} {st.get('executions', 0):>5} "
                  f"{st.get('blocks', '—'):>7} "
                  f"{st.get('rows_total', '—'):>10} "
                  f"{st.get('wall_ms', 0):>9} {mb_s:>8.1f}")
    else:
        print("no dataset stages recorded")
    it = stats.get("iterator") or {}
    for phase in ("wait", "user", "transfer"):
        d = it.get(phase)
        if d:
            print(f"iterator {phase:<9} n={d['count']:<7} "
                  f"p50 {d['p50_ms']} ms  mean {d['mean_ms']} ms")
    occ = it.get("occupancy")
    if occ:
        print(f"prefetch occupancy: mean {occ['mean']} "
              f"({occ['samples']} samples)")
    sf = stats.get("stall_fraction")
    if sf is not None:
        print(f"stall fraction: {sf:.1%} of consumer loop wall time "
              f"starved for data")
    else:
        print("stall fraction: — (no consumer loops recorded)")


def cmd_train(args):
    """Training goodput: ``ray-tpu train stats`` prints per-trial
    report counts, step-phase latencies, rank skew, and the downtime
    ledger's goodput %."""
    _connect(args)
    from ray_tpu import state

    if args.action != "stats":
        raise SystemExit(f"unknown train action {args.action!r}")
    stats = state.train_stats()
    if args.json:
        print(json.dumps(stats, indent=2, default=str))
        return
    trials = stats.get("trials") or {}
    if not trials:
        print("no train sessions recorded")
        return
    for name, t in trials.items():
        gp = t.get("goodput_pct")
        skew = t.get("rank_skew")
        print(f"trial {name}: {t.get('reports', 0)} reports"
              + (f", goodput {gp}%" if gp is not None else "")
              + (f", rank skew {skew}x" if skew is not None else ""))
        for phase, d in (t.get("phases") or {}).items():
            print(f"    {phase:<18} n={d['count']:<7} "
                  f"p50 {d['p50_ms']} ms  mean {d['mean_ms']} ms")
        ranks = t.get("rank_step_s")
        if ranks:
            line = "  ".join(f"r{r}={s * 1e3:.1f}ms"
                             for r, s in ranks.items())
            print(f"    rank step: {line}")
        anat = t.get("anatomy") or {}
        for rank, phases in sorted((anat.get("ranks") or {}).items()):
            line = "  ".join(f"{p}={s * 1e3:.1f}ms"
                             for p, s in phases.items())
            print(f"    anatomy r{rank}: {line}")
        strag = anat.get("straggler")
        if strag:
            if strag.get("cause") == "balanced":
                print("    straggler: none (balanced gang)")
            else:
                print(f"    straggler: rank {strag.get('rank')} "
                      f"{strag.get('cause')} "
                      f"(+{strag.get('excess_s', 0) * 1e3:.1f}ms over "
                      f"median, phase {strag.get('phase')})")
        for cause, s in (t.get("downtime_s") or {}).items():
            print(f"    downtime [{cause}]: {s:.2f} s")


def cmd_logs(args):
    """List captured worker logs, or print (and follow) one worker's."""
    from ray_tpu import state

    _connect(args)
    if not args.worker_id:
        rows = state.list_logs()
        if not rows:
            print("no captured worker logs (local backend, or no "
                  "workers spawned yet)")
            return
        print(f"{'WORKER':<16} {'NODE':<10} {'PID':>7} {'ALIVE':<5} "
              f"{'ACTOR':<10} {'OUT':>9} {'ERR':>9}")
        for r in rows:
            print(f"{r['worker_id']:<16} {r['node_id'][-8:]:<10} "
                  f"{r['pid']:>7} {str(r['alive']):<5} "
                  f"{(r.get('actor_id') or '')[-8:]:<10} "
                  f"{r.get('stdout_bytes', 0):>9} "
                  f"{r.get('stderr_bytes', 0):>9}")
        return
    from ray_tpu._private import worker as worker_mod

    backend = worker_mod.backend()
    rec = backend.get_log(args.worker_id, args.stream,
                          tail_lines=args.tail)
    sys.stdout.write(rec["data"])
    sys.stdout.flush()
    if args.follow:
        for chunk in state.follow_log(
                args.worker_id, args.stream, offset=rec["offset"],
                idle_timeout_s=args.idle_timeout):
            sys.stdout.write(chunk["data"])
            sys.stdout.flush()


def cmd_stack(args):
    """Stack dump (or timed stack profile) of live workers
    (``ray stack`` / py-spy analog)."""
    import json as _json

    from ray_tpu import state

    _connect(args)
    if args.worker_id:
        targets = [args.worker_id]
    else:
        targets = [r["worker_id"] for r in state.list_logs()
                   if r.get("alive")]
        if not targets:
            from ray_tpu._private import worker as worker_mod

            if hasattr(worker_mod.backend(), "head"):
                # Cluster with no live workers: routing a None worker
                # would just produce a lookup traceback.
                print("no live workers to inspect")
                return
            targets = [None]  # local backend: dump this process
    outputs = []
    for wid in targets:
        if args.duration:
            out = state.profile_worker(
                wid, duration_s=args.duration, interval_s=args.interval,
                fmt=args.format)
        else:
            out = state.dump_stack(wid)
        outputs.append(out)
    if args.format == "chrome" and args.duration:
        events = [e for ev in outputs for e in ev]
        if args.output:
            with open(args.output, "w") as f:
                _json.dump(events, f)
            print(f"wrote chrome trace to {args.output}")
        else:
            print(_json.dumps(events))
        return
    for wid, out in zip(targets, outputs):
        if len(targets) > 1:
            print(f"==== worker {wid} ====")
        print(out if isinstance(out, str) else _json.dumps(out, indent=1))


def cmd_tprof(args):
    """Remote profiler capture (``jax.profiler.trace`` in the worker;
    stack-sampler fallback off-jax): trace files stream back and land
    in --output, TensorBoard/Perfetto-loadable."""
    from ray_tpu import state

    _connect(args)
    wid = args.worker_id
    if wid is None:
        from ray_tpu._private import worker as worker_mod

        if hasattr(worker_mod.backend(), "head"):
            live = [r["worker_id"] for r in state.list_logs()
                    if r.get("alive")]
            if not live:
                print("no live workers to profile")
                return
            wid = live[0]
    res = state.capture_profile(
        wid, duration_s=args.duration, interval_s=args.interval,
        out_dir=args.output)
    print(f"captured {res['kind']} profile of "
          f"{res.get('worker_id') or 'this process'} "
          f"({res['duration_s']:g}s) -> {res['dir']}")
    for path in res["files"]:
        print(f"  {path}")


def cmd_metrics(args):
    """Dump the federated Prometheus scrape (one body covering every
    alive agent), or write a file-SD targets document for
    scrape-config bootstrap."""
    from ray_tpu._private import worker as worker_mod

    _connect(args)
    backend = worker_mod.backend()
    if args.targets_json:
        import json as _json

        from ray_tpu.util.metrics import file_sd_targets

        ep = (backend.metrics_endpoint()
              if hasattr(backend, "metrics_endpoint") else None)
        if ep is None:
            raise SystemExit(
                "no metrics endpoint (local backend, or exposition "
                "disabled on the head)")
        doc = file_sd_targets(ep["address"], path=ep["cluster_path"])
        with open(args.targets_json, "w") as f:
            _json.dump(doc, f, indent=1)
        print(f"wrote prometheus file-SD targets to {args.targets_json}")
        return
    if not hasattr(backend, "cluster_metrics_text"):
        raise SystemExit("this backend exports no metrics")
    sys.stdout.write(backend.cluster_metrics_text())


def cmd_chaos(args):
    """Deterministic fault injection: arm/disarm failpoints cluster-wide
    and manage network-chaos partitions (``GcsClient.chaos``)."""
    if not args.address:
        raise SystemExit("chaos requires --address <head>")
    from ray_tpu.cluster.gcs_client import GcsClient

    gcs = GcsClient(args.address)
    try:
        op = args.op
        if op == "list":
            print(json.dumps({
                "failpoints": gcs.chaos.list(),
                "channel_chaos": gcs.chaos.list_channel_chaos(),
            }, indent=2, default=str))
        elif op == "arm":
            if not args.site or not args.spec:
                raise SystemExit("chaos arm <site> <spec>")
            print(json.dumps(gcs.chaos.arm(args.site, args.spec),
                             indent=2, default=str))
        elif op == "disarm":
            if args.all:
                sites = set()

                def walk(table):
                    # Armed tables nest per process ({"head": {...},
                    # node: {"agent": {...}, worker: {...}}}); a site's
                    # leaf record always carries its "spec".
                    for key, val in (table or {}).items():
                        if not isinstance(val, dict):
                            continue
                        if "spec" in val and "site" in val:
                            sites.add(key)
                        else:
                            walk(val)

                walk(gcs.chaos.list())
                print(json.dumps(gcs.chaos.set_failpoints(
                    {s: None for s in sites}), indent=2, default=str))
            elif args.site:
                print(json.dumps(gcs.chaos.disarm(args.site),
                                 indent=2, default=str))
            else:
                raise SystemExit("chaos disarm <site> (or --all)")
        elif op == "partition":
            # Groups arrive via --groups, but the first two also land in
            # the (site, spec) positional slots when given bare.
            raw = list(args.groups or ())
            if not raw:
                raw = [g for g in (args.site, args.spec) if g]
            if len(raw) < 2:
                raise SystemExit(
                    "chaos partition <group> <group> ... — each group a "
                    "comma-separated list of node ids (or 'head')")
            groups = [g.split(",") for g in raw]
            print(json.dumps(gcs.chaos.partition(groups),
                             indent=2, default=str))
        elif op == "heal":
            print(json.dumps(gcs.chaos.heal(), indent=2, default=str))
        else:
            raise SystemExit(f"unknown chaos op {op!r}")
    finally:
        gcs.close()


def cmd_submit(args):
    from ray_tpu.job_submission import JobSubmissionClient

    _connect(args)
    client = JobSubmissionClient()
    job_id = client.submit_job(entrypoint=" ".join(args.entrypoint))
    print(f"submitted {job_id}")
    if args.wait:
        status = client.wait_until_finished(job_id)
        print(f"{job_id}: {status}")
        print(client.get_job_logs(job_id))


def cmd_dashboard(args):
    from ray_tpu.dashboard import Dashboard

    if not args.address:
        raise SystemExit("dashboard requires --address <head host:port>")
    dash = Dashboard(args.address, host=args.host, port=args.port)
    print(f"dashboard at {dash.url} (head {args.address}); Ctrl-C to stop")
    try:
        import threading

        threading.Event().wait()
    except KeyboardInterrupt:
        dash.shutdown()


def cmd_client_server(args):
    from ray_tpu.util.client import ClientProxyServer

    if not args.address:
        raise SystemExit(
            "client-server requires --address <head host:port>")
    srv = ClientProxyServer(args.address, host=args.host, port=args.port)
    print(f"client proxy at ray://{srv.address} (head {args.address}); "
          f"Ctrl-C to stop")
    try:
        import threading

        threading.Event().wait()
    except KeyboardInterrupt:
        srv.shutdown()


def cmd_up(args):
    """Foreground cluster from YAML; Ctrl-C tears it down (``ray up``)."""
    import signal
    import threading as _threading

    from ray_tpu.autoscaler.launcher import create_or_update_cluster

    handle = create_or_update_cluster(args.config)
    print(f"cluster '{handle.config['cluster_name']}' up at "
          f"{handle.address} — Ctrl-C to tear down")
    done = _threading.Event()
    signal.signal(signal.SIGINT, lambda *a: done.set())
    signal.signal(signal.SIGTERM, lambda *a: done.set())
    done.wait()
    print("tearing down…")
    handle.teardown()


def main(argv=None):
    import sys as _sys

    argv = list(_sys.argv[1:]) if argv is None else list(argv)
    parser = argparse.ArgumentParser(prog="ray-tpu")
    parser.add_argument("--address", default=None,
                        help="cluster head host:port (default: local)")
    sub = parser.add_subparsers(dest="command", required=True)

    p = sub.add_parser("start", help="start a head/worker daemon")
    p.add_argument("--head", action="store_true")
    p.add_argument("--port", type=int, default=6380)
    p.add_argument("--num-cpus", type=float, default=None)
    p.set_defaults(fn=cmd_start)

    p = sub.add_parser("status", help="cluster resource status")
    p.set_defaults(fn=cmd_status)

    p = sub.add_parser(
        "drain",
        help="gracefully drain a node (migrate actors, finish tasks, "
             "then remove)")
    p.add_argument("node_id")
    p.add_argument("--reason", default="cli")
    p.add_argument("--deadline", type=float, default=None,
                   help="seconds in-flight tasks get before force-removal")
    p.add_argument("--no-wait", action="store_true",
                   help="initiate the drain and return immediately")
    p.set_defaults(fn=cmd_drain)

    p = sub.add_parser("list", help="list tasks/actors/objects")
    p.add_argument("kind", choices=["tasks", "actors", "objects"])
    p.set_defaults(fn=cmd_list)

    p = sub.add_parser("summary", help="task/actor state summary")
    p.set_defaults(fn=cmd_summary)

    p = sub.add_parser("timeline", help="dump chrome trace")
    p.add_argument("--output", "-o", default="/tmp/ray_tpu_timeline.json")
    p.set_defaults(fn=cmd_timeline)

    p = sub.add_parser(
        "memory",
        help="object & memory observability (ray memory analog): "
             "occupancy, attribution, leaks, OOM reports")
    p.add_argument("--group-by", choices=["callsite", "task", "node",
                                          "owner"],
                   default=None,
                   help="aggregate live bytes by creation site "
                        "(default: callsite)")
    p.add_argument("--leaks", action="store_true",
                   help="print objects the leak sweeper flags")
    p.add_argument("--stats-only", action="store_true",
                   help="raw per-node store stats, no per-object join")
    p.add_argument("--node", default=None,
                   help="restrict to one node id (also surfaces its "
                        "OOM reports)")
    p.add_argument("--top", type=int, default=20,
                   help="how many top-by-size objects to show")
    p.set_defaults(fn=cmd_memory)

    p = sub.add_parser(
        "logs", help="list/print captured worker logs (ray logs analog)")
    p.add_argument("worker_id", nargs="?", default=None)
    p.add_argument("--stream", choices=["out", "err"], default="out")
    p.add_argument("--tail", type=int, default=200)
    p.add_argument("--follow", "-f", action="store_true",
                   help="stream the log as it grows")
    p.add_argument("--idle-timeout", type=float, default=10.0,
                   help="stop following after this long without growth")
    p.set_defaults(fn=cmd_logs)

    p = sub.add_parser(
        "stack", help="stack dump / profile of workers (ray stack analog)")
    p.add_argument("worker_id", nargs="?", default=None,
                   help="default: every live worker (local: this process)")
    p.add_argument("--duration", "-d", type=float, default=None,
                   help="time-sample for this many seconds instead of "
                        "an instantaneous dump")
    p.add_argument("--interval", type=float, default=0.01)
    p.add_argument("--format", choices=["text", "collapsed", "chrome"],
                   default="text")
    p.add_argument("--output", "-o", default=None,
                   help="write chrome-trace output here")
    p.set_defaults(fn=cmd_stack)

    p = sub.add_parser(
        "tprof",
        help="remote profiler capture (jax.profiler.trace / stack "
             "sampler fallback)")
    p.add_argument("worker_id", nargs="?", default=None,
                   help="default: first live worker (local: this process)")
    p.add_argument("--duration", "-d", type=float, default=2.0)
    p.add_argument("--interval", type=float, default=0.01,
                   help="stack-sampler fallback interval")
    p.add_argument("--output", "-o", default=None,
                   help="directory for the trace files (default: tmp)")
    p.set_defaults(fn=cmd_tprof)

    p = sub.add_parser(
        "metrics",
        help="dump the federated /metrics/cluster scrape body")
    p.add_argument("--targets-json", default=None,
                   help="instead write a prometheus file-SD targets "
                        "document here")
    p.set_defaults(fn=cmd_metrics)

    p = sub.add_parser(
        "chaos",
        help="deterministic fault injection: failpoints + partitions")
    p.add_argument("op",
                   choices=["list", "arm", "disarm", "partition", "heal"])
    p.add_argument("site", nargs="?", default=None,
                   help="failpoint site (arm/disarm)")
    p.add_argument("spec", nargs="?", default=None,
                   help="failpoint spec, e.g. 'raise,once' / 'delay:0.2' "
                        "/ 'kill,p=0.1' (arm)")
    p.add_argument("--all", action="store_true",
                   help="disarm: clear every armed site")
    p.add_argument("--groups", nargs="*", default=None,
                   help="partition: comma-separated node ids per group "
                        "(use 'head' for the head), e.g. "
                        "--groups head,node-a node-b")
    p.set_defaults(fn=cmd_chaos)

    p = sub.add_parser(
        "serve",
        help="serve observability (per-deployment p50/p99/QPS/shed)")
    p.add_argument("action", choices=["stats"])
    p.add_argument("--window", type=float, default=1.0,
                   help="QPS sampling window seconds (0 = single scrape, "
                        "no QPS)")
    p.add_argument("--phases", action="store_true",
                   help="also print the per-phase latency breakdown")
    p.add_argument("--json", action="store_true")
    p.set_defaults(fn=cmd_serve)

    p = sub.add_parser(
        "top",
        help="live cluster view from the head's metrics history "
             "(nodes, serve, train, SLOs — zero sleeps in the path)")
    p.add_argument("--window", type=float, default=60.0,
                   help="query window seconds")
    p.add_argument("--watch", action="store_true",
                   help="refresh continuously until ^C")
    p.add_argument("--interval", type=float, default=2.0,
                   help="--watch refresh cadence seconds")
    p.add_argument("--json", action="store_true")
    p.set_defaults(fn=cmd_top)

    p = sub.add_parser(
        "slo",
        help="SLO registry: burn-rate table / register / remove")
    p.add_argument("op", nargs="?", default="status",
                   choices=["status", "register", "remove"])
    p.add_argument("name", nargs="?", default=None)
    p.add_argument("expr", nargs="*",
                   help="SLO expression, e.g. "
                        "ttft_p50{deployment=\"d\"} < 2s over 60s")
    p.add_argument("--json", action="store_true")
    p.set_defaults(fn=cmd_slo)

    p = sub.add_parser(
        "trace",
        help="flight recorder: list kept traces, render one "
             "cross-process tree, or the windowed TTFT decomposition")
    p.add_argument("trace_id", nargs="?", default=None)
    p.add_argument("--ttft", action="store_true",
                   help="windowed per-phase TTFT decomposition")
    p.add_argument("--window", type=float, default=None,
                   help="--ttft window seconds (default: all retained)")
    p.add_argument("--deployment", default=None,
                   help="--ttft filter by deployment")
    p.add_argument("--limit", type=int, default=30,
                   help="list mode: max traces shown")
    p.add_argument("--chrome", metavar="PATH", default=None,
                   help="export the trace as Chrome/Perfetto events")
    p.add_argument("--path", action="store_true",
                   help="print the critical-path segments")
    p.add_argument("--json", action="store_true")
    p.set_defaults(fn=cmd_trace)

    p = sub.add_parser(
        "data",
        help="input-pipeline observability (stage rollup + stall "
             "fraction)")
    p.add_argument("action", choices=["stats"])
    p.add_argument("--json", action="store_true")
    p.set_defaults(fn=cmd_data)

    p = sub.add_parser(
        "train",
        help="training goodput (step phases, rank skew, downtime "
             "ledger)")
    p.add_argument("action", choices=["stats"])
    p.add_argument("--json", action="store_true")
    p.set_defaults(fn=cmd_train)

    p = sub.add_parser("submit", help="submit a job entrypoint")
    p.add_argument("--wait", action="store_true")
    p.add_argument("entrypoint", nargs=argparse.REMAINDER)
    p.set_defaults(fn=cmd_submit)

    p = sub.add_parser(
        "up", help="launch a cluster from a YAML config (ray up analog)")
    p.add_argument("config", help="cluster YAML path")
    p.set_defaults(fn=cmd_up)

    p = sub.add_parser("dashboard", help="serve the REST dashboard")
    p.add_argument("--host", default="127.0.0.1")
    p.add_argument("--port", type=int, default=8265)
    p.set_defaults(fn=cmd_dashboard)

    p = sub.add_parser(
        "client-server", help="serve a ray:// client proxy")
    p.add_argument("--host", default="127.0.0.1")
    p.add_argument("--port", type=int, default=10001)
    p.set_defaults(fn=cmd_client_server)

    p = sub.add_parser(
        "analyze",
        help="concurrency & contract static analysis (lock order, "
             "blocking-under-lock, finalizer safety, async-holding-"
             "lock, failpoint/metric contract drift); exits 1 on any "
             "unbaselined finding")
    p.set_defaults(fn=None)

    # `analyze` forwards its whole tail verbatim to the analyzer's own
    # parser: parse_known_args lets the main parser consume the global
    # flags (wherever they sit) and leaves the analyzer's flags/paths
    # in `rest` — no hardcoded list of value-taking globals.
    args, rest = parser.parse_known_args(argv)
    if args.command == "analyze":
        from ray_tpu.scripts.analyze import main as analyze_main

        raise SystemExit(analyze_main(rest))
    if rest:
        parser.error(f"unrecognized arguments: {' '.join(rest)}")
    args.fn(args)


if __name__ == "__main__":
    main()
