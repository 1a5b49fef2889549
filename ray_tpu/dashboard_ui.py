"""Dashboard web frontend: a single-file SPA over the REST API.

Reference parity: ``dashboard/client/`` — the reference ships a React/TS
client built to static assets the dashboard server serves. Same
architecture here at a sane scope: one self-contained HTML+JS page
(no build step, no dependencies) that polls the same ``/api/...`` routes
a human would otherwise curl, with tabs for cluster / nodes / actors /
tasks / objects / placement groups / jobs / serve and a live log tail
(cursor-incremental, ``/api/logs`` long-poll analog). All rendering goes
through ``textContent`` — cluster-user-controlled strings (names,
addresses, log lines) are never interpolated as HTML.
"""

INDEX_HTML = r"""<!doctype html>
<html>
<head>
<meta charset="utf-8">
<title>ray_tpu dashboard</title>
<style>
  :root { --bg:#101418; --panel:#1a2026; --fg:#d8dee6; --dim:#8b98a5;
          --acc:#4fa3ff; --ok:#39c07b; --bad:#e25d5d; --warn:#e2b33d; }
  body { margin:0; background:var(--bg); color:var(--fg);
         font:13px/1.5 system-ui, sans-serif; }
  header { display:flex; align-items:baseline; gap:16px;
           padding:10px 16px; background:var(--panel);
           border-bottom:1px solid #2a323a; }
  header h1 { font-size:15px; margin:0; }
  header .dim { color:var(--dim); font-size:12px; }
  nav { display:flex; gap:2px; padding:0 12px; background:var(--panel); }
  nav button { background:none; border:none; color:var(--dim);
               padding:8px 12px; cursor:pointer; font:inherit;
               border-bottom:2px solid transparent; }
  nav button.active { color:var(--fg); border-color:var(--acc); }
  main { padding:14px 16px; }
  .tiles { display:flex; flex-wrap:wrap; gap:10px; margin-bottom:14px; }
  .tile { background:var(--panel); border:1px solid #2a323a;
          border-radius:6px; padding:10px 14px; min-width:130px; }
  .tile .v { font-size:20px; font-weight:600; }
  .tile .k { color:var(--dim); font-size:11px;
             text-transform:uppercase; letter-spacing:.05em; }
  table { border-collapse:collapse; width:100%; background:var(--panel);
          border:1px solid #2a323a; }
  th, td { text-align:left; padding:5px 10px;
           border-bottom:1px solid #242c34; font-size:12.5px; }
  th { color:var(--dim); font-weight:500; position:sticky; top:0;
       background:var(--panel); }
  td.mono, .mono { font-family:ui-monospace, monospace; font-size:12px; }
  .ALIVE, .FINISHED, .RUNNING_OK, .ok { color:var(--ok); }
  .DEAD, .FAILED, .ERROR, .bad { color:var(--bad); }
  .PENDING, .RESTARTING, .DRAINING, .warn { color:var(--warn); }
  #logs { background:#0b0e11; border:1px solid #2a323a; padding:10px;
          height:60vh; overflow-y:auto; white-space:pre-wrap;
          font-family:ui-monospace, monospace; font-size:12px; }
  .err { color:var(--bad); padding:8px 0; }
  input[type=text] { background:#0b0e11; color:var(--fg);
          border:1px solid #2a323a; border-radius:4px; padding:4px 8px; }
</style>
</head>
<body>
<header>
  <h1>ray_tpu</h1>
  <span class="dim" id="addr"></span>
  <span class="dim" id="updated"></span>
  <span class="err" id="error"></span>
</header>
<nav id="tabs"></nav>
<main>
  <div class="tiles" id="tiles"></div>
  <div id="view"></div>
</main>
<script>
"use strict";
const TABS = ["cluster", "nodes", "workers", "devices", "actors", "tasks",
              "objects", "memory", "placement_groups", "jobs", "serve",
              "train", "signals", "traces", "logs"];
let active = location.hash.slice(1) || "cluster";
let logCursor = 0;
const logBuf = [];

const $ = (id) => document.getElementById(id);

function el(tag, cls, text) {
  const e = document.createElement(tag);
  if (cls) e.className = cls;
  if (text !== undefined) e.textContent = String(text);
  return e;
}

function table(cols, rows, cellFn) {
  const t = el("table");
  const tr = el("tr");
  cols.forEach(c => tr.appendChild(el("th", "", c)));
  t.appendChild(tr);
  rows.forEach(r => {
    const row = el("tr");
    cols.forEach(c => row.appendChild(cellFn(r, c)));
    t.appendChild(row);
  });
  return t;
}

function stateCell(v) {
  const td = el("td", /^[A-Z_]+$/.test(String(v)) ? String(v) : "", v);
  return td;
}

async function api(path) {
  const r = await fetch(path);
  if (!r.ok) throw new Error(path + " -> " + r.status);
  return r.json();
}

function setTiles(items) {
  const box = $("tiles");
  box.replaceChildren();
  items.forEach(([k, v, cls]) => {
    const t = el("div", "tile");
    t.appendChild(el("div", "v " + (cls || ""), v));
    t.appendChild(el("div", "k", k));
    box.appendChild(t);
  });
}

function short(id) { return id && id.length > 14 ? id.slice(0, 14) + "…" : id; }

const RENDER = {
  async cluster() {
    const s = await api("/api/cluster_status");
    const res = s.resources_total || {}, avail = s.resources_available || {};
    setTiles([
      ["nodes alive", s.alive_nodes ?? "?",
       (s.dead_nodes || 0) > 0 ? "warn" : "ok"],
      ["nodes draining", s.draining_nodes ?? 0,
       (s.draining_nodes || 0) > 0 ? "warn" : ""],
      ["nodes dead", s.dead_nodes ?? 0,
       (s.dead_nodes || 0) > 0 ? "bad" : ""],
      ["CPU avail / total", `${avail.CPU ?? "?"} / ${res.CPU ?? "?"}`],
      ["head", s.head_address ?? "?"],
    ]);
    const rows = Object.entries(s).map(([k, v]) => ({k, v}));
    $("view").replaceChildren(table(["field", "value"], rows, (r, c) => {
      if (c === "field") return el("td", "", r.k);
      const td = el("td", "mono");
      td.textContent = typeof r.v === "object"
        ? JSON.stringify(r.v) : String(r.v);
      return td;
    }));
  },
  async nodes() {
    const [d, fleetD] = await Promise.all(
      [api("/api/nodes"), api("/api/autoscaler")]);
    const fleet = (fleetD || {}).autoscaler || {};
    const quarantined = new Set(Object.entries(fleet.types || {})
      .filter(([, t]) => t.quarantined).map(([name]) => name));
    $("view").replaceChildren(table(
      ["NodeID", "Address", "State", "Type", "Cause", "Resources",
       "StorePath"],
      d.nodes || [], (r, c) => {
        if (c === "State")
          return stateCell(r.State || (r.Alive ? "ALIVE" : "DEAD"));
        if (c === "Type") {
          // node_type/spot from the agent's labels; a quarantined type
          // (autoscaler boot-loop bench) is flagged inline.
          const labels = r.Labels || {};
          let txt = labels.node_type || "";
          if (labels.spot) txt += " (spot)";
          if (quarantined.has(labels.node_type)) txt += " [quarantined]";
          return el("td", "mono", txt);
        }
        if (c === "Cause") {
          // DRAINING shows its reason; DEAD its cause (crash vs drain).
          const td = el("td", "mono");
          td.textContent = r.DeathCause || r.DrainReason || "";
          return td;
        }
        if (c === "Resources") {
          const td = el("td", "mono");
          td.textContent = JSON.stringify(r.Resources || r.resources || {});
          return td;
        }
        const td = el("td", c === "NodeID" ? "mono" : "");
        td.textContent = c === "NodeID" ? short(r[c]) : (r[c] ?? "");
        return td;
      }));
  },
  async workers() {
    // Node reporter pane: per-worker telemetry merged with the log
    // index, plus on-demand log tail / stack dump / profile detail.
    const [statsD, logsD] = await Promise.all(
      [api("/api/worker_stats"), api("/api/worker_logs")]);
    const stats = {};
    (statsD.workers || []).forEach(s => { stats[s.worker_id] = s; });
    const rows = (logsD.workers || []).map(r =>
      ({...r, ...(stats[r.worker_id] || {})}));
    rows.sort((a, b) => (b.alive - a.alive)
      || (b.cpu_percent || 0) - (a.cpu_percent || 0));
    const alive = rows.filter(r => r.alive);
    setTiles([
      ["workers alive", alive.length],
      ["actors", alive.filter(r => r.is_actor).length],
      ["total cpu %", alive.reduce(
        (s, r) => s + (r.cpu_percent || 0), 0).toFixed(0)],
      ["total rss MiB", (alive.reduce(
        (s, r) => s + (r.rss_bytes || 0), 0) / 1048576).toFixed(0)],
    ]);
    const detail = el("pre", "");
    detail.id = "wdetail";
    detail.style.cssText = "background:#0b0e11;border:1px solid #2a323a;" +
      "padding:10px;max-height:45vh;overflow:auto;white-space:pre-wrap;" +
      "font:12px ui-monospace,monospace;";
    detail.textContent =
      "select log / stack / profile on a worker above";
    const show = async (label, path, isJson) => {
      detail.textContent = label + " …";
      try {
        const r = await fetch(path);
        const body = await r.text();
        detail.textContent = label + "\n\n" + (isJson
          ? JSON.stringify(JSON.parse(body), null, 1) : body);
      } catch (e) { detail.textContent = label + " failed: " + e; }
    };
    const t = table(
      ["worker_id", "node", "pid", "state", "cpu %", "rss MiB",
       "uptime s", "actor", "inspect"],
      rows, (r, c) => {
        if (c === "worker_id")
          { const td = el("td", "mono"); td.textContent = r.worker_id; return td; }
        if (c === "node")
          { const td = el("td", "mono"); td.textContent = short(r.node_id || ""); return td; }
        if (c === "pid") return el("td", "", r.pid ?? "");
        if (c === "state") return stateCell(r.alive ? "ALIVE" : "DEAD");
        if (c === "cpu %") return el("td", "", r.cpu_percent ?? "");
        if (c === "rss MiB") return el("td", "",
          r.rss_bytes ? (r.rss_bytes / 1048576).toFixed(1) : "");
        if (c === "uptime s") return el("td", "", r.uptime_s ?? "");
        if (c === "actor") return el("td", "mono",
          r.is_actor ? short(r.actor_id || "") : "");
        const td = el("td");
        const wid = encodeURIComponent(r.worker_id);
        [["out", `/api/worker_log?worker_id=${wid}&stream=out&tail=200`, true],
         ["err", `/api/worker_log?worker_id=${wid}&stream=err&tail=200`, true],
         ...(r.alive ? [
           ["stack", `/api/stack?worker_id=${wid}`, false],
           ["profile", `/api/profile?worker_id=${wid}&duration=0.5`, false],
         ] : [])].forEach(([label, path, isLog]) => {
          const b = el("button", "", label);
          b.style.cssText = "margin-right:4px;background:#0b0e11;" +
            "color:var(--fg);border:1px solid #2a323a;border-radius:3px;" +
            "cursor:pointer;font:11px inherit;padding:2px 6px;";
          b.onclick = async () => {
            if (!isLog) return show(`${label} ${r.worker_id}`, path, false);
            // worker_log returns JSON with a "data" field.
            detail.textContent = `${label} ${r.worker_id} …`;
            try {
              const d = await api(path);
              detail.textContent =
                `${label} ${r.worker_id} (${d.size} bytes)\n\n` + d.data;
            } catch (e) { detail.textContent = "failed: " + e; }
          };
          td.appendChild(b);
        });
        return td;
      });
    const wrap = el("div");
    wrap.appendChild(t);
    wrap.appendChild(el("div", "", " "));
    wrap.appendChild(detail);
    const old = $("wdetail");
    if (old && old.textContent && !old.textContent.startsWith("select"))
      detail.textContent = old.textContent;  // survive the 2s refresh
    $("view").replaceChildren(wrap);
  },
  async devices() {
    // JAX/XLA device telemetry: one row per (jax-loaded worker, device)
    // — HBM in use/peak/limit where the backend reports it — plus a
    // per-worker compile-counter row set. Stub workers (jax never
    // imported) are omitted; the tiles say how many reported.
    const d = await api("/api/device_stats");
    const snaps = (d.devices || []).filter(s => s.available);
    const rows = [];
    snaps.forEach(s => {
      const comp = s.compile || {};
      (s.devices || []).forEach(dev => rows.push({
        worker: s.worker_id, node: s.node_id,
        device: `${dev.platform}:${dev.id}`, kind: dev.device_kind,
        used: dev.bytes_in_use, peak: dev.peak_bytes_in_use,
        limit: dev.bytes_limit,
        compiles: comp.backend_compiles,
        compile_s: comp.compile_seconds,
      }));
    });
    const gib = v => v === undefined ? "" : (v / 2 ** 30).toFixed(2);
    const usedT = rows.reduce((a, r) => a + (r.used || 0), 0);
    const limitT = rows.reduce((a, r) => a + (r.limit || 0), 0);
    setTiles([
      ["jax workers", snaps.length],
      ["devices", rows.length],
      ["HBM used GiB", gib(usedT) || "0.00"],
      ["HBM total GiB", gib(limitT) || "0.00"],
    ]);
    if (!rows.length) {
      $("view").replaceChildren(el("div", "",
        "no jax-loaded workers reported device telemetry yet"));
      return;
    }
    $("view").replaceChildren(table(
      ["worker", "node", "device", "kind", "HBM used GiB",
       "HBM peak GiB", "HBM limit GiB", "compiles", "compile s"],
      rows, (r, c) => {
        if (c === "worker" || c === "node") {
          const td = el("td", "mono");
          td.textContent = c === "node" ? short(r.node || "") : r.worker;
          return td;
        }
        if (c === "HBM used GiB") return el("td", "", gib(r.used));
        if (c === "HBM peak GiB") return el("td", "", gib(r.peak));
        if (c === "HBM limit GiB") return el("td", "", gib(r.limit));
        if (c === "compile s") return el("td", "", r.compile_s ?? "");
        return el("td", c === "device" ? "mono" : "", r[c] ?? "");
      }));
  },
  async actors() {
    const d = await api("/api/actors");
    $("view").replaceChildren(table(
      ["actor_id", "class_name", "name", "state", "node_id", "pid",
       "num_restarts"],
      d.actors || [], (r, c) => {
        if (c === "state") return stateCell(r.state);
        const td = el("td",
          (c === "actor_id" || c === "node_id") ? "mono" : "");
        td.textContent = (c === "actor_id" || c === "node_id")
          ? short(r[c] || "") : (r[c] ?? "");
        return td;
      }));
  },
  async tasks() {
    const d = await api("/api/tasks?limit=500");
    const tasks = d.tasks || [];
    const byState = {};
    tasks.forEach(t => { byState[t.state] = (byState[t.state] || 0) + 1; });
    setTiles(Object.entries(byState).map(([k, v]) =>
      [k.toLowerCase(), v, k === "FAILED" ? "bad" : ""]));
    $("view").replaceChildren(table(
      ["task_id", "name", "type", "state", "node_id", "error"],
      tasks, (r, c) => {
        if (c === "state") return stateCell(r.state);
        const td = el("td",
          (c === "task_id" || c === "node_id") ? "mono" : "");
        td.textContent = (c === "task_id" || c === "node_id")
          ? short(r[c] || "") : (r[c] ?? "");
        return td;
      }));
  },
  async objects() {
    const d = await api("/api/objects?limit=500");
    setTiles([
      ["objects", d.total ?? (d.objects || []).length],
      ...(d.truncated ? [["showing", (d.objects || []).length, "warn"]]
                      : []),
    ]);
    $("view").replaceChildren(table(
      ["object_id", "size", "owner", "task", "callsite", "age s",
       "locations", "error"],
      d.objects || [], (r, c) => {
        const td = el("td",
          (c === "object_id" || c === "callsite") ? "mono" : "");
        if (c === "locations")
          td.textContent = (r.locations || []).map(short).join(", ");
        else if (c === "age s") td.textContent = r.age_s ?? "";
        else if (c === "owner") td.textContent = short(r.owner || "");
        else td.textContent = c === "object_id"
          ? short(r[c] || "") : (r[c] ?? "");
        return td;
      }));
  },
  async memory() {
    // Memory pane: cluster object-store rollup + per-node occupancy +
    // top objects with put-time attribution + the leak sweeper's flags.
    const [d, leaksD] = await Promise.all(
      [api("/api/memory_summary?group_by=callsite"),
       api("/api/memory_leaks")]);
    const t = d.totals || {};
    const mib = v => ((v || 0) / 1048576).toFixed(1);
    const leaks = leaksD.leaks || [];
    setTiles([
      ["store used MiB", mib(t.bytes_used)],
      ["capacity MiB", mib(t.bytes_capacity)],
      ["objects", t.objects ?? 0],
      ["evictions", t.evictions ?? 0, (t.evictions || 0) > 0 ? "warn" : ""],
      ["spilled MiB", mib(t.spilled_bytes)],
      ["leaks", leaks.length, leaks.length > 0 ? "bad" : "ok"],
    ]);
    const wrap = el("div");
    const nodes = Object.entries(d.nodes || {}).map(([id, n]) =>
      ({node: id, ...n}));
    wrap.appendChild(el("h3", "", "per-node occupancy"));
    wrap.appendChild(table(
      ["node", "used MiB", "capacity MiB", "occupancy", "objects",
       "evictions", "spilled MiB", "oom reports"],
      nodes, (r, c) => {
        if (c === "node")
          { const td = el("td", "mono"); td.textContent = short(r.node); return td; }
        if (c === "used MiB") return el("td", "", mib(r.bytes_used));
        if (c === "capacity MiB") return el("td", "", mib(r.bytes_capacity));
        if (c === "occupancy") return el("td",
          (r.occupancy || 0) > 0.8 ? "warn" : "",
          ((r.occupancy || 0) * 100).toFixed(0) + "%");
        if (c === "spilled MiB") return el("td", "", mib(r.spilled_bytes));
        if (c === "oom reports") {
          const td = el("td", "mono");
          td.textContent = (r.oom_reports || []).join(", ");
          return td;
        }
        return el("td", "", r[c.replace(" ", "_")] ?? r[c] ?? "");
      }));
    if (leaks.length) {
      wrap.appendChild(el("h3", "bad", "leaked objects"));
      wrap.appendChild(table(
        ["object_id", "kind", "size MiB", "age s", "task", "owner",
         "callsite"],
        leaks, (r, c) => {
          const td = el("td",
            (c === "object_id" || c === "callsite") ? "mono" : "");
          if (c === "size MiB") td.textContent = mib(r.size);
          else if (c === "age s") td.textContent = r.age_s ?? "";
          else if (c === "object_id") td.textContent = short(r.object_id);
          else if (c === "owner") td.textContent = short(r.owner || "");
          else td.textContent = r[c] ?? "";
          return td;
        }));
    }
    wrap.appendChild(el("h3", "", "top objects by size"));
    wrap.appendChild(table(
      ["object_id", "size MiB", "refs", "pinned", "task", "owner",
       "callsite", "age s", "nodes"],
      d.top_objects || [], (r, c) => {
        const td = el("td",
          (c === "object_id" || c === "callsite") ? "mono" : "");
        if (c === "size MiB") td.textContent = mib(r.size);
        else if (c === "refs")
          td.textContent = r.refcount ?? r.ref_holders ?? "";
        else if (c === "pinned") td.textContent = r.pinned ? "yes" : "";
        else if (c === "age s") td.textContent = r.age_s ?? "";
        else if (c === "object_id") td.textContent = short(r.object_id);
        else if (c === "owner") td.textContent = short(r.owner || "");
        else if (c === "nodes")
          td.textContent = (r.nodes || []).map(short).join(", ");
        else td.textContent = r[c] ?? "";
        return td;
      }));
    wrap.appendChild(el("h3", "",
      "bytes by " + (d.group_by || "callsite")));
    wrap.appendChild(table(
      ["key", "bytes MiB", "objects"],
      d.groups || [], (r, c) => {
        if (c === "bytes MiB") return el("td", "", mib(r.bytes));
        return el("td", c === "key" ? "mono" : "", r[c] ?? "");
      }));
    $("view").replaceChildren(wrap);
  },
  async placement_groups() {
    const d = await api("/api/placement_groups");
    let pgs = d.placement_groups || [];
    if (!Array.isArray(pgs))  // head returns {pg_id: info}
      pgs = Object.entries(pgs).map(([id, info]) =>
        ({pg_id: id, ...info}));
    $("view").replaceChildren(table(
      ["pg_id", "name", "state", "strategy", "bundles", "live",
       "reschedules"],
      pgs, (r, c) => {
        if (c === "state") return stateCell(r.state);
        const td = el("td", c === "pg_id" ? "mono" : "");
        if (c === "bundles")
          td.textContent = JSON.stringify(r.bundles || []);
        else if (c === "live")
          td.textContent = r.bundles
            ? `${(r.live_bundles || []).length}/${r.bundles.length}` : "";
        else if (c === "reschedules")
          td.textContent = r.reschedules ?? 0;
        else td.textContent = c === "pg_id"
          ? short(r.pg_id || r.id || "") : (r[c] ?? "");
        return td;
      }));
  },
  async jobs() {
    const d = await api("/api/jobs");
    const jobs = d.jobs || [];
    $("view").replaceChildren(table(
      ["job_id", "status", "entrypoint", "message"],
      jobs, (r, c) => {
        if (c === "status") return stateCell(r.status);
        const td = el("td", c === "job_id" ? "mono" : "");
        td.textContent = r[c] ?? "";
        return td;
      }));
  },
  async serve() {
    // Serve pane (memory-pane shape): SLO tiles + per-deployment
    // latency/shed table from the request-path plane, then the raw
    // application listing.
    // ?window= answers QPS from the head's metrics history ring —
    // no stall by construction (the route forbids the legacy
    // sleeping double-scrape); without a ring the field is simply
    // absent and the column shows "—".
    const [s, d] = await Promise.all(
      [api("/api/serve_stats?window=30"), api("/api/serve/applications")]);
    const deps = Object.entries(s.deployments || {})
      .map(([name, info]) => ({name, ...info}));
    const totals = deps.reduce((acc, r) => {
      const req = r.requests || {};
      acc.ok += req.ok || 0; acc.err += req.error || 0;
      acc.shed += Object.values(r.shed || {}).reduce((a, b) => a + b, 0);
      acc.ongoing += r.ongoing || 0;
      return acc;
    }, {ok: 0, err: 0, shed: 0, ongoing: 0});
    const worstP99 = Math.max(0, ...deps.map(r => r.p99_ms || 0));
    setTiles([
      ["deployments", deps.length],
      ["requests ok", totals.ok],
      ["errors", totals.err, totals.err > 0 ? "bad" : "ok"],
      ["shed (503)", totals.shed, totals.shed > 0 ? "warn" : ""],
      ["in flight", totals.ongoing],
      ["worst p99 ms", worstP99 ? worstP99.toFixed(1) : "—"],
    ]);
    const wrap = el("div");
    wrap.appendChild(el("h3", "", "per-deployment SLO"));
    wrap.appendChild(table(
      ["deployment", "replicas", "qps", "p50 ms", "p99 ms", "ok",
       "errors", "shed", "ongoing", "queued", "phases"],
      deps, (r, c) => {
        const req = r.requests || {};
        if (c === "deployment") return el("td", "", r.name);
        if (c === "replicas") return el("td", "", r.replicas ?? "?");
        if (c === "qps") return el("td", "", r.qps ?? "—");
        if (c === "p50 ms") return el("td", "", r.p50_ms ?? "—");
        if (c === "p99 ms") return el("td",
          (r.p99_ms || 0) > 1000 ? "warn" : "", r.p99_ms ?? "—");
        if (c === "ok") return el("td", "", req.ok || 0);
        if (c === "errors") return el("td",
          (req.error || 0) > 0 ? "bad" : "", req.error || 0);
        if (c === "shed") {
          const n = Object.values(r.shed || {})
            .reduce((a, b) => a + b, 0);
          return el("td", n > 0 ? "warn" : "", n);
        }
        if (c === "ongoing") return el("td", "", r.ongoing || 0);
        if (c === "queued") return el("td", "", r.queued || 0);
        const td = el("td", "mono");
        td.textContent = Object.entries(r.phases || {})
          .map(([p, v]) => `${p}:${v.p50_ms}ms`).join(" ");
        return td;
      }));
    const apps = d.applications || {};
    const rows = Object.entries(apps).flatMap(([app, info]) =>
      (info.deployments ? Object.entries(info.deployments) : [["", info]])
        .map(([dep, di]) => ({app, dep, info: di})));
    wrap.appendChild(el("h3", "", "applications"));
    wrap.appendChild(table(
      ["application", "deployment", "detail"],
      rows, (r, c) => {
        if (c === "application") return el("td", "", r.app);
        if (c === "deployment") return el("td", "", r.dep);
        const td = el("td", "mono");
        td.textContent = JSON.stringify(r.info);
        return td;
      }));
    $("view").replaceChildren(wrap);
  },
  async signals() {
    // Signal-plane pane: SLO burn-rate table + the `top` rollup, all
    // windowed queries over the head's metrics history ring (the API
    // route performs zero sleeps — pure ring reads).
    const d = await api("/api/signals?window=60");
    const slo = d.slo || {}, top = d.top || {};
    if (slo.ok === false) {
      setTiles([["signal plane", slo.error || "disabled", "warn"]]);
      $("view").replaceChildren(
        el("p", "dim", "enable with RAY_TPU_SIGNAL_SCRAPE_INTERVAL_S"));
      return;
    }
    const slos = Object.entries(slo.slos || {})
      .map(([name, s]) => ({name, ...s}));
    const burning = slos.filter(s => s.state === "burning").length;
    const warning = slos.filter(s => s.state === "warning").length;
    const evict = Object.values(top.evictions || {})
      .reduce((a, b) => a + b, 0);
    setTiles([
      ["series", top.series ?? slo.series ?? "?"],
      ["evictions", evict, evict > 0 ? "warn" : ""],
      ["SLOs", slos.length],
      ["burning", burning, burning > 0 ? "bad" : "ok"],
      ["warning", warning, warning > 0 ? "warn" : ""],
    ]);
    const wrap = el("div");
    wrap.appendChild(el("h3", "", "SLO burn rate"));
    wrap.appendChild(table(
      ["name", "state", "value", "threshold", "window s", "breaches",
       "expr"],
      slos, (r, c) => {
        if (c === "name") return el("td", "", r.name);
        if (c === "state") return el("td",
          r.state === "burning" ? "bad"
            : r.state === "warning" ? "warn" : "ok", r.state);
        if (c === "value") return el("td", "mono",
          r.value != null ? Number(r.value).toPrecision(4) : "—");
        if (c === "threshold") return el("td", "mono",
          `${r.op} ${r.threshold}`);
        if (c === "window s") return el("td", "", r.window_s);
        if (c === "breaches") return el("td", "", r.breach_streak);
        return el("td", "mono", r.expr);
      }));
    const nodes = Object.entries(top.nodes || {})
      .map(([id, n]) => ({id, ...n}));
    wrap.appendChild(el("h3", "", "nodes (windowed)"));
    wrap.appendChild(table(
      ["node", "cpu %", "rss MB", "store", "workers"],
      nodes, (r, c) => {
        if (c === "node") return el("td", "mono", short(r.id));
        if (c === "cpu %") return el("td", "", r.cpu_percent ?? "—");
        if (c === "rss MB") return el("td", "",
          r.rss_bytes != null ? (r.rss_bytes / 1e6).toFixed(1) : "—");
        if (c === "store") return el("td",
          (r.store_occupancy || 0) > 0.8 ? "warn" : "",
          r.store_occupancy != null
            ? (r.store_occupancy * 100).toFixed(1) + "%" : "—");
        return el("td", "", r.workers ?? "—");
      }));
    const deps = Object.entries(top.serve || {})
      .map(([name, s]) => ({name, ...s}));
    if (deps.length) {
      wrap.appendChild(el("h3", "", "serve (windowed)"));
      wrap.appendChild(table(
        ["deployment", "qps", "shed", "ttft p50 ms", "itl p50 ms",
         "latency p50 ms"],
        deps, (r, c) => {
          const ms = (v) => v != null ? (v * 1e3).toFixed(1) : "—";
          if (c === "deployment") return el("td", "", r.name);
          if (c === "qps") return el("td", "", r.qps ?? "—");
          if (c === "shed") return el("td",
            (r.shed_ratio || 0) > 0 ? "warn" : "",
            r.shed_ratio != null
              ? (r.shed_ratio * 100).toFixed(2) + "%" : "—");
          if (c === "ttft p50 ms") return el("td", "", ms(r.ttft_p50_s));
          if (c === "itl p50 ms") return el("td", "", ms(r.itl_p50_s));
          return el("td", "", ms(r.latency_p50_s));
        }));
    }
    $("view").replaceChildren(wrap);
  },
  async traces() {
    // Flight-recorder pane: store health tiles, the windowed TTFT
    // decomposition, and kept-trace rows — click a trace id to render
    // its assembled cross-process span tree inline.
    const d = await api("/api/traces?window=300");
    const st = d.stats || {}, ttft = d.ttft || {};
    const drops = Object.values(st.dropped || {})
      .reduce((a, b) => a + b, 0);
    const ms = (v) => v != null ? (v * 1e3).toFixed(1) : "—";
    setTiles([
      ["kept", st.kept ?? 0],
      ["assembled", st.assembled_total ?? 0],
      ["pending", st.pending ?? 0],
      ["dropped", drops, drops > 0 ? "warn" : ""],
      ["ttft p50 ms", ms(ttft.ttft_p50_s)],
      ["dominant", ttft.dominant || "—"],
    ]);
    const wrap = el("div");
    const phases = Object.entries(ttft.phases || {})
      .map(([name, p]) => ({name, ...p}))
      .sort((a, b) => (b.p50_s || 0) - (a.p50_s || 0));
    if (phases.length) {
      wrap.appendChild(el("h3", "",
        `ttft decomposition (${ttft.traces} traces, 5m window)`));
      wrap.appendChild(table(
        ["phase", "p50 ms", "p99 ms", "mean ms", "count"],
        phases, (r, c) => {
          if (c === "phase") return el("td", "", r.name);
          if (c === "p50 ms") return el("td", "mono", ms(r.p50_s));
          if (c === "p99 ms") return el("td", "mono", ms(r.p99_s));
          if (c === "mean ms") return el("td", "mono", ms(r.mean_s));
          return el("td", "", r.count);
        }));
    }
    wrap.appendChild(el("h3", "", "kept traces"));
    const pre = el("pre", "mono", "");
    wrap.appendChild(table(
      ["trace", "root", "dur ms", "spans", "kept", "dominant"],
      d.traces || [], (r, c) => {
        if (c === "trace") {
          const td = el("td", "mono");
          const a = el("a", "", r.trace_id.slice(0, 16) + "…");
          a.href = "#traces";
          a.onclick = async (ev) => {
            ev.preventDefault();
            const tr = await api("/api/trace?id=" + r.trace_id);
            const spans = tr.spans || [];
            const byId = {};
            spans.forEach(s => { byId[s.span_id] = s; });
            const depth = (s) => {
              let n = 0, p = s.parent_id;
              while (p && byId[p]) { n++; p = byId[p].parent_id; }
              return n;
            };
            const t0 = Math.min(
              ...spans.map(s => s.start_ns || Infinity));
            pre.textContent = "trace " + tr.trace_id + "\n" +
              spans.slice()
                .sort((a2, b2) => (a2.start_ns || 0) - (b2.start_ns || 0))
                .map(s => "  ".repeat(depth(s)) + s.name +
                  "  [+" + (((s.start_ns || t0) - t0) / 1e6).toFixed(1)
                  + "ms  " + (((s.end_ns || s.start_ns || 0)
                  - (s.start_ns || 0)) / 1e6).toFixed(1) + "ms  "
                  + (s.node_id || ("pid " + (s.pid ?? "?"))) + "]"
                  + ((s.status || "OK") !== "OK"
                    ? "  !! " + s.status : ""))
                .join("\n");
          };
          td.appendChild(a);
          return td;
        }
        if (c === "root") return el("td", "mono", r.root || "?");
        if (c === "dur ms") return el("td",
          r.errored ? "bad" : "", (r.duration_s * 1e3).toFixed(1));
        if (c === "spans") return el("td", "", r.spans);
        if (c === "kept") return el("td", "", r.kept_because);
        return el("td", "", r.dominant || "—");
      }));
    wrap.appendChild(pre);
    $("view").replaceChildren(wrap);
  },
  async train() {
    // Training goodput pane (serve-pane shape): stall-fraction /
    // goodput tiles, per-trial step-phase table with the downtime
    // ledger, then the input-pipeline stage rollup.
    const [t, d] = await Promise.all(
      [api("/api/train_stats"), api("/api/data_stats")]);
    const trials = Object.entries(t.trials || {})
      .map(([name, info]) => ({name, ...info}));
    const reports = trials.reduce((a, r) => a + (r.reports || 0), 0);
    const downtime = trials.reduce((a, r) =>
      a + Object.values(r.downtime_s || {}).reduce((x, y) => x + y, 0),
      0);
    const worstSkew = Math.max(0, ...trials.map(r => r.rank_skew || 0));
    const stall = d.stall_fraction;
    setTiles([
      ["trials", trials.length],
      ["reports", reports],
      ["stall fraction", stall != null
        ? (stall * 100).toFixed(1) + "%" : "—",
        stall > 0.3 ? "warn" : ""],
      ["downtime s", downtime.toFixed(1),
        downtime > 0 ? "warn" : ""],
      ["worst rank skew", worstSkew ? worstSkew.toFixed(2) + "x" : "—"],
    ]);
    const wrap = el("div");
    wrap.appendChild(el("h3", "", "per-trial goodput"));
    wrap.appendChild(table(
      ["trial", "reports", "goodput %", "rank skew", "downtime",
       "phases (p50)"],
      trials, (r, c) => {
        if (c === "trial") return el("td", "", r.name);
        if (c === "reports") return el("td", "", r.reports || 0);
        if (c === "goodput %") return el("td",
          (r.goodput_pct || 100) < 95 ? "warn" : "",
          r.goodput_pct ?? "—");
        if (c === "rank skew") return el("td", "", r.rank_skew ?? "—");
        if (c === "downtime") {
          const td = el("td", "mono");
          td.textContent = Object.entries(r.downtime_s || {})
            .map(([cz, s]) => `${cz}:${s.toFixed(1)}s`).join(" ");
          return td;
        }
        const td = el("td", "mono");
        td.textContent = Object.entries(r.phases || {})
          .map(([p, v]) => `${p}:${v.p50_ms}ms`).join(" ");
        return td;
      }));
    const anatRows = trials.flatMap(r => {
      const anat = r.anatomy || {};
      return Object.entries(anat.ranks || {}).map(([rank, phases]) => ({
        trial: r.name, rank, phases,
        straggler: anat.straggler,
      }));
    });
    if (anatRows.length) {
      wrap.appendChild(el("h3", "", "step anatomy (per rank)"));
      wrap.appendChild(table(
        ["trial", "rank", "data_wait", "host", "compute", "sync",
         "verdict"],
        anatRows, (r, c) => {
          if (c === "trial") return el("td", "", r.trial);
          if (c === "rank") return el("td", "", r.rank);
          if (["data_wait", "host", "compute", "sync"].includes(c)) {
            const v = (r.phases || {})[c];
            return el("td", "mono",
              v != null ? (v * 1e3).toFixed(1) + "ms" : "—");
          }
          const s = r.straggler;
          if (!s || String(s.rank) !== String(r.rank)
              || s.cause === "balanced")
            return el("td", "", "—");
          return el("td", "warn",
            s.cause + " +" + ((s.excess_s || 0) * 1e3).toFixed(1)
            + "ms");
        }));
    }
    const stages = Object.entries(d.stages || {})
      .map(([name, info]) => ({name, ...info}));
    wrap.appendChild(el("h3", "", "input-pipeline stages"));
    wrap.appendChild(table(
      ["stage", "executions", "blocks", "rows", "wall ms", "MB/s"],
      stages, (r, c) => {
        if (c === "stage") return el("td", "", r.name);
        if (c === "executions") return el("td", "", r.executions || 0);
        if (c === "blocks") return el("td", "", r.blocks ?? "—");
        if (c === "rows") return el("td", "", r.rows_total ?? "—");
        if (c === "wall ms") return el("td", "", r.wall_ms ?? "—");
        return el("td", "",
          r.bytes_per_s ? (r.bytes_per_s / 1e6).toFixed(1) : "—");
      }));
    const it = d.iterator || {};
    const iterRows = ["wait", "user", "transfer"]
      .filter(p => it[p]).map(p => ({phase: p, ...it[p]}));
    if (iterRows.length) {
      wrap.appendChild(el("h3", "", "consumer loop"));
      wrap.appendChild(table(
        ["phase", "batches", "p50 ms", "mean ms"],
        iterRows, (r, c) => {
          if (c === "phase") return el("td", "", r.phase);
          if (c === "batches") return el("td", "", r.count);
          if (c === "p50 ms") return el("td", "", r.p50_ms ?? "—");
          return el("td", "", r.mean_ms ?? "—");
        }));
    }
    $("view").replaceChildren(wrap);
  },
  async logs() {
    if (!$("logs")) {
      const pre = el("div"); pre.id = "logs";
      $("view").replaceChildren(pre);
      logBuf.forEach(line => pre.appendChild(el("div", "", line)));
    }
    const d = await api(`/api/logs?after_seq=${logCursor}&limit=500`);
    logCursor = d.cursor ?? logCursor;
    const pre = $("logs");
    // Autoscroll ONLY when the user was already at the bottom —
    // scrollback must survive the 2s refresh cadence.
    const pinned = pre.scrollHeight - pre.scrollTop - pre.clientHeight < 40;
    (d.entries || []).forEach(e => {
      const line = typeof e === "string" ? e
        : `[${e.pid ?? "?"}@${short(e.node_id || "")}] ${e.line ?? JSON.stringify(e)}`;
      logBuf.push(line);
      pre.appendChild(el("div", "", line));
    });
    while (logBuf.length > 3000) { logBuf.shift(); pre.firstChild.remove(); }
    if (pinned) pre.scrollTop = pre.scrollHeight;
  },
};

function buildTabs() {
  const nav = $("tabs");
  TABS.forEach(t => {
    const b = el("button", t === active ? "active" : "", t.replace("_", " "));
    b.onclick = () => {
      active = t; location.hash = t;
      [...nav.children].forEach(x => x.classList.remove("active"));
      b.classList.add("active");
      if (t !== "logs") $("view").replaceChildren();
      setTiles([]);
      refresh();
    };
    nav.appendChild(b);
  });
}

async function refresh() {
  try {
    await RENDER[active]();
    $("error").textContent = "";
    $("updated").textContent =
      "updated " + new Date().toLocaleTimeString();
  } catch (e) {
    $("error").textContent = String(e);
  }
}

buildTabs();
$("addr").textContent = location.host;
refresh();
setInterval(refresh, 2000);
</script>
</body>
</html>
"""
