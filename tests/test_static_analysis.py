"""`ray-tpu analyze` — the concurrency & contract static-analysis gate.

Two jobs: (1) each seeded-regression fixture — the PR-5 finalizer
deadlock, a head-shaped `_obj_lock -> _lock` inversion, RPC-under-lock,
await-under-lock, an unregistered failpoint site — must produce exactly
its expected rule id (the analyzer can reproduce the postmortems); and
(2) the repo-wide run must be clean (zero unbaselined findings) — the
tier-1 gate that keeps those bug classes unrepresentable.
"""

from __future__ import annotations

import json
import os
import subprocess
import sys
import textwrap

import pytest

from ray_tpu.util import analyze
from ray_tpu.util.analyze import core as acore


def _scan(tmp_path, source, rules=None, name="fixture.py"):
    """Run the analyzer over one fixture file rooted at tmp_path."""
    p = tmp_path / name
    p.write_text(textwrap.dedent(source))
    return analyze.run_paths([str(p)], rules=rules, root=str(tmp_path))


def _rules(findings):
    return sorted({f.rule for f in findings})


# ---------------------------------------------------------------------------
# The five seeded regressions (acceptance: each fails with its rule id).
# ---------------------------------------------------------------------------


def test_seeded_pr5_finalizer_deadlock(tmp_path):
    """The EXACT PR-5 pattern: ObjectRef weakref finalizers calling
    _decref under a plain (non-reentrant) Lock — FS001."""
    findings = _scan(tmp_path, """\
        import threading
        import weakref


        class LocalBackend:
            def __init__(self):
                self._objects = {}
                self._refcounts = {}
                self._objects_lock = threading.Lock()

            def make_ref(self, ref, oid):
                with self._objects_lock:
                    self._refcounts[oid] = self._refcounts.get(oid, 0) + 1
                weakref.finalize(ref, self._decref, oid)
                return ref

            def _decref(self, oid):
                with self._objects_lock:
                    n = self._refcounts.get(oid, 0) - 1
                    if n <= 0:
                        self._refcounts.pop(oid, None)
                        self._objects.pop(oid, None)
        """)
    fs = [f for f in findings if f.rule == "FS001"]
    assert fs, f"PR-5 pattern must produce FS001, got {_rules(findings)}"
    assert any("_objects_lock" in f.detail for f in fs)
    assert any(f.scope == "LocalBackend._decref" for f in fs)


def test_seeded_shard_lock_inversion(tmp_path):
    """A `_obj_lock -> _lock` inversion in head-shaped code (declared
    LOCK_ORDER tuple, _ShardLock-style shards) — LO001."""
    findings = _scan(tmp_path, """\
        import threading

        LOCK_ORDER = ("_lock", "_obj_lock", "_event_lock")


        class HeadServer:
            def __init__(self):
                self._lock = threading.RLock()
                self._obj_lock = threading.RLock()
                self._event_lock = threading.RLock()
                self._refs = {}
                self._actors = {}

            def rpc_actor_death(self, actor_id, oid):
                with self._obj_lock:
                    self._refs.pop(oid, None)
                    with self._lock:
                        self._actors.pop(actor_id, None)
        """)
    lo = [f for f in findings if f.rule == "LO001"]
    assert lo, f"inversion must produce LO001, got {_rules(findings)}"
    assert lo[0].detail == "_obj_lock->_lock"


def test_seeded_rpc_under_lock(tmp_path):
    findings = _scan(tmp_path, """\
        import threading


        class Agent:
            def __init__(self, head):
                self._lock = threading.RLock()
                self.head = head

            def report(self, payload):
                with self._lock:
                    self.head.call("upload", payload)
        """)
    bl = [f for f in findings if f.rule == "BL001"]
    assert bl, f"RPC under lock must produce BL001, got {_rules(findings)}"
    assert bl[0].scope == "Agent.report"


def test_seeded_await_under_lock(tmp_path):
    findings = _scan(tmp_path, """\
        import threading


        class Router:
            def __init__(self):
                self._lock = threading.Lock()
                self._queue = []

            async def assign(self, request):
                with self._lock:
                    return await request.ready()
        """)
    ah = [f for f in findings if f.rule == "AH001"]
    assert ah, f"await under lock must produce AH001, got {_rules(findings)}"


def test_seeded_unregistered_failpoint(tmp_path):
    findings = _scan(tmp_path, """\
        from ray_tpu.util import failpoints


        def schedule(batch):
            failpoints.hit("head.schedule.not_a_registered_site")
            return batch
        """)
    cd = [f for f in findings if f.rule == "CD001"]
    assert cd, f"unregistered site must produce CD001, got {_rules(findings)}"
    assert cd[0].detail == "head.schedule.not_a_registered_site"
    # A registered site is clean.
    clean = _scan(tmp_path, """\
        from ray_tpu.util import failpoints


        def schedule(batch):
            failpoints.hit("head.schedule.batch")
            return batch
        """, name="ok.py")
    assert not [f for f in clean if f.rule == "CD001"]


# ---------------------------------------------------------------------------
# Rule mechanics beyond the five seeds.
# ---------------------------------------------------------------------------


def test_nonreentrant_reentry_via_helper(tmp_path):
    findings = _scan(tmp_path, """\
        import threading


        class Store:
            def __init__(self):
                self._mu = threading.Lock()
                self._t = {}

            def put(self, k, v):
                with self._mu:
                    self._evict()
                    self._t[k] = v

            def _evict(self):
                with self._mu:
                    self._t.clear()
        """)
    assert any(f.rule == "LO002" for f in findings)
    # RLock re-entry is fine.
    clean = _scan(tmp_path, """\
        import threading


        class Store:
            def __init__(self):
                self._mu = threading.RLock()

            def put(self):
                with self._mu:
                    self._evict()

            def _evict(self):
                with self._mu:
                    pass
        """, name="ok.py")
    assert not [f for f in clean if f.rule == "LO002"]


def test_inconsistent_order_lo003(tmp_path):
    findings = _scan(tmp_path, """\
        import threading


        class T:
            def __init__(self):
                self._a = threading.RLock()
                self._b = threading.RLock()

            def ab(self):
                with self._a:
                    with self._b:
                        pass

            def ba(self):
                with self._b:
                    with self._a:
                        pass
        """)
    assert any(f.rule == "LO003" for f in findings)


def test_lock_order_drift_lo004_and_head_tuple():
    """head.py's LOCK_ORDER is live, importable, matches the shard
    locks the analyzer discovers — and a drifted tuple is flagged."""
    from ray_tpu.cluster.head import LOCK_ORDER

    assert LOCK_ORDER == ("_lock", "_obj_lock", "_event_lock")
    head_py = os.path.join(acore.repo_root(), "ray_tpu", "cluster",
                           "head.py")
    findings = analyze.run_paths([head_py], rules=["lock-order"])
    assert not [f for f in findings if f.rule == "LO004"]


def test_lock_order_drift_lo004_fixture(tmp_path):
    findings = _scan(tmp_path, """\
        import threading

        LOCK_ORDER = ("_lock", "_gone_lock")


        class H:
            def __init__(self):
                self._lock = threading.RLock()
        """)
    lo4 = [f for f in findings if f.rule == "LO004"]
    assert len(lo4) == 1 and lo4[0].detail == "_gone_lock"


def test_guarded_by_mutation_and_caller_inference(tmp_path):
    findings = _scan(tmp_path, """\
        import threading


        class H:
            def __init__(self):
                self._lock = threading.RLock()
                self._nodes = {}  # guarded-by: _lock

            def rpc_register(self, nid, info):
                with self._lock:
                    self._admit(nid, info)

            def _admit(self, nid, info):
                self._nodes[nid] = info      # ok: caller holds _lock

            def rpc_rogue(self, nid):
                self._nodes.pop(nid, None)   # GB001
        """)
    gb = [f for f in findings if f.rule == "GB001"]
    assert len(gb) == 1
    assert gb[0].scope == "H.rpc_rogue"
    # Unknown lock name in the annotation -> GB002.
    bad = _scan(tmp_path, """\
        import threading


        class H:
            def __init__(self):
                self._lock = threading.RLock()
                self._nodes = {}  # guarded-by: _node_lock
        """, name="bad.py")
    assert any(f.rule == "GB002" for f in bad)


def test_guarded_by_closure_called_under_lock(tmp_path):
    """A closure defined AND invoked inside the critical section is
    guarded by its call site; one only handed to a Thread has no call
    site and must lock for itself."""
    findings = _scan(tmp_path, """\
        import threading


        class C:
            def __init__(self):
                self._lock = threading.RLock()
                self._actors = {}  # guarded-by: _lock

            def run(self, k, v):
                with self._lock:
                    def inner():
                        self._actors[k] = v
                    inner()

            def spawn(self, k):
                def body():
                    self._actors.pop(k, None)   # GB001: runs unlocked
                threading.Thread(target=body).start()
        """)
    gb = [f for f in findings if f.rule == "GB001"]
    assert [f.scope for f in gb] == ["C.spawn.body"]


def test_allow_blocking_pragma_and_cv_wait_exemption(tmp_path):
    findings = _scan(tmp_path, """\
        import threading


        class Store:
            def __init__(self, conn):
                self._mu = threading.Lock()  # analyze: allow-blocking
                self._q_lock = threading.RLock()
                self._cv = threading.Condition(self._q_lock)
                self._conn = conn
                self._q = []

            def flush(self):
                with self._mu:
                    self._conn.commit()      # exempt: allow-blocking

            def pop(self):
                with self._cv:
                    while not self._q:
                        self._cv.wait(0.5)   # exempt: releases q_lock
                    return self._q.pop()
        """)
    assert not [f for f in findings
                if f.rule in ("BL004", "BL005")], _rules(findings)
    # Without the pragma the commit IS a finding.
    hot = _scan(tmp_path, """\
        import threading


        class Store:
            def __init__(self, conn):
                self._mu = threading.Lock()
                self._conn = conn

            def flush(self):
                with self._mu:
                    self._conn.commit()
        """, name="hot.py")
    assert any(f.rule == "BL005" for f in hot)


def test_contract_metric_tag_keys(tmp_path):
    findings = _scan(tmp_path, """\
        from ray_tpu.util import metrics as _metrics


        def shed(dep):
            _metrics.SERVE_SHED_TOTAL.inc(
                tags={"node_id": "n", "deployment": dep})


        def phase(sec):
            _metrics.TASK_PHASE_SECONDS.observe(
                sec, tags={"node_id": "n", "phase": "execute",
                           "typo": "x"})


        def fake():
            _metrics.NOT_A_FAMILY.inc()
        """)
    cd3 = [f for f in findings if f.rule == "CD003"]
    assert len(cd3) == 2
    assert any("missing" in f.message and "reason" in f.message
               for f in cd3)
    assert any("extra" in f.message and "typo" in f.message
               for f in cd3)
    cd4 = [f for f in findings if f.rule == "CD004"]
    assert len(cd4) == 1 and cd4[0].detail == "NOT_A_FAMILY"


def test_contract_two_sided_recorder(tmp_path):
    findings = _scan(tmp_path, """\
        import collections
        import threading

        from ray_tpu.util import metrics as _metrics

        _buf = collections.deque(maxlen=128)
        _buf_lock = threading.Lock()


        def drain_events():
            with _buf_lock:
                out = list(_buf)
                _buf.clear()
            return out


        def apply_events(events, node_id):
            for ev in events:
                _metrics.SERVE_EVENTS_DROPPED.inc(
                    float(ev.get("n", 0)), tags={"node_id": node_id})


        def record_oneside(dep):
            _metrics.SERVE_BATCH_SIZE.observe(
                1.0, tags={"node_id": "local", "deployment": dep})
        """)
    cd5 = [f for f in findings if f.rule == "CD005"]
    assert len(cd5) == 1 and cd5[0].scope == "record_oneside"
    assert any(f.rule == "CD006" for f in findings)  # no _emit at all


def test_blocking_in_nested_closure(tmp_path):
    """Drain-coordinator-style nested thread bodies are analyzed too."""
    findings = _scan(tmp_path, """\
        import threading


        class Head:
            def __init__(self):
                self._lock = threading.RLock()

            def rpc_drain(self, node):
                def _drain():
                    with self._lock:
                        node.client.call("drain_self")
                threading.Thread(target=_drain, daemon=True).start()
        """)
    bl = [f for f in findings if f.rule == "BL001"]
    assert len(bl) == 1 and bl[0].scope == "Head.rpc_drain._drain"


# ---------------------------------------------------------------------------
# The round-15 pass families: RT / DL / TO / JX / LC seeded regressions.
# ---------------------------------------------------------------------------


def test_seeded_pr13_blind_resubmit_rt(tmp_path):
    """The EXACT PR-13 shape: a bounded submit retry catching broadly —
    a timed-out submit MAY have executed on a wedged replica, so the
    blind resubmit double-admits (RT001 + RT003)."""
    findings = _scan(tmp_path, """\
        import time


        def stream_call(backend, args):
            for attempt in range(3):
                try:
                    return backend.call("llm_submit", args, timeout=60.0)
                except Exception:
                    time.sleep(0.2 * (attempt + 1))
        """)
    assert any(f.rule == "RT001" and f.detail == "llm_submit"
               for f in findings), _rules(findings)
    assert any(f.rule == "RT003" for f in findings), _rules(findings)
    # Narrowed guard + maybe_executed branch: clean.
    clean = _scan(tmp_path, """\
        import time


        def stream_call(backend, args):
            for attempt in range(3):
                try:
                    return backend.call("llm_submit", args, timeout=60.0)
                except Exception as e:
                    if getattr(e, "maybe_executed", False):
                        raise
                    time.sleep(0.2 * (attempt + 1))
        """, name="ok.py")
    assert not [f for f in clean if f.rule.startswith("RT")]


def test_rt_idempotent_declaration_and_fanout_exemption(tmp_path):
    """A same-module `# idempotent` handler satisfies RT001; a fan-out
    loop (call references the loop variable) is never a retry."""
    findings = _scan(tmp_path, """\
        class Head:
            def commit_all(self, nodes, pg_id):
                for bi in range(3):
                    for attempt in range(3):
                        try:
                            self.node.call("commit_bundle", pg_id, bi)
                            break
                        except Exception:
                            if attempt == 2:
                                return False
                    # fall through: next attempt replays the commit

            def fanout(self, nodes):
                for n in nodes:
                    try:
                        n.client.call("free_object", "oid")
                    except Exception:
                        continue


        class Agent:
            def rpc_commit_bundle(self, pg_id, bi):  # idempotent
                if (pg_id, bi) in self._bundles:
                    self._state[(pg_id, bi)] = "COMMITTED"
                return True
        """)
    rt = [f for f in findings if f.rule == "RT001"]
    # commit_bundle is declared idempotent in-module; the fan-out loop
    # references its loop variable. 'bi' in commit_all's outer loop IS
    # referenced by the call -> fan-out there too; the `for attempt`
    # loop is the retry but the handler is declared. Nothing fires.
    assert not rt, [(f.detail, f.scope) for f in rt]


def test_rt002_declared_idempotent_must_absorb(tmp_path):
    findings = _scan(tmp_path, """\
        class Agent:
            def rpc_track(self, item):  # idempotent
                self._log.append(item)
                return True
        """)
    assert any(f.rule == "RT002" for f in findings), _rules(findings)
    # The above-the-def marker form is honored by BOTH halves: RT002
    # scrutiny AND the RT001 idempotent table (a declaration must never
    # be half-honored).
    from ray_tpu.util.analyze.retry import _declared_idempotent

    src = textwrap.dedent("""\
        class Agent:
            # idempotent
            def rpc_above(self, key):
                if key in self._seen:
                    return True
                self._seen[key] = True
                return True
        """)
    assert "above" in _declared_idempotent(src.splitlines())
    above = _scan(tmp_path, """\
        class Agent:
            # idempotent
            def rpc_above(self, key):
                self._log.append(key)
                return True
        """, name="above.py")
    assert any(f.rule == "RT002" for f in above)
    clean = _scan(tmp_path, """\
        class Agent:
            def rpc_track(self, key, item):  # idempotent
                if key in self._seen:
                    return True
                self._log.append(item)
                return True
        """, name="ok.py")
    assert not [f for f in clean if f.rule == "RT002"]


def test_seeded_bare_reaper_loop_dl(tmp_path):
    """A bare daemon loop doing RPC: one exception kills the thread
    (DL001); a swallowing survival handler must count (DL002)."""
    findings = _scan(tmp_path, """\
        import time


        class Agent:
            def _reap_loop(self):
                while True:
                    time.sleep(1.0)
                    self.head.call("report_corpses", self.node_id)
        """)
    assert any(f.rule == "DL001" for f in findings), _rules(findings)
    swallowing = _scan(tmp_path, """\
        import time


        class Agent:
            def _reap_loop(self):
                while True:
                    time.sleep(1.0)
                    try:
                        self.head.call("report_corpses", self.node_id)
                    except Exception:
                        pass
        """, name="swallow.py")
    assert any(f.rule == "DL002" for f in swallowing)
    assert not [f for f in swallowing if f.rule == "DL001"]
    counted = _scan(tmp_path, """\
        import time

        from ray_tpu.util import metrics


        class Agent:
            def _reap_loop(self):
                while True:
                    time.sleep(1.0)
                    try:
                        self.head.call("report_corpses", self.node_id)
                    except Exception:
                        metrics.count_loop_restart("agent.reap")
        """, name="counted.py")
    assert not [f for f in counted if f.rule.startswith("DL")]


def test_seeded_timeout_inversion_to(tmp_path):
    """The PR-14 pair: a 60s RPC timeout declared to outlast a 300s
    budget fails TO001; deriving it from the budget passes."""
    findings = _scan(tmp_path, """\
        REACQUIRE_BUDGET_S = 300.0


        def hook(agent, wid):
            agent.call("task_unblocked", wid,
                       # timeout-budget: outlasts REACQUIRE_BUDGET_S
                       timeout=60.0)
        """)
    to = [f for f in findings if f.rule == "TO001"]
    assert len(to) == 1 and "60" in to[0].detail
    clean = _scan(tmp_path, """\
        REACQUIRE_BUDGET_S = 300.0


        def hook(agent, wid):
            agent.call("task_unblocked", wid,
                       # timeout-budget: outlasts REACQUIRE_BUDGET_S
                       timeout=REACQUIRE_BUDGET_S + 30.0)
        """, name="ok.py")
    assert not [f for f in clean if f.rule.startswith("TO")]
    # config.<knob> budgets resolve against the live registry defaults.
    cfgcase = _scan(tmp_path, """\
        def hook(agent, wid):
            agent.call("task_unblocked", wid,
                       # timeout-budget: outlasts config.cpu_reacquire_budget_s
                       timeout=60.0)
        """, name="cfg.py")
    assert any(f.rule == "TO001" for f in cfgcase)
    # Unresolvable budget ref / detached annotation -> TO002 drift.
    drift = _scan(tmp_path, """\
        def hook(agent, wid):
            agent.call("task_unblocked", wid,
                       # timeout-budget: outlasts config.no_such_knob
                       timeout=60.0)


        # timeout-budget: outlasts 10.0
        x = 1
        """, name="drift.py")
    assert len([f for f in drift if f.rule == "TO002"]) == 2


def test_seeded_unmarked_static_jit_scalar_jx(tmp_path):
    findings = _scan(tmp_path, """\
        import jax


        def build(fn, x):
            step = jax.jit(fn)
            return step(x, 5)
        """)
    jx = [f for f in findings if f.rule == "JX001"]
    assert len(jx) == 1 and jx[0].detail == "step"
    clean = _scan(tmp_path, """\
        import jax


        def build(fn, x):
            step = jax.jit(fn, static_argnums=(1,))
            return step(x, 5)
        """, name="ok.py")
    assert not [f for f in clean if f.rule == "JX001"]


def test_jx_host_sync_and_decode_dtype_regions(tmp_path):
    findings = _scan(tmp_path, """\
        import jax
        import jax.numpy as jnp
        import numpy as np


        def step_once(engine):  # jax-hot-path
            out = engine.step()
            host = np.asarray(out)
            out.block_until_ready()
            return host


        def init_cache(cfg, slots):  # decode-path
            return jnp.zeros((slots, 64), jnp.float32)


        def unmarked(engine):
            return np.asarray(engine.step())
        """)
    jx2 = [f for f in findings if f.rule == "JX002"]
    assert len(jx2) == 2, [(f.detail) for f in jx2]
    assert all(f.scope == "step_once" for f in jx2)  # unmarked exempt
    jx4 = [f for f in findings if f.rule == "JX004"]
    assert len(jx4) == 1 and jx4[0].scope == "init_cache"


def test_jx_sleepless_poll_spin(tmp_path):
    findings = _scan(tmp_path, """\
        def collect(handle, rids):
            out = {}
            while rids:
                got = handle.llm_poll(rids)
                out.update(got)
            return out
        """)
    assert any(f.rule == "JX003" for f in findings), _rules(findings)
    clean = _scan(tmp_path, """\
        import time


        def collect(handle, rids):
            out = {}
            while rids:
                got = handle.llm_poll(rids)
                out.update(got)
                time.sleep(0.05)
            return out
        """, name="ok.py")
    assert not [f for f in clean if f.rule == "JX003"]
    # Blocking lives one level down in a self-helper: exempt.
    helper = _scan(tmp_path, """\
        class Runner:
            def _drain(self):
                return self.queue.get(timeout=0.2)

            def run(self):
                while True:
                    self._drain()
                    self._poll_completions()

            def _poll_completions(self):
                pass
        """, name="helper.py")
    assert not [f for f in helper if f.rule == "JX003"]


def test_seeded_unretracted_gauge_lc001(tmp_path):
    """A per-entity gauge family emitted with no retraction anywhere in
    the scanned tree — the dead-replica-forever drift."""
    from ray_tpu.util.analyze import lifecycle

    p = tmp_path / "emit.py"
    p.write_text(textwrap.dedent("""\
        from ray_tpu.util import metrics as _metrics


        def record(trial, rank, sec):
            _metrics.TRAIN_RANK_STEP_SECONDS.set(
                sec, tags={"node_id": "n", "trial": trial,
                           "rank": str(rank)})
        """))
    mod = acore.parse_file(str(p), root=str(tmp_path))
    findings = lifecycle.unretracted_gauge_findings([mod])
    assert any(f.rule == "LC001"
               and f.detail == "TRAIN_RANK_STEP_SECONDS"
               for f in findings), [f.detail for f in findings]
    # A retraction sweep anywhere in view clears it.
    q = tmp_path / "retract.py"
    q.write_text(textwrap.dedent("""\
        from ray_tpu.util import metrics as _metrics


        def retract(trial, rank):
            _metrics.TRAIN_RANK_STEP_SECONDS.remove(
                tags={"node_id": "n", "trial": trial,
                      "rank": str(rank)})
        """))
    mod2 = acore.parse_file(str(q), root=str(tmp_path))
    findings2 = lifecycle.unretracted_gauge_findings([mod, mod2])
    assert not [f for f in findings2
                if f.detail == "TRAIN_RANK_STEP_SECONDS"]


def test_lc002_drain_without_requeue(tmp_path):
    findings = _scan(tmp_path, """\
        def flush_loop(agent, obs):
            while True:
                events = obs.drain_events()
                try:
                    agent.call("worker_events", events)
                except Exception:
                    pass
        """)
    assert any(f.rule == "LC002" for f in findings), _rules(findings)
    clean = _scan(tmp_path, """\
        def flush_loop(agent, obs):
            while True:
                events = obs.drain_events()
                try:
                    agent.call("worker_events", events)
                except Exception:
                    obs.requeue_events(events)
        """, name="ok.py")
    assert not [f for f in clean if f.rule == "LC002"]


def test_lc003_slot_guard_release_edge(tmp_path):
    findings = _scan(tmp_path, """\
        class Engine:
            def admit(self, batch, free):
                slots = free[:len(batch)]  # slot-guard: _requeue
                self._prefill(batch, slots)
        """)
    lc3 = [f for f in findings if f.rule == "LC003"]
    assert len(lc3) == 1 and lc3[0].detail == "_requeue"
    clean = _scan(tmp_path, """\
        class Engine:
            def admit(self, batch, free):
                slots = free[:len(batch)]  # slot-guard: _requeue
                try:
                    self._prefill(batch, slots)
                except Exception:
                    self._requeue(batch)
        """, name="ok.py")
    assert not [f for f in clean if f.rule == "LC003"]


def test_new_rule_pragma_baseline_and_diff_workflows(tmp_path):
    """The pragma/baseline/diff machinery covers the new families the
    same way it covers PR-10's."""
    src = """\
        import time


        def resubmit(backend, args):
            for attempt in range(3):
                try:
                    return backend.call("llm_submit", args)
                except Exception:
                    time.sleep(0.1)
        """
    # Inline ignore silences exactly the pragma'd rule.
    pragma = textwrap.dedent(src).replace(
        'backend.call("llm_submit", args)',
        'backend.call("llm_submit", args)  '
        '# analyze: ignore[RT001,RT003]')
    p = tmp_path / "m.py"
    p.write_text(pragma)
    res = analyze.run(paths=[str(p)], use_baseline=False,
                      root=str(tmp_path))
    assert not [f for f in res["new"] if f.rule.startswith("RT")]
    # Baseline allowlists the stable key.
    p.write_text(textwrap.dedent(src))
    res = analyze.run(paths=[str(p)], use_baseline=False,
                      root=str(tmp_path))
    keys = {f.key for f in res["new"]}
    assert keys, "expected RT findings"
    bl = tmp_path / "ANALYZE_BASELINE.json"
    bl.write_text(json.dumps(
        {"entries": {k: "justified in test" for k in keys}}))
    res2 = analyze.run(paths=[str(p)], baseline_file=str(bl),
                       root=str(tmp_path))
    assert res2["ok"] and len(res2["allowed"]) == len(keys)
    # Diff mode: only the lines a PR touched fire.
    subprocess.run(["git", "init", "-q", str(tmp_path)], check=True)
    clean_seed = tmp_path / "seed.py"
    clean_seed.write_text("x = 1\n")
    subprocess.run(["git", "add", "seed.py"], cwd=str(tmp_path),
                   check=True)
    subprocess.run(["git", "-c", "user.email=t@t", "-c", "user.name=t",
                    "commit", "-qm", "seed"], cwd=str(tmp_path),
                   check=True)
    res3 = analyze.run(paths=[str(clean_seed), str(p)],
                       use_baseline=False, diff_rev="HEAD",
                       root=str(tmp_path))
    assert {f.rule for f in res3["new"]} >= {"RT001"}  # untracked = new


def test_live_contract_annotations_repo_wide():
    """The real declarations this round added are live: the idempotent
    table covers the 2PC + client-id-keyed handlers, and the five new
    pass families are registered."""
    from ray_tpu.util.analyze import retry as retry_pass_mod

    table = retry_pass_mod.repo_idempotent_table()
    assert {"prepare_bundle", "commit_bundle", "return_bundle",
            "worker_events", "task_done", "heartbeat", "gossip",
            "spill", "free_object", "cancel_task"} <= set(table), table
    assert {"retry", "daemon-loop", "timeout-order", "jax-hotpath",
            "lifecycle"} <= set(analyze.PASSES)
    # The timeout-budget relations hold on config defaults by
    # construction (derived expressions) — and the knobs exist.
    from ray_tpu.core.config import config

    assert config.cpu_reacquire_budget_s > 0
    assert config.bundle_reserve_timeout_s > 0


def test_loop_restart_counter_mechanics():
    """count_loop_restart ticks the registry family; retract_loop_series
    drops the child (the retracted-on-stop contract)."""
    from ray_tpu.util import metrics as m

    m.count_loop_restart("test.loop.abc")
    text = "\n".join(m.LOOP_RESTARTS_TOTAL.expose())
    assert 'loop="test.loop.abc"' in text
    m.retract_loop_series(["test.loop.abc"])
    text = "\n".join(m.LOOP_RESTARTS_TOTAL.expose())
    assert 'loop="test.loop.abc"' not in text


def test_worker_events_seq_dedup_absorbs_replay():
    """The rpc_worker_events idempotence contract: a resent batch under
    its original seq is absorbed; later seqs apply; a fresh pid (new
    incarnation) starts its own numbering."""
    import collections
    import threading

    from ray_tpu.cluster.node_agent import NodeAgent

    class Stub:
        _lock = threading.Lock()
        _event_seqs: "collections.OrderedDict" = collections.OrderedDict()

    stub = Stub()
    dup = NodeAgent._is_duplicate_event_batch
    assert dup(stub, "w1", 100, 1) is False
    assert dup(stub, "w1", 100, 1) is True      # replay absorbed
    assert dup(stub, "w1", 100, 2) is False     # progress applies
    assert dup(stub, "w1", 100, 1) is True      # stale replay absorbed
    assert dup(stub, "w1", 101, 1) is False     # new incarnation
    assert dup(stub, "w2", 100, None) is False  # probe: no contract
    assert dup(stub, "w2", 100, None) is False


# ---------------------------------------------------------------------------
# Baseline / ignore / diff workflows.
# ---------------------------------------------------------------------------

_BASELINE_FIXTURE = """\
    import threading


    class Agent:
        def __init__(self, head):
            self._lock = threading.RLock()
            self.head = head

        def report(self, payload):
            with self._lock:
                self.head.call("upload", payload)
    """


def test_baseline_allowlists_only_known_keys(tmp_path):
    p = tmp_path / "m.py"
    p.write_text(textwrap.dedent(_BASELINE_FIXTURE))
    res = analyze.run(paths=[str(p)], use_baseline=False,
                      root=str(tmp_path))
    assert not res["ok"] and len(res["new"]) == 1
    key = res["new"][0].key
    bl = tmp_path / "ANALYZE_BASELINE.json"
    bl.write_text(json.dumps({"entries": {key: "test justification"}}))
    res2 = analyze.run(paths=[str(p)], baseline_file=str(bl),
                       root=str(tmp_path))
    assert res2["ok"] and len(res2["allowed"]) == 1
    assert not res2["stale_baseline"]
    # A stale key for a SCANNED file (matches nothing) is reported,
    # never silently kept; a key for a file outside the scanned slice
    # is NOT called stale — advising "remove it" from a restricted run
    # would delete a still-needed justification.
    bl.write_text(json.dumps({"entries": {
        key: "test justification",
        "BL001:m.py:Agent.gone:rpc:_lock": "stale, in-scope",
        "BL001:other.py:X:rpc": "out of scope, not stale here"}}))
    res3 = analyze.run(paths=[str(p)], baseline_file=str(bl),
                       root=str(tmp_path))
    assert res3["ok"] and res3["stale_baseline"] == [
        "BL001:m.py:Agent.gone:rpc:_lock"]
    # Diff- and rule-restricted runs hide findings by design: no stale
    # reporting at all.
    res4 = analyze.run(paths=[str(p)], baseline_file=str(bl),
                       rules=["contracts"], root=str(tmp_path))
    assert res4["stale_baseline"] == []


def test_inline_ignore_pragma(tmp_path):
    findings = _scan(tmp_path, """\
        import threading


        class Agent:
            def __init__(self, head):
                self._lock = threading.RLock()
                self.head = head

            def report(self, payload):
                with self._lock:
                    self.head.call("upload", payload)  # analyze: ignore[BL001]
        """)
    assert not [f for f in findings if f.rule == "BL001"]


def test_diff_mode_restricts_to_changed_lines(tmp_path):
    subprocess.run(["git", "init", "-q", str(tmp_path)], check=True)
    sub = subprocess.run
    env_args = dict(cwd=str(tmp_path), check=True)
    p = tmp_path / "m.py"
    p.write_text(textwrap.dedent("""\
        import threading


        class A:
            def __init__(self, head):
                self._lock = threading.RLock()
                self.head = head

            def old_violation(self):
                with self._lock:
                    self.head.call("x")
        """))
    sub(["git", "add", "-A"], **env_args)
    sub(["git", "-c", "user.email=t@t", "-c", "user.name=t",
         "commit", "-qm", "seed"], **env_args)
    # Append a NEW violation; the old one predates the diff rev.
    p.write_text(p.read_text() + textwrap.dedent("""\


        class B:
            def __init__(self, head):
                self._lock = threading.RLock()
                self.head = head

            def new_violation(self):
                with self._lock:
                    self.head.call("y")
        """))
    res = analyze.run(paths=[str(p)], use_baseline=False,
                      diff_rev="HEAD", root=str(tmp_path))
    scopes = {f.scope for f in res["new"]}
    assert scopes == {"B.new_violation"}
    # Unrestricted sees both.
    res_all = analyze.run(paths=[str(p)], use_baseline=False,
                          root=str(tmp_path))
    assert {f.scope for f in res_all["new"]} == {
        "A.old_violation", "B.new_violation"}


# ---------------------------------------------------------------------------
# The CLI as a process + the repo-wide tier-1 gate.
# ---------------------------------------------------------------------------


def test_analyze_cli_runs_as_a_process(tmp_path):
    clean = tmp_path / "clean.py"
    clean.write_text("x = 1\n")
    # Scoped to one tiny file: the CLI plumbing is what's under test
    # here — the repo-wide scan already runs once in this module.
    r = subprocess.run(
        [sys.executable, "-m", "ray_tpu.scripts.analyze", "--json",
         str(clean)],
        capture_output=True, text=True, cwd=acore.repo_root())
    assert r.returncode == 0, r.stdout + r.stderr
    printed = json.loads(r.stdout)
    assert printed["ok"] is True
    assert printed["new"] == []
    assert printed["files_scanned"] == 1


def test_cli_rule_selection_rejects_typo():
    with pytest.raises(ValueError):
        analyze.run_paths([], rules=["lock-ordre"])


@pytest.fixture(scope="module")
def repo_result():
    """One repo-wide scan shared by the gate assertions below."""
    return analyze.run()


def test_repo_wide_run_is_clean(repo_result):
    """THE gate: zero unbaselined findings across the whole package.
    If this fails, either fix the new finding or baseline it in
    ANALYZE_BASELINE.json with a one-line justification (head.py
    lock-order/blocking findings must be fixed, never baselined)."""
    res = repo_result
    msgs = "\n".join(f.format() for f in res["new"])
    assert res["ok"], f"new analyzer findings:\n{msgs}"
    # The allowlist may only shrink: no stale keys either.
    assert not res["stale_baseline"], res["stale_baseline"]
    # head.py must carry ZERO baselined lock-order/blocking entries.
    head_baselined = [
        f for f in res["allowed"]
        if f.path.endswith("cluster/head.py")
        and f.rule.startswith(("LO", "BL", "GB"))]
    assert not head_baselined, [f.key for f in head_baselined]


def test_every_hit_site_is_registered_repo_wide(repo_result):
    """CD001/CD002 on the live tree, asserted directly (baselined or
    not): the SITES table and the compiled-in hit() sites cannot drift
    in either direction."""
    drift = [f for f in repo_result["findings"]
             if f.rule in ("CD001", "CD002")]
    assert not drift, [(f.rule, f.detail) for f in drift]


def test_stale_site_cd002(tmp_path):
    """A registered site with no remaining hit() anywhere is flagged on
    full-tree view (and a live site is not)."""
    from ray_tpu.util.analyze import contracts

    p = tmp_path / "m.py"
    p.write_text(textwrap.dedent("""\
        from ray_tpu.util import failpoints


        def f():
            failpoints.hit("head.schedule.batch")
        """))
    mod = acore.parse_file(str(p), root=str(tmp_path))
    findings = contracts.stale_site_findings([mod])
    stale = {f.detail for f in findings}
    assert "head.schedule.batch" not in stale
    assert "agent.heartbeat" in stale  # registered, not hit in view
    assert all(f.rule == "CD002" for f in findings)


def test_write_baseline_refuses_restricted_scope(tmp_path):
    """--write-baseline from a path- or diff-restricted run would drop
    every allowlist entry outside the slice — it must refuse."""
    from ray_tpu.scripts.analyze import main as analyze_main

    clean = tmp_path / "clean.py"
    clean.write_text("x = 1\n")
    bl = tmp_path / "bl.json"
    assert analyze_main(["--write-baseline",
                         "--baseline-file", str(bl),
                         str(clean)]) == 2
    assert not bl.exists()
    assert analyze_main(["--write-baseline", "--diff", "HEAD",
                         "--baseline-file", str(bl)]) == 2
    assert not bl.exists()
    # --rule restricts to one pass: writing from it would drop every
    # other pass's allowlist entries.
    assert analyze_main(["--write-baseline", "--rule", "lock-order",
                         "--baseline-file", str(bl)]) == 2
    assert not bl.exists()


def test_diff_mode_covers_untracked_new_files(tmp_path):
    """git diff omits untracked files — a brand-new module's violations
    are 100% the PR's lines and must fail --diff mode."""
    subprocess.run(["git", "init", "-q", str(tmp_path)], check=True)
    seed = tmp_path / "seed.py"
    seed.write_text("x = 1\n")
    subprocess.run(["git", "add", "-A"], cwd=str(tmp_path), check=True)
    subprocess.run(["git", "-c", "user.email=t@t", "-c", "user.name=t",
                    "commit", "-qm", "seed"], cwd=str(tmp_path),
                   check=True)
    newmod = tmp_path / "newmod.py"
    newmod.write_text(textwrap.dedent(_BASELINE_FIXTURE))
    res = analyze.run(paths=[str(seed), str(newmod)],
                      use_baseline=False, diff_rev="HEAD",
                      root=str(tmp_path))
    assert [f.rule for f in res["new"]] == ["BL001"]


def test_cli_passthrough_with_global_flag():
    """`ray-tpu --address H analyze --json ...` must still reach the
    analyzer's own parser with its flags intact."""
    from ray_tpu.scripts import cli

    clean = os.path.join(acore.repo_root(), "ray_tpu", "version.py")
    with pytest.raises(SystemExit) as e:
        cli.main(["--address", "h:1", "analyze", "--no-baseline",
                  "--rule", "contracts", clean])
    assert e.value.code == 0


def test_changed_lines_skips_pure_deletion_hunks(tmp_path):
    """A deletion-only PR touches no surviving line — `+N,0` hunks must
    not pin a neighboring line's pre-existing finding on it."""
    subprocess.run(["git", "init", "-q", str(tmp_path)], check=True)
    p = tmp_path / "m.py"
    p.write_text("a = 1\nb = 2\nc = 3\n")
    subprocess.run(["git", "add", "-A"], cwd=str(tmp_path), check=True)
    subprocess.run(["git", "-c", "user.email=t@t", "-c", "user.name=t",
                    "commit", "-qm", "seed"], cwd=str(tmp_path),
                   check=True)
    p.write_text("a = 1\nc = 3\n")  # delete line 2 only
    changed = acore.changed_lines("HEAD", str(tmp_path))
    assert changed.get("m.py", set()) == set()
