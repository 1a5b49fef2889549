"""Concurrency & contract static analysis (``ray-tpu analyze``).

The framework's worst shipped bugs were never logic errors — they were
concurrency-contract violations found only at runtime: the PR-5
GC-finalizer deadlock (a non-reentrant lock reachable from ``__del__``/
``weakref.finalize`` callbacks wedged the whole local backend), the
lock-order discipline the round-6 head shard split could only *document*
in a comment, and blocking RPC/sqlite work under a shard lock that
serialized the control plane. This package turns those postmortems into
AST-level passes that run in tier-1.

Passes (rule-id prefix):

* ``lock-order`` (LO/GB) — lock acquisition partial order against the
  declared ``LOCK_ORDER`` tuple + discovered nesting; non-reentrant
  same-lock re-entry; ``# guarded-by: <lock>`` declared-intent checks.
* ``blocking`` (BL) — RPC calls, thread joins / future results, event
  waits, sleeps and sqlite commits inside a lock's critical section.
* ``finalizer`` (FS) — code reachable from ``__del__`` / ``weakref
  .finalize`` callbacks must only take RLock-protocol locks and must
  never make RPC calls (the PR-5 deadlock, now a rule).
* ``async-lock`` (AH) — ``await`` / blocking calls while a sync lock is
  held inside ``async def`` (the serve/router path bug class).
* ``contracts`` (CD) — every ``failpoints.hit(site)`` registered in
  ``failpoints.SITES``; every metric family emitted with exactly its
  declared tag keys and declared in the (grafana-feeding) registry;
  two-sided recorders observing locally AND buffering for replay.
* ``retry`` (RT) — retried RPC call sites must target handlers
  declared ``# idempotent`` (which must visibly absorb replays) or
  consult ``maybe_executed``; bounded resubmits must narrow what they
  retry (the PR-13 blind-resubmit / severed-2PC-commit class).
* ``daemon-loop`` (DL) — forever-loops doing RPC/IO must survive
  exceptions, and every survival handler must count into
  ``ray_tpu_loop_restarts_total{loop}`` (a crash-restart cycle must
  be visible on the scrape).
* ``timeout-order`` (TO) — ``# timeout-budget: outlasts <ref>``
  relations checked against config defaults: an inner RPC timeout can
  never undercut the outer budget it serves (the PR-14
  task-unblocked-kills-healthy-task shape).
* ``jax-hotpath`` (JX) — unmarked-static jit scalars, host syncs and
  sleepless poll spins in ``# jax-hot-path`` regions, fp32 upcasts in
  ``# decode-path`` (activation-dtype) regions — the per-request
  recompile / GIL-starvation throughput class PR 13's compile
  counters guard at runtime.
* ``lifecycle`` (LC) — per-entity gauge families must appear in a
  retraction sweep; ship-buffer drains must requeue on upload
  failure; ``# slot-guard`` declared acquire/release pairs must keep
  their failure-edge release.
* ``timing`` (TH) — step-timing honesty: a ``# step-timed`` region's
  timer reads must bracket a real host sync (``block_until_ready`` /
  ``.item()`` / ``np.asarray`` / ``float()`` of a device scalar) — an
  unsynced wall around async dispatch times the launch, not the
  device, and the anatomy plane built on it would be fiction; a
  marked region that times nothing is a stale annotation.
* ``trace-propagation`` (TP) — manual flight-recorder spans
  (``tracing.start_span``) must be closable: never-finished local
  spans, finishes that aren't exception-safe (no ``finally`` and no
  except/normal pair), and created-and-discarded span handles — the
  leak shapes that stall trace assembly's quiet window.

Heuristic and precise-by-allowlist rather than sound-and-noisy: the
committed ``ANALYZE_BASELINE.json`` allowlists justified findings so
only *new* violations fail; in-code pragmas
(``# analyze: allow-blocking(<why>)`` on a lock declaration,
``# analyze: ignore[RULE]`` on a finding line) record intent next to
the code they bless.

Entry points: ``ray-tpu analyze [--rule ...] [--baseline] [--json]
[--diff REV]`` and ``python -m ray_tpu.scripts.analyze``; the repo-wide
run is asserted clean by ``tests/test_static_analysis.py``.
"""

from ray_tpu.util.analyze.core import (  # noqa: F401
    Finding,
    PASSES,
    analysis_pass,
    default_paths,
    load_baseline,
    run,
    run_paths,
)

# Importing the pass modules registers them with the PASSES registry.
from ray_tpu.util.analyze import (  # noqa: F401,E402
    blocking,
    contracts,
    daemon_loops,
    finalizers,
    jax_hotpath,
    lifecycle,
    lock_order,
    retry,
    timeouts,
    timing,
    trace_propagation,
)
