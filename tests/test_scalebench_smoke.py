"""Standing scalebench smoke (round 6): the envelope harness itself is
exercised as a ``-m slow`` gate — mirroring how ``chaos_soak`` became
the standing robustness gate — so the scale harness can't rot between
envelope rounds. Small shape: 4 nodes / 2k tasks / 64 actors real
cluster (plus a parked-queue audit), and a reduced head-at-scale pass
with the span cap lowered so the retention/drop machinery is observed.

Full envelope runs: ``python -m ray_tpu.scripts.scalebench --nodes 4
--queued 100000 --head-scale`` (SCALING.md, "Head at scale",
describes what it drives).
"""

import pytest

from ray_tpu.core.config import config


@pytest.mark.slow
def test_scalebench_small_shape():
    from ray_tpu.scripts import scalebench

    res = scalebench.run(nodes=4, cpus=2, tasks=2000, actors=64,
                         broadcast_mb=16, queued=2000)
    # Shape + liveness invariants (rates are box-dependent; the
    # INVARIANTS are not).
    assert res["burst_nodes_used"]["value"] >= 2  # burst actually spread
    assert res["actor_distinct_pids"]["value"] == 64
    # Parked-queue audit: every infeasible spec parked, the submitter
    # stayed live under the backlog, and retry backoff bounded the
    # steady-state head RPC rate (2000/256 = 8 batches per max-backoff
    # window ~2s; 50/s is an order of magnitude of slack for a loaded
    # box, vs ~32/s at the old flat 0.25s timer for THIS depth — the
    # flat timer scales O(backlog), backoff does not).
    assert res["queued_pending"]["value"] >= 2000
    assert res["queued_sched_rpcs_per_s"]["value"] < 50
    assert res["queued_probe_latency_s"]["value"] < 120
    assert res["queued_shutdown_s"]["value"] < 120
    assert "schedule_batch" in res["head_rpc_counts"]


@pytest.mark.slow
def test_scalebench_head_scale_small():
    from ray_tpu.scripts import scalebench

    config.override("head_span_retention", 10_000)
    try:
        res = scalebench.run_head_scale(
            nodes=16, queued=20_000, actors=200, subscribers=4,
            spans=12_000, heartbeat_rounds=3)
    finally:
        config.reset("head_span_retention")
    # Bounded-retention invariants at depth.
    assert res["span_retained"]["value"] == 10_000
    assert res["span_dropped"]["value"] == 2_000
    assert res["demand_miss_table"]["value"] <= 1000
    # Coalescing bounded the never-polling subscribers: without it each
    # would buffer rounds x actors (2000) messages.
    assert res["pubsub_buffered"]["value"] <= 4 * (200 + 16 + 1)
    assert res["pubsub_coalesced"]["value"] > 0
    # Per-RPC accounting is present and machine-independent.
    assert res["head_rpc_counts"]["ref_task_begin_batch"] == \
        (20_000 + 255) // 256
    assert res["sched_feasible_placed"]["value"] == 10_000
