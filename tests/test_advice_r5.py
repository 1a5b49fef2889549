"""Regression tests for round-4 advisor findings (round 4).

Covers: variant-expanding searchers run to exhaustion (not capped at
num_samples), Trial persistence uses a monotonic version (not id()),
ActorPool raises clearly when backlogged with zero actors, and client
shutdown fails retry-parked specs into their refs instead of dropping them.
"""

import time

import pytest

import ray_tpu
from ray_tpu import tune
from ray_tpu.train.config import RunConfig
from ray_tpu.tune import BasicVariantSearcher, TuneConfig, Tuner
from ray_tpu.util.actor_pool import ActorPool


@pytest.fixture(autouse=True, scope="module")
def _runtime():
    ray_tpu.shutdown()
    ray_tpu.init(num_cpus=16)
    yield
    ray_tpu.shutdown()


def test_variant_searcher_runs_full_grid():
    # grid of 3 x num_samples=2 = 6 variants: all must run, even though
    # TuneConfig.num_samples (2) is below the expanded count.
    space = {"a": tune.grid_search([1, 2, 3]), "b": tune.uniform(0, 1)}
    searcher = BasicVariantSearcher(space, num_samples=2, seed=0)

    def train_fn(config):
        return {"score": config["a"]}

    tuner = Tuner(
        train_fn,
        param_space=space,
        tune_config=TuneConfig(
            metric="score", mode="max", num_samples=2, search_alg=searcher
        ),
    )
    results = tuner.fit()
    assert len(results) == 6
    assert sorted(r.config["a"] for r in results) == [1, 1, 2, 2, 3, 3]


def test_variant_searcher_restore_no_redeal(tmp_path):
    # Tuner.restore with a fresh BasicVariantSearcher must not re-deal
    # variants already consumed by the completed run.
    space = {"a": tune.grid_search([1, 2, 3])}

    def train_fn(config):
        return {"score": config["a"]}

    tuner = Tuner(
        train_fn,
        param_space=space,
        tune_config=TuneConfig(
            metric="score", mode="max", num_samples=1,
            search_alg=BasicVariantSearcher(space, num_samples=1, seed=0),
        ),
        run_config=RunConfig(storage_path=str(tmp_path), name="exp"),
    )
    results = tuner.fit()
    assert len(results) == 3
    restored = Tuner.restore(
        str(tmp_path / "exp"),
        train_fn,
        param_space=space,
        tune_config=TuneConfig(
            metric="score", mode="max", num_samples=1,
            search_alg=BasicVariantSearcher(space, num_samples=1, seed=0),
        ),
    )
    results2 = restored.fit()
    assert len(results2) == 3  # nothing re-dealt


def test_trial_version_bumps_on_mutation():
    from ray_tpu.tune.trial_runner import Trial

    t = Trial({"x": 1})
    v0 = t.version
    t.last_result = {"score": 1.0}
    assert t.version > v0
    v1 = t.version
    t.last_result = {"score": 1.0}  # same value, new object: still dirty
    assert t.version > v1
    v2 = t.version
    t.num_failures += 1
    assert t.version > v2


def test_actor_pool_no_actors_clear_error():
    pool = ActorPool([])
    pool.submit(lambda a, v: a.f.remote(v), 1)
    assert pool.has_next()
    with pytest.raises(RuntimeError, match="no actors"):
        pool.get_next(timeout=1)
    with pytest.raises(RuntimeError, match="no actors"):
        pool.get_next_unordered(timeout=1)


def test_actor_pool_all_popped_clear_error():
    @ray_tpu.remote
    class A:
        def f(self, v):
            return v

    a = A.remote()
    pool = ActorPool([a])
    popped = pool.pop_idle()
    assert popped is not None
    pool.submit(lambda ac, v: ac.f.remote(v), 1)
    with pytest.raises(RuntimeError, match="no actors"):
        pool.get_next(timeout=1)
    # Returning the actor un-wedges the backlog.
    pool.push(popped)
    assert pool.get_next(timeout=30) == 1


def test_shutdown_fails_unplaceable_specs():
    """An infeasible task parked on the retry timer must fail into its ref
    at shutdown, so a concurrent get() raises promptly instead of blocking
    until its own timeout (advisor r4)."""
    import threading

    from ray_tpu.cluster import Cluster

    ray_tpu.shutdown()
    c = Cluster()
    try:
        c.add_node(num_cpus=1)
        ray_tpu.init(address=c.address)

        @ray_tpu.remote(num_cpus=64)  # unsatisfiable on this cluster
        def big():
            return 1

        ref = big.remote()
        time.sleep(1.5)  # let the spec park on the retry heap
        outcome: dict = {}

        def getter():
            t0 = time.monotonic()
            try:
                ray_tpu.get(ref, timeout=60)
                outcome["result"] = "value"
            except Exception as e:
                outcome["result"] = repr(e)
            outcome["elapsed"] = time.monotonic() - t0

        th = threading.Thread(target=getter)
        th.start()
        time.sleep(0.5)  # getter is blocked waiting on the ref
        ray_tpu.shutdown()
        th.join(timeout=30)
        assert not th.is_alive(), "get() still blocked after shutdown"
        assert outcome["elapsed"] < 15, outcome
        assert "shut down" in outcome["result"] or "closed" in \
            outcome["result"], outcome
    finally:
        ray_tpu.shutdown()
        c.shutdown()
        # Restore the module-scoped runtime for any test that follows.
        ray_tpu.init(num_cpus=16)
