"""Regression tests for the round-5 advisor findings fixed in this PR
(round 5): registry pairing (CQL/bandits), warm-up priority creep in
the prioritized replay buffer, sklearn fit_time scope, DDPPO actor
lifecycle, and the on-chip bench evidence trail."""

import time

import numpy as np
import pytest

import ray_tpu
from ray_tpu.rllib.registry import (
    ALGORITHMS,
    get_algorithm_class,
    get_algorithm_config,
)
from ray_tpu.rllib.sample_batch import SampleBatch


@pytest.fixture(autouse=True, scope="module")
def _runtime():
    ray_tpu.shutdown()
    ray_tpu.init(num_cpus=8)
    yield
    ray_tpu.shutdown()


def _tiny_dataset(n: int = 64) -> SampleBatch:
    rng = np.random.default_rng(0)
    return SampleBatch({
        "obs": rng.normal(size=(n, 4)).astype(np.float32),
        "actions": rng.integers(0, 2, n),
        "rewards": rng.normal(size=n).astype(np.float32),
        "next_obs": rng.normal(size=(n, 4)).astype(np.float32),
        "dones": (rng.random(n) < 0.1).astype(np.float32),
    })


def _tiny_episodes(n: int = 4, t: int = 8) -> dict:
    rng = np.random.default_rng(0)
    return {
        "obs": rng.normal(size=(n, t, 4)).astype(np.float32),
        "actions": rng.integers(0, 2, (n, t)),
        "rewards": rng.normal(size=(n, t)).astype(np.float32),
        "mask": np.ones((n, t), np.float32),
    }


# Smallest-footprint overrides so the full-registry build sweep stays
# cheap; entries that spawn actors get exactly one.
_BUILD_OVERRIDES = {
    "A3C": {"num_rollout_workers": 1},
    "DDPPO": {"num_workers": 1},
}
_NEEDS_DATASET = {"BC", "MARWIL", "CQL", "CRR"}


def test_registry_every_entry_builds_registered_class():
    """cfg_cls().build(...) must yield the registered class for EVERY
    entry — the CQL entry used to pair CQL with MARWILConfig, whose
    build() silently constructed a MARWIL."""
    for name in sorted(ALGORITHMS):
        cls = get_algorithm_class(name)
        cfg = get_algorithm_config(name)
        for k, v in _BUILD_OVERRIDES.get(name, {}).items():
            setattr(cfg, k, v)
        if name == "DT":
            algo = cfg.build(_tiny_episodes())
        elif name in _NEEDS_DATASET:
            algo = cfg.build(_tiny_dataset())
        else:
            algo = cfg.build()
        try:
            assert isinstance(algo, cls), (
                f"{name}: build() produced {type(algo).__name__}, "
                f"registered class is {cls.__name__}")
        finally:
            if hasattr(algo, "stop"):
                algo.stop()


def test_cql_config_is_dqn_based_and_builds_cql():
    from ray_tpu.rllib.dqn import DQNConfig
    from ray_tpu.rllib.offline_algos import CQL

    cfg = get_algorithm_config("CQL")
    assert isinstance(cfg, DQNConfig)
    cfg.training(cql_alpha=2.5, updates_per_iter=2, batch_size=16)
    algo = cfg.build(_tiny_dataset())
    assert isinstance(algo, CQL)
    assert algo.cql_alpha == 2.5
    result = algo.train()
    assert "conservative_gap" in result


def test_bandit_config_build_by_name():
    from ray_tpu.rllib.bandit import BanditConfig, BanditLinTS, BanditLinUCB

    ucb = get_algorithm_config("BanditLinUCB")
    ts = get_algorithm_config("BanditLinTS")
    assert isinstance(ucb, BanditConfig) and isinstance(ts, BanditConfig)
    assert isinstance(ucb.build(), BanditLinUCB)
    assert isinstance(ts.build(), BanditLinTS)
    # A hand-built config defaults to LinUCB.
    assert isinstance(BanditConfig().build(), BanditLinUCB)


def test_pbuffer_warmup_rewrite_preserves_priorities():
    """The learning_starts gating path re-writes sampled rows with their
    EXISTING priorities; the unconditional +eps used to creep them up by
    1e-3 per warm-up update."""
    import jax.numpy as jnp

    from ray_tpu.rllib.replay import (
        pbuffer_add,
        pbuffer_init,
        pbuffer_update_priorities,
    )

    buf = pbuffer_init(32, {"obs": (1,)})
    buf = pbuffer_add(buf, 32, obs=jnp.ones((8, 1)))
    before = np.asarray(buf["priority"])
    idx = jnp.arange(8)
    ready = 0.0  # warm-up: gradients and priorities both gated off
    for _ in range(10):
        old = buf["priority"][idx]
        new_p = ready * (jnp.abs(old * 2.0) + 1e-3) + (1.0 - ready) * old
        buf = pbuffer_update_priorities(buf, idx, new_p, eps=0.0)
    np.testing.assert_allclose(np.asarray(buf["priority"]), before)
    # Post-warm-up the TD branch still floors priorities above zero.
    buf = pbuffer_update_priorities(
        buf, idx, 1.0 * (jnp.abs(jnp.zeros(8)) + 1e-3), eps=0.0)
    assert float(jnp.min(buf["priority"][idx])) >= 1e-3


class _SlowScoreEstimator:
    """fit() is instant; score() sleeps — so CV wall time dwarfs the fit
    and any fit_time that includes the CV gather is caught."""

    def __init__(self, delay: float):
        self.delay = delay
        self.mean_ = None

    def fit(self, x, y):
        self.mean_ = float(np.mean(y))
        return self

    def score(self, x, y):
        time.sleep(self.delay)
        return 1.0


def test_sklearn_fit_time_excludes_cv_gather():
    from ray_tpu.train.sklearn import SklearnTrainer

    x = np.random.randn(30, 3)
    y = np.random.randn(30)
    t0 = time.perf_counter()
    result = SklearnTrainer(
        estimator=_SlowScoreEstimator(0.3),
        datasets={"train": (x, y)},
        cv=3,
        parallelize_cv=False,  # serial folds: ~0.9s of pure CV time
    ).fit()
    total = time.perf_counter() - t0
    assert result.metrics["cv"]["test_score_mean"] == 1.0
    assert total >= 0.9  # the CV time really was spent...
    assert result.metrics["fit_time"] < total - 0.6, (
        result.metrics["fit_time"], total)  # ...and fit_time excludes it


def test_ddppo_context_manager_stops_workers():
    from ray_tpu import state
    from ray_tpu.rllib.ddppo import DDPPO, DDPPOConfig

    cfg = DDPPOConfig()
    cfg.num_workers = 1
    with DDPPO(cfg) as algo:
        assert len(algo._workers) == 1
    assert algo._workers == []  # __exit__ ran stop()
    deadline = time.time() + 15
    while time.time() < deadline:
        workers = [a for a in state.list_actors()
                   if a["class_name"] == "DDPPOWorker"]
        if workers and all(a["state"] == "DEAD" for a in workers):
            break
        time.sleep(0.2)
    assert all(a["state"] == "DEAD" for a in state.list_actors()
               if a["class_name"] == "DDPPOWorker")
    algo.stop()  # idempotent
