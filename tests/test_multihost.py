"""Multi-host mesh bootstrap: jax.distributed across cluster worker
processes (the CPU analog of a two-host TPU slice).

Reference parity: rank-0 addr/port fan-out + process-group init of
``python/ray/train/torch/config.py:129-181`` and the KV rendezvous of
``python/ray/util/collective`` — here via ``ray_tpu.parallel.distributed``
(coordinator address through the cluster KV) and ``JaxTrainer``.

Each of the 2 train workers is a separate OS process with 4 virtual CPU
devices; after bootstrap, ``jax.devices()`` spans 8 devices and one pjit
train step runs SPMD across both processes (Gloo collectives).
"""

import numpy as np
import pytest

import ray_tpu
from ray_tpu import train
from ray_tpu.cluster.cluster_utils import Cluster
from ray_tpu.train import session


@pytest.fixture(scope="module")
def two_node_cluster():
    ray_tpu.shutdown()
    cluster = Cluster()
    for _ in range(2):
        cluster.add_node(num_cpus=4)
    cluster.wait_for_nodes()
    ray_tpu.init(cluster.address)
    yield cluster
    ray_tpu.shutdown()
    cluster.shutdown()


def test_two_process_mesh_train_step(two_node_cluster):
    # The loop is defined inline so cloudpickle ships it by value to the
    # worker processes (test modules aren't importable there).
    def loop(config):
        import jax
        import numpy as np
        from jax.sharding import NamedSharding, PartitionSpec as P

        from ray_tpu.models.gpt2 import (
            GPT2Config, gpt2_init, gpt2_loss, gpt2_shardings,
        )
        from ray_tpu.parallel.mesh import MeshConfig, build_mesh
        from ray_tpu.train import session
        from ray_tpu.train.train_step import make_init_fn, make_train_step

        # The full sharded train step over the GLOBAL 8-device mesh
        # spanning both worker processes.
        mesh = build_mesh(MeshConfig(fsdp=-1))
        cfg = GPT2Config(vocab_size=128, n_layer=2, n_head=2, d_model=32,
                         seq_len=16)
        shardings = gpt2_shardings(cfg, mesh)
        init_fn = make_init_fn(lambda r: gpt2_init(r, cfg), shardings, mesh)
        state = init_fn(jax.random.key(0))
        step_fn = make_train_step(lambda p, b: gpt2_loss(p, b, cfg),
                                  shardings, mesh)

        bsh = NamedSharding(mesh, P(("dp", "fsdp")))
        rng = np.random.default_rng(0)
        host_tokens = rng.integers(0, cfg.vocab_size, (8, cfg.seq_len + 1))

        def cb(index):
            return host_tokens[index].astype(np.int32)

        tokens = jax.make_array_from_callback((8, cfg.seq_len + 1), bsh, cb)
        state, metrics = step_fn(state, {"tokens": tokens})
        loss1 = float(metrics["loss"])
        state, metrics = step_fn(state, {"tokens": tokens})
        loss2 = float(metrics["loss"])

        session.report({
            "global_devices": jax.device_count(),
            "local_devices": jax.local_device_count(),
            "process_count": jax.process_count(),
            "process_index": jax.process_index(),
            "world_rank": session.get_world_rank(),
            "local_rank": session.get_local_rank(),
            "node_rank": session.get_node_rank(),
            "loss1": loss1,
            "loss2": loss2,
        })

    trainer = train.JaxTrainer(
        loop,
        scaling_config=train.ScalingConfig(
            num_workers=2,
            resources_per_worker={"CPU": 2},
            placement_strategy="STRICT_SPREAD",
        ),
        jax_config=train.JaxConfig(platform="cpu", num_cpu_devices=4),
    )
    result = trainer.fit()
    assert result.error is None, result.error
    m = result.metrics
    # Rank 0's view: 8 global devices over 2 processes x 4 local.
    assert m["global_devices"] == 8
    assert m["local_devices"] == 4
    assert m["process_count"] == 2
    assert m["world_rank"] == 0
    # Training actually progressed (loss changed across the step).
    assert m["loss1"] != m["loss2"]
    assert np.isfinite(m["loss1"]) and np.isfinite(m["loss2"])


def test_multiprocess_sharded_checkpoint_resume(two_node_cluster, tmp_path_factory):
    """2-process fsdp-sharded save -> resume-mid-training roundtrip.

    Proves the exactly-once-writer and reshard-on-load paths of
    ``train/checkpoint.py`` where they matter: each worker process writes
    only its addressable shards, the checkpoint is re-assembled onto the
    live 8-device mesh, and training resumed from disk matches training
    continued in memory (SURVEY.md §5.4).
    """
    ckpt_dir = str(tmp_path_factory.mktemp("shared_ckpt"))

    def loop(config):
        import os

        import jax
        import numpy as np
        from jax.experimental import multihost_utils
        from jax.sharding import NamedSharding, PartitionSpec as P

        from ray_tpu.models.gpt2 import (
            GPT2Config, gpt2_init, gpt2_loss, gpt2_shardings,
        )
        from ray_tpu.parallel.mesh import MeshConfig, build_mesh
        from ray_tpu.train import session
        from ray_tpu.train.checkpoint import load_sharded, save_sharded
        from ray_tpu.train.train_step import (
            make_init_fn, make_train_step, state_shardings,
        )

        mesh = build_mesh(MeshConfig(fsdp=-1))
        cfg = GPT2Config(vocab_size=128, n_layer=2, n_head=2, d_model=32,
                         seq_len=16)
        shardings = gpt2_shardings(cfg, mesh)
        init_fn = make_init_fn(lambda r: gpt2_init(r, cfg), shardings, mesh)
        state = init_fn(jax.random.key(0))
        step_fn = make_train_step(lambda p, b: gpt2_loss(p, b, cfg),
                                  shardings, mesh)

        bsh = NamedSharding(mesh, P(("dp", "fsdp")))
        rng = np.random.default_rng(0)
        host_tokens = rng.integers(0, cfg.vocab_size, (8, cfg.seq_len + 1))
        tokens = jax.make_array_from_callback(
            (8, cfg.seq_len + 1), bsh,
            lambda i: host_tokens[i].astype(np.int32))

        # One step, then checkpoint mid-training from every process.
        state, _ = step_fn(state, {"tokens": tokens})
        ckpt = config["ckpt_dir"]
        save_sharded(state, ckpt)
        multihost_utils.sync_global_devices("ckpt-written")
        n_shard_files = len(
            [f for f in os.listdir(ckpt) if f.endswith(".npy")])

        # Resume from disk (reshard-on-load onto the live mesh) BEFORE
        # taking the next live step — step_fn donates its input state.
        resumed = load_sharded(ckpt, state_shardings(shardings, mesh))
        step_at_resume = int(resumed["step"])
        live, live_m = step_fn(state, {"tokens": tokens})
        resumed, resumed_m = step_fn(resumed, {"tokens": tokens})

        diffs = jax.tree.map(
            lambda a, b: float(jnp_abs_max(a, b)) if hasattr(a, "dtype") else 0.0,
            live["params"], resumed["params"])
        max_param_diff = max(jax.tree.leaves(diffs)) if jax.tree.leaves(diffs) else 0.0

        session.report({
            "step_at_resume": step_at_resume,
            "loss_live": float(live_m["loss"]),
            "loss_resumed": float(resumed_m["loss"]),
            "max_param_diff": max_param_diff,
            "n_shard_files": n_shard_files,
        })

    # Helper shipped by value with the loop closure.
    def jnp_abs_max(a, b):
        import jax.numpy as jnp
        return jnp.max(jnp.abs(a.astype(jnp.float32) - b.astype(jnp.float32)))

    trainer = train.JaxTrainer(
        loop,
        train_loop_config={"ckpt_dir": ckpt_dir},
        scaling_config=train.ScalingConfig(
            num_workers=2,
            resources_per_worker={"CPU": 2},
            placement_strategy="STRICT_SPREAD",
        ),
        jax_config=train.JaxConfig(platform="cpu", num_cpu_devices=4),
    )
    result = trainer.fit()
    assert result.error is None, result.error
    m = result.metrics
    assert m["step_at_resume"] == 1
    assert m["n_shard_files"] > 0
    assert np.isfinite(m["loss_live"])
    # Resumed training is bit-for-bit the same trajectory.
    assert m["loss_resumed"] == pytest.approx(m["loss_live"], abs=1e-5)
    assert m["max_param_diff"] < 1e-5


def test_local_ranks_one_node():
    """Two workers packed on ONE node get node_rank 0 and local ranks 0/1."""
    ray_tpu.shutdown()
    cluster = Cluster()
    cluster.add_node(num_cpus=4)
    cluster.wait_for_nodes()
    ray_tpu.init(cluster.address)
    try:
        def loop(config):
            from ray_tpu.train import session
            session.report({
                "world_rank": session.get_world_rank(),
                "local_rank": session.get_local_rank(),
                "node_rank": session.get_node_rank(),
            })

        trainer = train.DataParallelTrainer(
            loop,
            scaling_config=train.ScalingConfig(
                num_workers=2, resources_per_worker={"CPU": 1},
            ),
        )
        result = trainer.fit()
        assert result.error is None
        # Rank 0 on the single node: first worker on its node.
        assert result.metrics["local_rank"] == 0
        assert result.metrics["node_rank"] == 0
    finally:
        ray_tpu.shutdown()
        cluster.shutdown()
