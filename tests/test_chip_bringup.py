"""What a CPU can check of the chip bring-up (the chip itself is checked by
``chip_smoke.py``): on four virtual devices the train step splits the batch
instead of replicating it, the flash kernel runs under ``shard_map``, the
compile cache lands where it should, the chip-only entry point refuses to
run here, and telemetry is never the first backend touch.
"""

import dataclasses
import os
import subprocess
import sys

import jax
import jax.numpy as jnp
import pytest

from ray_tpu.models.gpt2 import (GPT2Config, gpt2_init, gpt2_loss,
                                 gpt2_shardings)
from ray_tpu.parallel.mesh import MeshConfig, build_mesh
from ray_tpu.train.train_step import make_init_fn, make_train_step

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

# 24 sequences over four devices: a number no model dimension shares, so
# a "24" leading an HLO shape is the global batch.
GLOBAL_BATCH, N_DEV = 24, 4
CFG = GPT2Config(vocab_size=256, n_layer=2, n_head=4, d_model=64,
                 seq_len=64, remat=False, scan_layers=False)


def _fsdp4(cfg=CFG, devices=None):
    mesh = build_mesh(MeshConfig(
        fsdp=-1, devices=devices or jax.devices()[:N_DEV]))
    shardings = gpt2_shardings(cfg, mesh)
    state = make_init_fn(lambda r: gpt2_init(r, cfg), shardings, mesh)(
        jax.random.key(0))
    step = make_train_step(lambda p, b: gpt2_loss(p, b, cfg), shardings,
                           mesh)
    batch = {"tokens": jax.random.randint(
        jax.random.key(1), (GLOBAL_BATCH, cfg.seq_len + 1), 0,
        cfg.vocab_size, jnp.int32)}
    return step, state, batch


def test_train_step_splits_the_batch_on_four_devices():
    step, state, batch = _fsdp4()
    lowered = step.lower(state, batch)
    # Every with_logical_constraint of the forward pass (embedding + three
    # per block) reaches the program; traced under no mesh they were no-ops.
    assert lowered.as_text().count("sdy.sharding_constraint") \
        >= 1 + 3 * CFG.n_layer
    # No per-device buffer holds the global batch (the smoke's own check;
    # at the parent every device computed f32[24,64,256] logits).
    from chip_smoke import global_batch_buffers

    assert not global_batch_buffers(
        lowered.compile().as_text(), GLOBAL_BATCH,
        GLOBAL_BATCH // N_DEV * CFG.seq_len * CFG.d_model)


def test_flash_kernel_is_shard_mapped_and_agrees_with_one_device():
    cfg = dataclasses.replace(CFG, use_flash=True)
    step, state, batch = _fsdp4(cfg)
    assert "sdy.manual_computation" in step.lower(state, batch).as_text()
    _, metrics = step(state, batch)
    step1, state1, _ = _fsdp4(cfg, devices=jax.devices()[:1])
    _, metrics1 = step1(state1, batch)
    assert abs(float(metrics["loss"]) - float(metrics1["loss"])) < 1e-4


def test_auto_attention_decides_from_the_shape_not_an_exception():
    from ray_tpu.ops.flash_attention import (flash_block,
                                             flash_causal_attention)

    assert flash_block(1024, 1024) == 1024
    assert flash_block(1024, 1031) is None  # prime: the kernel cannot tile
    q = jnp.zeros((1, 1031, 2, 8))
    with pytest.raises(ValueError, match="cannot tile"):
        flash_causal_attention(q, q, q)


def test_compile_cache_is_placed_from_outside_or_at_the_checkout(monkeypatch):
    from ray_tpu.util.compile_cache import ensure_compile_cache

    before = jax.config.jax_compilation_cache_dir
    try:
        jax.config.update("jax_compilation_cache_dir", None)
        monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", "/placed/elsewhere")
        assert ensure_compile_cache() == "/placed/elsewhere"
        assert jax.config.jax_compilation_cache_dir is None  # set nothing
        monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR")
        fixed = os.path.join(REPO, ".jax_cache")
        assert ensure_compile_cache() == fixed
        assert jax.config.jax_compilation_cache_dir == fixed
    finally:
        jax.config.update("jax_compilation_cache_dir", before)


def _run(*argv):
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    return subprocess.run(
        [sys.executable, *argv], cwd=REPO, env=env,
        capture_output=True, text=True, timeout=120)


@pytest.mark.parametrize("script", ["chip_smoke.py"])
def test_chip_entry_points_refuse_to_run_on_a_cpu(script):
    proc = _run(script)
    assert proc.returncode != 0
    assert '"ok"' not in proc.stdout and '"metric"' not in proc.stdout


def test_telemetry_is_never_the_first_backend_touch():
    """A worker that imported jax but initialised no backend ships no
    device snapshot (the event flusher asks ``backend_initialized``)."""
    proc = _run("-c", """
import jax
from jax._src import xla_bridge
from ray_tpu.util import device_telemetry as dt
assert not dt.backend_initialized()
assert dt.snapshot()["available"] is False
assert not xla_bridge.backends_are_initialized(), "snapshot took the device"
jax.devices()
assert dt.backend_initialized() and dt.snapshot()["available"] is True
""")
    assert proc.returncode == 0, proc.stderr[-2000:]
