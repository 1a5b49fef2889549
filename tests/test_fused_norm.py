"""Fused Pallas norm/residual/GELU kernels (``ops/fused_norm.py``) vs
the plain-JAX chains they replace — interpret-mode parity on CPU, the
same contract the flash-attention kernels carry.

Covers forward AND gradient parity for
LayerNorm (GPT-2 D=768 shape), RMSNorm (Llama D=1024 shape), and the
tanh-GELU epilogue, including the dscale/dbias column reductions and
the fused residual-add gradient; odd-shape XLA fallback asserted via
the trace-time kernel counters; and end-to-end ``fused_norm=True``
GPT-2/Llama training mirroring the round-5 lever tests.
"""

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from ray_tpu.ops import fused_norm as fn
from ray_tpu.ops.fused_norm import (
    fused_gelu,
    fused_layer_norm,
    fused_layer_norm_residual,
    fused_rms_norm,
    fused_rms_norm_residual,
)

# GPT-2 small and Llama small hidden sizes — the shapes the kernels
# must cover on-chip. Row counts stay small so interpret mode is fast.
GPT2_D = 768
LLAMA_D = 1024
ROWS = 64


def _data(d, rows=ROWS, dtype=jnp.float32, seed=0):
    ks = jax.random.split(jax.random.key(seed), 3)
    x = jax.random.normal(ks[0], (rows, d), dtype)
    scale = (jax.random.normal(ks[1], (d,), jnp.float32) * 0.1 + 1.0)
    bias = jax.random.normal(ks[2], (d,), jnp.float32) * 0.1
    return x, scale, bias


def _cosine(tree_a, tree_b):
    fa = jnp.concatenate(
        [g.ravel().astype(jnp.float32) for g in jax.tree.leaves(tree_a)])
    fb = jnp.concatenate(
        [g.ravel().astype(jnp.float32) for g in jax.tree.leaves(tree_b)])
    return float(jnp.vdot(fa, fb) /
                 (jnp.linalg.norm(fa) * jnp.linalg.norm(fb)))


@pytest.mark.parametrize("d", [GPT2_D, LLAMA_D])
def test_layer_norm_forward_parity(d):
    x, scale, bias = _data(d)
    before = fn.KERNEL_INVOCATIONS["ln_fwd"]
    out = fused_layer_norm(x, scale, bias)
    assert fn.KERNEL_INVOCATIONS["ln_fwd"] > before, "kernel not taken"
    np.testing.assert_allclose(
        np.asarray(out), np.asarray(fn.ref_layer_norm(x, scale, bias)),
        rtol=1e-5, atol=1e-5)


def test_layer_norm_gradient_parity_fp32():
    """dx AND the dscale/dbias column reductions, with the residual-add
    gradient fused: rtol 1e-4 against the plain-JAX chain."""
    x, scale, bias = _data(GPT2_D)
    w = jax.random.normal(jax.random.key(7), (GPT2_D,), jnp.float32)

    def loss_fused(x, s, b):
        y, x_skip = fused_layer_norm_residual(x, s, b)
        return jnp.sum((x_skip + y * w) ** 2)

    def loss_ref(x, s, b):
        return jnp.sum((x + fn.ref_layer_norm(x, s, b) * w) ** 2)

    before = fn.KERNEL_INVOCATIONS["ln_bwd"]
    g_fused = jax.grad(loss_fused, argnums=(0, 1, 2))(x, scale, bias)
    assert fn.KERNEL_INVOCATIONS["ln_bwd"] > before, "bwd kernel not taken"
    g_ref = jax.grad(loss_ref, argnums=(0, 1, 2))(x, scale, bias)
    for gf, gr, name in zip(g_fused, g_ref, ("dx", "dscale", "dbias")):
        np.testing.assert_allclose(
            np.asarray(gf), np.asarray(gr), rtol=1e-4, atol=1e-4,
            err_msg=name)


def test_rms_norm_parity_fp32():
    """Llama-shape RMSNorm: forward + dx/dscale (+ residual) parity."""
    x, scale, _ = _data(LLAMA_D, seed=1)
    np.testing.assert_allclose(
        np.asarray(fused_rms_norm(x, scale)),
        np.asarray(fn.ref_rms_norm(x, scale)), rtol=1e-5, atol=1e-5)

    def loss_fused(x, s):
        y, x_skip = fused_rms_norm_residual(x, s)
        return jnp.sum((x_skip + y * 2.0) ** 2)

    def loss_ref(x, s):
        return jnp.sum((x + fn.ref_rms_norm(x, s) * 2.0) ** 2)

    g_fused = jax.grad(loss_fused, argnums=(0, 1))(x, scale)
    g_ref = jax.grad(loss_ref, argnums=(0, 1))(x, scale)
    for gf, gr, name in zip(g_fused, g_ref, ("dx", "dscale")):
        np.testing.assert_allclose(
            np.asarray(gf), np.asarray(gr), rtol=1e-4, atol=1e-4,
            err_msg=name)


def test_gelu_parity_fp32():
    x = jax.random.normal(jax.random.key(3), (ROWS, GPT2_D)) * 2.0
    np.testing.assert_allclose(
        np.asarray(fused_gelu(x)), np.asarray(fn.ref_gelu(x)),
        rtol=1e-5, atol=1e-5)
    before = fn.KERNEL_INVOCATIONS["gelu_bwd"]
    g_fused = jax.grad(lambda u: jnp.sum(fused_gelu(u) ** 2))(x)
    assert fn.KERNEL_INVOCATIONS["gelu_bwd"] > before
    g_ref = jax.grad(lambda u: jnp.sum(fn.ref_gelu(u) ** 2))(x)
    np.testing.assert_allclose(
        np.asarray(g_fused), np.asarray(g_ref), rtol=1e-4, atol=1e-4)


def test_bf16_gradient_cosine():
    """bf16 activations (the on-chip compute dtype): gradients track the
    fp32-reference direction to cosine > 0.999."""
    x, scale, bias = _data(GPT2_D, dtype=jnp.bfloat16, seed=2)

    def loss_fused(x, s, b):
        y, x_skip = fused_layer_norm_residual(x, s, b)
        return jnp.sum(((x_skip + y).astype(jnp.float32)) ** 2)

    def loss_ref(x, s, b):
        return jnp.sum(
            ((x + fn.ref_layer_norm(x, s, b)).astype(jnp.float32)) ** 2)

    g_fused = jax.grad(loss_fused, argnums=(0, 1, 2))(x, scale, bias)
    g_ref = jax.grad(loss_ref, argnums=(0, 1, 2))(x, scale, bias)
    assert _cosine(g_fused, g_ref) > 0.999


def test_ref_chains_match_the_models():
    """ops/fused_norm.py re-implements the model norm chains as its
    fallback path AND parity oracle; if the model definitions ever
    drift (eps, var formula), this pins the break to the real cause
    instead of letting untileable-shape fallbacks silently diverge."""
    from ray_tpu.models.gpt2 import _layer_norm
    from ray_tpu.models.llama import _rms_norm

    x, scale, bias = _data(100, rows=8, seed=5)  # untileable on purpose
    np.testing.assert_array_equal(
        np.asarray(fn.ref_layer_norm(x, scale, bias)),
        np.asarray(_layer_norm(x, scale, bias)))
    np.testing.assert_array_equal(
        np.asarray(fn.ref_rms_norm(x, scale)),
        np.asarray(_rms_norm(x, scale)))


def test_odd_shapes_fall_back_to_xla():
    """D not a multiple of 128 (and undividable row counts) must take
    the plain-XLA path — asserted via the trace-time kernel counters —
    and still match the reference bit-for-bit (it IS the reference)."""
    assert fn._should_fuse(64, 100, jnp.float32) is None   # D % 128
    assert fn._should_fuse(7, 768, jnp.float32) is None    # no row block
    assert fn._should_fuse(64, 768, jnp.float32) is not None

    x, scale, bias = _data(100, rows=8)
    before = dict(fn.KERNEL_INVOCATIONS)
    y = fused_layer_norm(x, scale, bias)
    y2, x_skip = fused_layer_norm_residual(x, scale, bias)
    r = fused_rms_norm(x, scale)
    g = fused_gelu(x)
    grads = jax.grad(
        lambda a: jnp.sum(fused_layer_norm_residual(a, scale, bias)[0]))(x)
    assert dict(fn.KERNEL_INVOCATIONS) == before, "fallback launched a kernel"
    np.testing.assert_allclose(
        np.asarray(y), np.asarray(fn.ref_layer_norm(x, scale, bias)))
    np.testing.assert_allclose(np.asarray(y2), np.asarray(y))
    np.testing.assert_allclose(np.asarray(x_skip), np.asarray(x))
    np.testing.assert_allclose(np.asarray(r),
                               np.asarray(fn.ref_rms_norm(x, scale)))
    np.testing.assert_allclose(np.asarray(g), np.asarray(fn.ref_gelu(x)))
    assert np.isfinite(np.asarray(grads)).all()


def test_fit_rows_respects_sublane_and_budget():
    assert fn._fit_rows(16384, 768, jnp.bfloat16) == 256
    assert fn._fit_rows(64, 768, jnp.float32) == 64
    # Wide rows (GELU [R, 4D]) shrink the block to fit the VMEM budget.
    wide = fn._fit_rows(16384, 4 * 3072, jnp.float32)
    assert wide is not None and wide * 4 * 3072 * 4 <= fn._BLOCK_BYTES
    # bf16 needs 16-row alignment.
    assert fn._fit_rows(24, 768, jnp.bfloat16) is None
    assert fn._fit_rows(32, 768, jnp.bfloat16) == 32


def test_gpt2_fused_norm_loss_and_grad_parity():
    """fused_norm=True must track the unfused model: same loss to bf16
    rounding, gradient cosine > 0.999 (whole-model integration incl.
    residual wiring and the final LN)."""
    from ray_tpu.models.gpt2 import GPT2Config, gpt2_init, gpt2_loss

    cfg = GPT2Config(vocab_size=256, n_layer=1, n_head=4, d_model=128,
                     seq_len=64)
    fcfg = dataclasses.replace(cfg, fused_norm=True)
    params = gpt2_init(jax.random.key(0), cfg)
    tokens = jax.random.randint(jax.random.key(1), (2, 33), 0, 256,
                                jnp.int32)
    batch = {"tokens": tokens}
    before = dict(fn.KERNEL_INVOCATIONS)
    l_base, g_base = jax.value_and_grad(
        lambda p: gpt2_loss(p, batch, cfg))(params)
    assert dict(fn.KERNEL_INVOCATIONS) == before  # unfused touches nothing
    l_fused, g_fused = jax.value_and_grad(
        lambda p: gpt2_loss(p, batch, fcfg))(params)
    assert fn.KERNEL_INVOCATIONS["ln_bwd"] > before.get("ln_bwd", 0)
    assert fn.KERNEL_INVOCATIONS["gelu_bwd"] > before.get("gelu_bwd", 0)
    np.testing.assert_allclose(float(l_fused), float(l_base), rtol=1e-2)
    assert _cosine(g_fused, g_base) > 0.999


def test_llama_fused_norm_loss_and_grad_parity():
    from ray_tpu.models.llama import LlamaConfig, llama_init, llama_loss

    cfg = LlamaConfig(vocab_size=256, n_layer=1, n_head=4, n_kv_head=2,
                      d_model=128, seq_len=64)
    fcfg = dataclasses.replace(cfg, fused_norm=True)
    params = llama_init(jax.random.key(0), cfg)
    tokens = jax.random.randint(jax.random.key(1), (2, 33), 0, 256,
                                jnp.int32)
    batch = {"tokens": tokens}
    before = fn.KERNEL_INVOCATIONS["rms_bwd"]
    l_base, g_base = jax.value_and_grad(
        lambda p: llama_loss(p, batch, cfg))(params)
    l_fused, g_fused = jax.value_and_grad(
        lambda p: llama_loss(p, batch, fcfg))(params)
    assert fn.KERNEL_INVOCATIONS["rms_bwd"] > before
    np.testing.assert_allclose(float(l_fused), float(l_base), rtol=1e-2)
    assert _cosine(g_fused, g_base) > 0.999


def test_gpt2_fused_norm_trains():
    """End-to-end: the full bench candidate combo (fused_norm on top of
    bf16 logits + chunked CE + dots remat + unrolled layers) optimizes —
    mirrors the round-5 lever test in test_gpt2.py."""
    from ray_tpu.models.gpt2 import (
        GPT2Config, gpt2_init, gpt2_loss, gpt2_shardings)
    from ray_tpu.parallel.mesh import MeshConfig, build_mesh
    from ray_tpu.train.train_step import make_init_fn, make_train_step

    cfg = GPT2Config(vocab_size=256, n_layer=2, n_head=4, d_model=128,
                     seq_len=64, fused_norm=True,
                     logits_dtype=jnp.bfloat16, ce_vocab_chunks=4,
                     remat="dots", scan_layers=False)
    mesh = build_mesh(MeshConfig())
    shardings = gpt2_shardings(cfg, mesh)
    state = make_init_fn(lambda r: gpt2_init(r, cfg), shardings, mesh)(
        jax.random.key(0))
    step = make_train_step(lambda p, b: gpt2_loss(p, b, cfg), shardings,
                           mesh)
    tokens = jax.random.randint(jax.random.key(1), (8, cfg.seq_len + 1),
                                0, cfg.vocab_size, jnp.int32)
    batch = {"tokens": tokens}
    first = None
    for _ in range(10):
        state, metrics = step(state, batch)
        if first is None:
            first = float(metrics["loss"])
    assert float(metrics["loss"]) < first
