"""Llama-family model: RMSNorm/RoPE/SwiGLU/GQA decoder
(``models/llama.py`` — second flagship family next to GPT-2)."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from ray_tpu.models.llama import (
    LlamaConfig,
    llama_forward,
    llama_init,
    llama_loss,
    llama_param_axes,
    llama_shardings,
)
from ray_tpu.parallel.mesh import MeshConfig, build_mesh
from ray_tpu.train.optim import AdamWConfig
from ray_tpu.train.train_step import make_init_fn, make_train_step

CFG = LlamaConfig.tiny()


def test_forward_shapes_and_finite():
    params = llama_init(jax.random.key(0), CFG)
    tokens = jnp.zeros((2, 16), jnp.int32)
    logits = llama_forward(params, tokens, CFG)
    assert logits.shape == (2, 16, CFG.vocab_size)
    assert logits.dtype == jnp.float32
    assert np.isfinite(np.asarray(logits)).all()


def test_param_axes_cover_every_leaf():
    params = llama_init(jax.random.key(0), CFG)
    axes = llama_param_axes(CFG)
    assert jax.tree.structure(
        params
    ) == jax.tree.structure(axes, is_leaf=lambda x: isinstance(x, tuple))
    # Stacked layer leaves lead with the layer dim.
    for name, leaf in params["blocks"].items():
        assert leaf.shape[0] == CFG.n_layer, name


def test_gqa_equals_mha_when_groups_are_one():
    """n_kv_head == n_head degenerates to standard MHA: same code path
    must produce identical logits with and without the repeat branch."""
    cfg_mha = LlamaConfig(vocab_size=128, n_layer=1, n_head=4, n_kv_head=4,
                          d_model=32, seq_len=16)
    params = llama_init(jax.random.key(0), cfg_mha)
    tokens = jax.random.randint(jax.random.key(1), (2, 16), 0, 128)
    base = llama_forward(params, tokens, cfg_mha)

    # Simulate GQA with 2 kv heads by duplicating kv projections: the
    # grouped model with duplicated weights must match the MHA model.
    cfg_gqa = LlamaConfig(vocab_size=128, n_layer=1, n_head=4, n_kv_head=2,
                          d_model=32, seq_len=16)
    hd = cfg_mha.head_dim
    wk = params["blocks"]["wk"]  # [1, d, 4*hd]
    wv = params["blocks"]["wv"]
    # Keep kv heads 0 and 2; groups (0,1)->kv0, (2,3)->kv2. For equality,
    # make the MHA weights grouped first: kv head i uses column block i.
    grouped = dict(params)
    grouped["blocks"] = dict(params["blocks"])
    grouped["blocks"]["wk"] = jnp.concatenate(
        [wk[..., 0:hd], wk[..., 2 * hd:3 * hd]], axis=-1)
    grouped["blocks"]["wv"] = jnp.concatenate(
        [wv[..., 0:hd], wv[..., 2 * hd:3 * hd]], axis=-1)
    out_gqa = llama_forward(grouped, tokens, cfg_gqa)

    mha_equiv = dict(params)
    mha_equiv["blocks"] = dict(params["blocks"])
    mha_equiv["blocks"]["wk"] = jnp.concatenate(
        [wk[..., 0:hd], wk[..., 0:hd], wk[..., 2 * hd:3 * hd],
         wk[..., 2 * hd:3 * hd]], axis=-1)
    mha_equiv["blocks"]["wv"] = jnp.concatenate(
        [wv[..., 0:hd], wv[..., 0:hd], wv[..., 2 * hd:3 * hd],
         wv[..., 2 * hd:3 * hd]], axis=-1)
    out_ref = llama_forward(mha_equiv, tokens, cfg_mha)
    np.testing.assert_allclose(
        np.asarray(out_gqa), np.asarray(out_ref), rtol=2e-3, atol=2e-3)


def test_rope_rotates_by_position():
    """RoPE: position 0 is identity, other positions rotate (norm
    preserved, vector changed) — the model's only position signal."""
    from ray_tpu.models.llama import _rope

    x = jax.random.normal(jax.random.key(0), (1, 8, 2, 16), jnp.float32)
    out = _rope(x, 10000.0)
    np.testing.assert_allclose(
        np.asarray(out[0, 0]), np.asarray(x[0, 0]), atol=1e-6)
    assert not np.allclose(np.asarray(out[0, 5]), np.asarray(x[0, 5]),
                           atol=1e-4)
    np.testing.assert_allclose(
        np.linalg.norm(np.asarray(out), axis=-1),
        np.linalg.norm(np.asarray(x), axis=-1),
        rtol=1e-5,
    )
    # Relative property: q·k after rotation depends on distance, so the
    # same (q, k) pair rotated at (2, 5) and (12, 15) scores identically.
    q = jax.random.normal(jax.random.key(1), (16,), jnp.float32)
    k = jax.random.normal(jax.random.key(2), (16,), jnp.float32)
    seq = jnp.zeros((1, 20, 1, 16))
    qs = _rope(seq.at[0, :, 0].set(q), 10000.0)
    ks = _rope(seq.at[0, :, 0].set(k), 10000.0)
    s1 = float(qs[0, 5, 0] @ ks[0, 2, 0])
    s2 = float(qs[0, 15, 0] @ ks[0, 12, 0])
    assert abs(s1 - s2) < 1e-3


def test_loss_decreases(devices8):
    mesh = build_mesh(MeshConfig(fsdp=1, devices=jax.devices()[:1]))
    shardings = llama_shardings(CFG, mesh)
    init_fn = make_init_fn(lambda r: llama_init(r, CFG), shardings, mesh)
    state = init_fn(jax.random.key(0))
    step = make_train_step(
        lambda p, b: llama_loss(p, b, CFG),
        shardings, mesh,
        optimizer=AdamWConfig(lr=3e-3, weight_decay=0.0),
    )
    tokens = jax.random.randint(jax.random.key(1), (4, 33), 0, CFG.vocab_size)
    batch = {"tokens": tokens.astype(jnp.int32)}
    first = None
    for _ in range(30):
        state, metrics = step(state, batch)
        if first is None:
            first = float(metrics["loss"])
    assert float(metrics["loss"]) < first * 0.7


def test_sharded_forward_on_mesh(devices8):
    """tp=2 x fsdp=2 x sp=2 mesh: sharded params + jitted loss compile
    and execute; GQA kv-head dim shards under tp."""
    mesh = build_mesh(MeshConfig(fsdp=2, tp=2, sp=2,
                                 devices=jax.devices()[:8]))
    cfg = LlamaConfig(vocab_size=256, n_layer=2, n_head=4, n_kv_head=2,
                      d_model=64, seq_len=64, mesh=mesh)
    shardings = llama_shardings(cfg, mesh)
    init_fn = make_init_fn(lambda r: llama_init(r, cfg), shardings, mesh)
    state = init_fn(jax.random.key(0))
    step = make_train_step(
        lambda p, b: llama_loss(p, b, cfg), shardings, mesh,
        optimizer=AdamWConfig(lr=1e-3),
    )
    tokens = jax.random.randint(jax.random.key(1), (4, 65), 0, 256)
    state, metrics = step(state, {"tokens": tokens.astype(jnp.int32)})
    assert np.isfinite(float(metrics["loss"]))


def test_n_params_counts_the_tree():
    params = llama_init(jax.random.key(0), CFG)
    counted = sum(int(np.prod(p.shape)) for p in jax.tree.leaves(params))
    assert counted == CFG.n_params
