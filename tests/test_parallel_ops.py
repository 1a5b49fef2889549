"""Parallelism kernels vs dense references on the 8-device CPU mesh.

This is the §5.7 coverage the reference lacks: ring attention, Ulysses,
MoE expert parallelism, pipeline parallelism — each checked numerically
against a single-device dense implementation.
"""

import numpy as np
import pytest

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from ray_tpu.ops.attention import xla_causal_attention
from ray_tpu.ops.flash_attention import flash_causal_attention
from ray_tpu.ops.moe import init_moe_params, moe_ffn, moe_ffn_ep
from ray_tpu.ops.ring_attention import ring_causal_attention
from ray_tpu.ops.ulysses import ulysses_attention
from ray_tpu.parallel.pipeline import pipeline_apply


def _qkv(rng_seed=0, b=2, t=64, h=4, d=16, dtype=jnp.float32):
    rng = jax.random.key(rng_seed)
    kq, kk, kv = jax.random.split(rng, 3)
    q = jax.random.normal(kq, (b, t, h, d), dtype)
    k = jax.random.normal(kk, (b, t, h, d), dtype)
    v = jax.random.normal(kv, (b, t, h, d), dtype)
    return q, k, v


@pytest.fixture(scope="module")
def sp_mesh(devices8):
    return Mesh(np.array(devices8).reshape(2, 4), ("dp", "sp"))


def test_flash_attention_matches_xla():
    q, k, v = _qkv(t=128)
    ref = xla_causal_attention(q, k, v)
    out = flash_causal_attention(q, k, v, block_q=32, block_k=32)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               rtol=2e-5, atol=2e-5)


def test_flash_attention_gradients_match():
    q, k, v = _qkv(t=64)

    def loss_flash(q, k, v):
        return jnp.sum(flash_causal_attention(q, k, v, block_q=16, block_k=16) ** 2)

    def loss_ref(q, k, v):
        return jnp.sum(xla_causal_attention(q, k, v) ** 2)

    g_flash = jax.grad(loss_flash, argnums=(0, 1, 2))(q, k, v)
    g_ref = jax.grad(loss_ref, argnums=(0, 1, 2))(q, k, v)
    for gf, gr in zip(g_flash, g_ref):
        np.testing.assert_allclose(np.asarray(gf), np.asarray(gr),
                                   rtol=2e-4, atol=2e-4)


def test_flash_attention_non_divisible_blocks():
    """Requested block sizes that don't divide T shrink to the largest
    divisor instead of erroring (T=192 with block 128 -> 96)."""
    from ray_tpu.ops.flash_attention import _fit_block

    assert _fit_block(128, 192) == 96
    assert _fit_block(1024, 1536) == 768
    q, k, v = _qkv(t=192)
    ref = xla_causal_attention(q, k, v)
    out = flash_causal_attention(q, k, v, block_q=128, block_k=128)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               rtol=2e-5, atol=2e-5)


def test_flash_attention_gradients_long_seq_path():
    """n_kb > _DQ_PARTIALS_MAX_KB exercises the O(T)-memory two-kernel
    backward (separate dQ kernel) instead of the fused dQ-partials path."""
    from ray_tpu.ops import flash_attention as fa

    q, k, v = _qkv(t=96)
    n_kb = 96 // 16
    assert n_kb > fa._DQ_PARTIALS_MAX_KB

    def loss_flash(q, k, v):
        return jnp.sum(
            flash_causal_attention(q, k, v, block_q=16, block_k=16) ** 2)

    def loss_ref(q, k, v):
        return jnp.sum(xla_causal_attention(q, k, v) ** 2)

    g_flash = jax.grad(loss_flash, argnums=(0, 1, 2))(q, k, v)
    g_ref = jax.grad(loss_ref, argnums=(0, 1, 2))(q, k, v)
    for gf, gr in zip(g_flash, g_ref):
        np.testing.assert_allclose(np.asarray(gf), np.asarray(gr),
                                   rtol=2e-4, atol=2e-4)


def test_ring_attention_matches_dense(sp_mesh):
    q, k, v = _qkv(t=64)
    ref = xla_causal_attention(q, k, v)
    out = ring_causal_attention(q, k, v, sp_mesh, axis="sp",
                                batch_axes=("dp",))
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               rtol=2e-5, atol=2e-5)


def test_ring_attention_differentiable(sp_mesh):
    q, k, v = _qkv(t=32)

    @jax.jit
    def loss(q, k, v):
        out = ring_causal_attention(q, k, v, sp_mesh, axis="sp",
                                    batch_axes=("dp",))
        return jnp.sum(out ** 2)

    def loss_ref(q, k, v):
        return jnp.sum(xla_causal_attention(q, k, v) ** 2)

    g = jax.grad(loss)(q, k, v)
    g_ref = jax.grad(loss_ref)(q, k, v)
    np.testing.assert_allclose(np.asarray(g), np.asarray(g_ref),
                               rtol=1e-4, atol=1e-4)


def test_ring_fused_matches_dense_impl(sp_mesh):
    """The Pallas-fused ring body (flash kernel per KV block, no
    [B,H,C,C] scores in HBM) agrees with the einsum ring body — forward
    and gradients (SURVEY §7 hard-part 5)."""
    q, k, v = _qkv(t=128)

    def loss(impl):
        def f(q, k, v):
            out = ring_causal_attention(q, k, v, sp_mesh, axis="sp",
                                        batch_axes=("dp",), impl=impl)
            return jnp.sum(out ** 2)
        return f

    # (jitted: op by op the interpreted kernels cost minutes, PR 64)
    out_f = jax.jit(lambda q, k, v: ring_causal_attention(
        q, k, v, sp_mesh, axis="sp", batch_axes=("dp",), impl="fused"))(
        q, k, v)
    out_d = jax.jit(lambda q, k, v: ring_causal_attention(
        q, k, v, sp_mesh, axis="sp", batch_axes=("dp",), impl="dense"))(
        q, k, v)
    np.testing.assert_allclose(np.asarray(out_f), np.asarray(out_d),
                               rtol=2e-5, atol=2e-5)
    g_f = jax.jit(jax.grad(loss("fused"), argnums=(0, 1, 2)))(q, k, v)
    g_d = jax.jit(jax.grad(loss("dense"), argnums=(0, 1, 2)))(q, k, v)
    for gf, gd in zip(g_f, g_d):
        np.testing.assert_allclose(np.asarray(gf), np.asarray(gd),
                                   rtol=1e-4, atol=1e-4)


def test_ulysses_matches_dense(sp_mesh):
    q, k, v = _qkv(t=64, h=8)  # heads divisible by sp=4
    ref = xla_causal_attention(q, k, v)
    out = ulysses_attention(q, k, v, sp_mesh, axis="sp", batch_axes=("dp",))
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               rtol=2e-5, atol=2e-5)


def test_moe_dense_routes_and_balances():
    rng = jax.random.key(0)
    params = init_moe_params(rng, d_model=16, d_ff=32, n_experts=4)
    x = jax.random.normal(jax.random.key(1), (2, 8, 16))
    out, aux = moe_ffn(params, x, top_k=2, capacity_factor=2.0)
    assert out.shape == x.shape
    assert np.isfinite(float(aux))
    # capacity large enough + top2 -> every token routed: output nonzero
    assert float(jnp.mean(jnp.abs(out))) > 1e-4


def test_moe_expert_parallel_matches_dense(devices8):
    mesh = Mesh(np.array(devices8).reshape(2, 4), ("dp", "ep"))
    rng = jax.random.key(0)
    params = init_moe_params(rng, d_model=16, d_ff=32, n_experts=8)
    x = jax.random.normal(jax.random.key(1), (4, 8, 16))
    dense_out, dense_aux = moe_ffn(params, x, top_k=1, capacity_factor=4.0)

    xs = jax.device_put(x, NamedSharding(mesh, P("dp", None, None)))
    ep_out, ep_aux = moe_ffn_ep(params, xs, mesh, axis="ep", top_k=1,
                                capacity_factor=4.0, batch_axes=("dp",))
    # Same routing math on the same tokens => identical outputs.
    np.testing.assert_allclose(np.asarray(ep_out), np.asarray(dense_out),
                               rtol=1e-4, atol=1e-4)


def test_pipeline_matches_sequential(devices8):
    mesh = Mesh(np.array(devices8[:4]), ("pp",))
    pp = 4
    rng = jax.random.key(0)
    d = 16
    ws = jax.random.normal(rng, (pp, d, d)) * 0.3
    stage_params = {"w": ws}

    def stage_fn(params, x):
        return jnp.tanh(x @ params["w"])

    x = jax.random.normal(jax.random.key(1), (8, d))
    # sequential reference
    ref = x
    for i in range(pp):
        ref = stage_fn({"w": ws[i]}, ref)

    ws_sharded = jax.device_put(ws, NamedSharding(mesh, P("pp", None, None)))
    out = pipeline_apply({"w": ws_sharded}, x, mesh, stage_fn=stage_fn,
                         n_micro=4, axis="pp")
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               rtol=1e-5, atol=1e-5)


def test_pipeline_differentiable(devices8):
    mesh = Mesh(np.array(devices8[:2]), ("pp",))
    d = 8
    ws = jax.random.normal(jax.random.key(0), (2, d, d)) * 0.3

    def stage_fn(params, x):
        return jnp.tanh(x @ params["w"])

    x = jax.random.normal(jax.random.key(1), (4, d))

    def loss_pp(ws):
        out = pipeline_apply(
            {"w": ws}, x, mesh, stage_fn=stage_fn, n_micro=2, axis="pp"
        )
        return jnp.sum(out ** 2)

    def loss_ref(ws):
        y = x
        for i in range(2):
            y = stage_fn({"w": ws[i]}, y)
        return jnp.sum(y ** 2)

    g = jax.grad(loss_pp)(ws)
    g_ref = jax.grad(loss_ref)(ws)
    np.testing.assert_allclose(np.asarray(g), np.asarray(g_ref),
                               rtol=1e-4, atol=1e-4)


def test_1f1b_schedule_properties():
    from ray_tpu.parallel.pipeline import build_1f1b_schedule

    for n_micro, pp in [(4, 2), (8, 4), (3, 4), (6, 3), (1, 2), (5, 1)]:
        fwd, bwd, f_arr, b_arr = build_1f1b_schedule(n_micro, pp)
        # Every stage forwards and backwards every microbatch exactly once,
        # in order.
        for s in range(pp):
            assert [r[s] for r in fwd if r[s] >= 0] == list(range(n_micro))
            assert [r[s] for r in bwd if r[s] >= 0] == list(range(n_micro))
        # 1F1B memory bound: in-flight fwds per stage <= max(1, pp - s).
        for s in range(pp):
            inflight = 0
            for t in range(len(fwd)):
                inflight += fwd[t][s] >= 0
                inflight -= bwd[t][s] >= 0
                assert inflight <= max(1, pp - s)
        # Steady state is tight: total ticks ~ 2*(n_micro + pp - 1) + pp.
        assert len(fwd) <= 2 * (n_micro + pp - 1) + pp


def test_1f1b_value_and_grad_matches_reference(devices8):
    from ray_tpu.parallel.pipeline import pipeline_value_and_grad

    pp = 4
    mesh = Mesh(np.array(devices8[:pp]), ("pp",))
    d = 12
    ws = jax.random.normal(jax.random.key(0), (pp, d, d)) * 0.4
    bs = jax.random.normal(jax.random.key(1), (pp, d)) * 0.1
    stage_params = {"w": ws, "b": bs}

    def stage_fn(params, x):
        return jnp.tanh(x @ params["w"] + params["b"])

    def loss_fn(out, y):
        return jnp.mean((out - y) ** 2)

    x = jax.random.normal(jax.random.key(2), (12, d))
    y = jax.random.normal(jax.random.key(3), (12, d))

    def ref_loss(sp):
        h = x
        for i in range(pp):
            h = stage_fn(jax.tree.map(lambda p: p[i], sp), h)
        # Mean over the 6 microbatches of per-microbatch MSE == full-batch
        # MSE here (equal microbatch sizes).
        return loss_fn(h, y)

    ref_l, ref_g = jax.value_and_grad(ref_loss)(stage_params)

    sharded = jax.tree.map(
        lambda p: jax.device_put(
            p, NamedSharding(mesh, P("pp", *([None] * (p.ndim - 1))))),
        stage_params,
    )
    for n_micro in (6, 4, 2):
        loss, grads = pipeline_value_and_grad(
            sharded, x, y, mesh, stage_fn=stage_fn, loss_fn=loss_fn,
            n_micro=n_micro, axis="pp",
        )
        np.testing.assert_allclose(float(loss), float(ref_l),
                                   rtol=1e-5, atol=1e-5)
        jax.tree.map(
            lambda a, b: np.testing.assert_allclose(
                np.asarray(a), np.asarray(b), rtol=1e-4, atol=1e-5),
            grads, ref_g,
        )


def test_1f1b_under_jit_and_pp2(devices8):
    from ray_tpu.parallel.pipeline import pipeline_value_and_grad

    mesh = Mesh(np.array(devices8[:2]), ("pp",))
    d = 8
    stage_params = {"w": jax.random.normal(jax.random.key(0), (2, d, d)) * 0.3}

    def stage_fn(params, x):
        return jnp.tanh(x @ params["w"])

    def loss_fn(out, y):
        return jnp.mean((out - y) ** 2)

    x = jax.random.normal(jax.random.key(1), (8, d))
    y = jnp.zeros((8, d))

    @jax.jit
    def step(sp):
        loss, grads = pipeline_value_and_grad(
            sp, x, y, mesh, stage_fn=stage_fn, loss_fn=loss_fn, n_micro=4)
        return loss, grads

    loss, grads = step(stage_params)

    def ref(sp):
        h = x
        for i in range(2):
            h = stage_fn(jax.tree.map(lambda p: p[i], sp), h)
        return loss_fn(h, y)

    ref_l, ref_g = jax.value_and_grad(ref)(stage_params)
    np.testing.assert_allclose(float(loss), float(ref_l), rtol=1e-5)
    np.testing.assert_allclose(np.asarray(grads["w"]),
                               np.asarray(ref_g["w"]), rtol=1e-4, atol=1e-5)


def test_gpipe_schedule_matches_1f1b_numerics(devices8):
    """GPipe-scheduled training (style="gpipe"): same math, different
    timetable — loss and grads must equal the 1F1B result exactly; the
    schedule itself must be all-forwards-then-all-backwards with more
    ticks and an O(n_micro) activation stash."""
    from ray_tpu.parallel.pipeline import (
        build_1f1b_schedule,
        pipeline_value_and_grad,
    )

    pp, n_micro = 4, 8
    mesh = Mesh(np.array(devices8[:pp]), ("pp",))
    d = 8
    sp = {"w": jax.random.normal(jax.random.key(0), (pp, d, d)) * 0.3}

    def stage_fn(params, x):
        return jnp.tanh(x @ params["w"])

    def loss_fn(out, y):
        return jnp.mean((out - y) ** 2)

    x = jax.random.normal(jax.random.key(1), (16, d))
    y = jax.random.normal(jax.random.key(2), (16, d))
    outs = {}
    for style in ("1f1b", "gpipe"):
        loss, grads = pipeline_value_and_grad(
            sp, x, y, mesh, stage_fn=stage_fn, loss_fn=loss_fn,
            n_micro=n_micro, style=style)
        outs[style] = (float(loss), np.asarray(grads["w"]))
    assert abs(outs["1f1b"][0] - outs["gpipe"][0]) < 1e-6
    np.testing.assert_allclose(outs["1f1b"][1], outs["gpipe"][1],
                               rtol=1e-5, atol=1e-6)

    fwd_g, bwd_g, _, _ = build_1f1b_schedule(n_micro, pp, "gpipe")
    fwd_1, _, _, _ = build_1f1b_schedule(n_micro, pp, "1f1b")
    assert len(fwd_g) > len(fwd_1)  # the flush tail costs ticks
    # all-fwd-then-all-bwd: no backward fires before the last forward.
    last_fwd = max(t for t, row in enumerate(fwd_g)
                   if any(m >= 0 for m in row))
    first_bwd = min(t for t, row in enumerate(bwd_g)
                    if any(m >= 0 for m in row))
    assert first_bwd >= last_fwd


def test_pipeline_sp_data_axis_grads(devices8):
    """data_spec + grad_psum_axes: sequence-sharded activations through
    the pipeline; grads must match the unsharded single-program
    reference (the dp x sp grad-allreduce, done inside the shard_map)."""
    from ray_tpu.parallel.pipeline import pipeline_value_and_grad

    pp, sp_sz = 2, 2
    mesh = Mesh(np.array(devices8[:4]).reshape(pp, sp_sz), ("pp", "sp"))
    d, seq = 8, 8
    stage_params = {
        "w": jax.random.normal(jax.random.key(0), (pp, d, d)) * 0.3}

    def stage_fn(params, x):  # x: [mb, seq_local, d]
        return jnp.tanh(x @ params["w"])

    def loss_fn(out, y):
        return jnp.mean((out - y) ** 2)

    x = jax.random.normal(jax.random.key(1), (8, seq, d))
    y = jax.random.normal(jax.random.key(2), (8, seq, d))

    loss, grads = pipeline_value_and_grad(
        stage_params, x, y, mesh, stage_fn=stage_fn, loss_fn=loss_fn,
        n_micro=4, data_spec=P(None, None, "sp", None),
        grad_psum_axes=("sp",))

    def ref(spar):
        h = x
        for i in range(pp):
            h = stage_fn(jax.tree.map(lambda p: p[i], spar), h)
        return loss_fn(h, y)

    ref_l, ref_g = jax.value_and_grad(ref)(stage_params)
    np.testing.assert_allclose(float(loss), float(ref_l),
                               rtol=1e-5, atol=1e-5)
    np.testing.assert_allclose(np.asarray(grads["w"]),
                               np.asarray(ref_g["w"]),
                               rtol=1e-4, atol=1e-5)
