"""The kernel of ``ops/merged_chunk.py`` (Pallas interpret mode on the CPU)
against the XLA arm of ``ops/attention.merged_chunk_attention`` over the same
stacks: a chunk's queries over the slot's ring rows ``< start`` and the
chunk's own under the causal triangle. Small shapes of whole lane tiles (a
chunk of 128, heads of 128 and 256, blocks of 128 rows), ``start`` at 0,
inside the first block, on a block's edge and at the window's end, grouped
and ungrouped heads, a padded query, and two rows of one call with slots and
starts of their own; and which shapes take the kernel at all: the four
serving cells' do, GPT-2 XL's and every tiny preset's keep the XLA arm.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from ray_tpu.ops import attention as ops
from ray_tpu.ops import merged_chunk

CHUNK, LAYERS, SLOTS = 128, 2, 3


def _normal(seed, dtype, *shape):
    return jax.random.normal(jax.random.PRNGKey(seed), shape,
                             jnp.float32).astype(dtype)


def _operands(h, g, hd, old, rows, dtype):
    """q [R, C, H, hd], both stacks [N, S, L, W] of noise (rings longer than
    the window, as the engine's are) and the chunk's own rows [R, C, W]."""
    w = g * hd
    return (_normal(1, dtype, rows, CHUNK, h, hd),
            _normal(2, dtype, LAYERS, SLOTS, old + 2 * CHUNK, w),
            _normal(3, dtype, LAYERS, SLOTS, old + 2 * CHUNK, w),
            _normal(4, dtype, rows, CHUNK, w),
            _normal(5, dtype, rows, CHUNK, w))


# (jitted: one compile a shape, not one an op; traced only under the patch)
_XLA_ARM = jax.jit(ops.merged_chunk_attention, static_argnums=(5, 8))


def _xla_arm(monkeypatch, *args):
    with monkeypatch.context() as patch:
        patch.setattr(merged_chunk, "takes_kernel", lambda *a: False)
        return _XLA_ARM(*args)


OLD, BLOCK = 384, 128
F32 = (4, 2, 128, jnp.float32, 2e-5)
# (query heads, K/V heads, head size, type, tolerance), slots, starts: the
# cases of one shape share one lowering of the kernel (``start`` is traced)
KERNEL = {
    "start-0": (F32, [2], [0]),
    "inside-the-first-block": (F32, [2], [50]),
    "at-the-windows-end": (F32, [2], [OLD]),
    "a-padded-query": (F32, [2], [200]),
    # rows with slots and starts of their own: one on a block's edge, one
    # inside a later block
    "two-rows-of-one-call": (F32, [2, 0], [256, 300]),
    # heads of 256, a K/V head a query head, in the rings' type on the chip:
    # the two arms round their probabilities at different points (before
    # and after the division by their sum)
    "on-a-blocks-edge-ungrouped-256-bfloat16": (
        (2, 2, 256, jnp.bfloat16, 3e-2), [1], [128]),
}


@pytest.mark.parametrize("case", list(KERNEL))
def test_the_kernel_gives_the_xla_arms_softmax_over_the_same_keys(
        case, monkeypatch):
    (h, g, hd, dtype, tol), slots, start = KERNEL[case]
    monkeypatch.setattr(merged_chunk, "block_rows", lambda old: BLOCK)
    assert merged_chunk.takes_kernel(CHUNK, hd, g * hd, OLD)
    q, k_all, v_all, k_own, v_own = _operands(h, g, hd, OLD, len(slots),
                                              dtype)
    if case == "a-padded-query":
        # a prompt's last chunk: the rows past its real tokens are the
        # pad's, zeros as queries and whatever as keys
        q = q.at[:, 100:].set(0)
        k_own, v_own = k_own.at[:, 100:].set(7.0), v_own.at[:, 100:].set(-7.0)

    def attend(k_own, v_own):
        return q, k_all, v_all, k_own, v_own, 1, jnp.asarray(slots), \
            jnp.asarray(start), OLD + CHUNK

    got = ops.merged_chunk_attention(*attend(k_own, v_own))
    want = _xla_arm(monkeypatch, *attend(k_own, v_own))
    assert got.shape == want.shape and got.dtype == want.dtype
    np.testing.assert_allclose(np.asarray(got, np.float32),
                               np.asarray(want, np.float32), atol=tol, rtol=0)
    if case == "a-padded-query":
        # a real query saw no padded key: its row is what it is without them
        clean = ops.merged_chunk_attention(*attend(
            k_own.at[:, 100:].set(0), v_own.at[:, 100:].set(0)))
        np.testing.assert_array_equal(np.asarray(got[:, :100]),
                                      np.asarray(clean[:, :100]))


def _tiny_presets():
    from ray_tpu.models import exaone_moe, falcon_h1, qwen3_next, smallthinker
    for cfg in (qwen3_next.Qwen3NextConfig.tiny(),
                smallthinker.SmallThinkerConfig.tiny(),
                exaone_moe.ExaoneMoeConfig.tiny(),
                falcon_h1.FalconH1Config.tiny()):
        w = ops.merged_row_width(cfg.n_kv_head, cfg.head_dim)
        for chunk, window in ((4, 16), (8, 32), (16, 64), (128, 640)):
            yield chunk, cfg.head_dim, w, window - chunk


# (chunk, head size, row width, window - chunk) -> block rows, 0: the XLA arm
SHAPES = {
    "qwen3-next-16:2:256": ([(512, 256, 512, 16384 - 512)], 512),
    "smallthinker-28:4:128": ([(512, 128, 512, 14336 - 512)], 512),
    "k-exaone-64:8:128": ([(512, 128, 1024, 4096 - 512)], 512),
    "falcon-h1-20:4:128": ([(256, 128, 512, 4096 - 256)], 256),
    # heads of 64 in a row of 13 tiles with a pad, contexts under 1,024
    "gpt2-xl-25:25:64": ([(256, 64, 1664, 768 - 256)], 0),
    "every-tiny-preset": (_tiny_presets, 0),
}


@pytest.mark.parametrize("name", list(SHAPES))
def test_which_shapes_take_the_kernel(name):
    shapes, block = SHAPES[name]
    shapes = list(shapes() if callable(shapes) else shapes)
    assert shapes
    for c, hd, w, old in shapes:
        assert merged_chunk.takes_kernel(c, hd, w, old) == bool(block)
        if block:
            assert merged_chunk.block_rows(old) == block
