"""The Pallas kernel of the latent chunk attention (PR 59,
``ray_tpu/ops/latent_chunk.py``) in interpret mode, at the smallest shapes
that take it: against ``latent_chunk_attention``'s XLA arm, which the toy
widths of every other CPU test keep, and against a float32 ``jax.numpy``
attention over the whole ring."""

import functools

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from ray_tpu.ops import attention, latent_chunk

RANK, NOPE, ROPE, V = 128, 128, 64, 128
SCALE = 0.07
LAYER = 1

BLOCK = latent_chunk.BLOCK_ROWS

# heads, chunk, ring rows (4 and 8 blocks), the cache's type
SHAPES = {"bf16_4_blocks": (4, 16, 4 * BLOCK, jnp.bfloat16),
          "f32_8_blocks": (2, 32, 8 * BLOCK, jnp.float32)}


@functools.lru_cache(maxsize=None)
def _operands(shape: str, rows: int):
    h, c, n_rows, dtype = SHAPES[shape]
    ks = jax.random.split(jax.random.PRNGKey(len(shape)), 5)
    return (jax.random.normal(ks[0], (rows, c, h, NOPE)).astype(dtype),
            jax.random.normal(ks[1], (rows, c, h, ROPE)).astype(dtype),
            jax.random.normal(ks[2], (2, 3, n_rows, 1, RANK + ROPE)
                              ).astype(dtype),
            (jax.random.normal(ks[3], (RANK, h, NOPE)) / RANK ** 0.5
             ).astype(dtype),
            (jax.random.normal(ks[4], (RANK, h, V)) / RANK ** 0.5
             ).astype(dtype))


@functools.lru_cache(maxsize=None)
def _arm(kernel: bool):
    """``latent_chunk_attention`` jitted with the shape rule as it is, or
    held to the XLA arm."""
    def fn(q_nope, q_pe, cache, slots, start, w_uk, w_uv):
        with pytest.MonkeyPatch.context() as patch:
            if not kernel:
                patch.setattr(latent_chunk, "takes_kernel",
                              lambda *a: False)
            return attention.latent_chunk_attention(
                q_nope, q_pe, cache, LAYER, slots, start, w_uk, w_uv, SCALE)
    return jax.jit(fn)


def _plain(q_nope, q_pe, cache, slots, start, w_uk, w_uv):
    """float32, every key and value of the ring decompressed, one softmax."""
    f32 = functools.partial(jnp.asarray, dtype=jnp.float32)
    c = q_nope.shape[1]
    outs = []
    for i in range(q_nope.shape[0]):
        rows = f32(cache[LAYER, slots[i], :, 0])
        k = jnp.einsum("br,rhd->bhd", rows[:, :RANK], f32(w_uk))
        v = jnp.einsum("br,rhd->bhd", rows[:, :RANK], f32(w_uv))
        scores = (jnp.einsum("chd,bhd->hcb", f32(q_nope[i]), k)
                  + jnp.einsum("chp,bp->hcb", f32(q_pe[i]), rows[:, RANK:])
                  ) * SCALE
        seen = jnp.arange(rows.shape[0])[None, :] \
            <= (start[i] + jnp.arange(c))[:, None]
        outs.append(jnp.einsum(
            "hcb,bhd->chd",
            jax.nn.softmax(jnp.where(seen[None], scores, -jnp.inf), -1), v))
    return jnp.stack(outs)


CASES = {
    # shape, slots, start a row, rows the slot's ring holds as NaN from
    "start_0": ("bf16_4_blocks", (1,), (0,), None),
    "mid_block": ("bf16_4_blocks", (1,), (100,), None),
    "over_a_blocks_edge": ("bf16_4_blocks", (2,), (BLOCK - 8,), None),
    "at_a_blocks_edge": ("bf16_4_blocks", (1,), (BLOCK,), None),
    "the_rings_last_chunk": ("bf16_4_blocks", (0,), (4 * BLOCK - 16,), None),
    "later_blocks_are_not_read": ("bf16_4_blocks", (1,), (100,), BLOCK),
    "two_rows_float32": ("f32_8_blocks", (2, 0),
                         (2 * BLOCK + 188, 8 * BLOCK - 32), None),
    "float32_mid_ring": ("f32_8_blocks", (1, 1), (4 * BLOCK, 3), None),
}


@pytest.mark.parametrize("case", list(CASES))
def test_the_kernel_is_the_xla_arm_and_the_plain_attention(case):
    shape, slots, start, nan_from = CASES[case]
    q_nope, q_pe, cache, w_uk, w_uv = _operands(shape, len(slots))
    h, c, n_rows, dtype = SHAPES[shape]
    assert latent_chunk.takes_kernel(c, RANK, NOPE, V, n_rows)
    if nan_from is not None:  # the chunk ends before: nobody may read them
        assert start[0] + c <= nan_from
        cache = cache.at[LAYER, slots[0], nan_from:].set(jnp.nan)
    slots, start = jnp.asarray(slots), jnp.asarray(start)
    args = (q_nope, q_pe, cache, slots, start, w_uk, w_uv)
    got = _arm(True)(*args)
    assert got.shape == (len(slots), c, h, V) and got.dtype == dtype
    got = np.asarray(got, np.float32)
    assert np.isfinite(got).all()
    # the same arithmetic: a rounding of the result's type apart at most
    ulp, far = (2 ** -7, 0.02) if dtype == jnp.bfloat16 else (1e-5, 1e-4)
    np.testing.assert_allclose(
        got, np.asarray(_arm(False)(*args), np.float32), atol=ulp, rtol=ulp)
    if nan_from is not None:
        cache = cache.at[LAYER, slots[0], nan_from:].set(0)
    np.testing.assert_allclose(
        got, np.asarray(_plain(q_nope, q_pe, cache, slots, start, w_uk,
                               w_uv)), atol=far, rtol=far)


@pytest.mark.parametrize("shape,takes", [
    ((512, 512, 128, 128, 16896), True),  # the cell's
    ((16, 128, 128, 128, 4 * BLOCK), True),
    ((16, 32, 16, 16, 4 * BLOCK), False),     # the tiny preset's widths
    ((16, 128, 128, 64, 4 * BLOCK), False),   # values of half a lane tile
    ((16, 128, 128, 128, 4 * BLOCK - 128), False),  # no whole blocks
    ((12, 128, 128, 128, 4 * BLOCK), False),  # no whole sublane tiles
])
def test_the_shape_rule(shape, takes):
    assert latent_chunk.takes_kernel(*shape) is takes


def test_toy_widths_keep_the_xla_arm(monkeypatch):
    def no_kernel(*a, **k):
        raise AssertionError("the kernel was called")

    monkeypatch.setattr(latent_chunk, "latent_chunk_attention", no_kernel)
    ks = jax.random.split(jax.random.PRNGKey(0), 5)
    out = attention.latent_chunk_attention(
        jax.random.normal(ks[0], (1, 8, 2, 16)),
        jax.random.normal(ks[1], (1, 8, 2, 8)),
        jax.random.normal(ks[2], (1, 2, 512, 1, 40)), 0, jnp.array([1]),
        jnp.array([5]), jax.random.normal(ks[3], (32, 2, 16)),
        jax.random.normal(ks[4], (32, 2, 16)), SCALE)
    assert out.shape == (1, 8, 2, 16)
