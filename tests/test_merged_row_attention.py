"""The two attentions over a cache of MERGED rows (``ops/attention.py``:
``cached_decode_attention``'s rank-3 arm and ``merged_chunk_attention``)
against the heads-apart forms they replace, at every kind of row a family
stores (PR 44): as many K/V heads as query heads (GPT-2: a toy row under a
lane tile, and XL's 25 heads of 64 padded 1600 -> 1664) and GROUPED queries
(Falcon-H1's 20 query heads over 4 K/V heads of 128, a row of four whole
lane tiles with a lane group a K/V head; its tiny preset's 4 over 2 of 16, a
row under one tile). Float32 throughout, on the CPU: what is held is the
softmax over the same keys, not a chip's rounding.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from ray_tpu.ops import attention as ops

# (query heads, K/V heads, head size)
ROWS = {"gpt2-toy-4:4:16": (4, 4, 16), "gpt2-xl-25:25:64": (25, 25, 64),
        "falcon-h1-20:4:128": (20, 4, 128), "falcon-h1-tiny-4:2:16": (4, 2, 16)}
SLOTS, RING, CHUNK, LAYERS = 3, 12, 4, 2


def _normal(seed, *shape):
    return jax.random.normal(jax.random.PRNGKey(seed), shape, jnp.float32)


def _merged(rows, w):
    """[..., G, hd] heads apart -> [..., W] as a merged cache holds them."""
    return ops.merged_rows(rows.reshape(*rows.shape[:-2], -1), w)


def _plain(q, k, v, seen):
    """The softmax written out: q [..., H, hd] over keys k / v [..., K, G,
    hd], ``seen`` [..., K] (or [..., Q, K] with q [..., Q, H, hd])."""
    rep = q.shape[-2] // k.shape[-2]
    k, v = (np.repeat(np.asarray(a, np.float64), rep, axis=-2)
            for a in (k, v))
    q = np.asarray(q, np.float64)
    if q.ndim == k.ndim:   # a chunk: [R, Q, H, hd] over [R, K, H, hd]
        scores = np.einsum("rqhd,rkhd->rhqk", q, k)
        seen = np.asarray(seen)[:, None]
    else:                  # a step: [S, H, hd] over [S, K, H, hd]
        scores = np.einsum("shd,skhd->shk", q, k)
        seen = np.asarray(seen)[:, None, :]
    scores = np.where(seen, scores / q.shape[-1] ** 0.5, -np.inf)
    probs = np.exp(scores - scores.max(-1, keepdims=True))
    probs /= probs.sum(-1, keepdims=True)
    if q.ndim == k.ndim:
        return np.einsum("rhqk,rkhd->rqhd", probs, v)
    return np.einsum("shk,skhd->shd", probs, v)


def _step(h, g, hd, pos):
    """One decode step at positions ``pos`` [S] over rings of noise whose
    row at the cursor holds what the new token must NOT be mistaken for."""
    w = ops.merged_row_width(g, hd)
    q = _normal(1, SLOTS, h, hd)
    k, v = _normal(2, SLOTS, RING, g, hd), _normal(3, SLOTS, RING, g, hd)
    k_new, v_new = _normal(4, SLOTS, g, hd), _normal(5, SLOTS, g, hd)
    pos = jnp.asarray(pos, jnp.int32)
    cursor, valid = pos % RING, jnp.minimum(pos + 1, RING)
    at = jnp.arange(SLOTS)
    k, v = k.at[at, cursor].set(50.0), v.at[at, cursor].set(-50.0)
    got = ops.cached_decode_attention(
        q, _merged(k, w), _merged(v, w), _merged(k_new, w),
        _merged(v_new, w), cursor, valid, jnp.float32)
    apart = ops.cached_decode_attention(q, k, v, k_new, v_new, cursor,
                                        valid, jnp.float32)
    # the row written first, then the window read: the cursor's old row
    # (a wrapped ring's oldest) is gone, the new token's stands there
    plain = _plain(q, k.at[at, cursor].set(k_new),
                   v.at[at, cursor].set(v_new),
                   np.arange(RING)[None, :] < np.asarray(valid)[:, None])
    return got, apart, plain


def _chunk(h, g, hd, start):
    """A chunk of CHUNK queries a row at ``start`` [R] over its slot's
    earlier rows and its own, in layer 1 of a stack of noise."""
    w = ops.merged_row_width(g, hd)
    slots = jnp.asarray([2, 0], jnp.int32)
    start = jnp.asarray(start, jnp.int32)
    window = 3 * CHUNK
    q = _normal(6, 2, CHUNK, h, hd)
    k_all = _normal(7, LAYERS, SLOTS, RING, g, hd)
    v_all = _normal(8, LAYERS, SLOTS, RING, g, hd)
    k_own, v_own = _normal(9, 2, CHUNK, g, hd), _normal(10, 2, CHUNK, g, hd)
    got = ops.merged_chunk_attention(
        q, _merged(k_all, w), _merged(v_all, w), _merged(k_own, w),
        _merged(v_own, w), 1, slots, start, window)
    # the form it replaces: written inside the loop, then read back
    k_wrote = ops.cache_write_prompt(k_all, 1, k_own, slots, start)
    v_wrote = ops.cache_write_prompt(v_all, 1, v_own, slots, start)
    apart = ops.cached_chunk_attention(q, k_wrote, v_wrote, 1, slots, start,
                                       window)
    seen = np.arange(window)[None, None, :] <= (
        np.asarray(start)[:, None] + np.arange(CHUNK)[None, :])[:, :, None]
    plain = _plain(q, k_wrote[1, slots, :window], v_wrote[1, slots, :window],
                   seen)
    return got, apart, plain


CASES = {
    # every slot's ring partly filled: rows past ``valid`` are not seen
    "step": lambda *row: _step(*row, pos=[0, 5, RING - 1]),
    # rings that have wrapped: every row seen but the cursor's old one
    "step-wrapped": lambda *row: _step(*row, pos=[RING, RING + 5,
                                                  3 * RING - 1]),
    # a prompt's first chunk: nothing of what the slot held is seen
    "chunk-at-0": lambda *row: _chunk(*row, start=[0, 0]),
    # later chunks: the slot's rows < start beside the chunk's own
    "chunk-later": lambda *row: _chunk(*row, start=[2 * CHUNK, CHUNK]),
}


@pytest.mark.parametrize("case", list(CASES))
@pytest.mark.parametrize("row", list(ROWS))
def test_merged_rows_give_the_heads_apart_softmax_over_the_same_keys(row,
                                                                     case):
    h, g, hd = ROWS[row]
    got, apart, plain = CASES[case](h, g, hd)
    assert got.shape == apart.shape == plain.shape
    assert got.dtype == apart.dtype == jnp.float32
    np.testing.assert_allclose(np.asarray(got), np.asarray(apart),
                               rtol=2e-5, atol=2e-6)
    np.testing.assert_allclose(np.asarray(got), plain, rtol=2e-5, atol=2e-6)


@pytest.mark.parametrize("row", [r for r in ROWS if ROWS[r][0] != ROWS[r][1]])
def test_grouped_queries_read_a_merged_row_as_it_lies(row):
    """No operand of either product is a re-laid or widened copy of the
    window: in the traced step the ring goes into both ``dot_general``s as
    it was handed in, and its float32 form only into the second."""
    h, g, hd = ROWS[row]
    w = ops.merged_row_width(g, hd)
    assert w == g * hd, "a grouped row is stored without a pad"
    ring = jnp.zeros((SLOTS, RING, w), jnp.bfloat16)
    new = jnp.zeros((SLOTS, w), jnp.bfloat16)
    text = str(jax.make_jaxpr(lambda q, k, v: ops.cached_decode_attention(
        q, k, v, new, new, jnp.zeros(SLOTS, jnp.int32),
        jnp.ones(SLOTS, jnp.int32), jnp.bfloat16))(
            jnp.zeros((SLOTS, h, hd), jnp.bfloat16), ring, ring))
    assert "transpose" not in text and "gather" not in text
    assert text.count("dot_general") == 3  # scores, the new row's, the sums


def test_a_row_that_is_no_whole_number_of_kv_heads_is_refused():
    with pytest.raises(ValueError, match="no whole number of K/V heads"):
        ops.cached_decode_attention(
            jnp.zeros((1, 6, 48)), jnp.zeros((1, 4, 256)),
            jnp.zeros((1, 4, 256)), jnp.zeros((1, 256)), jnp.zeros((1, 256)),
            jnp.zeros(1, jnp.int32), jnp.ones(1, jnp.int32), jnp.float32)
