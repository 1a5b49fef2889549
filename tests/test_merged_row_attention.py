"""The two attentions over a cache of MERGED rows (``ops/attention.py``:
``cached_decode_attention``'s rank-3 arm and ``merged_chunk_attention``)
against the heads-apart forms they replace, at every kind of row a family
stores (PR 44): as many K/V heads as query heads (GPT-2: a toy row under a
lane tile, and XL's 25 heads of 64 padded 1600 -> 1664) and GROUPED queries
(Falcon-H1's 20 query heads over 4 K/V heads of 128, a row of four whole
lane tiles with a lane group a K/V head; its tiny preset's 4 over 2 of 16, a
row under one tile). Float32 throughout, on the CPU: what is held is the
softmax over the same keys, not a chip's rounding.

Since PR 48 a family hands ``cached_decode_attention`` its STACKED merged
cache and a layer's index, and rings of whole blocks of rows of whole lane
tiles go through the kernel of ``ops/ring_decode.py`` (interpret mode here),
which stops each slot at its last live block. It is held to the XLA arm over
the same layer cut out of the stack, at the three rows the serving cells
store (GPT-2 XL's, Falcon-H1's, Qwen3-Next's 16 query heads over 2 K/V heads
of 256), at contexts on every side of a block's edge, side by side in one
call.

Since PR 51 a window layer's prompt chunk reads a ring that may have WRAPPED
by position (``wrapped_chunk_attention``): held to the plain masked softmax
over the prompt's own keys for a start of 0, a chunk short of the window, at
the window and several windows on; ``ring_positions`` and the ring's own
write (``cache_write_ring_chunk``) beside it.
"""

import functools

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from ray_tpu.ops import attention as ops
from ray_tpu.ops import ring_decode

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


# -- the kernel: the stacked cache and a layer's index --------------------------

KERNEL_ROWS = {"gpt2-xl-25:25:64": (25, 25, 64),
               "falcon-h1-20:4:128": (20, 4, 128),
               "qwen3-next-16:2:256": (16, 2, 256)}
BLOCK = ring_decode.BLOCK_ROWS
LONG_RING = 2 * BLOCK
# a slot each, side by side in ONE call: position of the new token
CONTEXTS = {
    "context-of-1-a-free-slot": 0,
    "one-short-of-a-blocks-edge": BLOCK - 2,
    "on-a-blocks-edge": BLOCK - 1,
    "one-past-a-blocks-edge": BLOCK,
    "a-full-ring": LONG_RING - 1,
    "a-wrapped-ring": LONG_RING + 5,
    "wrapped-many-times-cursor-at-the-end": 5 * LONG_RING - 1,
}


@functools.lru_cache(maxsize=None)
def _kernel_step(row, dtype, scale=None):
    """One step over a layer of a two-layer stack, every context of
    CONTEXTS a slot: (the kernel's, the XLA arm's over the layer cut out,
    the heads-apart form's), each [S, H, hd] float32, and cursor, valid."""
    h, g, hd = KERNEL_ROWS[row]
    dtype = jnp.dtype(dtype)
    w = ops.merged_row_width(g, hd)
    slots = len(CONTEXTS)
    q = _normal(11, slots, h, hd).astype(dtype)
    k = _normal(12, 2, slots, LONG_RING, g, hd).astype(dtype)
    v = _normal(13, 2, slots, LONG_RING, g, hd).astype(dtype)
    k_new = _normal(14, slots, g, hd).astype(dtype)
    v_new = _normal(15, slots, g, hd).astype(dtype)
    pos = jnp.asarray(list(CONTEXTS.values()), jnp.int32)
    cursor, valid = pos % LONG_RING, jnp.minimum(pos + 1, LONG_RING)
    at = jnp.arange(slots)
    # what the new token must not be mistaken for stands at its row
    k, v = k.at[:, at, cursor].set(50.0), v.at[:, at, cursor].set(-50.0)
    merged = [_merged(a, w) for a in (k, v, k_new, v_new)]
    assert ring_decode.takes_kernel(LONG_RING, w)
    got = ops.cached_decode_attention(
        q, merged[0], merged[1], merged[2], merged[3], cursor, valid,
        jnp.float32, scale, layer=1)
    xla = ops.cached_decode_attention(
        q, merged[0][1], merged[1][1], merged[2], merged[3], cursor, valid,
        jnp.float32, scale)
    apart = ops.cached_decode_attention(q, k[1], v[1], k_new, v_new, cursor,
                                        valid, jnp.float32, scale)
    return tuple(np.asarray(a) for a in (got, xla, apart, cursor, valid))


@pytest.mark.parametrize("context", list(CONTEXTS))
@pytest.mark.parametrize("row", list(KERNEL_ROWS))
def test_the_kernel_gives_the_xla_arms_softmax_to_rounding(row, context):
    """float32: the same softmax over the same keys, whichever side of a
    block's edge the context ends, whatever the neighbours' contexts."""
    got, xla, apart, cursor, valid = _kernel_step(row, "float32")
    i = list(CONTEXTS).index(context)
    assert valid[i] == min(CONTEXTS[context] + 1, LONG_RING)
    assert got.shape == xla.shape and got.dtype == np.float32
    np.testing.assert_allclose(got[i], xla[i], rtol=2e-5, atol=2e-6)
    np.testing.assert_allclose(got[i], apart[i], rtol=2e-5, atol=2e-6)


@pytest.mark.parametrize("context", list(CONTEXTS))
@pytest.mark.parametrize("row", list(KERNEL_ROWS))
def test_the_kernel_in_bfloat16_is_as_near_the_heads_apart_form(row, context):
    """bfloat16 operands, float32 scores and sums: the kernel lies within
    the XLA arm's own distance from the heads-apart form (which computes
    in float32 from the same bfloat16 numbers)."""
    got, xla, apart, _, _ = _kernel_step(row, "bfloat16")
    i = list(CONTEXTS).index(context)
    own = np.abs(xla[i] - apart[i]).max()
    assert np.abs(got[i] - apart[i]).max() <= 2 * own + 1e-5
    assert np.abs(apart[i]).max() > 0.1  # not a comparison of zeros


@pytest.mark.parametrize("scale", [None, 0.0625, 0.2])
def test_the_kernel_takes_a_models_own_scale(scale):
    got, xla, apart, _, _ = _kernel_step("falcon-h1-20:4:128", "float32",
                                         scale)
    np.testing.assert_allclose(got, xla, rtol=2e-5, atol=2e-6)
    if scale is not None:
        plain = _kernel_step("falcon-h1-20:4:128", "float32")[0]
        assert np.abs(got - plain).max() > 1e-3  # the scale was applied


@pytest.mark.parametrize("ring,row,kernel", [
    (LONG_RING, (25, 25, 64), True), (3 * BLOCK, (16, 2, 256), True),
    (LONG_RING + 8, (20, 4, 128), False),  # no whole number of blocks
    (LONG_RING, (4, 4, 16), False),        # a toy row inside one lane tile
])
def test_ring_rows_read_is_whole_blocks_up_to_each_slots_last(ring, row,
                                                              kernel):
    """The counter a decode step returns: where the kernel runs,
    ``ceil(valid / block) * block`` rows a slot a layer; where XLA reads
    the ring whole (a toy row, a ring of no whole blocks), every row."""
    h, g, hd = row
    w = ops.merged_row_width(g, hd)
    assert ring_decode.takes_kernel(ring, w) is kernel
    valid = jnp.asarray([1, BLOCK - 1, BLOCK, BLOCK + 1, ring], jnp.int32)
    counted = ops.ring_rows_counted(jnp.zeros((3, 5, ring, w), jnp.bfloat16),
                                    valid)
    assert {k: v.dtype for k, v in counted.items()} == {
        "ring_rows_read": jnp.int32, "ring_rows_held": jnp.int32}
    assert int(counted["ring_rows_held"]) == 3 * 5 * ring
    want = 3 * sum(-(-int(n) // BLOCK) * BLOCK for n in valid) if kernel \
        else 3 * 5 * ring
    assert int(counted["ring_rows_read"]) == want


def test_a_ring_the_kernel_does_not_take_is_cut_out_of_the_stack():
    """A stacked toy cache and a layer's index give what the layer's own
    slice gives: the XLA arm, no kernel in the traced step."""
    h, g, hd = ROWS["gpt2-toy-4:4:16"]
    w = ops.merged_row_width(g, hd)
    k, v = _normal(16, LAYERS, SLOTS, RING, w), _normal(17, LAYERS, SLOTS,
                                                        RING, w)
    q, k_new, v_new = _normal(18, SLOTS, h, hd), _normal(19, SLOTS, w), \
        _normal(20, SLOTS, w)
    cursor = jnp.asarray([0, 5, RING - 1], jnp.int32)
    step = lambda k, v, layer: ops.cached_decode_attention(
        q, k, v, k_new, v_new, cursor, cursor + 1, jnp.float32, layer=layer)
    np.testing.assert_array_equal(
        np.asarray(step(k, v, 1)),
        np.asarray(ops.cached_decode_attention(
            q, k[1], v[1], k_new, v_new, cursor, cursor + 1, jnp.float32)))
    assert "pallas_call" not in str(jax.make_jaxpr(step)(k, v, 1))
    ring = jnp.zeros((LAYERS, SLOTS, LONG_RING, 128), jnp.float32)
    new = jnp.zeros((SLOTS, 128), jnp.float32)
    assert str(jax.make_jaxpr(lambda k, layer: ops.cached_decode_attention(
        jnp.zeros((SLOTS, 2, 64)), k, k, new, new, cursor, cursor + 1,
        jnp.float32, layer=layer))(ring, 1)).count("pallas_call") == 1


# -- a chunk over a ring that has wrapped ---------------------------------------

WINDOW = 3 * CHUNK          # a window layer's ring IS its window
WRAPPED_ROWS = {**{r: ROWS[r] for r in ROWS if ROWS[r][0] != ROWS[r][1]},
                "smallthinker-28:4:128": (28, 4, 128),
                "gpt2-toy-4:4:16": ROWS["gpt2-toy-4:4:16"]}
# where the chunk starts, a row of the call each (any whole number of chunks)
STARTS = {"at-0-a-recycled-slot": 0, "a-chunk-short-of-the-window":
          WINDOW - CHUNK, "at-the-window": WINDOW, "a-chunk-past-it":
          WINDOW + CHUNK, "several-windows-on": 5 * WINDOW + 2 * CHUNK}


@functools.lru_cache(maxsize=None)
def _wrapped_chunk(row, group=ops.CHUNK_QUERIES):
    """Every start of STARTS a row of ONE call (its queries in groups of
    ``group``, where that divides the chunk), each over a slot of its own
    whose ring was filled as a prompt fills it (position p at row ``p mod
    WINDOW``, what it held before, another tenant's noise, left where the
    prompt has not written): (the op's, the plain masked softmax over the
    prompt's own keys at their positions)."""
    h, g, hd = WRAPPED_ROWS[row]
    w = ops.merged_row_width(g, hd)
    starts = np.asarray(list(STARTS.values()), np.int32)
    n, longest = len(starts), int(starts.max()) + CHUNK
    slots = jnp.asarray(np.arange(n)[::-1].copy(), jnp.int32)
    q = _normal(21, n, CHUNK, h, hd)
    keys = _normal(22, n, longest, g, hd)   # a prompt a row, every position
    values = _normal(23, n, longest, g, hd)
    k_all = np.array(50.0 + _normal(24, LAYERS, n, WINDOW, g, hd))
    v_all = np.array(-50.0 + _normal(25, LAYERS, n, WINDOW, g, hd))
    for i, start in enumerate(starts):
        for p in range(start):              # later positions overwrite
            k_all[1, int(slots[i]), p % WINDOW] = keys[i, p]
            v_all[1, int(slots[i]), p % WINDOW] = values[i, p]
    at = starts[:, None] + np.arange(CHUNK)[None, :]
    k_own = jnp.stack([keys[i, at[i]] for i in range(n)])
    v_own = jnp.stack([values[i, at[i]] for i in range(n)])
    with pytest.MonkeyPatch.context() as patch:
        patch.setattr(ops, "CHUNK_QUERIES", group)
        got = ops.wrapped_chunk_attention(
            q, _merged(jnp.asarray(k_all), w), _merged(jnp.asarray(v_all), w),
            _merged(k_own, w), _merged(v_own, w), 1, slots,
            jnp.asarray(starts))
    pos = np.arange(longest)[None, None, :]
    seen = (pos <= at[:, :, None]) & (pos > at[:, :, None] - WINDOW)
    return np.asarray(got), _plain(q, keys, values, seen)


@pytest.mark.parametrize("start", list(STARTS))
@pytest.mark.parametrize("row", list(WRAPPED_ROWS))
def test_a_chunk_over_a_wrapped_ring_sees_the_window_by_position(row, start):
    """``wrapped_chunk_attention`` against the plain softmax over the keys
    ``start + i - WINDOW < p <= start + i`` of the prompt itself: the ring
    read as it lies before the first wrap, at it and windows on, in ONE
    call; a recycled slot's rows, which no position of this prompt names,
    are not seen."""
    got, plain = _wrapped_chunk(row)
    i = list(STARTS).index(start)
    assert got.shape == plain.shape and got.dtype == np.float32
    np.testing.assert_allclose(got[i], plain[i], rtol=2e-5, atol=2e-6)


@pytest.mark.parametrize("row", list(WRAPPED_ROWS))
def test_a_long_chunks_queries_meet_the_ring_a_group_at_a_time(row):
    """A chunk longer than ``CHUNK_QUERIES`` (the expert families'
    512 tokens; here 4 queries in groups of 2) scores a group of its
    queries at a time, each over the ring and the chunk's rows up to the
    group's last: the same softmax at every start."""
    assert CHUNK % 2 == 0 and CHUNK % ops.CHUNK_QUERIES
    got, plain = _wrapped_chunk(row, group=2)
    np.testing.assert_allclose(got, plain, rtol=2e-5, atol=2e-6)
    np.testing.assert_allclose(got, _wrapped_chunk(row)[0], rtol=2e-5,
                               atol=2e-6)


def test_ring_positions_names_each_rows_position_or_none():
    got = np.asarray(ops.ring_positions(jnp.asarray([0, 3, 8, 13]), 8))
    assert (got[0] < 0).all()                      # nothing written yet
    assert got[1].tolist()[:3] == [0, 1, 2] and (got[1][3:] < 0).all()
    assert got[2].tolist() == list(range(8))       # exactly full
    assert got[3].tolist() == [8, 9, 10, 11, 12, 5, 6, 7]  # wrapped


@pytest.mark.parametrize("start, real", [(0, 4), (8, 3), (20, 1), (28, 0)])
def test_a_ring_chunk_write_lands_mod_the_ring_and_keeps_its_padded_rows(
        start, real):
    """``cache_write_ring_chunk``: the chunk's real rows at ``start mod L``
    in its slot, the padded rows and everything else as they were."""
    cache = _normal(31, LAYERS, SLOTS, WINDOW, 32)
    rows = _normal(32, LAYERS, 1, CHUNK, 32)
    got = np.asarray(ops.cache_write_ring_chunk(
        cache, rows, jnp.asarray([1]), jnp.asarray([start]),
        jnp.asarray([real])))
    want = np.array(cache)
    at = start % WINDOW
    want[:, 1, at:at + real] = np.asarray(rows)[:, 0, :real]
    np.testing.assert_array_equal(got, want)


def test_a_chunk_that_does_not_divide_the_ring_is_refused():
    w = 32
    with pytest.raises(ValueError, match="straddle the ring's end"):
        ops.wrapped_chunk_attention(
            jnp.zeros((1, 5, 4, 16)), jnp.zeros((1, 1, WINDOW, w)),
            jnp.zeros((1, 1, WINDOW, w)), jnp.zeros((1, 5, w)),
            jnp.zeros((1, 5, w)), 0, jnp.zeros(1, jnp.int32),
            jnp.zeros(1, jnp.int32))


# -- a verify step: a few consecutive query rows a slot (PR 54) ----------------

# (K-EXAONE's 8 : 1 grouping at a quarter of its 64 : 8 heads: the kernel in
# interpret mode is slow, and the compile-only file holds the real widths)
VERIFY_ROWS = {"k-exaone-16:2:128": (16, 2, 128), "many-heads-8:8:32": (8, 8, 32),
               "falcon-h1-tiny-4:2:16": ROWS["falcon-h1-tiny-4:2:16"]}
# position of a slot's FIRST new row, a slot each, in rings of LONG_RING
VERIFY_AT = {
    "a-free-slot": 0, "inside-the-first-block": 7,
    "the-pair-ends-a-block": BLOCK - 2, "the-pair-straddles-two-blocks":
    BLOCK - 1, "the-pair-fills-the-ring": LONG_RING - 2,
    "the-second-row-wraps-to-row-0": LONG_RING - 1,
    "a-wrapped-ring": LONG_RING + 5,
    "wrapped-many-times-the-second-row-wraps": 5 * LONG_RING - 1}


@functools.lru_cache(maxsize=None)
def _verify_step(row, n_rows, rows=2, dtype="float32"):
    """``rows`` consecutive query rows a slot over a layer of a two-layer
    stack of rings of ``n_rows`` filled as a context fills them (position p
    at row ``p mod n_rows``): (the op's result [S, R, H, hd], the plain
    softmax of query row i at ``pos + i`` over the positions ``pos + i -
    n_rows < p <= pos + i``, the new rows among them). What the ring holds
    at the places the new rows will take is the oldest keys of the FIRST
    row's window where the ring has wrapped, and noise where it has not."""
    h, g, hd = VERIFY_ROWS[row]
    dtype = jnp.dtype(dtype)
    w = ops.merged_row_width(g, hd)
    pos = np.asarray(list(VERIFY_AT.values()), np.int32)
    slots, longest = len(pos), int(pos.max()) + rows
    q = _normal(41, slots, rows, h, hd).astype(dtype)
    keys = _normal(42, slots, longest, g, hd).astype(dtype)
    values = _normal(43, slots, longest, g, hd).astype(dtype)
    k_all = np.array(50.0 + _normal(44, 2, slots, n_rows, g, hd), np.float32)
    v_all = np.array(-50.0 + _normal(45, 2, slots, n_rows, g, hd),
                     np.float32)
    for s, p in enumerate(pos):
        for at in range(max(0, p - n_rows), p):   # the last n_rows before p
            k_all[1, s, at % n_rows] = keys[s, at]
            v_all[1, s, at % n_rows] = values[s, at]
    new = pos[:, None] + np.arange(rows)[None, :]
    k_new = jnp.stack([keys[s, new[s]] for s in range(slots)])
    v_new = jnp.stack([values[s, new[s]] for s in range(slots)])
    got = ops.cached_verify_attention(
        q, _merged(jnp.asarray(k_all).astype(dtype), w),
        _merged(jnp.asarray(v_all).astype(dtype), w), _merged(k_new, w),
        _merged(v_new, w), jnp.asarray(pos % n_rows),
        jnp.asarray(np.minimum(pos + 1, n_rows)), jnp.float32, layer=1)
    at = np.arange(longest)[None, None, :]
    seen = (at <= new[:, :, None]) & (at > new[:, :, None] - n_rows)
    return np.asarray(got), _plain(q.astype(jnp.float32), keys, values, seen)


@pytest.mark.parametrize("at", list(VERIFY_AT))
@pytest.mark.parametrize("row", list(VERIFY_ROWS))
@pytest.mark.parametrize("arm", ["kernel", "xla"])
def test_a_verify_steps_rows_see_the_ring_and_each_other_causally(arm, row,
                                                                  at):
    """``cached_verify_attention``, both arms (rings of whole blocks of
    whole lane tiles through the kernel of ``ops/ring_decode.py``, its
    rows' heads stacked; any other ring read whole in XLA), against the
    plain softmax by position: the second row sees the first's key and its
    own, the first sees neither the second's key nor loses the key whose
    place the second will take, whichever side of a block's edge or of the
    ring's end the pair falls."""
    h, g, hd = VERIFY_ROWS[row]
    n_rows = LONG_RING if arm == "kernel" else LONG_RING - 8
    w = ops.merged_row_width(g, hd)
    if ring_decode.takes_kernel(n_rows, w) != (arm == "kernel"):
        pytest.skip("a toy row inside one lane tile keeps the XLA arm")
    got, plain = _verify_step(row, n_rows)
    i = list(VERIFY_AT).index(at)
    assert got.shape == plain.shape == (len(VERIFY_AT), 2, h, hd)
    np.testing.assert_allclose(got[i], plain[i], rtol=2e-5, atol=2e-6)


@pytest.mark.parametrize("arm", ["kernel", "xla"])
def test_three_rows_a_slot_and_one(arm):
    """The op is written for any few rows: three go as two do, and one row
    is ``cached_decode_attention`` itself."""
    n_rows = LONG_RING if arm == "kernel" else LONG_RING - 8
    got, plain = _verify_step("many-heads-8:8:32", n_rows, rows=3)
    np.testing.assert_allclose(got, plain, rtol=2e-5, atol=2e-6)
    one, plain = _verify_step("many-heads-8:8:32", n_rows, rows=1)
    np.testing.assert_allclose(one, plain, rtol=2e-5, atol=2e-6)


def test_the_verify_kernel_in_bfloat16_is_as_near_as_the_xla_arm():
    row = "k-exaone-16:2:128"
    got, plain = _verify_step(row, LONG_RING, dtype="bfloat16")
    xla, _ = _verify_step(row, LONG_RING - 8, dtype="bfloat16")
    # (other rings, the same distribution: a bound, not a comparison)
    assert np.abs(got - plain).max() <= 2 * np.abs(xla - _verify_step(
        row, LONG_RING - 8, dtype="bfloat16")[1]).max() + 1e-5
    assert np.abs(plain).max() > 0.1


def test_the_verify_step_calls_the_kernel_once_a_ring_of_whole_blocks():
    ring = jnp.zeros((LAYERS, SLOTS, LONG_RING, 128), jnp.float32)
    new = jnp.zeros((SLOTS, 2, 128), jnp.float32)
    cursor = jnp.zeros(SLOTS, jnp.int32)
    call = lambda k: ops.cached_verify_attention(
        jnp.zeros((SLOTS, 2, 2, 64)), k, k, new, new, cursor, cursor + 1,
        jnp.float32, layer=1)
    assert str(jax.make_jaxpr(call)(ring)).count("pallas_call") == 1
    assert "pallas_call" not in str(jax.make_jaxpr(call)(ring[:, :, :128]))


# -- a chunk of several windows over a ring of one (PR 54) ---------------------


@pytest.mark.parametrize("start", [0, 2 * WINDOW, 10 * WINDOW])
@pytest.mark.parametrize("row", list(WRAPPED_ROWS))
def test_a_chunk_of_two_windows_sees_each_querys_own_window(row, start):
    """``wrapped_chunk_attention`` where the chunk is two rings long: a
    query sees the ring by position (the window before the chunk) and of
    the chunk's own rows those inside ITS window, not every earlier one."""
    h, g, hd = WRAPPED_ROWS[row]
    w = ops.merged_row_width(g, hd)
    c = 2 * WINDOW
    q = _normal(51, 1, c, h, hd)
    keys = _normal(52, 1, start + c, g, hd)
    values = _normal(53, 1, start + c, g, hd)
    k_all = np.array(50.0 + _normal(54, LAYERS, 2, WINDOW, g, hd))
    v_all = np.array(-50.0 + _normal(55, LAYERS, 2, WINDOW, g, hd))
    for p in range(start):
        k_all[1, 1, p % WINDOW] = keys[0, p]
        v_all[1, 1, p % WINDOW] = values[0, p]
    got = ops.wrapped_chunk_attention(
        q, _merged(jnp.asarray(k_all), w), _merged(jnp.asarray(v_all), w),
        _merged(keys[:, start:], w), _merged(values[:, start:], w), 1,
        jnp.asarray([1]), jnp.asarray([start]))
    at = start + np.arange(c)[None, :, None]
    pos = np.arange(start + c)[None, None, :]
    seen = (pos <= at) & (pos > at - WINDOW)
    np.testing.assert_allclose(np.asarray(got), _plain(q, keys, values, seen),
                               rtol=2e-5, atol=2e-6)


@pytest.mark.parametrize("start, real", [
    (0, 2 * WINDOW), (0, WINDOW + 5), (0, 5), (2 * WINDOW, WINDOW - 1),
    (4 * WINDOW, 1), (4 * WINDOW, 0)])
def test_a_long_chunks_write_leaves_the_last_window_of_real_rows(start,
                                                                 real):
    """``cache_write_ring_chunk`` for a chunk of two rings: ring row j ends
    up with the LAST real position that lies there, the chunk's where it
    has one and what the ring held where it has not."""
    cache = _normal(61, LAYERS, SLOTS, WINDOW, 32)
    rows = _normal(62, LAYERS, 1, 2 * WINDOW, 32)
    got = np.asarray(ops.cache_write_ring_chunk(
        cache, rows, jnp.asarray([1]), jnp.asarray([start]),
        jnp.asarray([real])))
    want = np.array(cache)
    for i in range(real):    # in order: later positions overwrite
        want[:, 1, (start + i) % WINDOW] = np.asarray(rows)[:, 0, i]
    np.testing.assert_array_equal(got, want)
