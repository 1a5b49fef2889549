"""Pallas TPU kernel: one query token a slot (or, in a verify step, a few
consecutive ones: ``ring_decode_attention`` says how many query rows it
takes) over a ring of MERGED rows that stops at the slot's own context.

``cached_decode_attention``'s merged arm written out in XLA
(``ops/attention.py``, ``_merged_decode_attention``) reads every ring whole,
``L`` rows a slot a layer, and masks what lies past ``valid``. Here the grid
is ``(slot, row block)`` with each slot's ``valid`` and ``cursor`` and the
layer's index prefetched as scalars: the K and V block specs clamp the block
index at the slot's last live block, so past it the index stands still (at
the next slot's first block, fetched ahead), the pipeline issues no new copy
and the body does nothing. A step reads
``ceil(valid / BLOCK_ROWS)`` blocks a slot a layer, whatever ``L`` is; a
free slot (``valid`` 1) costs one.

The kernel is handed the STACKED cache ``[N, S, L, W]`` and a layer index:
a custom call's operand cannot be a fused slice, so a layer's slice handed
to it would be copied out first, the very traffic it is there to save.

The same softmax over the same keys in the same arithmetic as the XLA arm:
float32 scores from operands in the cache's type, a running maximum and sum
in float32 scratch (the flash kernel's scheme, ``ops/flash_attention.py``),
float32 probabilities into float32 sums. The new token's row is not in the
cache yet: its score opens the running softmax and the cursor's ring row is
masked out of the blocks (a wrapped ring thus drops the row the cursor is
about to overwrite, as the XLA arm does).

On CPU (tests) the kernel runs in Pallas interpret mode.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

_NEG_INF = -1e30

# Ring rows a block. It has to divide every ring that is to take the kernel
# (1024, 5120 and 18432 rows in the three cells that hold merged rings) and
# be well under the shortest of them, or a ring of 1024 rows saves nothing.
BLOCK_ROWS = 256


def takes_kernel(n_rows: int, w: int) -> bool:
    """Rings of whole blocks of rows of whole 128-lane tiles take the
    kernel; a toy row inside one tile (``merged_row_width``'s exception)
    and a ring that is no whole number of blocks keep the XLA arm."""
    return w % 128 == 0 and n_rows % BLOCK_ROWS == 0


def _weighted_sum(p: jax.Array, v: jax.Array) -> jax.Array:
    """float32 probabilities [H, B] into float32 sums over values [B, W] in
    the cache's type: the product at the highest precision. A bfloat16
    value is one bfloat16 piece, so of that product's passes only the three
    pieces of ``p`` are not zero; stacked along the rows they go through
    the MXU as ONE product over the block as it lies (no float32 copy of
    it), and the three partial sums are added in float32."""
    if v.dtype != jnp.bfloat16:
        return jnp.dot(p, v.astype(jnp.float32),
                       precision=jax.lax.Precision.HIGHEST,
                       preferred_element_type=jnp.float32)
    h = p.shape[0]
    hi = p.astype(jnp.bfloat16).astype(jnp.float32)
    rest = p - hi
    mid = rest.astype(jnp.bfloat16).astype(jnp.float32)
    pieces = jnp.concatenate([hi, mid, rest - mid], axis=0)
    sums = jnp.dot(pieces.astype(jnp.bfloat16), v,
                   preferred_element_type=jnp.float32)
    return sums[:h] + sums[h:2 * h] + sums[2 * h:]


def _take_block(scores, v_ref, m_scr, l_scr, acc_scr):
    """One block of masked scores [H, block] into the running (max, sum,
    sums) of the flash kernel's scheme."""
    m_prev = m_scr[...]
    m_new = jnp.maximum(m_prev, jnp.max(scores, axis=-1, keepdims=True))
    p = jnp.exp(scores - m_new)
    alpha = jnp.exp(m_prev - m_new)
    m_scr[...] = m_new
    l_scr[...] = l_scr[...] * alpha + jnp.sum(p, axis=-1, keepdims=True)
    acc_scr[...] = acc_scr[...] * alpha + _weighted_sum(p, v_ref[...])


def _kernel(layer_ref, valid_ref, cursor_ref, q_ref, k_new_ref, v_new_ref,
            k_ref, v_ref, o_ref, m_scr, l_scr, acc_scr, *, scaled):
    """Grid (slot, row block), the blocks sequential: running (max, sum,
    sums) live in VMEM scratch across a slot's blocks and the output is
    written at the last one."""
    del layer_ref  # the index maps' alone
    s, b = pl.program_id(0), pl.program_id(1)
    valid, cursor = valid_ref[s], cursor_ref[s]

    @pl.when(b == 0)
    def _new_token():
        # the token's own key, which stands at the cursor's row: weight 1
        # under a maximum that is its score
        q = q_ref[...].astype(jnp.float32)
        m_scr[...] = scaled(jnp.sum(
            q * k_new_ref[...].astype(jnp.float32), axis=-1, keepdims=True))
        l_scr[...] = jnp.ones_like(l_scr)
        acc_scr[...] = jnp.broadcast_to(
            v_new_ref[...].astype(jnp.float32), acc_scr.shape)

    @pl.when(b * BLOCK_ROWS < valid)
    def _live_block():
        scores = scaled(jax.lax.dot_general(
            q_ref[...], k_ref[...], (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32))  # [H, block]
        row = b * BLOCK_ROWS + jax.lax.broadcasted_iota(
            jnp.int32, scores.shape, 1)
        scores = jnp.where((row < valid) & (row != cursor), scores,
                           _NEG_INF)
        _take_block(scores, v_ref, m_scr, l_scr, acc_scr)

    @pl.when(b == pl.num_programs(1) - 1)
    def _out():
        o_ref[...] = acc_scr[...] / l_scr[...]


def _verify_kernel(layer_ref, valid_ref, cursor_ref, q_ref, k_new_ref,
                   v_new_ref, k_ref, v_ref, o_ref, m_scr, l_scr, acc_scr, *,
                   scaled, rows, n_rows):
    """``_kernel`` for ``rows`` consecutive query rows a slot (their heads
    stacked, row i's at ``i * H'``), query row i at the position of new key
    i: it sees the new keys ``<= i`` (they open its running softmax) and of
    the ring of ``n_rows`` every live row but those the new keys ``<= i``
    will take, ``(cursor + j) mod n_rows`` for ``j <= i``: a LATER row's
    place still holds a key the earlier query may need (in a ring that has
    wrapped, the oldest key of its window)."""
    del layer_ref  # the index maps' alone
    s, b = pl.program_id(0), pl.program_id(1)
    valid, cursor = valid_ref[s], cursor_ref[s]
    per = q_ref.shape[0] // rows  # heads a query row (with their pad)

    @pl.when(b == 0)
    def _new_tokens():
        q = q_ref[...].astype(jnp.float32)
        at = jax.lax.broadcasted_iota(jnp.int32, (q.shape[0], 1), 0) // per
        scores = [jnp.where(at >= j, scaled(jnp.sum(
            q * k_new_ref[j:j + 1, :].astype(jnp.float32), axis=-1,
            keepdims=True)), _NEG_INF) for j in range(rows)]
        top = functools.reduce(jnp.maximum, scores)
        probs = [jnp.exp(x - top) for x in scores]
        m_scr[...] = top
        l_scr[...] = sum(probs)
        acc_scr[...] = sum(
            p * v_new_ref[j:j + 1, :].astype(jnp.float32)
            for j, p in enumerate(probs))

    @pl.when(b * BLOCK_ROWS < valid)
    def _live_block():
        scores = scaled(jax.lax.dot_general(
            q_ref[...], k_ref[...], (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32))  # [rows * H', block]
        row = b * BLOCK_ROWS + jax.lax.broadcasted_iota(
            jnp.int32, scores.shape, 1)
        at = jax.lax.broadcasted_iota(jnp.int32, scores.shape, 0) // per
        seen = row < valid
        for j in range(rows):
            taken = cursor + j
            taken = jnp.where(taken >= n_rows, taken - n_rows, taken)
            seen = seen & ~((row == taken) & (at >= j))
        scores = jnp.where(seen, scores, _NEG_INF)
        _take_block(scores, v_ref, m_scr, l_scr, acc_scr)

    @pl.when(b == pl.num_programs(1) - 1)
    def _out():
        o_ref[...] = acc_scr[...] / l_scr[...]


def ring_decode_attention(q: jax.Array, k_all: jax.Array, v_all: jax.Array,
                          layer, k_new: jax.Array, v_new: jax.Array,
                          cursor: jax.Array, valid: jax.Array,
                          scaled) -> jax.Array:
    """q [S, H, W]: each head's query in the columns of a merged row that
    are its own (``_heads_apart`` / ``_heads_in_group_columns``), in the
    cache's type, H a multiple of 8; k_all / v_all the stacked cache [N, S,
    L, W] as it was before this step, ``layer`` which of its N; k_new /
    v_new [S, W] this token's merged rows; cursor, valid [S] int32;
    ``scaled`` what turns float32 products into scores. -> float32 sums
    [S, H, W] over merged value rows, a row a head: the caller picks each
    head's own columns.

    ONE query row a slot as above, or R consecutive ones (a verify step):
    k_new / v_new [S, R, W], the rows of positions ``p .. p + R - 1``, q
    [S, R * H', W] with query row i's heads at ``i * H'`` (H' a multiple of
    8), ``cursor`` and ``valid`` the FIRST new row's. Query row i sees the
    new keys ``<= i`` and the ring without the rows those will take."""
    s, h, w = q.shape
    n_rows = k_all.shape[2]
    rows = 1 if k_new.ndim == 2 else k_new.shape[1]
    if rows == 1:
        kernel = functools.partial(_kernel, scaled=scaled)
        k_new, v_new = k_new[:, None], v_new[:, None]
    else:
        kernel = functools.partial(_verify_kernel, scaled=scaled, rows=rows,
                                   n_rows=n_rows)

    def ring(i, b, layer_ref, valid_ref, cursor_ref):
        # Past the slot's last live block the index stands still, so the
        # pipeline issues no new copy: at the NEXT slot's first block,
        # which is thus fetched while this slot's last live block is
        # computed and lies ready when that slot begins (standing at the
        # last live block, every slot's first copy would be waited for).
        # The last slot has no next and stands at its own.
        last = (valid_ref[i] - 1) // BLOCK_ROWS
        ahead = (b > last) & (i + 1 < s)
        return (layer_ref[0], jnp.where(ahead, i + 1, i),
                jnp.where(ahead, 0, jnp.minimum(b, last)), 0)

    def slot(i, b, *_):
        return (i, 0, 0)

    return pl.pallas_call(
        kernel,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=3,
            grid=(s, n_rows // BLOCK_ROWS),
            in_specs=[
                pl.BlockSpec((None, h, w), slot),
                pl.BlockSpec((None, rows, w), slot),
                pl.BlockSpec((None, rows, w), slot),
                pl.BlockSpec((None, None, BLOCK_ROWS, w), ring),
                pl.BlockSpec((None, None, BLOCK_ROWS, w), ring),
            ],
            out_specs=pl.BlockSpec((None, h, w), slot),
            scratch_shapes=[
                pltpu.VMEM((h, 1), jnp.float32),
                pltpu.VMEM((h, 1), jnp.float32),
                pltpu.VMEM((h, w), jnp.float32),
            ]),
        out_shape=jax.ShapeDtypeStruct((s, h, w), jnp.float32),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary")),
        interpret=jax.default_backend() == "cpu",
        name="ring_decode_attention",
    )(jnp.asarray(layer, jnp.int32).reshape(1), valid.astype(jnp.int32),
      cursor.astype(jnp.int32), q, k_new, v_new, k_all, v_all)
