"""Pallas TPU kernel: a chunk of prompt queries over ONE slot's ring of
latent rows, in the decompressed form, with a block's keys, values, scores
and probabilities in VMEM only.

``latent_chunk_attention``'s XLA arm (``ops/attention.py``) is a ``fori_loop``
over blocks of ring rows whose body decompresses the block into ``k`` and
``v`` [block, H, 128], writes them out, reads them back for each group of
queries, and sends that group's float32 scores [H, queries, block] to memory
and back between the products and the softmax: at 128 heads those round
trips, not the products, are what a block costs. Here the grid is ``(head
group,)``, and a grid step holds its heads' queries, their two slices of
``w_uk`` / ``w_uv`` and their running softmax in VMEM while it walks the
ring's blocks in a loop of its own: a block's rows (its latents and rotary
keys, 590 KB at the published widths) are copied in by hand, one block ahead
of the one being computed; ``k = c w_uk`` and ``v = c w_uv`` are made for
the group's heads and rounded to the cache's type; each head's float32
scores ``(q_nope . k + q_pe . k_pe) * scale`` meet the running maximum, sum
and sums (the flash kernel's scheme, ``ops/flash_attention.py``) and are
gone. The loop ends at the last block that holds a key the chunk may see,
``(start + C - 1) // block`` (``start`` is prefetched as a scalar), so the
work follows the keys in sight with no grid step that does nothing; the
blocks that lie wholly before ``start`` skip the mask, which is all true
there.

The kernel is handed ONE slot's ring of ONE layer, ``[L, rank + rope]``
row-major: the stacked cache lies ring-rows-minor and a Mosaic call takes its
operands row-major, so the caller slices the slot out (one copy of 19.5 MB a
layer at the published widths, 0.23 ms with its pad to whole lane tiles)
rather than have the whole cache re-laid.

The same softmax over the same keys in the same arithmetic as the XLA arm
(``_online_softmax``): operands in the cache's type into every product,
float32 scores, statistics and sums, probabilities cast to the values' type
before the value product. Every query sees row 0, which the first block
holds, so a masked score's probability is ``exp(-1e30 - m)`` = 0 exactly and
needs no second mask.

On CPU (tests) the kernel runs in Pallas interpret mode.
"""

from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

_NEG_INF = -1e30
_LANES = 128

# Ring rows a block and heads a grid step, as measured on a v5e at 128 heads
# over a chunk of 512 queries and rings of 16896 rows (PERF.md section 6,
# PR 59; one layer's call over 4096 / 16384 keys, the slot's copy included):
# 2.31 / 7.80 ms at 512 rows, 4.32 / 15.94 at 256 with the statistics in
# columns (2.94 / 10.35 at 512), 2.60 / 8.19 at 768, 3.28 / 8.85 at 1536;
# 4, 8 and 16 heads a step 2.33 / 2.31 / 2.55. What a block costs beyond its
# products is paid a block a head (rescaling the sums, the maximum across
# lanes), so longer blocks win until the last block's dead rows outweigh it:
# a chunk's start is a multiple of the chunk, and 512 divides both.
BLOCK_ROWS = 512
HEAD_GROUP = 8

# A group of 8 heads over 512 queries holds 10 MB of statistics and sums,
# 5 MB of queries and weight slices twice (the pipeline's two buffers) and
# some MB of one block's keys, values and scores (a v5e core has 128 MiB).
_VMEM_LIMIT_BYTES = 100 * 2 ** 20


def takes_kernel(c: int, rank: int, nope: int, v: int, n_rows: int) -> bool:
    """Latents, keys and values of whole 128-lane tiles, a ring of whole
    blocks and a chunk of whole sublane tiles take the kernel; the toy
    widths of the tiny presets keep the XLA arm."""
    return rank % 128 == 0 and nope % 128 == 0 and v % 128 == 0 \
        and n_rows % BLOCK_ROWS == 0 and c % 8 == 0


def _kernel(start_ref, q_nope_ref, q_pe_ref, w_uk_ref, w_uv_ref, rows_hbm,
            o_ref, rows_scr, sem, m_scr, l_scr, acc_scr, *, scale, heads,
            rank, block):
    """Grid (head group,): the group's running (max, sum, sums) live in
    VMEM scratch across the ring's blocks, which a loop walks with the next
    block's copy in flight, and the output is written after the last.

    The statistics lie as the vector unit has them, a query's in all 128
    lanes of its row: the maximum replicated (so that rescaling the sums is
    a plain product), the sum of probabilities a PARTIAL sum a lane, added
    up across the lanes once, at the end; a [C, 1] column would cost a
    reduction across lanes and a broadcast a block a head more (a third of
    the kernel's time at 512 rows a block: PERF.md section 6, PR 59)."""
    c = q_nope_ref.shape[0]
    nope = q_nope_ref.shape[1] // heads
    rope = q_pe_ref.shape[1] // heads
    v_dim = w_uv_ref.shape[1] // heads
    dtype = rows_scr.dtype
    start = start_ref[0]
    n_blocks = jnp.minimum((start + c - 1) // block + 1,
                           rows_hbm.shape[0] // block)
    # the blocks every query sees whole
    n_whole = jnp.minimum((start + 1) // block, n_blocks)
    contract_last = (((1,), (1,)), ((), ()))

    def copy(b):
        return pltpu.make_async_copy(
            rows_hbm.at[pl.ds(b * block, block)], rows_scr.at[b % 2],
            sem.at[b % 2])

    m_scr[...] = jnp.full_like(m_scr, _NEG_INF)
    l_scr[...] = jnp.zeros_like(l_scr)
    acc_scr[...] = jnp.zeros_like(acc_scr)
    copy(0).start()

    def take_block(b, masked):
        @pl.when(b + 1 < n_blocks)
        def _ahead():
            copy(b + 1).start()

        copy(b).wait()
        rows = rows_scr[b % 2]
        latents, k_pe = rows[:, :rank], rows[:, rank:rank + rope]
        k = jnp.dot(latents, w_uk_ref[...],
                    preferred_element_type=jnp.float32).astype(dtype)
        v = jnp.dot(latents, w_uv_ref[...],
                    preferred_element_type=jnp.float32).astype(dtype)
        if masked:
            seen = b * block + jax.lax.broadcasted_iota(
                jnp.int32, (c, block), 1) <= start \
                + jax.lax.broadcasted_iota(jnp.int32, (c, block), 0)
        for h in range(heads):
            scores = (jax.lax.dot_general(
                q_nope_ref[:, h * nope:(h + 1) * nope],
                k[:, h * nope:(h + 1) * nope], contract_last,
                preferred_element_type=jnp.float32)
                + jax.lax.dot_general(
                    q_pe_ref[:, h * rope:(h + 1) * rope], k_pe,
                    contract_last, preferred_element_type=jnp.float32)
                ) * scale  # [C, block]
            if masked:
                scores = jnp.where(seen, scores, _NEG_INF)
            m_prev = m_scr[h]  # [C, 128], a row's maximum in every lane
            m_new = jnp.maximum(m_prev,
                                jnp.max(scores, axis=-1, keepdims=True))
            alpha = jnp.exp(m_prev - m_new)
            m_scr[h] = m_new
            # a lane tile of keys at a time against the replicated maximum
            p = [jnp.exp(scores[:, j:j + _LANES] - m_new)
                 for j in range(0, block, _LANES)]
            l_scr[h] = l_scr[h] * alpha + sum(p[1:], p[0])
            acc_scr[h] = acc_scr[h] * jnp.tile(
                alpha, (1, v_dim // _LANES)) + jnp.dot(
                jnp.concatenate([x.astype(dtype) for x in p], axis=1),
                v[:, h * v_dim:(h + 1) * v_dim],
                preferred_element_type=jnp.float32)

    def whole(b, carry):
        take_block(b, masked=False)
        return carry

    def edge(b, carry):
        take_block(b, masked=True)
        return carry

    jax.lax.fori_loop(0, n_whole, whole, None)
    jax.lax.fori_loop(n_whole, n_blocks, edge, None)
    for h in range(heads):
        o_ref[:, h * v_dim:(h + 1) * v_dim] = (
            acc_scr[h] / jnp.sum(l_scr[h], axis=-1, keepdims=True)
        ).astype(o_ref.dtype)


# jitted so that a program's layers (and rows) share ONE traced and lowered
# copy of the kernel: lowering the body to Mosaic costs the host about 0.7 s
# a call, in every process, whatever the compile cache holds
@functools.partial(jax.jit, static_argnames=("scale",))
def latent_chunk_attention(q_nope: jax.Array, q_pe: jax.Array,
                           rows: jax.Array, start: jax.Array,
                           w_uk: jax.Array, w_uv: jax.Array,
                           scale: float) -> jax.Array:
    """q_nope [C, H, nope] and q_pe [C, H, rope], the queries of positions
    ``start + i``, in the rows' type; rows [L, rank + rope] one slot's ring
    of one layer, the chunk's own rows written; start int32 scalar; w_uk
    [rank, H, nope] and w_uv [rank, H, v] in the rows' type. Query i sees
    the rows ``<= start + i``. -> [C, H, v] in q_nope's type."""
    c, h, nope = q_nope.shape
    rope = q_pe.shape[-1]
    rank, _, v_dim = w_uv.shape
    heads = math.gcd(h, HEAD_GROUP)
    # whole lane tiles for the blocks' copies (576 columns lie in 640): the
    # pad rides behind the copy that slices the ring out
    rows = jnp.pad(rows, ((0, 0), (0, -rows.shape[1] % _LANES)))

    def group(g, *_):
        return (0, g)

    out = pl.pallas_call(
        functools.partial(_kernel, scale=scale, heads=heads, rank=rank,
                          block=BLOCK_ROWS),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1,
            grid=(h // heads,),
            in_specs=[
                pl.BlockSpec((c, heads * nope), group),
                pl.BlockSpec((c, heads * rope), group),
                pl.BlockSpec((rank, heads * nope), group),
                pl.BlockSpec((rank, heads * v_dim), group),
                pl.BlockSpec(memory_space=pl.ANY),
            ],
            out_specs=pl.BlockSpec((c, heads * v_dim), group),
            scratch_shapes=[
                pltpu.VMEM((2, BLOCK_ROWS, rows.shape[1]), rows.dtype),
                pltpu.SemaphoreType.DMA((2,)),
                pltpu.VMEM((heads, c, _LANES), jnp.float32),
                pltpu.VMEM((heads, c, _LANES), jnp.float32),
                pltpu.VMEM((heads, c, v_dim), jnp.float32),
            ]),
        out_shape=jax.ShapeDtypeStruct((c, h * v_dim), q_nope.dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",),
            vmem_limit_bytes=_VMEM_LIMIT_BYTES),
        interpret=jax.default_backend() == "cpu",
        name="latent_chunk_attention",
    )(jnp.asarray(start, jnp.int32).reshape(1),
      q_nope.reshape(c, h * nope), q_pe.reshape(c, h * rope),
      w_uk.reshape(rank, h * nope), w_uv.reshape(rank, h * v_dim), rows)
    return out.reshape(c, h, v_dim)
