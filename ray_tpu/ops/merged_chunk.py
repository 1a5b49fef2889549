"""Pallas TPU kernel: a chunk of prompt queries over ONE slot's ring of
merged K/V rows, read only as far as ``start``, with a block's scores and
probabilities in VMEM only.

``ops/attention.merged_chunk_attention``'s XLA arm cuts the first ``window
- C`` rows of the slot's ring out of both stacks (a copy), scores ALL of
them for every query whatever ``start`` is, and sends float32 scores
``[heads, C, window]`` to memory and back between the product and the
softmax (537 MB a full layer at Qwen3-Next's widths): a chunk's attention
costs the same at the first chunk of a prompt as at its last (PERF.md
section 6, PR 64). Here the grid is ``(K/V head, key block)``, the blocks
sequential (``ops/sparse_chunk.py``'s scheme without its bias: every query
of a chunk sees the same ring rows): a grid step holds the K/V head's query
heads' queries and their running softmax (the flash kernel's scheme,
``ops/flash_attention.py``) in VMEM, the pipeline brings the next block of
the ring's keys and values (that K/V head's ``hd`` columns of a row) while
this one is computed, and each query head's float32 scores of the block
meet the running maximum, sum and sums and are gone.

Block 0 of the grid is the chunk's own rows, which are not in the ring yet
(the ring is read as it was), under the causal triangle; block b the ring's
rows ``(b - 1) * block ..``. A ring block that lies wholly at or past
``start`` holds no key the chunk may see: its grid step does nothing and
its index stands still, so nothing is fetched for it and the work follows
the keys in sight. In the one block that straddles ``start`` the rows ``>=
start`` are masked; a block wholly before it needs no mask.

The kernel is handed the STACKED caches as they lie and the layer's and
slot's indices (scalars, prefetched): merged rows lie row-minor
(``ops/attention.py``), as a Mosaic call takes its operands, so nothing is
copied out.

The same softmax over the same keys as the XLA arm: operands in the rings'
type into both products, float32 scores, statistics and sums, probabilities
cast to the values' type before their product. Every query sees its own
row, which block 0 holds, so a masked score's probability is ``exp(-1e30 -
m)`` = 0 exactly; a query that saw nothing would end in zeros, not 0 / 0.

On CPU (tests) the kernel runs in Pallas interpret mode.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

_NEG_INF = -1e30
_LANES = 128

# Ring rows a block: the longest of these that the ring's window is whole
# blocks of. As measured on a v5e (PERF.md section 6, PR 64; one layer's
# call at the window's end, blocks of 512 / 256): 0.867 / 0.858 ms at
# Qwen3-Next's 16 heads of 256 over 16,384 rows, 0.703 / 0.778 at
# SmallThinker's 28 heads of 128 over 14,336, 0.530 / 0.577 at K-EXAONE's 64
# heads of 128 over 4,096: what a block costs beyond its products is paid a
# block a head (``ops/latent_chunk.py``). Falcon-H1's 3,840 rows (a window
# of 4,096 less a chunk of 256) are whole blocks of 256 only.
BLOCK_ROWS = (512, 256)

_VMEM_LIMIT_BYTES = 64 * 2 ** 20


def block_rows(old: int) -> int:
    """Rows a block of a ring window of ``old`` rows; 0 where the window is
    not whole blocks of any length the kernel takes."""
    return next((b for b in BLOCK_ROWS if old > 0 and old % b == 0), 0)


def takes_kernel(c: int, hd: int, w: int, old: int) -> bool:
    """(``w``: the columns of a merged row.) Heads of whole 128-lane tiles
    in rows of whole K/V heads, a chunk of whole lane tiles and a ring
    window of whole blocks take the kernel; GPT-2's heads of 64 and the toy
    widths of the tiny presets keep the XLA arm."""
    return hd % _LANES == 0 and w % hd == 0 and c % _LANES == 0 \
        and block_rows(old) > 0


def _kernel(meta_ref, q_ref, k_own_ref, v_own_ref, k_ref, v_ref, o_ref,
            m_scr, l_scr, acc_scr, *, heads, hd, block, scale):
    """Grid (K/V head, key block), the blocks sequential: block 0 is the
    chunk's own rows, block b the ring's rows ``(b - 1) * block ..``. The
    heads' running (max, sum, sums) live in VMEM scratch across the blocks
    and the output is written at the last one. The statistics lie as the
    vector unit has them, a query's in all 128 lanes of its row
    (``ops/latent_chunk.py`` says why)."""
    b = pl.program_id(1)
    start = meta_ref[2]
    c = q_ref.shape[0]
    dtype = k_ref.dtype
    contract_last = (((1,), (1,)), ((), ()))

    def take(k, v, seen):
        """k, v [K, hd] in the rings' type; seen [C, K] bool, or None
        where every query sees every key."""
        n = k.shape[0]
        for h in range(heads):
            scores = jax.lax.dot_general(
                q_ref[:, h * hd:(h + 1) * hd], k, contract_last,
                preferred_element_type=jnp.float32) * scale
            if seen is not None:
                scores = jnp.where(seen, scores, _NEG_INF)
            m_prev = m_scr[h]  # [C, 128], a row's maximum in every lane
            m_new = jnp.maximum(m_prev,
                                jnp.max(scores, axis=-1, keepdims=True))
            alpha = jnp.exp(m_prev - m_new)
            m_scr[h] = m_new
            # a lane tile of keys at a time against the replicated maximum
            p = [jnp.exp(scores[:, j:j + _LANES] - m_new)
                 for j in range(0, n, _LANES)]
            l_scr[h] = l_scr[h] * alpha + sum(p[1:], p[0])
            acc_scr[h] = acc_scr[h] * jnp.tile(
                alpha, (1, hd // _LANES)) + jnp.dot(
                jnp.concatenate([x.astype(dtype) for x in p], axis=1), v,
                preferred_element_type=jnp.float32)

    def iota(shape, axis):
        return jax.lax.broadcasted_iota(jnp.int32, shape, axis)

    @pl.when(b == 0)
    def _own():
        m_scr[...] = jnp.full_like(m_scr, _NEG_INF)
        l_scr[...] = jnp.zeros_like(l_scr)
        acc_scr[...] = jnp.zeros_like(acc_scr)
        # the causal triangle: query i sees the chunk's rows <= i
        take(k_own_ref[...], v_own_ref[...],
             iota((c, c), 1) <= iota((c, c), 0))

    @pl.when((b > 0) & (b * block <= start))
    def _ring():  # wholly before ``start``
        take(k_ref[...], v_ref[...], None)

    @pl.when(((b - 1) * block < start) & (start < b * block))
    def _edge():  # straddles ``start``: rows >= start are not seen
        take(k_ref[...], v_ref[...],
             (b - 1) * block + iota((c, block), 1) < start)

    @pl.when(b == pl.num_programs(1) - 1)
    def _out():
        for h in range(heads):
            # (a query that saw nothing: zeros, not 0 / 0)
            o_ref[:, h * hd:(h + 1) * hd] = (acc_scr[h] / jnp.maximum(
                jnp.sum(l_scr[h], axis=-1, keepdims=True), 1e-30)
            ).astype(o_ref.dtype)


# jitted so that a program's layers (and rows) share ONE traced and lowered
# copy of the kernel (``ops/latent_chunk.py`` says what a lowering costs)
@functools.partial(jax.jit, static_argnames=("old",))
def merged_chunk_attention(q: jax.Array, k_all: jax.Array, v_all: jax.Array,
                           k_own: jax.Array, v_own: jax.Array, layer, slot,
                           start, old: int) -> jax.Array:
    """q [C, H, hd], the queries of positions ``start + i``; k_all / v_all
    the stacked caches [N, S, L, W] of merged rows as they were before this
    chunk (read only), ``layer`` and ``slot`` which ring (int32 scalars);
    k_own / v_own [C, W] the chunk's own merged rows in the rings' type;
    start int32 scalar: of the ring's first ``old`` rows (whole blocks,
    ``block_rows``) those ``< start`` are seen by every query and the
    others are not read; query i sees the chunk's own rows ``<= i``.
    -> [C, H, hd] in q's type."""
    c, h, hd = q.shape
    groups = k_all.shape[-1] // hd
    heads = h // groups
    block = block_rows(old)

    def ring_block(b, meta):
        # a block with no row before ``start`` is not fetched: the index
        # stands at the last that has one
        last = jnp.maximum(meta[2] - 1, 0) // block
        return jnp.minimum(jnp.maximum(b - 1, 0), last)

    def group(g, b, meta):
        return (0, g)

    # K/V head g's keys (or values): column block g of a row
    ring = pl.BlockSpec(
        (None, None, block, hd),
        lambda g, b, meta: (meta[0], meta[1], ring_block(b, meta), g))
    own = pl.BlockSpec((c, hd), group)

    out = pl.pallas_call(
        functools.partial(_kernel, heads=heads, hd=hd, block=block,
                          scale=hd ** -0.5),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1,
            grid=(groups, old // block + 1),
            in_specs=[pl.BlockSpec((c, heads * hd), group), own, own,
                      ring, ring],
            out_specs=pl.BlockSpec((c, heads * hd), group),
            scratch_shapes=[
                pltpu.VMEM((heads, c, _LANES), jnp.float32),
                pltpu.VMEM((heads, c, _LANES), jnp.float32),
                pltpu.VMEM((heads, c, hd), jnp.float32),
            ]),
        out_shape=jax.ShapeDtypeStruct((c, h * hd), q.dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary"),
            vmem_limit_bytes=_VMEM_LIMIT_BYTES),
        interpret=jax.default_backend() == "cpu",
        name="merged_chunk_attention",
    )(jnp.stack([jnp.asarray(x, jnp.int32).reshape(())
                 for x in (layer, slot, start)]),
      q.reshape(c, h * hd).astype(k_all.dtype), k_own, v_own, k_all, v_all)
    return out.reshape(c, h, hd)
