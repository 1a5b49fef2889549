"""Pallas TPU kernel: a chunk of prompt queries over ONE slot's ring of
merged K/V rows under a per-query SELECTION of keys, with a block's scores
and probabilities in VMEM only.

``ops/sparse_select.sparse_chunk_attention``'s XLA arm makes, a group of
queries and a block of keys at a time, float32 scores [heads, queries,
keys] that go to memory and back between the product and the softmax: at
32 heads over tens of thousands of keys those round trips, not the
products, are what a chunk's attention costs (8.6 ms a layer over 32,768
keys where the products alone are 1.4: PERF.md section 6, PR 60). Here the
grid is ``(K/V head, key block)``, the blocks sequential: a grid step holds
the K/V head's query heads' queries and their running softmax (the flash
kernel's scheme, ``ops/flash_attention.py``) in VMEM, the pipeline brings
the next block of the ring's keys and values (that K/V head's 128 columns
of the row's K half and of its V half) and of the selection while this one
is computed, and
each query head's float32 scores of the block meet the running maximum, sum
and sums and are gone.

The selection comes in as an additive BIAS in bfloat16, 0 where the query
picked the key and -1e30 where it did not (what the kernel of
``ops/sparse_pick.py`` writes: ``select_mask``'s mask, which also holds
causality and the padded rows): one array for the ring's rows
``[C, keys]`` and one for the chunk's own ``[C, C]``, which are not in the
ring yet (block 0 of the grid; the ring is read as it was). A ring block
that lies wholly at or past ``start`` holds no key the chunk may see: its
grid step does nothing and its index stands still, so nothing is fetched
for it and the work follows the keys in sight.

The kernel is handed the STACKED cache (a token's merged K row and V row
side by side, ``ops/sparse_select.py``) and the layer's and slot's indices
(scalars, prefetched): merged rows lie row-minor (``ops/attention.py``), as
a Mosaic call takes its operands, so nothing is copied out.

The same softmax over the same keys as the XLA arm: operands in the rings'
type into every product, float32 scores, statistics and sums, probabilities
cast to the values' type before their product. A key that was not picked
scores below the running maximum's first value (the bias rounds to a little
under -1e30 in bfloat16), so its probability is 0 exactly whether or not a
picked key has been seen yet, and a padded query (no key at all) ends with
zeros, as in the XLA arm.

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

# Ring rows a block: ``ops/latent_chunk.py``'s, which was swept on a v5e for
# the same loop (a chunk of 512 queries, a block's scores [512, block] a
# head): what a block costs beyond its products is paid a block a head.
BLOCK_ROWS = 512

_VMEM_LIMIT_BYTES = 64 * 2 ** 20


def takes_kernel(c: int, hd: int, w: int, old: int,
                 block: int = BLOCK_ROWS) -> bool:
    """(``w``: the columns of a merged K row, half a ring row's.) Heads of
    whole 128-lane tiles in rows of whole K/V heads, a chunk of
    whole lane tiles and a ring window of whole blocks take the kernel; the
    toy widths of the tiny presets keep the XLA arm."""
    return hd % _LANES == 0 and w % hd == 0 and c % _LANES == 0 \
        and old > 0 and old % block == 0


def _kernel(meta_ref, q_ref, k_own_ref, v_own_ref, bias_own_ref, k_ref,
            v_ref, bias_ref, o_ref, m_scr, l_scr, acc_scr, *, heads, hd,
            block, scale):
    """Grid (K/V head, key block), the blocks sequential: block 0 is the
    chunk's own rows, block b the ring's rows ``(b - 1) * block ..``. The
    heads' running (max, sum, sums) live in VMEM scratch across the blocks
    and the output is written at the last one. The statistics lie as the
    vector unit has them, a query's in all 128 lanes of its row
    (``ops/latent_chunk.py`` says why)."""
    b = pl.program_id(1)
    start = meta_ref[2]
    dtype = k_ref.dtype
    contract_last = (((1,), (1,)), ((), ()))

    def take(k, v, bias):
        """k, v [K, hd] in the rings' type, bias [C, K] bfloat16."""
        bias = bias.astype(jnp.float32)
        n = k.shape[0]
        for h in range(heads):
            scores = jax.lax.dot_general(
                q_ref[:, h * hd:(h + 1) * hd], k, contract_last,
                preferred_element_type=jnp.float32) * scale + bias
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

    @pl.when(b == 0)
    def _own():
        m_scr[...] = jnp.full_like(m_scr, _NEG_INF)
        l_scr[...] = jnp.zeros_like(l_scr)
        acc_scr[...] = jnp.zeros_like(acc_scr)
        take(k_own_ref[...], v_own_ref[...], bias_own_ref[...])

    @pl.when((b > 0) & ((b - 1) * block < start))
    def _ring():
        take(k_ref[...], v_ref[...], bias_ref[...])

    @pl.when(b == pl.num_programs(1) - 1)
    def _out():
        for h in range(heads):
            # (a padded query saw nothing: zeros, not 0 / 0)
            o_ref[:, h * hd:(h + 1) * hd] = (acc_scr[h] / jnp.maximum(
                jnp.sum(l_scr[h], axis=-1, keepdims=True), 1e-30)
            ).astype(o_ref.dtype)


# jitted so that a program's layers (and rows) share ONE traced and lowered
# copy of the kernel (``ops/latent_chunk.py`` says what a lowering costs)
@functools.partial(jax.jit, static_argnames=("block",))
def sparse_chunk_attention(q: jax.Array, kv_all: jax.Array,
                           kv_own: jax.Array, bias_old: jax.Array,
                           bias_own: jax.Array, layer, slot, start,
                           block: int = BLOCK_ROWS) -> jax.Array:
    """q [C, H, hd], the queries of positions ``start + i``, in the ring's
    type; kv_all the stacked cache [N, S, L, 2 W] (a token's merged K row
    and V row side by side) as it was before this chunk (read only),
    ``layer`` and ``slot`` which ring (int32 scalars); kv_own [C, 2 W] the
    chunk's own rows; bias_old [C, old] bfloat16 over the ring's first
    ``old`` rows (whole blocks) and bias_own [C, C] over the chunk's own: 0
    where query i picked the key, -1e30 where it did not; start int32
    scalar: ring rows ``>= start`` are not read. -> [C, H, hd] in q's
    type."""
    c, h, hd = q.shape
    groups = kv_all.shape[-1] // 2 // hd
    heads = h // groups
    n_blocks = bias_old.shape[1] // block

    def ring_block(b, meta):
        # a block with no row before ``start`` is not fetched: the index
        # stands at the last that has one
        last = jnp.maximum(meta[2] - 1, 0) // block
        return jnp.minimum(jnp.maximum(b - 1, 0), last)

    def group(g, b, meta):
        return (0, g)

    # K/V head g's keys: column block g of a row; its values: block G + g
    def ring_of(half):
        return pl.BlockSpec(
            (None, None, block, hd), lambda g, b, meta: (
                meta[0], meta[1], ring_block(b, meta), half * groups + g))

    def own_of(half):
        return pl.BlockSpec((c, hd),
                            lambda g, b, meta: (0, half * groups + g))

    out = pl.pallas_call(
        functools.partial(_kernel, heads=heads, hd=hd, block=block,
                          scale=hd ** -0.5),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1,
            grid=(groups, n_blocks + 1),
            in_specs=[
                pl.BlockSpec((c, heads * hd), group),
                own_of(0), own_of(1),
                pl.BlockSpec((c, c), lambda g, b, meta: (0, 0)),
                ring_of(0), ring_of(1),
                pl.BlockSpec((c, block),
                             lambda g, b, meta: (0, ring_block(b, meta))),
            ],
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
        name="sparse_chunk_attention",
    )(jnp.stack([jnp.asarray(x, jnp.int32).reshape(())
                 for x in (layer, slot, start)]),
      q.reshape(c, h * hd), kv_own, kv_own, bias_own, kv_all, kv_all,
      bias_old)
    return out.reshape(c, h, hd)
