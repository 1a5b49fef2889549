"""Attention ops.

``causal_attention`` dispatches between:
  * a pure-XLA implementation (always correct; XLA fuses the softmax chain
    and maps the two einsums onto the MXU) — also the CPU-test path;
  * a Pallas flash-attention TPU kernel (``ray_tpu.ops.flash_attention``)
    for long sequences where materializing the [T, T] score matrix would be
    HBM-bound.

The reference has no attention ops at all (it defers to torch); this module
exists because on TPU the framework owns the compute path (SURVEY.md §5.7).
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from ray_tpu.ops import latent_chunk, merged_chunk, ring_decode
from ray_tpu.ops.flash_attention import flash_block, flash_causal_attention

# Sequence length at/above which the flash kernel pays for itself.
# Measured on v5e (GPT-2 small, batch 16): at seq 1024 the Pallas kernel
# beats XLA attention by ~7 MFU points in-model (fp32 [T,T] score
# materialization is HBM-bound); below 1024 it is unmeasured, so XLA's
# fused attention stays the default there.
_FLASH_MIN_SEQ = 1024


def xla_causal_attention(
    q: jax.Array, k: jax.Array, v: jax.Array, *, softmax_scale: float | None = None
) -> jax.Array:
    """Causal multi-head attention, pure XLA.

    Args are [batch, seq, heads, head_dim]. Computes in the input dtype
    (bf16 on TPU) with fp32 softmax accumulation.
    """
    *_, t, _h, d = q.shape
    scale = softmax_scale if softmax_scale is not None else d**-0.5
    # [B, H, T, T] scores in fp32 for a stable softmax.
    scores = jnp.einsum("bqhd,bkhd->bhqk", q, k, preferred_element_type=jnp.float32)
    scores = scores * scale
    mask = jnp.tril(jnp.ones((t, t), dtype=bool))
    scores = jnp.where(mask, scores, -jnp.inf)
    probs = jax.nn.softmax(scores, axis=-1).astype(q.dtype)
    return jnp.einsum("bhqk,bkhd->bqhd", probs, v)


def causal_attention(
    q: jax.Array,
    k: jax.Array,
    v: jax.Array,
    *,
    softmax_scale: float | None = None,
    use_flash: bool | None = None,
) -> jax.Array:
    """[B, T, H, D] causal attention with automatic kernel selection.

    ``use_flash=None`` picks the Pallas kernel from what can be observed
    before calling it: an accelerator backend, a sequence long enough to
    pay for it, and a length the kernel can tile. ``use_flash=True``
    insists on it (and raises on a length it cannot tile)."""
    t = q.shape[1]
    if use_flash is None:
        use_flash = (
            t >= _FLASH_MIN_SEQ
            and jax.default_backend() != "cpu"
            and flash_block(1024, t) is not None
        )
    if use_flash:
        return _mesh_flash_attention(q, k, v, softmax_scale)
    return xla_causal_attention(q, k, v, softmax_scale=softmax_scale)


def _mesh_flash_attention(q, k, v, softmax_scale):
    """The flash kernel under the mesh the computation is traced in.

    A Mosaic kernel cannot be partitioned by GSPMD ("wrap the call in a
    shard_map"), so on a mesh of several devices the call is mapped by
    hand: batch over the data axes, heads over ``tp`` — the layout the
    activations already have — and each device runs the kernel on its
    own block. Attention mixes nothing across batch or heads, so no
    collective is needed inside."""
    flash = functools.partial(flash_causal_attention,
                              softmax_scale=softmax_scale)
    mesh = jax.sharding.get_abstract_mesh()
    if mesh.empty or mesh.size == 1:
        return flash(q, k, v)
    batch_axes = tuple(a for a in ("dp", "fsdp") if a in mesh.axis_names)
    spec = P(batch_axes or None, None,
             "tp" if "tp" in mesh.axis_names else None, None)
    return jax.shard_map(
        flash, mesh=mesh, in_specs=(spec, spec, spec), out_specs=spec,
        check_vma=False,
    )(q, k, v)


# -- KV cache of the serving path ---------------------------------------------
#
# Shared by the GPT-2 and Llama decode APIs (``models/gpt2.py`` /
# ``models/llama.py``) and the hybrids: the head-count axis differs (full vs
# GQA ``n_kv_head``) but the contract is identical, so it lives here once.
#
# The cache is ONE stacked array per K and V, donated by the engine. Inside
# the layer loop it is only read; the only bytes written in a step are the
# new rows, and they go into the stacked buffer in place: never cut a
# layer's block out of the stack, write into it and put it back (the cache
# as ``lax.scan``'s ``xs -> ys``), which copies the whole cache twice a step
# (PERF.md section 6, PR 25). Both writes are static-count
# ``dynamic_update_slice``s whose update is row-sized; a scatter, or the same
# updates under a ``fori_loop``, make XLA re-lay out the whole cache around
# the write. What a row is differs by the cache's rank:
#
# * rank 5, ``[n_layer, S, L, G, hd]`` (Llama, Nemotron-H's and Granite's few
#   attention layers, the latent rows): heads apart. Where ``G x hd`` is no
#   whole number of 128-lane tiles the TPU's compiler lays it out with ``L``
#   minor-most, and one token's row is then ``G x hd`` tiles of 4 KB. The
#   grouped products (``_grouped_decode_attention``) want a window with
#   heads major of rows, so the compiler re-lays each window out before it
#   reads it. These families keep this rank: the op is under a tenth of
#   their steps (one attention layer in ten or so), so they have nothing
#   to gain from another layout and their programs stay what they were.
# * rank 4, ``[n_layer, S, L, W]`` (GPT-2, PR 42; Falcon-H1, PR 44, whose
#   EVERY layer owns a ring: eighteen re-laid windows a step were half of
#   it): a token's row MERGED, all K/V heads side by side, and padded with
#   zero columns to whole lane tiles, ``W = merged_row_width(G, hd)``. Only
#   with the pad does the compiler keep the row minor-most (``{3,2,1,0}``;
#   at XL 1600 columns are 12.5 tiles and ``L`` goes minor again,
#   tests/test_serving_programs_v5e.py), so that a row is ``W / 128`` tiles
#   (Falcon-H1's 4 x 128 are four whole tiles and its row has no pad). The
#   pad belongs to no head: queries hold zeros there and a head's columns
#   are picked out of the sums. With FEWER K/V heads than query heads
#   (grouped queries, ``_kv_heads``) each query head stands in ITS K/V
#   head's columns, a group's heads sharing them. The
#   layout survives a program only if the program reads the cache BEFORE it
#   writes it: a loop that writes rows into the cache it carries and then
#   reads a window from the same carry made the compiler re-lay the whole
#   cache out and back (or, short of memory, copy whole stacks). So both
#   merged-row programs read the cache as it was, take their own new rows
#   beside it, and write after the layer loop (``cache_write_token`` /
#   ``cache_write_chunk``). The decode step hands the STACK and a layer's
#   index to ``cached_decode_attention`` (PR 48): rings of whole blocks of
#   rows go through the kernel of ``ops/ring_decode.py``, which fetches a
#   slot's blocks up to its last live row and no further, and a custom
#   call handed a layer's slice would have it copied out first.
#
# * rank 4 twice over (SmallThinker, PR 51): window layers beside global
#   ones hold TWO such stacks of different lengths in one cache, the window
#   layers' rings as long as the window and no longer. A ring that has
#   wrapped is a window for the decode step as it stands; a prompt chunk
#   reads it by POSITION (``wrapped_chunk_attention``, ``ring_positions``)
#   and writes at ``start mod L`` (``cache_write_ring_chunk``).
#
# * a verify step (K-EXAONE, PR 54): a few consecutive query rows a slot
#   over the same two stacks, the window rings exactly 128 rows: every ring
#   read as it was, the new rows scored apart and causal among themselves
#   (``cached_verify_attention``), all of them written after the layer
#   loop. A chunk there is several window rings long.
#
# A family picks its layout once, in its ``init_cache``; the ops below take
# the path the rank of what they are handed names. The same bytes reshaped
# inside an op are another tiled layout on the chip, a copy: only a cache
# STORED as merged rows is read as it lies.


def merged_row_width(n_head: int, head_dim: int) -> int:
    """Columns of a merged K or V row: the (K/V) heads side by side, padded to
    whole 128-lane tiles. A row that fits inside one tile (the toy sizes)
    has no tile boundary to straddle and is left as it is, so a toy
    cache's bytes are still its shape's."""
    d = n_head * head_dim
    return d if d <= 128 else -(-d // 128) * 128


def merged_rows(x: jax.Array, w: int) -> jax.Array:
    """[..., H * hd], the heads side by side as a projection gives them,
    -> [..., W]: zeros in the pad columns."""
    return jnp.pad(x, [(0, 0)] * (x.ndim - 1) + [(0, w - x.shape[-1])])


# decode-path  # jax-hot-path: the KV cache stays in the activation dtype
def cache_write_token(cache: jax.Array, rows: jax.Array,
                      cursor: jax.Array) -> jax.Array:
    """Ring-cursor write of ONE token's K or V rows, every layer at once,
    after the layer loop.

    cache [N, S, L, H, hd] and rows [N, S, H, hd] (the loop's stacked new
    rows), or merged: cache [N, S, L, W] and rows [N, S, W]; cursor [S]
    int32 — slot ``s``'s rows land at ``cache[:, s, cursor[s]]``; nothing
    else of the cache is touched."""
    rows = rows.astype(cache.dtype)
    for s in range(rows.shape[1]):
        cache = jax.lax.dynamic_update_slice(
            cache, rows[:, s, None, None],
            (0, s, cursor[s]) + (0,) * (cache.ndim - 3))
    return cache


# decode-path  # jax-hot-path: the KV cache stays in the activation dtype
def cache_write_chunk(cache: jax.Array, rows: jax.Array, slots: jax.Array,
                      start: jax.Array) -> jax.Array:
    """Prefill write of a chunk's merged rows, every layer at once, after
    the layer loop: row block ``rows[:, i]`` ([N, C, W], the loop's stacked
    rows) lands at ``cache[:, slots[i], start[i] : start[i] + C]`` of the
    merged cache [N, S, L, W]. Sequential over the (small, static) row
    axis; distinct slots make the order immaterial (rows that share a
    scratch slot write only garbage there)."""
    rows = rows.astype(cache.dtype)
    for i in range(rows.shape[1]):
        cache = jax.lax.dynamic_update_slice(
            cache, rows[:, i, None], (0, slots[i], start[i], 0))
    return cache


# decode-path  # jax-hot-path: the KV cache stays in the activation dtype
def cache_write_prompt(cache: jax.Array, layer: jax.Array, rows: jax.Array,
                       slots: jax.Array, start: jax.Array) -> jax.Array:
    """Prefill write of one layer's rows, inside the layer loop (which
    carries the stacked cache): row block ``rows[i]`` ([C, H, hd]) lands
    at ``cache[layer, slots[i], start[i] : start[i] + C]`` of cache
    [N, S, L, H, hd]. Sequential over the (small, static) row axis —
    each write must see the prior ones, and distinct slots make the order
    immaterial (rows that share a scratch slot write only garbage there)."""
    rows = rows.astype(cache.dtype)
    for i in range(rows.shape[0]):
        cache = jax.lax.dynamic_update_slice(
            cache, rows[i, None, None], (layer, slots[i], start[i], 0, 0))
    return cache


def cached_chunk_attention(q: jax.Array, k_all: jax.Array, v_all: jax.Array,
                           layer: jax.Array, slots: jax.Array,
                           start: jax.Array, window: int,
                           scale: float | None = None) -> jax.Array:
    """A chunk of C prompt tokens a row over the row's own slot, AFTER the
    chunk's K/V rows were written there (``cache_write_prompt``).

    q [R, C, H, hd], the queries of positions ``start[r] + i``; k_all /
    v_all the stacked cache [N, S, L, G, hd] the layer loop carries (H a
    multiple of G: each K/V head serves its H // G query heads as it lies);
    slots, start [R] int32. Query i sees keys ``<= start + i`` among the
    slot's first ``window`` rows (static; the caller's bound on
    ``start + C``): the rows earlier chunks of the same prompt left, and
    the chunk's own up to itself. Only that window is cut out of the
    stack, R blocks of [window, G, hd]; the cache itself is not touched.
    Operands in the cache's type, float32 scores and softmax, output in
    q's type: ``xla_causal_attention``'s arithmetic on a wider key axis.
    ``scale`` multiplies the scores where a model publishes its own
    constant; None is ``hd ** -0.5``."""
    r, c, h, hd = q.shape
    g = k_all.shape[3]

    def rows_of(cache):
        return jnp.stack([jax.lax.dynamic_slice(
            cache, (layer, slots[i], 0, 0, 0), (1, 1, window, g, hd))[0, 0]
            for i in range(r)])

    k, v = rows_of(k_all), rows_of(v_all)  # [R, W, G, hd]
    q = q.reshape(r, c, g, h // g, hd)
    scores = jnp.einsum("rqgpd,rkgd->rgpqk", q.astype(k.dtype), k,
                        preferred_element_type=jnp.float32) \
        * (hd ** -0.5 if scale is None else scale)
    seen = jnp.arange(window)[None, None, :] \
        <= (start[:, None] + jnp.arange(c)[None, :])[:, :, None]  # [R, C, W]
    probs = jax.nn.softmax(
        jnp.where(seen[:, None, None], scores, -1e30), axis=-1)
    out = jnp.einsum("rgpqk,rkgd->rqgpd", probs.astype(v.dtype), v)
    return out.reshape(r, c, h, hd).astype(q.dtype)


def _own_columns(g: int, hd: int, cols: int) -> jax.Array:
    """[g, cols] bool: column w of a group of merged columns is head
    ``w // hd``'s (a pad column, past ``g * hd``, is nobody's)."""
    return jnp.arange(cols)[None, :] // hd == jnp.arange(g)[:, None]


def _heads_apart(rows: jax.Array, g: int, hd: int) -> jax.Array:
    """Merged query columns [..., cols] -> [..., g, cols], one row a head
    of the group: the head's own ``hd`` columns and zeros everywhere else,
    so a product over the columns of a merged key row is the head's own
    product plus exact zeros."""
    return jnp.where(_own_columns(g, hd, rows.shape[-1]),
                     rows[..., None, :], 0)


def _heads_merged(sums: jax.Array, hd: int) -> jax.Array:
    """The pick back: sums over merged value rows [..., g, cols], a row a
    head, -> [..., cols], every column from its own head's row."""
    g, cols = sums.shape[-2:]
    return jnp.sum(jnp.where(_own_columns(g, hd, cols), sums, 0), axis=-2)


def _kv_heads(h: int, hd: int, w: int) -> int:
    """K/V heads a merged row of ``w`` columns holds under ``h`` query
    heads of ``hd``: ``h`` where the row has columns for every query head
    (GPT-2; a pad is nobody's), else ``w // hd``, each serving ``h`` over it
    query heads (grouped queries: such a row is stored without a pad)."""
    if h * hd <= w:
        return h
    g = w // hd
    if g * hd != w or h % g:
        raise ValueError(
            f"merged rows of {w} columns are no whole number of K/V heads "
            f"of {hd} that {h} query heads divide into")
    return g


def _group_columns(heads: int, n_kv: int, hd: int) -> jax.Array:
    """[heads, n_kv * hd] bool: column w is K/V head ``w // hd``'s, and
    that is query head j's own where ``j // (heads // n_kv)`` names it."""
    return jnp.arange(n_kv * hd)[None, :] // hd \
        == jnp.arange(heads)[:, None] // (heads // n_kv)


def _heads_in_group_columns(q: jax.Array, n_kv: int) -> jax.Array:
    """Grouped queries [..., n_kv * R, hd], R query heads under each of
    ``n_kv`` K/V heads whose columns lie side by side, -> [..., n_kv * R,
    n_kv * hd]: query head j's numbers stand in K/V head ``j // R``'s
    columns and zeros in the others (``_heads_apart`` with a group's heads
    sharing columns). One K/V head's queries are its product's rows as
    they are: no zero column is made."""
    if n_kv == 1:
        return q
    heads, hd = q.shape[-2:]
    return jnp.where(_group_columns(heads, n_kv, hd),
                     jnp.tile(q, (1,) * (q.ndim - 1) + (n_kv,)), 0)


def _heads_out_of_group_columns(sums: jax.Array, n_kv: int) -> jax.Array:
    """The pick back: sums over merged value rows [..., n_kv * R, n_kv *
    hd], a row a query head, -> [..., n_kv * R, hd], each head's own
    K/V head's columns."""
    if n_kv == 1:
        return sums
    heads, cols = sums.shape[-2:]
    hd = cols // n_kv
    own = jnp.where(_group_columns(heads, n_kv, hd), sums, 0)
    return jnp.sum(own.reshape(*sums.shape[:-1], n_kv, hd), axis=-2)


def _lane_groups(rows: jax.Array, hd: int) -> jax.Array:
    """Merged rows [..., W] cut at whole lane tiles, [..., W / 128, 128]
    (GPT-2's two heads a tile): the cut is a free reshape and a product
    over a tile does ``128 / hd`` times the needed operations, where one
    over the whole row does ``H`` times. A head size that does not divide
    a tile, or a row narrower than one, keeps the row whole, [..., 1, W]."""
    w = rows.shape[-1]
    cols = _lane_cols(w, hd)
    return rows.reshape(*rows.shape[:-1], w // cols, cols)


def _lane_cols(w: int, hd: int) -> int:
    """Columns of one of ``_lane_groups``' groups of a row of ``w``."""
    return w if 128 % hd or w % 128 else 128


def merged_chunk_attention(q: jax.Array, k_all: jax.Array, v_all: jax.Array,
                           k_own: jax.Array, v_own: jax.Array,
                           layer: jax.Array, slots: jax.Array,
                           start: jax.Array, window: int) -> jax.Array:
    """``cached_chunk_attention`` over a cache of merged rows, WITHOUT the
    chunk's rows being in the cache yet.

    q [R, C, H, hd]; k_all / v_all the stacked cache [N, S, L, W] as it was
    before this chunk (read only: the caller writes after its layer loop,
    ``cache_write_chunk``); k_own / v_own [R, C, W], the chunk's own merged
    rows in the cache's type; slots, start [R] int32. A row holds H heads
    (and a pad) or, grouped queries, ``W // hd`` K/V heads that H is a
    multiple of (``_kv_heads``). Query i sees the
    slot's rows ``< start`` as earlier chunks left them (cut out of the
    stack: the first ``window - C`` rows, all that a start within the
    caller's bound ``start + C <= window`` can name) and the chunk's own
    rows ``<= i``: the same softmax over the same keys as a write and then
    a read, in the same arithmetic (float32 scores, one maximum and one
    sum over both parts, probabilities in the values' type, float32
    sums). Which of two implementations runs is decided statically, by
    the shapes (``merged_chunk.takes_kernel``): heads of whole lane tiles
    over a window of whole blocks go through the Pallas kernel of
    ``ops/merged_chunk.py``, which reads the ring's blocks out of the stacks
    as they lie and stops at ``start``; everything else (GPT-2's heads of
    64, the tiny presets) scores the whole window in XLA, below.
    -> [R, C, H, hd] in q's type."""
    c = q.shape[1]
    w = k_all.shape[-1]
    old = window - c
    if chunk_attention_arm(c, q.shape[-1], w, window) == "kernel":
        # one call a row: the kernel reads the slot's ring out of the
        # stacks as they lie, block by block and only up to ``start``
        return jnp.stack([merged_chunk.merged_chunk_attention(
            q[i], k_all, v_all, k_own[i], v_own[i], layer, slots[i],
            start[i], old=old) for i in range(q.shape[0])])
    rows = _chunk_query_rows(q, w, k_all.dtype)
    # (keys, values, who sees them): the slot's old rows, then the chunk's
    parts = [(k_own, v_own, jnp.tril(jnp.ones((c, c), bool))[None])]
    if old:
        parts.insert(0, (_slot_rows(k_all, layer, slots, old),
                         _slot_rows(v_all, layer, slots, old),
                         jnp.arange(old)[None, None, :]
                         < start[:, None, None]))               # [R, 1, L]
    return _chunk_softmax(q, rows, parts, w)


def chunk_attention_arm(c: int, hd: int, w: int, window: int) -> str:
    """Which of ``merged_chunk_attention``'s two implementations a chunk
    program of these shapes is built with, ``"kernel"`` or ``"xla"``: a
    chunk of c queries with heads of ``hd`` lanes in merged rows of ``w``
    columns over a key window of ``window`` rows (the chunk's own among
    them). An engine with no ring to read runs the XLA arm."""
    takes = merged_chunk.takes_kernel(c, hd, w, max(window - c, 0))
    return "kernel" if takes else "xla"


def _slot_rows(cache: jax.Array, layer, slots: jax.Array,
               n_rows: int) -> jax.Array:
    """The first ``n_rows`` rows of each of ``slots``' rings in ``layer`` of
    a stacked cache of merged rows [N, S, L, W], cut out as they lie:
    [R, n_rows, W]."""
    return jnp.stack([jax.lax.dynamic_slice(
        cache, (layer, slots[i], 0, 0),
        (1, 1, n_rows, cache.shape[-1]))[0, 0]
        for i in range(slots.shape[0])])


def _chunk_query_rows(q: jax.Array, w: int, dtype) -> jax.Array:
    """A chunk's queries q [R, C, H, hd] as the products over merged rows
    of ``w`` columns take them, [R, C, T, g, cols]: each head's numbers in
    its own columns of a lane group (grouped queries: in its K/V head's),
    zeros in the others."""
    r, c, h, hd = q.shape
    g = _kv_heads(h, hd, w)
    if g == h:
        rows = _lane_groups(
            merged_rows(q.reshape(r, c, h * hd), w).astype(dtype), hd)
        # [R, C, T, g, cols]: g heads a group of columns
        return _heads_apart(rows, -(-rows.shape[-1] // hd), hd)
    # grouped queries: a group of columns holds ``per`` K/V heads and
    # their ``per * h // g`` query heads are the product's rows (at
    # hd = 128 a lane group IS one K/V head)
    tiles = w // _lane_cols(w, hd)
    per = g // tiles
    return _heads_in_group_columns(
        q.astype(dtype).reshape(r, c, tiles, h // tiles, hd), per)


def _chunk_softmax(q: jax.Array, rows: jax.Array, parts: list,
                   w: int) -> jax.Array:
    """ONE softmax of a chunk's queries (``_chunk_query_rows``) over several
    parts of keys, each (keys [R, K, W], values [R, K, W], seen, which
    broadcasts against [R, C, K]): float32 scores, one maximum and one sum
    over all parts, probabilities in the values' type, float32 sums.
    -> [R, C, H, hd] in q's type."""
    r, c, h, hd = q.shape
    g = _kv_heads(h, hd, w)
    scores = [jnp.where(
        seen[:, None, None],
        jnp.einsum("rqtgw,rktw->rtgqk", rows, _lane_groups(k, hd),
                   preferred_element_type=jnp.float32) * hd ** -0.5, -1e30)
        for k, _, seen in parts]
    top = functools.reduce(jnp.maximum, [s.max(axis=-1) for s in scores])
    probs = [jnp.exp(s - top[..., None]) for s in scores]
    total = sum(p.sum(axis=-1) for p in probs)
    out = sum(jnp.einsum("rtgqk,rktw->rqtgw",
                         (p / total[..., None]).astype(v.dtype),
                         _lane_groups(v, hd),
                         preferred_element_type=jnp.float32)
              for p, (_, v, _) in zip(probs, parts))
    if g == h:
        out = _heads_merged(out, hd).reshape(r, c, w)[..., :h * hd]
    else:
        out = _heads_out_of_group_columns(
            out, g // (w // _lane_cols(w, hd)))
    return out.reshape(r, c, h, hd).astype(q.dtype)


# Queries a chunk scores at a time where a longer chunk's scores would
# leave the chip's fast memory (PERF.md section 6, PR 53; a chunk is 512
# tokens where a token multiplies with a share of the experts,
# ``models/prefill.chunk_len``). A window layer's float32 scores of 256
# queries of 28 heads over a ring of 4,096 rows (117 MB) stay there and
# those of 512 (235 MB) do not: six layers cost 6.2 ms a chunk of 512 in
# one piece where two chunks of 256 cost 2.3. A latent block's scores are
# [128, 256, 256] (34 MB) at 256 queries and 67 MB at 512, where the cell
# lost 8 % of its rate in one piece and gained 8 % in groups of 256 against
# a block decompressed once. The scores over a global layer's whole window
# are in memory at either length and stay one piece.
CHUNK_QUERIES = 256


def ring_positions(start: jax.Array, n_rows: int) -> jax.Array:
    """What a ring of ``n_rows`` rows holds of a prompt whose positions
    ``< start`` were written at ``position mod n_rows``: [..., n_rows]
    int32, row j's position, the largest ``p < start`` with ``p mod n_rows
    == j``; negative where the prompt has written no such row yet (the row
    is a former tenant's, or nothing)."""
    last = start[..., None] - 1
    return last - jnp.mod(last - jnp.arange(n_rows), n_rows)


def wrapped_chunk_attention(q: jax.Array, k_all: jax.Array, v_all: jax.Array,
                            k_own: jax.Array, v_own: jax.Array,
                            layer: jax.Array, slots: jax.Array,
                            start: jax.Array) -> jax.Array:
    """``merged_chunk_attention`` over a ring that may have WRAPPED: a
    window layer's chunk, masked by position.

    q [R, C, H, hd], the queries of positions ``start[r] + i``; k_all /
    v_all the stacked cache [N, S, L, W] of merged rows as it was before
    this chunk (read only), a slot's ring of ``L`` rows holding position p
    at row ``p mod L``: the WINDOW is the ring's length; k_own / v_own [R,
    C, W] the chunk's own merged rows; slots, start [R] int32. Query i sees
    the keys at positions ``start + i - L < p <= start + i``: of the ring
    as it lies the rows whose position (``ring_positions``) is inside that
    and not negative, whatever ``start`` (before the first wrap, at it and
    windows on: one program), and of the chunk's own rows those ``<= i``
    (where ``C <= L`` none of them is out of the window; a chunk of several
    windows, ``C`` a multiple of ``L``, also drops its own rows ``<= i -
    L``). The caller then writes the chunk's rows at ``start mod L``
    (``cache_write_ring_chunk``; ``C`` divides ``L`` or is whole rings
    long, so a chunk never straddles the ring's end). The same
    arithmetic as ``merged_chunk_attention``. -> [R, C, H, hd] in q's
    type."""
    c = q.shape[1]
    n_rows, w = k_all.shape[2:]
    if n_rows % c and c % n_rows:
        raise ValueError(f"a chunk of {c} rows neither divides a ring of "
                         f"{n_rows} nor is whole rings long: it would "
                         f"straddle the ring's end")
    rows = _chunk_query_rows(q, w, k_all.dtype)
    held = ring_positions(start, n_rows)[:, None, :]              # [R, 1, L]
    sees = (start[:, None] + jnp.arange(c)[None, :])[:, :, None]  # [R, C, 1]
    ring_k, ring_v = (_slot_rows(x, layer, slots, n_rows)
                      for x in (k_all, v_all))
    in_ring = (held >= 0) & (held > sees - n_rows)                # [R, C, L]
    own = jnp.tril(jnp.ones((c, c), bool))[None]
    if c > n_rows:
        # a chunk of several windows: its own rows leave the window too
        own = own & ~jnp.tril(jnp.ones((c, c), bool), -n_rows)[None]
    # a group of queries at a time over the ring and the chunk's rows up to
    # the group's last (``CHUNK_QUERIES``); a chunk that is no whole number
    # of groups, a shorter one among them, is one softmax over all, as ever
    g = CHUNK_QUERIES if c % CHUNK_QUERIES == 0 else c
    return jnp.concatenate([_chunk_softmax(
        q[:, at:at + g], rows[:, at:at + g],
        [(ring_k, ring_v, in_ring[:, at:at + g]),
         (k_own[:, :at + g], v_own[:, :at + g],
          own[:, at:at + g, :at + g])], w) for at in range(0, c, g)], axis=1)


# decode-path  # jax-hot-path: the KV cache stays in the activation dtype
def cache_write_ring_chunk(cache: jax.Array, rows: jax.Array,
                           slots: jax.Array, start: jax.Array,
                           lengths: jax.Array) -> jax.Array:
    """``cache_write_chunk`` into rings that wrap: row block ``rows[:, i]``
    ([N, C, W]) lands at ``cache[:, slots[i], start[i] mod L :][:C]`` (``C``
    divides ``L``; a chunk of several rings, ``C`` a multiple of ``L``,
    leaves its last ``L`` real rows: ``_write_ring_last_rows``). Only the
    chunk's first ``lengths[i]`` rows are real:
    the others keep what the ring held, because in a ring that has wrapped
    that is the prompt's own rows a window back, which the tokens after the
    prompt's end still see (a straight cache has nothing there yet)."""
    rows = rows.astype(cache.dtype)
    n, _, c, w = rows.shape
    n_rows = cache.shape[2]
    if c > n_rows:
        return _write_ring_last_rows(cache, rows, slots, start, lengths)
    real = jnp.arange(c)[None, :] < lengths[:, None]  # [R, C]
    for i in range(rows.shape[1]):
        at = (0, slots[i], jnp.mod(start[i], n_rows), 0)
        was = jax.lax.dynamic_slice(cache, at, (n, 1, c, w))
        cache = jax.lax.dynamic_update_slice(
            cache, jnp.where(real[i][None, None, :, None],
                             rows[:, i, None], was), at)
    return cache


def _write_ring_last_rows(cache, rows, slots, start, lengths):
    """``cache_write_ring_chunk`` where the chunk is several rings long
    (``C`` a multiple of ``L``): ring row j ends up with the LAST real
    position that lies there, the largest ``p < start + lengths`` with ``p
    mod L == j`` (``ring_positions``): the chunk's row ``p - start`` where
    that is in the chunk, else what the ring held (an earlier chunk's row,
    still in the window of the tokens after a prompt that ends early in
    this chunk)."""
    n, _, c, w = rows.shape
    n_rows = cache.shape[2]
    last = ring_positions(start + lengths, n_rows)         # [R, L]
    takes = last >= start[:, None]
    at = jnp.clip(last - start[:, None], 0, c - 1)
    for i in range(rows.shape[1]):
        where = (0, slots[i], 0, 0)
        was = jax.lax.dynamic_slice(cache, where, (n, 1, n_rows, w))
        new = jnp.take(rows[:, i], at[i], axis=1)[:, None]  # [N, 1, L, W]
        cache = jax.lax.dynamic_update_slice(
            cache, jnp.where(takes[i][None, None, :, None], new, was), where)
    return cache


def cached_verify_attention(q: jax.Array, k_all: jax.Array, v_all: jax.Array,
                            k_new: jax.Array, v_new: jax.Array,
                            cursor: jax.Array, valid: jax.Array, out_dtype,
                            layer, scale: float | None = None) -> jax.Array:
    """``cached_decode_attention`` for R consecutive query rows a slot (a
    verify step: the newest token and its drafts), over a STACKED cache of
    merged rows, WITHOUT their rows being in the cache yet.

    q [S, R, H, hd], row i at position ``p + i``; k_all / v_all [N, S, L,
    W] as they were before this step (read only) and ``layer`` which of the
    N; k_new / v_new [S, R, W] the rows' own merged K/V rows in the cache's
    type; cursor [S] the ring row the FIRST new row will take (row i takes
    ``(cursor + i) mod L``), valid [S] the live entries with the first row.
    Query row i sees the new keys ``<= i`` (causal among themselves) and of
    the ring every live row but those the new keys ``<= i`` will take: not
    the places of LATER rows, which in a ring that has wrapped still hold
    the oldest keys of an earlier row's window (a window ring of exactly
    ``window`` rows serves both rows so, read before written). What a
    rejected row wrote is the next step's cursor row or lies past its
    ``valid``: never read. One softmax over both parts in
    ``cached_decode_attention``'s arithmetic; rings of whole blocks of
    whole lane tiles take the kernel of ``ops/ring_decode.py`` (the rows'
    heads stacked), any other is cut out of the stack and read whole. One
    row is ``cached_decode_attention`` itself. -> [S, R, H, hd]."""
    s, r, h, hd = q.shape
    if r == 1:
        return cached_decode_attention(
            q[:, 0], k_all, v_all, k_new[:, 0], v_new[:, 0], cursor, valid,
            out_dtype, scale, layer)[:, None]
    n_rows, w = k_all.shape[2:]
    rows = _step_query_rows(q.reshape(s * r, h, hd), w,
                            k_all.dtype).reshape(s, r, h, w)
    if ring_decode.takes_kernel(n_rows, w):
        # whole sublane tiles of heads a query row: a zero query's row is
        # cut off again
        pad = -h % 8
        out = ring_decode.ring_decode_attention(
            jnp.pad(rows, ((0, 0), (0, 0), (0, pad), (0, 0))).reshape(
                s, r * (h + pad), w), k_all, v_all, layer, k_new, v_new,
            cursor, valid, functools.partial(_scaled, hd=hd, scale=scale))
        out = out.reshape(s, r, h + pad, w)[:, :, :h]
    else:
        out = _verify_ring_sums(rows, k_all[layer], v_all[layer], k_new,
                                v_new, cursor, valid, hd, scale)
    return _step_own_columns(out.reshape(s * r, h, w), hd).reshape(
        s, r, h, hd).astype(out_dtype)


def _verify_ring_sums(q, k, v, k_new, v_new, cursor, valid, hd, scale):
    """``_whole_ring_sums`` for R query rows a slot: float32 sums [S, R, H,
    W] of queries q [S, R, H, W] over a ring k / v [S, L, W] read whole and
    the rows' own keys k_new / v_new [S, R, W]; who sees what is
    ``cached_verify_attention``'s."""
    n, r = k.shape[1], q.shape[1]
    idx = jnp.arange(n)
    # how many places past the cursor a ring row lies: new row j takes j
    past = jnp.mod(idx[None, :] - cursor[:, None], n)[:, None, :]  # [S, 1, L]
    seen = (idx[None, :] < valid[:, None])[:, None, :] \
        & (past > jnp.arange(r)[None, :, None])                    # [S, R, L]
    ring = jnp.einsum("srhw,slw->srhl", q, k,
                      preferred_element_type=jnp.float32)
    own = jnp.einsum("srhw,sjw->srhj", q, k_new,
                     preferred_element_type=jnp.float32)
    scores = jnp.concatenate([
        jnp.where(seen[:, :, None, :], _scaled(ring, hd, scale), -1e30),
        jnp.where(jnp.tril(jnp.ones((r, r), bool))[None, :, None, :],
                  _scaled(own, hd, scale), -1e30)], axis=-1)
    weights = jax.nn.softmax(scores, axis=-1)
    # float32 probabilities into the sums, as ``_whole_ring_sums`` has them
    return jnp.einsum("srhl,slw->srhw", weights[..., :n],
                      v.astype(jnp.float32),
                      precision=jax.lax.Precision.HIGHEST) \
        + jnp.einsum("srhj,sjw->srhw", weights[..., n:],
                     v_new.astype(jnp.float32),
                     precision=jax.lax.Precision.HIGHEST)


def cached_decode_attention(q: jax.Array, k: jax.Array, v: jax.Array,
                            k_new: jax.Array, v_new: jax.Array,
                            cursor: jax.Array, valid: jax.Array,
                            out_dtype, scale: float | None = None,
                            layer=None) -> jax.Array:
    """One query token per slot (ONE query row a slot; a verify step's few
    consecutive rows are ``cached_verify_attention``'s) over the slot's
    ring-cache window, the token itself included, WITHOUT its row being in
    the cache yet.

    q [S, H, hd]; k/v [S, L, H, hd], the layer's cache as it was before
    this step (read only); k_new/v_new [S, H, hd], this token's rows in
    the cache's dtype (GQA callers expand KV heads to the query heads
    first, here as there); cursor [S] = the ring row this token will
    take; valid [S] = live cache entries with it (the ring mask, always
    > cursor). The score at the cursor is the new token's and its value
    is added beside the window's, so it is the same softmax over the
    same keys as if the row had been written first — a wrapped ring
    drops the row the cursor overwrites, as ever. Where k/v hold FEWER
    heads than q (grouped queries: [S, L, G, hd] and [S, G, hd], H a
    multiple of G) each K/V head serves its H // G query heads as it lies,
    with no expanded copy of the window. Where k/v are MERGED rows ([S, L,
    W] and [S, W], ``merged_row_width`` of as many heads as q has or of
    the K/V heads that number is a multiple of) they are read as they lie
    too, with no re-laid copy either. A family that holds merged rows hands
    in its STACKED cache [N, S, L, W] and ``layer``, which of the N (an int
    or an int32 scalar): rings of whole blocks of rows of whole lane tiles
    then go through the kernel of ``ops/ring_decode.py``, which stops each
    slot at its last live block and is handed the stack because a slice
    handed to a custom call would be copied out first; any other merged
    ring is cut out of the stack and read whole.
    fp32 scores/softmax, output cast to the activation dtype — shared by
    the model families' decode steps so the masking/scaling contract lives
    here once.
    ``scale`` multiplies the scores where a model publishes its own
    constant; None divides them by ``hd ** 0.5`` as ever."""
    hd = q.shape[-1]
    if layer is not None and not ring_decode.takes_kernel(*k.shape[2:]):
        k, v, layer = k[layer], v[layer], None
    if layer is not None or k.ndim == 3:
        return _merged_decode_attention(q, k, v, k_new, v_new, cursor,
                                        valid, out_dtype, scale, layer)
    if k.shape[2] != q.shape[1]:
        return _grouped_decode_attention(q, k, v, k_new, v_new, cursor,
                                         valid, out_dtype, scale)
    q = q.astype(jnp.float32)
    idx = jnp.arange(k.shape[1])
    at_cursor = (idx[None, :] == cursor[:, None])[:, None, :]  # [S, 1, L]
    mask = (idx[None, :] < valid[:, None])[:, None, :]
    scores = jnp.einsum("shd,slhd->shl", q, k.astype(jnp.float32))
    score_new = jnp.sum(q * k_new.astype(jnp.float32), axis=-1)  # [S, H]
    scores = _scaled(jnp.where(at_cursor, score_new[..., None], scores),
                     hd, scale)
    weights = jax.nn.softmax(jnp.where(mask, scores, -1e30), axis=-1)
    weight_new = jnp.sum(jnp.where(at_cursor, weights, 0.0), axis=-1)
    out = jnp.einsum("shl,slhd->shd", jnp.where(at_cursor, 0.0, weights),
                     v.astype(jnp.float32))
    out = out + weight_new[..., None] * v_new.astype(jnp.float32)
    return out.astype(out_dtype)


def ring_rows_counted(k_all: jax.Array, valid: jax.Array) -> dict:
    """What one decode step's ``cached_decode_attention`` calls read of a
    stacked cache of merged rows [N, S, L, W] over slots of ``valid`` [S]
    live rows, and what the rings hold: ``ring_rows_read`` (over layers and
    slots, whole blocks up to the last live one where the kernel runs, every
    row where XLA reads the ring whole) and ``ring_rows_held`` (N x S x L),
    the int32 scalars a family's decode step returns behind its tokens."""
    n, s, n_rows, w = k_all.shape
    held = jnp.int32(n * s * n_rows)
    if not ring_decode.takes_kernel(n_rows, w):
        return {"ring_rows_read": held, "ring_rows_held": held}
    block = ring_decode.BLOCK_ROWS
    return {"ring_rows_read": (n * jnp.sum(-(-valid // block) * block)
                               ).astype(jnp.int32),
            "ring_rows_held": held}


def _scaled(scores, hd: int, scale: float | None):
    # None keeps the division the compiled steps have always held
    return scores / (hd ** 0.5) if scale is None else scores * scale


def _grouped_decode_attention(q, k, v, k_new, v_new, cursor, valid,
                              out_dtype, scale=None):
    """``cached_decode_attention`` for G K/V heads under H = G * R query
    heads: the same softmax over the same keys, the window read once."""
    s, h, hd = q.shape
    g = k.shape[2]
    q = q.astype(jnp.float32).reshape(s, g, h // g, hd)
    idx = jnp.arange(k.shape[1])
    at_cursor = (idx[None, :] == cursor[:, None])[:, None, None, :]
    mask = (idx[None, :] < valid[:, None])[:, None, None, :]
    scores = jnp.einsum("sgrd,slgd->sgrl", q, k.astype(jnp.float32))
    score_new = jnp.einsum("sgrd,sgd->sgr", q, k_new.astype(jnp.float32))
    scores = _scaled(jnp.where(at_cursor, score_new[..., None], scores),
                     hd, scale)
    weights = jax.nn.softmax(jnp.where(mask, scores, -1e30), axis=-1)
    weight_new = jnp.sum(jnp.where(at_cursor, weights, 0.0), axis=-1)
    out = jnp.einsum("sgrl,slgd->sgrd", jnp.where(at_cursor, 0.0, weights),
                     v.astype(jnp.float32))
    out = out + weight_new[..., None] \
        * v_new.astype(jnp.float32)[:, :, None, :]
    return out.reshape(s, h, hd).astype(out_dtype)


def _merged_decode_attention(q, k, v, k_new, v_new, cursor, valid,
                             out_dtype, scale=None, layer=None):
    """``cached_decode_attention`` over merged rows: k/v [S, L, W], k_new /
    v_new [S, W]. The same softmax over the same keys; each head's query
    stands in its own columns of a row-wide vector (grouped queries: in
    its K/V head's, which the group's heads share), so both products read
    the layer's block as it lies (two MXU products a layer) and the other
    columns add exact zeros. With ``layer``, k/v are the stacked cache [N,
    S, L, W] and the kernel makes the sums, up to each slot's last live
    block of rows; without, XLA does over the whole ring (the form the
    kernel is held to)."""
    _, h, hd = q.shape
    w = k.shape[-1]
    q = _step_query_rows(q, w, k.dtype)
    if layer is None:
        out = _whole_ring_sums(q, k, v, k_new, v_new, cursor, valid, hd,
                               scale)
    else:
        # whole sublane tiles of heads: a zero query's row is cut off again
        out = ring_decode.ring_decode_attention(
            jnp.pad(q, ((0, 0), (0, -h % 8), (0, 0))), k, v, layer, k_new,
            v_new, cursor, valid,
            functools.partial(_scaled, hd=hd, scale=scale))[:, :h]
    return _step_own_columns(out, hd).astype(out_dtype)


def _step_query_rows(q: jax.Array, w: int, dtype) -> jax.Array:
    """A step's queries q [N, H, hd] as the products over merged rows of
    ``w`` columns take them, [N, H, W] in ``dtype``: each head's numbers in
    its own columns (grouped queries: in its K/V head's), zeros in the
    others."""
    n, h, hd = q.shape
    g = _kv_heads(h, hd, w)
    if g == h:
        return _heads_apart(
            merged_rows(q.reshape(n, h * hd), w).astype(dtype), h, hd)
    return _heads_in_group_columns(q.astype(dtype), g)


def _step_own_columns(sums: jax.Array, hd: int) -> jax.Array:
    """The pick back of ``_step_query_rows``: sums [N, H, W] over merged
    value rows, a row a head -> [N, H, hd], each head's own columns."""
    n, h, w = sums.shape
    g = _kv_heads(h, hd, w)
    if g == h:
        return _heads_merged(sums, hd)[:, :h * hd].reshape(n, h, hd)
    return _heads_out_of_group_columns(sums, g)


def _whole_ring_sums(q, k, v, k_new, v_new, cursor, valid, hd, scale):
    """float32 sums [S, H, W] over merged value rows of queries q [S, H, W]
    that stand in their own columns: every ring row is read, a mask keeps
    the live ones, the new token's score stands at the cursor's row and its
    value is added beside the window's."""
    idx = jnp.arange(k.shape[1])
    at_cursor = (idx[None, :] == cursor[:, None])[:, None, :]  # [S, 1, L]
    mask = (idx[None, :] < valid[:, None])[:, None, :]
    scores = jnp.einsum("shw,slw->shl", q, k,
                        preferred_element_type=jnp.float32)
    score_new = jnp.einsum("shw,sw->sh", q, k_new,
                           preferred_element_type=jnp.float32)
    scores = _scaled(jnp.where(at_cursor, score_new[..., None], scores),
                     hd, scale)
    weights = jax.nn.softmax(jnp.where(mask, scores, -1e30), axis=-1)
    weight_new = jnp.sum(jnp.where(at_cursor, weights, 0.0), axis=-1)
    # float32 probabilities into the sums, as the heads-apart forms have
    # them: at the default precision the MXU would round them to bfloat16
    out = jnp.einsum("shl,slw->shw", jnp.where(at_cursor, 0.0, weights),
                     v.astype(jnp.float32),
                     precision=jax.lax.Precision.HIGHEST)
    return out + weight_new[..., None] * v_new.astype(jnp.float32)[:, None]


# -- latent cache of the serving path ------------------------------------------
#
# Latent attention (MLA) caches, a token a layer, ONE row that every query
# head shares: the normalised latent ``c`` (``rank`` numbers, from which a
# head's key and value are products ``c W_uk,h`` and ``c W_uv,h``) beside the
# rotated position key (``rope`` numbers). The stacked cache is
# [N, S, L, 1, rank + rope]: a row is one "head" as wide as both, so
# ``cache_write_token`` / ``cache_write_prompt`` above write it as they write
# K/V rows. Its two attentions differ from the K/V ones in more than width.
# At the shapes this cache exists for (a hundred heads over tens of thousands
# of rows a slot) neither a float32 copy of a window nor a scores array over
# the whole ring fits beside the weights, and the decode attention does as
# many operations a byte as the chip's ridge, so both read the ring in
# blocks of keys, in the cache's type, with a running softmax in float32,
# and stop at the last block that holds a key anyone may see (a ``while``
# whose trip count the positions decide: one compiled program, whose work
# follows the keys in sight).

# Keys a block, as measured on a v5e at 128 heads over rows of 576 (PERF.md
# section 6, PR 38; a block's scores are [S, H, block] float32 in the step,
# [H, C, block] in a chunk, and what a block costs is mostly their trips
# to memory and back). One layer's step over 65 rings of 16896 rows:
# 5.59 / 4.70 / 4.23 / 5.08 / 7.37 ms at 256 / 512 / 1024 / 1280 / 2048.
# One layer's chunk of 256 queries over 4352 keys: 2.38 / 1.97 / 2.65 /
# 3.12 / 5.63 ms at 128 / 256 / 384 / 512 / 1024 (a block that is no
# multiple of 128 lanes costs five times as much).
# Since PR 59 the chunk's XLA loop, and with it ``LATENT_CHUNK_BLOCK``, is
# what the shapes run that ``latent_chunk.takes_kernel`` turns away: a rank
# or a head of no whole 128-lane tiles (every tiny preset of the CPU tests
# and of the benchmark's rehearsals), a ring of no whole kernel blocks, a
# chunk of no whole sublane tiles. Whole-tile shapes, the published widths
# among them, go through the kernel of ``ops/latent_chunk.py``, whose block
# is its own (``latent_chunk.BLOCK_ROWS``). The 256 stands for the loop: it
# was swept where a block's cost is its scores' trips to memory, and that is
# still what the loop is wherever it runs.
LATENT_DECODE_BLOCK = 1024
LATENT_CHUNK_BLOCK = 256


def _online_softmax(carry, scores, seen, product):
    """One block of a running softmax: carry (m, l, acc) in float32,
    scores float32 with ``seen`` the keys that count, ``product(p)`` the
    block's weighted values for float32 probabilities p (the caller casts
    them to its values' type)."""
    m, l, acc = carry
    scores = jnp.where(seen, scores, -1e30)
    m_new = jnp.maximum(m, jnp.max(scores, axis=-1))
    alpha = jnp.exp(m - m_new)
    p = jnp.where(seen, jnp.exp(scores - m_new[..., None]), 0.0)
    return (m_new, l * alpha + jnp.sum(p, axis=-1),
            acc * alpha[..., None] + product(p))


def _ring_block(j, block: int, n_rows: int):
    """Block j of a ring of n_rows: the row it starts at, its rows'
    indices, and which of them no earlier block gave (the last block is
    moved back inside the ring, so some of its rows were read before)."""
    at = jnp.minimum(j * block, n_rows - block)
    idx = at + jnp.arange(block)
    return at, idx, idx >= j * block


# decode-path  # jax-hot-path: the latent cache stays in the activation dtype
def latent_decode_attention(q_lat: jax.Array, q_pe: jax.Array,
                            cache: jax.Array, layer: int, row_new: jax.Array,
                            cursor: jax.Array, valid: jax.Array,
                            scale: float, out_dtype,
                            block: int = LATENT_DECODE_BLOCK) -> jax.Array:
    """The ABSORBED form: one query token a slot over the slot's ring of
    latent rows, the token itself included, WITHOUT its row being in the
    cache yet (``cached_decode_attention``'s contract).

    q_lat [S, H, rank], a head's query already multiplied into the latent
    space (``q_nope_h W_uk,h^T``); q_pe [S, H, rope], rotated; cache the
    stacked [N, S, L, 1, rank + rope] as it was before this step (read
    only), ``layer`` the static index of this layer's rings; row_new
    [S, rank + rope], this token's row in the cache's type; cursor [S] the
    ring row it will take, valid [S] the live rows with it.
    ``score = (q_lat . c + q_pe . k_pe) * scale``; -> [S, H, rank], the
    probabilities' sum of the latents ``c`` (the caller multiplies it by
    ``W_uv``). The new token's score and latent start the running softmax;
    the ring is then read in blocks of ``block`` rows up to the longest
    live context of any slot, the row at a slot's cursor left out (a
    wrapped ring drops it, as ever). Operands in the cache's type; one
    block's scores, the running statistics and the sums are the only
    single-precision values (each such line carries its waiver of the
    region's rule: none is as long as a ring)."""
    s, h, rank = q_lat.shape
    n_rows = cache.shape[2]
    block = min(block, n_rows)
    q = jnp.concatenate([q_lat, q_pe], axis=-1).astype(cache.dtype)
    row_new = row_new.astype(cache.dtype)
    score_new = jnp.einsum(
        "shw,sw->sh", q, row_new,  # [S, H] scores of the new row
        preferred_element_type=jnp.float32) * scale  # analyze: ignore[JX004]
    # the running softmax's statistics and sums, [S, H] and [S, H, rank]
    carry = (score_new, jnp.ones((s, h), jnp.float32),  # analyze: ignore[JX004]
             jnp.broadcast_to(
                 row_new[:, None, :rank].astype(jnp.float32),  # analyze: ignore[JX004]
                 (s, h, rank)))

    def body(j, carry):
        at, idx, fresh = _ring_block(j, block, n_rows)
        # two reads of the ring, each by the one product that uses it:
        # a block sliced once for both is copied out first (76 MB a block
        # at 65 slots; seen on the chip)
        rows, latents = (jax.lax.dynamic_slice(
            cache, (layer, 0, at, 0, 0),
            (1, s, block, 1, width))[0, :, :, 0]
            for width in (cache.shape[-1], rank))  # [S, B, W], [S, B, rank]
        seen = (idx[None, :] < valid[:, None]) \
            & (idx[None, :] != cursor[:, None]) & fresh[None, :]
        # one block's scores [S, H, B] and its sums [S, H, rank]: the
        # products' accumulators, not a copy of the rows
        scores = jnp.einsum(
            "shw,sbw->shb", q, rows,
            preferred_element_type=jnp.float32) * scale  # analyze: ignore[JX004]
        return _online_softmax(
            carry, scores, seen[:, None, :],
            lambda p: jnp.einsum(
                "shb,sbr->shr", p.astype(latents.dtype), latents,
                preferred_element_type=jnp.float32))  # analyze: ignore[JX004]

    n_blocks = (jnp.max(valid) + block - 1) // block
    _, l, acc = jax.lax.fori_loop(0, n_blocks, body, carry)
    return (acc / l[..., None]).astype(out_dtype)


def latent_chunk_attention(q_nope: jax.Array, q_pe: jax.Array,
                           cache: jax.Array, layer: int, slots: jax.Array,
                           start: jax.Array, w_uk: jax.Array,
                           w_uv: jax.Array, scale: float,
                           block: int = LATENT_CHUNK_BLOCK) -> jax.Array:
    """The DECOMPRESSED form: a chunk of C prompt tokens a row over the
    row's own slot, AFTER the chunk's latent rows were written there
    (``cache_write_prompt``; ``cached_chunk_attention``'s contract).

    q_nope [R, C, H, nope] and q_pe [R, C, H, rope] (rotated), the queries
    of positions ``start[r] + i``; cache the stacked [N, S, L, 1, rank +
    rope] the layer loop carries; w_uk [rank, H, nope] and w_uv [rank, H,
    v] the products that make a head's key and value of a latent. Query i
    sees the slot's rows ``<= start + i``. The ring is read in blocks of
    rows up to the last one that holds such a row (``start + C``): each
    block's keys and values are decompressed, scored against the chunk's
    queries and folded into their running softmax, so the work follows the
    keys in sight and not the longest prompt. -> [R, C, H, v] in q's type.
    Operands in the cache's type, float32 scores, statistics and sums.

    One algorithm, two implementations, chosen by whether Mosaic can tile
    the shapes (``latent_chunk.takes_kernel``). Latents, keys and values of
    whole 128-lane tiles over a ring of whole blocks: the Pallas kernel of
    ``ops/latent_chunk.py``, a call a row, handed the row's own slot of
    this layer copied out of the stack (a custom call's operand is
    row-major; the stack lies ring-rows-minor), in which a block's keys,
    values and scores never leave VMEM. Everything else (toy widths): the
    XLA loop below over blocks of ``block`` rows, the block decompressed
    under scope ``kv_up`` and met by the chunk's queries ``CHUNK_QUERIES``
    at a time, its scores through memory."""
    r, c, h, _ = q_nope.shape
    rank, v_dim = w_uv.shape[0], w_uv.shape[-1]
    n_rows = cache.shape[2]
    block = min(block, n_rows)
    dt_ = cache.dtype
    w_uk, w_uv = w_uk.astype(dt_), w_uv.astype(dt_)
    if latent_chunk.takes_kernel(c, rank, q_nope.shape[-1], v_dim, n_rows):
        def ring_of(i):  # [L, W]: row i's slot of this layer, copied out
            return jax.lax.dynamic_slice(
                cache, (layer, slots[i], 0, 0, 0),
                (1, 1, n_rows, 1, cache.shape[-1]))[0, 0, :, 0]
        return jnp.stack([latent_chunk.latent_chunk_attention(
            q_nope[i].astype(dt_), q_pe[i].astype(dt_), ring_of(i),
            start[i], w_uk, w_uv, scale) for i in range(r)]).astype(
                q_nope.dtype)

    # a block is decompressed once and meets the chunk's queries a group
    # at a time (``CHUNK_QUERIES``; a chunk that is no whole number of
    # groups, a shorter one among them, goes whole)
    g = CHUNK_QUERIES if c % CHUNK_QUERIES == 0 else c
    groups = [slice(at, at + g) for at in range(0, c, g)]

    def one_row(i):  # over its own slot and its own keys
        qn, qp = q_nope[i].astype(dt_), q_pe[i].astype(dt_)
        sees = start[i] + jnp.arange(c)  # [C]

        def body(j, carry):
            at, idx, fresh = _ring_block(j, block, n_rows)
            rows = jax.lax.dynamic_slice(
                cache, (layer, slots[i], at, 0, 0),
                (1, 1, block, 1, cache.shape[-1]))[0, 0, :, 0]  # [B, W]
            with jax.named_scope("kv_up"):
                k = jnp.einsum("br,rhd->bhd", rows[:, :rank], w_uk)
                v = jnp.einsum("br,rhd->bhd", rows[:, :rank], w_uv)

            def fold(of, carry):  # the group's queries over this block
                seen = (idx[None, :] <= sees[of][:, None]) \
                    & fresh[None, :]  # [G, B]
                scores = (jnp.einsum("chd,bhd->hcb", qn[of], k,
                                     preferred_element_type=jnp.float32)
                          + jnp.einsum("chp,bp->hcb", qp[of], rows[:, rank:],
                                       preferred_element_type=jnp.float32)) \
                    * scale
                return _online_softmax(
                    carry, scores, seen[None],
                    lambda p: jnp.einsum(
                        "hcb,bhd->hcd", p.astype(v.dtype), v,
                        preferred_element_type=jnp.float32))

            return tuple(fold(of, part) for of, part in zip(groups, carry))

        n_blocks = jnp.minimum(start[i] + c + block - 1,
                               n_rows + block - 1) // block
        parts = jax.lax.fori_loop(0, n_blocks, body, tuple((
            jnp.full((h, g), -1e30, jnp.float32),
            jnp.zeros((h, g), jnp.float32),
            jnp.zeros((h, g, v_dim), jnp.float32)) for _ in groups))
        return jnp.concatenate([jnp.swapaxes(acc / l[..., None], 0, 1)
                                for _, l, acc in parts])  # [C, H, v]

    # a few rows, each a loop of its own length
    return jnp.stack([one_row(i) for i in range(r)]).astype(q_nope.dtype)
