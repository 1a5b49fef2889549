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


# -- KV-cache writes (serving decode path) ----------------------------------
#
# Shared by the GPT-2 and Llama decode APIs (``models/gpt2.py`` /
# ``models/llama.py``): the head-count axis differs (full vs GQA
# ``n_kv_head``) but the cursor-write contract is identical, so it lives
# here once.


# decode-path  # jax-hot-path: the KV cache stays in the activation dtype
def cache_write_token(cache: jax.Array, rows: jax.Array,
                      cursor: jax.Array) -> jax.Array:
    """Per-slot ring-cursor write of ONE token's K or V rows.

    cache [S, L, H, hd], rows [S, 1, H, hd], cursor [S] int32 — each
    slot's row lands at its own cursor (vmapped dynamic_update_slice)."""
    return jax.vmap(
        lambda c, r, i: jax.lax.dynamic_update_slice(
            c, r.astype(c.dtype), (i, 0, 0))
    )(cache, rows, cursor)


# decode-path  # jax-hot-path: the KV cache stays in the activation dtype
def cache_write_prompt(cache: jax.Array, rows: jax.Array,
                       slots: jax.Array) -> jax.Array:
    """Prefill-lane write: row block ``rows[i]`` ([P, H, hd]) lands at
    rows ``[0, P)`` of cache slot ``slots[i]``. Sequential over the
    (small, static) prefill-row axis — each write must see the prior
    ones, and distinct slots make the order immaterial."""
    def body(i, c):
        return jax.lax.dynamic_update_slice(
            c, rows[i][None].astype(c.dtype), (slots[i], 0, 0, 0))
    return jax.lax.fori_loop(0, rows.shape[0], body, cache)


def cached_decode_attention(q: jax.Array, k: jax.Array, v: jax.Array,
                            valid: jax.Array, out_dtype) -> jax.Array:
    """One query token per slot over the slot's ring-cache window.

    q [S, H, hd]; k/v [S, L, H, hd] (GQA callers expand KV heads to the
    query heads first); valid [S] = live cache entries (the ring mask).
    fp32 scores/softmax, output cast to the activation dtype — shared
    by both model families' decode steps so the masking/scaling
    contract lives here once."""
    hd = q.shape[-1]
    scores = jnp.einsum(
        "shd,slhd->shl", q.astype(jnp.float32), k.astype(jnp.float32)
    ) / (hd ** 0.5)
    mask = jnp.arange(k.shape[1])[None, :] < valid[:, None]  # [S, L]
    weights = jax.nn.softmax(
        jnp.where(mask[:, None, :], scores, -1e30), axis=-1)
    out = jnp.einsum("shl,slhd->shd", weights, v.astype(jnp.float32))
    return out.astype(out_dtype)
