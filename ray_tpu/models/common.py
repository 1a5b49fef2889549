"""What the served families' modules would otherwise each copy: the seeded
draw, the RMSNorm, the gated activation and the merged cache row. Only
statements that are the same in every family that uses them live here, and
none takes an argument that picks a variant: Qwen3-Next's zero-centred norm,
Llama's norm with its fixed epsilon and GPT-2's row from a flat projection
stay in their modules.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from ray_tpu.ops.attention import merged_rows


# (Named as each family's own copy was: a jitted callee's name is part of the
# text of every program that calls it, here the families' ``init``, and so of
# the compile cache's key. Renamed, a machine whose cache holds a family's
# init would compile it once more.)
@functools.partial(jax.jit, static_argnums=(1, 2, 3))
def _normal(key, shape, std, dtype):
    # under jit the float32 draw is never held whole beside its cast
    return (jax.random.normal(key, shape, jnp.float32) * std).astype(dtype)


def rms_norm(x: jax.Array, w: jax.Array, eps: float) -> jax.Array:
    """The RMSNorm ``N(x; w)`` over the last axis."""
    xf = x.astype(jnp.float32)
    xf = xf * jax.lax.rsqrt(jnp.mean(xf * xf, axis=-1, keepdims=True) + eps)
    return (xf * w.astype(jnp.float32)).astype(x.dtype)


def gate(ab: jax.Array) -> jax.Array:
    """``silu(a) * b`` of ``[a, b]`` side by side in the last axis."""
    half = ab.shape[-1] // 2
    return jax.nn.silu(ab[..., :half]) * ab[..., half:]


def merged_row(rows: jax.Array, cache: jax.Array) -> jax.Array:
    """A token's K or V heads [..., G, hd] as the cache holds them: side
    by side in one row [..., W], in its type."""
    return merged_rows(rows.reshape(*rows.shape[:-2], -1).astype(cache.dtype),
                       cache.shape[-1])
