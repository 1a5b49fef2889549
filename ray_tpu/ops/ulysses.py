"""Ulysses sequence parallelism: all-to-all head scatter.

The second SP scheme (SURVEY.md §2.4/§5.7): inputs arrive sequence-sharded
[B, T/sp, H, D]; an ``all_to_all`` over the ``sp`` axis re-shards to
head-sharded [B, T, H/sp, D], each device runs FULL-sequence attention on
its head subset (any kernel — XLA or flash), and a second all_to_all
restores sequence sharding. Two collectives bound the whole exchange, vs
sp ppermutes for ring attention; preferable when H >= sp and the ICI
all-to-all bandwidth is good (intra-slice).
"""

from __future__ import annotations

import functools
from typing import Callable

import jax
from jax import shard_map
from jax.sharding import Mesh, PartitionSpec as P

from ray_tpu.ops.attention import xla_causal_attention


def ulysses_attention_local(q, k, v, *, axis: str = "sp",
                            attn_fn: Callable = xla_causal_attention,
                            softmax_scale: float | None = None):
    """Per-device body (call inside shard_map over ``axis``).

    q/k/v: [B, T/sp, H, D] -> out [B, T/sp, H, D].
    """

    def scatter_heads(x):
        # [B, C, H, D] -> [B, sp*C, H/sp, D]: split heads, gather sequence.
        return jax.lax.all_to_all(
            x, axis, split_axis=2, concat_axis=1, tiled=True
        )

    def gather_heads(x):
        return jax.lax.all_to_all(
            x, axis, split_axis=1, concat_axis=2, tiled=True
        )

    qh, kh, vh = scatter_heads(q), scatter_heads(k), scatter_heads(v)
    out = attn_fn(qh, kh, vh, softmax_scale=softmax_scale)
    return gather_heads(out)


def ulysses_attention(q, k, v, mesh: Mesh, *, axis: str = "sp",
                      attn_fn: Callable = xla_causal_attention,
                      softmax_scale: float | None = None,
                      batch_axes=("dp", "fsdp")):
    """Full-array entry: [B, T, H, D] with T sharded over ``axis``."""
    if q.shape[2] % mesh.shape[axis]:
        raise ValueError(
            f"n_heads {q.shape[2]} must divide by sp={mesh.shape[axis]}"
        )
    spec = P(batch_axes, axis, None, None)
    fn = shard_map(
        functools.partial(
            ulysses_attention_local, axis=axis, attn_fn=attn_fn,
            softmax_scale=softmax_scale,
        ),
        mesh=mesh,
        in_specs=(spec, spec, spec),
        out_specs=spec,
        check_vma=False,
    )
    return fn(q, k, v)
