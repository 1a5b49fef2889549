"""Ring attention: causal attention over a sequence-sharded axis.

Long-context scaling (SURVEY.md §5.7 — absent from the reference, required
here): Q/K/V are sharded over mesh axis ``sp`` ([B, T/sp, H, D] per
device). Each device computes blockwise attention of its Q shard against
the K/V shard it currently holds, then rotates K/V around the ring with
``ppermute`` — sp steps visit every KV block while only ever holding
O(T/sp) keys, and the permute overlaps with the next block's compute (XLA
schedules the collective-permute concurrently with the matmuls).

Causal masking across shards: a KV shard strictly *ahead* of the Q shard
contributes nothing (skipped by masking the whole block), the diagonal
shard uses the triangular mask, and shards behind contribute fully.
Online-softmax merging keeps fp32 running (max, denom, acc) — the same
math as flash attention, at ring granularity.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax import shard_map
from jax.sharding import Mesh, PartitionSpec as P

_NEG_INF = -1e30


def _block_attend(q, k, v, q_chunk_idx, kv_chunk_idx, chunk, scale):
    """Scores of local q against one kv chunk with cross-chunk causality.
    q,k,v: [B, C, H, D]; returns (scores_max m, exp-sum l, weighted acc)."""
    s = jnp.einsum("bqhd,bkhd->bhqk", q, k,
                   preferred_element_type=jnp.float32) * scale
    q_pos = q_chunk_idx * chunk + jnp.arange(chunk)
    k_pos = kv_chunk_idx * chunk + jnp.arange(chunk)
    mask = q_pos[:, None] >= k_pos[None, :]
    s = jnp.where(mask[None, None], s, _NEG_INF)
    m = jnp.max(s, axis=-1, keepdims=True)  # [B,H,Q,1]
    # Guard fully-masked rows (kv chunk entirely in the future).
    m_safe = jnp.maximum(m, _NEG_INF / 2)
    p = jnp.exp(s - m_safe)
    p = jnp.where(mask[None, None], p, 0.0)
    l = jnp.sum(p, axis=-1, keepdims=True)
    acc = jnp.einsum("bhqk,bkhd->bqhd", p, v.astype(jnp.float32))
    return m_safe, l, acc


def ring_causal_attention_local(q, k, v, *, axis_size: int, axis: str = "sp",
                                softmax_scale: float | None = None):
    """The per-device body: call INSIDE shard_map over ``axis``.

    q/k/v per-device: [B, C, H, D] where C = T / sp. The ring loop is
    unrolled (sp is small and static) so the whole op stays reverse-mode
    differentiable and XLA can overlap each ppermute with the next
    block's compute.
    """
    b, c, h, d = q.shape
    scale = softmax_scale if softmax_scale is not None else d**-0.5
    sp = axis_size
    my_idx = jax.lax.axis_index(axis)
    perm = [(i, (i - 1) % sp) for i in range(sp)]  # kv travels backward

    qf = q.astype(jnp.float32)
    m = jnp.full((b, h, c, 1), _NEG_INF / 2, jnp.float32)
    l = jnp.zeros((b, h, c, 1), jnp.float32)
    acc = jnp.zeros((b, c, h, d), jnp.float32)
    k_cur, v_cur = k, v
    for i in range(sp):
        kv_idx = (my_idx + i) % sp
        bm, bl, bacc = _block_attend(qf, k_cur, v_cur, my_idx, kv_idx, c, scale)
        m_new = jnp.maximum(m, bm)
        alpha = jnp.exp(m - m_new)
        beta = jnp.exp(bm - m_new)
        l = l * alpha + bl * beta
        acc = acc * jnp.swapaxes(alpha, 1, 2) + bacc * jnp.swapaxes(beta, 1, 2)
        m = m_new
        if i != sp - 1:
            k_cur = jax.lax.ppermute(k_cur, axis, perm)
            v_cur = jax.lax.ppermute(v_cur, axis, perm)
    out = acc / jnp.maximum(jnp.swapaxes(l, 1, 2), 1e-30)
    return out.astype(q.dtype)


# -- Pallas-fused ring attention (SURVEY §7 hard-part 5) -------------------
#
# Each ring step runs the flash kernel on (local Q) x (current KV block):
# per-block scores live only in VMEM tiles, never [B,H,C,C] in HBM. The
# per-block results are BLOCK-normalized (out_i, lse_i); merging in log
# space reconstructs the global softmax exactly:
#     lse = logaddexp_i(lse_i);  out = sum_i exp(lse_i - lse) * out_i.
# KV blocks strictly ahead of the Q shard are masked out of the merge with
# lse_i = -inf (same FLOPs as the dense ring variant, which also computed
# every block; skipping them is a load-balancing follow-up — cf. striped
# attention).
#
# Backward is a second ring pass: _flash_bwd with the GLOBAL (out, lse)
# yields this block's exact (dq, dk, dv) contributions (p = exp(s - lse)
# is the true global probability of the tile). dQ accumulates locally;
# dK/dV accumulators travel WITH their KV block and take one final
# ppermute home.


def _lse_to_weights(lse_bh, b, h, c):
    """[B*H, C, 1] fp32 -> broadcastable [B, C, H, 1] weight exponent."""
    return lse_bh.reshape(b, h, c, 1).transpose(0, 2, 1, 3)


def _ring_flash_fwd(q, k, v, axis, axis_size, scale, interpret):
    from ray_tpu.ops.flash_attention import _fit_block, _flash_fwd

    b, c, h, d = q.shape
    block_q = _fit_block(1024, c)
    block_k = _fit_block(1024, c)
    sp = axis_size
    my_idx = jax.lax.axis_index(axis)
    perm = [(i, (i - 1) % sp) for i in range(sp)]  # kv travels backward

    kwargs = dict(block_q=block_q, block_k=block_k, softmax_scale=scale,
                  interpret=interpret)
    out_r, lse_r = _flash_fwd(q, k, v, causal=True, **kwargs)
    out_r = out_r.astype(jnp.float32)
    k_cur, v_cur = k, v
    for i in range(1, sp):
        k_cur = jax.lax.ppermute(k_cur, axis, perm)
        v_cur = jax.lax.ppermute(v_cur, axis, perm)
        kv_idx = (my_idx + i) % sp
        o_i, lse_i = _flash_fwd(q, k_cur, v_cur, causal=False, **kwargs)
        lse_i = jnp.where(kv_idx > my_idx, _NEG_INF, lse_i)
        lse_new = jnp.logaddexp(lse_r, lse_i)
        w_r = jnp.exp(_lse_to_weights(lse_r - lse_new, b, h, c))
        w_i = jnp.exp(_lse_to_weights(lse_i - lse_new, b, h, c))
        out_r = out_r * w_r + o_i.astype(jnp.float32) * w_i
        lse_r = lse_new
    return out_r.astype(q.dtype), lse_r


@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4, 5, 6))
def _ring_flash_attention(q, k, v, axis, axis_size, scale, interpret):
    out, _ = _ring_flash_fwd(q, k, v, axis, axis_size, scale, interpret)
    return out


def _ring_vjp_fwd(q, k, v, axis, axis_size, scale, interpret):
    out, lse = _ring_flash_fwd(q, k, v, axis, axis_size, scale, interpret)
    return out, (q, k, v, out, lse)


def _ring_vjp_bwd(axis, axis_size, scale, interpret, res, g):
    from ray_tpu.ops.flash_attention import _fit_block, _flash_bwd

    q, k, v, out, lse = res
    b, c, h, d = q.shape
    block_q = _fit_block(1024, c)
    block_k = _fit_block(1024, c)
    sp = axis_size
    my_idx = jax.lax.axis_index(axis)
    perm = [(i, (i - 1) % sp) for i in range(sp)]

    kwargs = dict(block_q=block_q, block_k=block_k, softmax_scale=scale,
                  interpret=interpret)
    dq = jnp.zeros(q.shape, jnp.float32)
    k_cur, v_cur = k, v
    dk_acc = jnp.zeros(k.shape, jnp.float32)
    dv_acc = jnp.zeros(v.shape, jnp.float32)
    for i in range(sp):
        if i:
            k_cur = jax.lax.ppermute(k_cur, axis, perm)
            v_cur = jax.lax.ppermute(v_cur, axis, perm)
            dk_acc = jax.lax.ppermute(dk_acc, axis, perm)
            dv_acc = jax.lax.ppermute(dv_acc, axis, perm)
        if i:
            # Blocks ahead of this Q shard contributed nothing forward.
            # Masking through the lse (p = exp(s - huge) -> 0) zeroes
            # their gradients WITHOUT the overflow risk of computing
            # exp(s - lse) against an unrelated lse and multiplying by 0
            # afterwards (0 * inf = nan).
            kv_idx = (my_idx + i) % sp
            ahead = kv_idx > my_idx
            lse_use = jnp.where(ahead, jnp.full_like(lse, -_NEG_INF), lse)
            keep = (~ahead).astype(jnp.float32)
        else:
            lse_use, keep = lse, 1.0
        dq_i, dk_i, dv_i = _flash_bwd(
            q, k_cur, v_cur, out, lse_use, g, causal=(i == 0), **kwargs)
        dq_i = dq_i * keep
        dk_i = dk_i * keep
        dv_i = dv_i * keep
        dq = dq + dq_i.astype(jnp.float32)
        dk_acc = dk_acc + dk_i.astype(jnp.float32)
        dv_acc = dv_acc + dv_i.astype(jnp.float32)
    # One more hop returns each accumulator to its KV block's owner.
    dk_acc = jax.lax.ppermute(dk_acc, axis, perm)
    dv_acc = jax.lax.ppermute(dv_acc, axis, perm)
    return (dq.astype(q.dtype), dk_acc.astype(k.dtype),
            dv_acc.astype(v.dtype))


_ring_flash_attention.defvjp(_ring_vjp_fwd, _ring_vjp_bwd)


def ring_flash_attention_local(q, k, v, *, axis_size: int, axis: str = "sp",
                               softmax_scale: float | None = None):
    """Pallas-fused per-device ring attention body (call inside shard_map
    over ``axis``); differentiable. Falls back implicitly to interpret
    mode on CPU."""
    d = q.shape[-1]
    scale = softmax_scale if softmax_scale is not None else d**-0.5
    interpret = jax.default_backend() == "cpu"
    return _ring_flash_attention(
        q, k, v, axis, axis_size, scale, interpret)


def ring_causal_attention(q, k, v, mesh: Mesh, *, axis: str = "sp",
                          softmax_scale: float | None = None,
                          batch_axes=("dp", "fsdp"), impl: str = "fused"):
    """Full-array entry: q/k/v [B, T, H, D] with T sharded over ``axis``.

    ``impl="fused"`` (default) runs the flash kernel on every ring block;
    ``impl="dense"`` keeps the einsum body (debug/fallback — materializes
    [B,H,C,C] scores per block)."""
    local = (ring_flash_attention_local if impl == "fused"
             else ring_causal_attention_local)
    spec = P(batch_axes, axis, None, None)
    fn = shard_map(
        functools.partial(
            local, axis=axis,
            axis_size=mesh.shape[axis], softmax_scale=softmax_scale,
        ),
        mesh=mesh,
        in_specs=(spec, spec, spec),
        out_specs=spec,
        check_vma=False,
    )
    return fn(q, k, v)
