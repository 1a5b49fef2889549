"""Operations and bytes an algorithm needs, computed from shapes alone.

These are the numerators of every utilization and roofline share the
benchmark reports. Nothing here looks at the program: what is counted is
the work the mathematics requires, so recomputation, padding rows and
wasted reads all lower a share instead of hiding in it.
"""

from __future__ import annotations


def train_flops_per_token(n_layer: int, d_model: int, vocab: int,
                          seq_len: int) -> float:
    """Forward + backward operations one trained token requires.

    ``6 * (12 L d^2 + V d) + 6 L d T``: every weight matrix is used in one
    multiply-add forward and two backward (6 operations a parameter a
    token); the block's matrices hold 12 d^2 parameters (qkv 3, out 1,
    MLP 8); the tied head is counted once, at the published vocabulary
    (padding rows are not required work); the embedding lookup is not a
    matmul and is not counted; causal attention's scores and weighted sum
    are counted at half the square (4 d T forward for the full square,
    halved, times 3 for forward + backward); nothing recomputed counts.
    """
    matmul_params = 12 * n_layer * d_model * d_model + vocab * d_model
    return 6.0 * matmul_params + 6.0 * n_layer * d_model * seq_len


def flash_forward(batch: int, heads: int, seq: int, head_dim: int,
                  dtype_bytes: int = 2) -> tuple[float, float]:
    """(operations, bytes) of one causal attention forward call.

    Two matmuls (QK^T and PV) of 2 T^2 hd operations each per head at the
    full square, halved for causality. Bytes: read Q, K, V and write O
    once, plus the fp32 log-sum-exp row statistics."""
    ops = batch * heads * 2.0 * seq * seq * head_dim
    io = 4.0 * batch * heads * seq * head_dim * dtype_bytes \
        + 4.0 * batch * heads * seq
    return ops, io


def flash_backward(batch: int, heads: int, seq: int, head_dim: int,
                   dtype_bytes: int = 2) -> tuple[float, float]:
    """(operations, bytes) of one causal attention backward call.

    Five matmuls (recompute S; dP = dO V^T; dV = P^T dO; dK = dS^T Q;
    dQ = dS K), halved for causality. Bytes: read Q, K, V, O, dO and the
    statistics, write dQ, dK, dV."""
    ops = batch * heads * 5.0 * seq * seq * head_dim
    io = 8.0 * batch * heads * seq * head_dim * dtype_bytes \
        + 8.0 * batch * heads * seq
    return ops, io


def roofline_seconds(ops: float, io_bytes: float, peak: dict
                     ) -> tuple[float, str]:
    """Least time the chip could take, and which peak bounds it."""
    t_ops = ops / peak["flops_per_s"]
    t_io = io_bytes / peak["bytes_per_s"]
    return (t_ops, "compute") if t_ops >= t_io else (t_io, "memory")


def kv_bytes_per_token(n_layer: int, d_model: int,
                       dtype_bytes: int = 2) -> float:
    """K and V rows one cached token holds across all layers."""
    return 2.0 * n_layer * d_model * dtype_bytes


def kv_bytes_per_slot(n_layer: int, d_model: int, cache_len: int,
                      dtype_bytes: int = 2) -> float:
    return kv_bytes_per_token(n_layer, d_model, dtype_bytes) * cache_len


def gpt2_param_count(n_layer: int, d_model: int, vocab_rows: int,
                     n_positions: int) -> int:
    """Parameters as held: tied embedding (``vocab_rows`` may include
    padding), positions, 12 d^2 + 13 d a block, final LayerNorm."""
    per_layer = 12 * d_model * d_model + 13 * d_model
    return (vocab_rows * d_model + n_positions * d_model
            + n_layer * per_layer + 2 * d_model)


def decode_step_bytes(weight_bytes: float, occupancy: float,
                      mean_context: float, n_layer: int, d_model: int,
                      kv_dtype_bytes: int = 2) -> float:
    """Bytes one decode step must read: every weight once, as stored,
    and the live K/V rows of the occupied slots."""
    return weight_bytes + occupancy * mean_context * kv_bytes_per_token(
        n_layer, d_model, kv_dtype_bytes)
