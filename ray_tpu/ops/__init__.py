"""Compute ops: attention (XLA + Pallas flash), fused layers, collectives.

The MXU-facing layer: everything here is written for large, static-shaped,
bf16 matmuls that XLA can tile onto the systolic array, with Pallas kernels
for the ops XLA does not fuse well (flash attention with causal masking).
"""

from ray_tpu.ops.attention import causal_attention, xla_causal_attention
from ray_tpu.ops.flash_attention import flash_causal_attention
from ray_tpu.ops.ring_attention import (
    ring_causal_attention,
    ring_causal_attention_local,
    ring_flash_attention_local,
)
from ray_tpu.ops.ulysses import ulysses_attention, ulysses_attention_local
from ray_tpu.ops.moe import (dropless_experts, init_moe_params, moe_ffn,
                             moe_ffn_ep, route, route_topk_softmax)

__all__ = [
    "causal_attention",
    "xla_causal_attention",
    "flash_causal_attention",
    "ring_causal_attention",
    "ring_causal_attention_local",
    "ring_flash_attention_local",
    "ulysses_attention",
    "ulysses_attention_local",
    "dropless_experts",
    "init_moe_params",
    "moe_ffn",
    "moe_ffn_ep",
    "route",
    "route_topk_softmax",
]
