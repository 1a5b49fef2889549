"""A Llama-style decoder, plainly: forward pass, loss and gradients in
float32 jax.numpy, for the toy family that proves the harness takes a
second family as files (never a benchmark configuration).

Written from the published description (Touvron et al. 2023, "LLaMA: Open
and Efficient Foundation Language Models", and the ``config.json`` keys of
its released checkpoints): token embedding, no position table; pre-RMSNorm
blocks of causal self-attention with rotary position embeddings on queries
and keys and grouped-query heads (each key/value head serves
``n_head // n_kv_head`` query heads), and a SwiGLU MLP
(``down(silu(gate(x)) * up(x))``); a final RMSNorm; an output head that is
not tied to the embedding. No bias anywhere. No kernel, no cache, no
sharding; every matmul runs under ``jax.default_matmul_precision("highest")``.
Independent of ``ray_tpu/models/llama.py``: it shares no code with it, and
takes its weights under the released checkpoints' own names
(``embed_tokens``, ``layers.q_proj`` ... ``lm_head``), block weights
stacked on a leading layer axis and stored ``[in, out]``.
"""

from __future__ import annotations

import math

import jax
import jax.numpy as jnp


def rms_norm(x, g, eps):
    return x / jnp.sqrt(jnp.mean(jnp.square(x), -1, keepdims=True) + eps) * g


def rotate_half(x):
    x1, x2 = jnp.split(x, 2, axis=-1)
    return jnp.concatenate([-x2, x1], axis=-1)


def rope(x, theta: float):
    """x [B, H, T, hd]: pair (i, i + hd/2) turns by t * theta^(-2i/hd)."""
    t, hd = x.shape[-2], x.shape[-1]
    inv = theta ** (-jnp.arange(0, hd, 2, dtype=jnp.float32) / hd)
    ang = jnp.arange(t, dtype=jnp.float32)[:, None] * inv[None, :]
    ang = jnp.concatenate([ang, ang], axis=-1)
    return x * jnp.cos(ang) + rotate_half(x) * jnp.sin(ang)


def block(x, p, n_head: int, n_kv_head: int, eps: float, theta: float):
    """One decoder block. x [B, T, d] float32."""
    b, t, d = x.shape
    hd = d // n_head
    a = rms_norm(x, p["input_layernorm"], eps)
    heads = lambda z, n: z.reshape(b, t, n, hd).transpose(0, 2, 1, 3)
    q = rope(heads(a @ p["q_proj"], n_head), theta)
    k = rope(heads(a @ p["k_proj"], n_kv_head), theta)
    v = heads(a @ p["v_proj"], n_kv_head)
    group = n_head // n_kv_head
    k, v = (jnp.repeat(z, group, axis=1) for z in (k, v))
    scores = q @ k.transpose(0, 1, 3, 2) / math.sqrt(hd)
    scores = jnp.where(jnp.tril(jnp.ones((t, t), bool)), scores, -jnp.inf)
    attn = jax.nn.softmax(scores, axis=-1) @ v
    x = x + attn.transpose(0, 2, 1, 3).reshape(b, t, d) @ p["o_proj"]
    m = rms_norm(x, p["post_attention_layernorm"], eps)
    m = jax.nn.silu(m @ p["gate_proj"]) * (m @ p["up_proj"])
    return x + m @ p["down_proj"]


def forward(params, tokens, *, n_head: int, n_kv_head: int, eps: float,
            theta: float, remat: bool = False):
    """tokens [B, T] int32 -> logits [B, T, V] float32."""
    with jax.default_matmul_precision("highest"):
        x = params["embed_tokens"][tokens]

        def body(x, p):
            return block(x, p, n_head, n_kv_head, eps, theta), None

        if remat:
            body = jax.checkpoint(body)
        x, _ = jax.lax.scan(body, x, params["layers"])
        return rms_norm(x, params["norm"], eps) @ params["lm_head"]


def loss(params, tokens, *, remat: bool = False, **kwargs):
    """Mean next-token cross-entropy of rows of T+1 tokens."""
    logits = forward(params, tokens[:, :-1], remat=remat, **kwargs)
    logp = jax.nn.log_softmax(logits, axis=-1)
    return -jnp.mean(jnp.take_along_axis(logp, tokens[:, 1:, None], axis=-1))


def loss_and_grad_norm(params, tokens, *, remat: bool = False, **kwargs):
    """(loss, global L2 norm of its gradient over every parameter)."""
    value, grads = jax.value_and_grad(loss)(params, tokens, remat=remat,
                                            **kwargs)
    sq = sum(jnp.sum(jnp.square(g)) for g in jax.tree.leaves(grads))
    return value, jnp.sqrt(sq)
