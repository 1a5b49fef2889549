"""GPT-2, plainly: forward pass, loss and gradients in float32 jax.numpy.

Written from the published description (Radford et al. 2019, "Language
Models are Unsupervised Multitask Learners", and the released model's
``config.json``): learned token and position embeddings; pre-LayerNorm
blocks of multi-head causal self-attention and a 4x MLP with the tanh
approximation of GELU ("gelu_new"); a final LayerNorm; the output head tied
to the token embedding. No kernel, no cache, no sharding, no batching
tricks; every matmul runs under ``jax.default_matmul_precision("highest")``
so that a TPU does not quietly compute it in bfloat16. Independent of
``ray_tpu/models/gpt2.py``: it shares no code with it, and takes its
weights under the released checkpoint's own names (``wte``, ``wpe``,
``h.ln_1``, ``h.attn.c_attn`` ...), block weights stacked on a leading
layer axis.

Departures from the plainest possible text, none of which changes the
mathematics: the blocks are iterated with ``lax.scan`` (one compiled block
instead of 48 copies); and ``remat=True`` recomputes each block in the
backward pass, which the harness asks for only where the plain gradient
does not fit the chip (GPT-2 XL: 6.2 GB of weights and as much again of
gradients).
"""

from __future__ import annotations

import math

import jax
import jax.numpy as jnp


def layer_norm(x, g, b, eps):
    mu = jnp.mean(x, axis=-1, keepdims=True)
    var = jnp.mean(jnp.square(x - mu), axis=-1, keepdims=True)
    return (x - mu) / jnp.sqrt(var + eps) * g + b


def gelu_new(x):
    return 0.5 * x * (1.0 + jnp.tanh(
        math.sqrt(2.0 / math.pi) * (x + 0.044715 * x ** 3)))


def block(x, p, n_head: int, eps: float):
    """One transformer block. x [B, T, d] float32."""
    b, t, d = x.shape
    hd = d // n_head
    a = layer_norm(x, p["ln_1_g"], p["ln_1_b"], eps)
    qkv = a @ p["c_attn_w"] + p["c_attn_b"]
    q, k, v = jnp.split(qkv, 3, axis=-1)
    q, k, v = (z.reshape(b, t, n_head, hd).transpose(0, 2, 1, 3)
               for z in (q, k, v))
    scores = q @ k.transpose(0, 1, 3, 2) / math.sqrt(hd)
    causal = jnp.tril(jnp.ones((t, t), bool))
    scores = jnp.where(causal, scores, -jnp.inf)
    attn = jax.nn.softmax(scores, axis=-1) @ v
    attn = attn.transpose(0, 2, 1, 3).reshape(b, t, d)
    x = x + attn @ p["attn_c_proj_w"] + p["attn_c_proj_b"]
    m = layer_norm(x, p["ln_2_g"], p["ln_2_b"], eps)
    m = gelu_new(m @ p["c_fc_w"] + p["c_fc_b"])
    return x + m @ p["mlp_c_proj_w"] + p["mlp_c_proj_b"]


def forward(params, tokens, *, n_head: int, eps: float = 1e-5,
            remat: bool = False):
    """tokens [B, T] int32 -> logits [B, T, V] float32."""
    with jax.default_matmul_precision("highest"):
        t = tokens.shape[1]
        x = params["wte"][tokens] + params["wpe"][:t]

        def body(x, p):
            return block(x, p, n_head, eps), None

        if remat:
            body = jax.checkpoint(body)
        x, _ = jax.lax.scan(body, x, params["h"])
        x = layer_norm(x, params["ln_f_g"], params["ln_f_b"], eps)
        return x @ params["wte"].T


def loss(params, tokens, *, n_head: int, eps: float = 1e-5,
         remat: bool = False):
    """Mean next-token cross-entropy of rows of T+1 tokens."""
    logits = forward(params, tokens[:, :-1], n_head=n_head, eps=eps,
                     remat=remat)
    logp = jax.nn.log_softmax(logits, axis=-1)
    picked = jnp.take_along_axis(logp, tokens[:, 1:, None], axis=-1)
    return -jnp.mean(picked)


def loss_and_grad_norm(params, tokens, *, n_head: int, eps: float = 1e-5,
                       remat: bool = False):
    """(loss, global L2 norm of its gradient over every parameter)."""
    value, grads = jax.value_and_grad(loss)(
        params, tokens, n_head=n_head, eps=eps, remat=remat)
    sq = sum(jnp.sum(jnp.square(g)) for g in jax.tree.leaves(grads))
    return value, jnp.sqrt(sq)
