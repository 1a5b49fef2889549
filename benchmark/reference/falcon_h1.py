"""Falcon-H1 (``model_type: falcon_h1``), written plainly: float32
``jax.numpy`` at ``highest`` matmul precision, the recurrence as a scan over
tokens, no cache, no chunking, no kernels, nothing imported from the
program.

Source: the ``config.json`` of ``tiiuae/Falcon-H1-34B-Instruct`` and the
released ``modeling_falcon_h1``'s order of operations.

The equations (d = ``hidden_size``; RMSNorm(x) = ``x / sqrt(mean(x^2) + eps)
* w``; no bias anywhere except the convolution's). EVERY layer runs
attention and a Mamba-2 mixer on the same normed input and adds both to the
stream, then a gated MLP. The fourteen published multipliers are applied to
activations exactly where the released code applies them, none folded into
a weight:

  ``x = E[tokens] * embedding_multiplier``
  every layer, ``y = RMSNorm(x; input_layernorm)``:
    ``A``: from ``u = y * attention_in_multiplier``: ``q = u Wq`` (n_head
      heads of head_dim), ``k = (u Wk) * key_multiplier`` and ``v = u Wv``
      (n_kv_head heads); q and k rotated over the WHOLE head, lane i paired
      with lane i + head_dim / 2 (``rotate_half``), by ``pos * rope_theta
      ** (-2 i / head_dim)``; causal ``softmax(q k^T / sqrt(head_dim)) v``,
      n_head / n_kv_head query heads a K/V head; ``o_proj``.
    ``S``, a Mamba-2 mixer (H heads of P channels, d_inner = H P =
      ``mamba_d_ssm``, NOT ``mamba_expand * d``; G groups, state N, kernel
      K) of ``u = y * ssm_in_multiplier``:
      ``[z, xBC, dt] = in_proj(u) * mup_vector``, ``mup_vector`` the five
      ``ssm_multipliers`` over the z [d_inner], x [d_inner], B [G N],
      C [G N] and dt [H] columns;
      ``xBC = silu(causal depthwise conv1d(xBC, K) + conv_bias)``, split
      x [H, P], B [G, N], C [G, N]; head h uses group h // (H / G);
      ``dt = softplus(dt + dt_bias)``; ``A = -exp(A_log)`` [H];
      per head ``S_t = exp(dt_t A) S_{t-1} + dt_t x_t B_t^T`` (P x N),
      ``o_t = S_t C_t + D x_t``;
      ``RMSNorm_groups(o * silu(z)) * w`` (``mamba_rms_norm`` true,
      ``mamba_norm_before_gate`` false: the gate first, then the norm over
      each of the G groups of d_inner / G channels); ``out_proj``.
    ``x = x + A * attention_out_multiplier + S * ssm_out_multiplier``
    ``x = x + down(up(y2) * silu(gate(y2) * mlp_multipliers[0]))
          * mlp_multipliers[1]``, ``y2 = RMSNorm(x; pre_ff_layernorm)``
  ``logits = (RMSNorm(x; final_layernorm) W_head) * lm_head_multiplier``
  (``tie_word_embeddings`` false: the head is a table of its own).

Departures, each also in the configuration file:
* The vocabulary held is a slice: ``embed_tokens`` and ``lm_head`` hold the
  rows of this chip's slice, token ids are drawn below its length and the
  logits are over it. With the whole tables this is the whole model.
* The rotary frequencies are rounded to float32 once, from float64.

Leaves may come in bfloat16 (exact to widen); each is widened where it is
used, so that no float32 copy of all the weights is ever made. Attention
runs in blocks of ``QUERY_BLOCK`` queries so that a long row's scores fit:
a block's are [heads, block, T], not [heads, T, T].
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np

F32 = jnp.float32
QUERY_BLOCK = 512


def _w(x):
    return x.astype(F32)


def rms_norm(x, w, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps) \
        * _w(w)


def rotary(x, theta):
    """x [R, T, heads, head_dim] at positions 0 .. T-1, the halves of a head
    turned against each other: ``x cos + rotate_half(x) sin``."""
    t, hd = x.shape[1], x.shape[-1]
    inv = (1.0 / float(theta) ** (np.arange(0, hd, 2, dtype=np.float64)
                                  / hd)).astype(np.float32)
    angles = jnp.arange(t, dtype=F32)[:, None] * inv[None, :]  # [T, hd / 2]
    cos = jnp.concatenate([jnp.cos(angles)] * 2, -1)[None, :, None, :]
    sin = jnp.concatenate([jnp.sin(angles)] * 2, -1)[None, :, None, :]
    half = jnp.concatenate([-x[..., hd // 2:], x[..., :hd // 2]], -1)
    return x * cos + half * sin


def attention(p, u, *, n_head, n_kv_head, head_dim, rope_theta,
              key_multiplier):
    """u [R, T, d] (normed, times ``attention_in_multiplier``)."""
    r, t, _ = u.shape
    q = (u @ _w(p["q_proj"])).reshape(r, t, n_head, head_dim)
    k = ((u @ _w(p["k_proj"])) * key_multiplier).reshape(
        r, t, n_kv_head, head_dim)
    v = (u @ _w(p["v_proj"])).reshape(r, t, n_kv_head, head_dim)
    q = rotary(q, rope_theta).reshape(r, t, n_kv_head, n_head // n_kv_head,
                                      head_dim)
    k = rotary(k, rope_theta)
    out = []
    for at in range(0, t, QUERY_BLOCK):  # a block of queries over all keys
        qb = q[:, at:at + QUERY_BLOCK]
        scores = jnp.einsum("rigqd,rjgd->rgqij", qb, k) / head_dim ** 0.5
        seen = jnp.arange(t)[None, :] <= (at + jnp.arange(qb.shape[1]))[:, None]
        scores = jnp.where(seen, scores, -jnp.inf)
        out.append(jnp.einsum("rgqij,rjgd->rigqd",
                              jax.nn.softmax(scores, axis=-1), v))
    out = jnp.concatenate(out, axis=1)
    return out.reshape(r, t, n_head * head_dim) @ _w(p["o_proj"])


def mamba2(p, u, *, eps, mamba_heads, mamba_head_dim, n_groups, ssm_state,
           ssm_multipliers):
    """u [R, T, d] (normed, times ``ssm_in_multiplier``) -> [R, T, d]."""
    r, t, _ = u.shape
    h, pd, g, n = mamba_heads, mamba_head_dim, n_groups, ssm_state
    di, gn = h * pd, g * n
    mz, mx, mb, mc, mdt = ssm_multipliers
    mup_vector = jnp.concatenate([
        jnp.full((di,), mz, F32), jnp.full((di,), mx, F32),
        jnp.full((gn,), mb, F32), jnp.full((gn,), mc, F32),
        jnp.full((h,), mdt, F32)])
    proj = (u @ _w(p["in_proj"])) * mup_vector
    z, xbc, dt = proj[..., :di], proj[..., di:2 * di + 2 * gn], \
        proj[..., 2 * di + 2 * gn:]
    conv_w = _w(p["conv_w"])  # [K, C]
    k = conv_w.shape[0]
    padded = jnp.pad(xbc, ((0, 0), (k - 1, 0), (0, 0)))
    xbc = jax.nn.silu(sum(padded[:, j:j + t] * conv_w[j] for j in range(k))
                      + _w(p["conv_b"]))
    xs = xbc[..., :di].reshape(r, t, h, pd)
    b = xbc[..., di:di + gn].reshape(r, t, g, n)
    c = xbc[..., di + gn:].reshape(r, t, g, n)
    dt = jax.nn.softplus(dt + _w(p["dt_bias"]))  # [R, T, H]
    a = -jnp.exp(_w(p["A_log"]))

    def token(state, inp):  # state [R, H, P, N]
        x_t, b_t, c_t, dt_t = inp
        b_t = jnp.repeat(b_t, h // g, axis=1)  # [R, H, N]: a head's group
        c_t = jnp.repeat(c_t, h // g, axis=1)
        state = jnp.exp(dt_t * a)[..., None, None] * state \
            + (dt_t[..., None] * x_t)[..., None] * b_t[:, :, None, :]
        return state, jnp.einsum("rhpn,rhn->rhp", state, c_t)

    _, y = jax.lax.scan(
        token, jnp.zeros((r, h, pd, n), F32),
        (xs.swapaxes(0, 1), b.swapaxes(0, 1), c.swapaxes(0, 1),
         dt.swapaxes(0, 1)))
    y = y.swapaxes(0, 1) + _w(p["D"])[None, None, :, None] * xs
    y = y.reshape(r, t, di) * jax.nn.silu(z)  # the gate BEFORE the norm
    grouped = y.reshape(r, t, g, di // g)
    grouped = grouped * jax.lax.rsqrt(
        jnp.mean(grouped * grouped, axis=-1, keepdims=True) + eps)
    return (grouped.reshape(r, t, di) * _w(p["norm_w"])) @ _w(p["out_proj"])


def mlp(p, y, mlp_multipliers):
    gate = (y @ _w(p["gate_proj"])) * mlp_multipliers[0]
    return ((y @ _w(p["up_proj"])) * jax.nn.silu(gate)) \
        @ _w(p["down_proj"]) * mlp_multipliers[1]


def forward(params, tokens, *, eps, n_head, n_kv_head, head_dim, rope_theta,
            mamba_heads, mamba_head_dim, n_groups, ssm_state,
            embedding_multiplier, lm_head_multiplier, key_multiplier,
            attention_in_multiplier, attention_out_multiplier,
            ssm_in_multiplier, ssm_out_multiplier, ssm_multipliers,
            mlp_multipliers):
    """Logits [R, T, V] float32 of tokens [R, T]."""
    with jax.default_matmul_precision("highest"):
        x = _w(params["embed_tokens"][tokens]) * embedding_multiplier
        for p in params["layers"]:
            y = rms_norm(x, p["input_layernorm"], eps)
            a = attention(p, y * attention_in_multiplier, n_head=n_head,
                          n_kv_head=n_kv_head, head_dim=head_dim,
                          rope_theta=rope_theta,
                          key_multiplier=key_multiplier)
            s = mamba2(p, y * ssm_in_multiplier, eps=eps,
                       mamba_heads=mamba_heads,
                       mamba_head_dim=mamba_head_dim, n_groups=n_groups,
                       ssm_state=ssm_state, ssm_multipliers=ssm_multipliers)
            x = x + a * attention_out_multiplier + s * ssm_out_multiplier
            x = x + mlp(p, rms_norm(x, p["pre_ff_layernorm"], eps),
                        mlp_multipliers)
        x = rms_norm(x, params["final_layernorm"], eps)
        return (x @ _w(params["lm_head"]).T) * lm_head_multiplier


def loss_and_grad_norm(params, tokens, *, remat=False, **kwargs):
    """Mean next-token cross-entropy of rows of T + 1 tokens and the
    global L2 norm of its gradient. No training cell of this family exists:
    this is here because the interface asks, a test runs it at a toy size,
    and ``remat`` changes nothing."""

    def loss(p):
        logp = jax.nn.log_softmax(
            forward(p, tokens[:, :-1], **kwargs), axis=-1)
        return -jnp.mean(jnp.take_along_axis(
            logp, tokens[:, 1:, None], axis=-1))

    value, grads = jax.value_and_grad(loss)(jax.tree.map(_w, params))
    return value, jnp.sqrt(sum(jnp.sum(jnp.square(g))
                               for g in jax.tree.leaves(grads)))
