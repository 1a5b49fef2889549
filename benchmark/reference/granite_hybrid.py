"""Granite 4.0-H (``model_type: granitemoehybrid``), written plainly:
float32 ``jax.numpy`` at ``highest`` matmul precision, the recurrence as a
scan over tokens, every held expert applied to every token and masked by
the routing weights, no cache, no chunking, no kernels, nothing imported
from the program.

Source: the ``config.json`` of ``ibm-granite/granite-4.0-h-small`` and the
released ``granitemoehybrid`` modelling code's order of operations.

The equations (d = ``hidden_size``; RMSNorm(x) = ``x / sqrt(mean(x^2) + eps)
* w``; no bias anywhere except the convolution's; e, a, r, s the four
published multipliers ``embedding_multiplier``, ``attention_multiplier``,
``residual_multiplier``, ``logits_scaling``):

  ``x = E[tokens] * e``
  every layer: ``x = x + r * Mixer(RMSNorm_1(x))`` and then
               ``x = x + r * (Routed(h) + Shared(h))``, ``h = RMSNorm_2(x)``
  ``logits = RMSNorm_f(x) E^T / s`` (the head is the embedding)

``Mixer`` is, by the layer's entry in ``layer_types``:
``attention``: q d -> n_head x head_dim, k and v d -> n_kv_head x head_dim,
  no position embedding (``position_embedding_type: nope``), causal
  ``softmax(q k^T * a)`` (a is NOT ``head_dim ** -0.5``), n_head / n_kv_head
  query heads a K/V head, ``o_proj``.
``mamba``, a Mamba-2 mixer (H heads of P channels, d_inner = H P, G groups,
  state N, kernel K):
  ``in_proj``: d -> 2 d_inner + 2 G N + H, split z [d_inner], xBC [d_inner +
  2 G N], dt [H].
  ``xBC = silu(causal depthwise conv1d(xBC, K) + conv_bias)``, split
  x [H, P], B [G, N], C [G, N]; head h uses group h // (H / G).
  ``dt = softplus(dt + dt_bias)``; ``A = -exp(A_log)`` [H].
  per head: ``S_t = exp(dt_t A) S_{t-1} + dt_t x_t B_t^T`` (S is P x N),
  ``y_t = S_t C_t + D x_t``.
  ``y = RMSNorm_groups(y * silu(z)) * w``: the norm over each of the G
  groups of d_inner / G channels (G = 1 as published: over all of them).
  ``out_proj``: d_inner -> d.
``Routed``: ``l = h W_r`` (all experts' logits); the ``top_k`` largest;
  ``g = softmax`` over those ``top_k`` logits; expert e is gated:
  ``y_e = W2_e (silu(a) * b)``, ``[a, b] = W1_e h``; ``sum_k g_k y_{e_k}``.
``Shared``: the same gated form at its own width, every token.

Departures, each also under ``assumed`` in the configuration file:
* ``intermediate_size`` is read as one expert's width: ``config.json`` has no
  key of its own for it (the catalog's note).
* The experts held are a share: ``params["layers"][i]["experts_in"]`` holds
  the experts ``first_expert .. first_expert + E_held`` of the router's
  width, and what the others would add is left out (other chips add it).
  With every expert held this is the whole layer.

Leaves may come in bfloat16 (exact to widen); each is widened where it is
used, an expert at a time, so that no float32 copy of all the weights is
ever made. Attention runs in blocks of ``QUERY_BLOCK`` queries so that a
long row's scores fit: a block's are [heads, block, T], not [heads, T, T].
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

F32 = jnp.float32
QUERY_BLOCK = 512


def _w(x):
    return x.astype(F32)


def rms_norm(x, w, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps) \
        * _w(w)


def gated(ab):
    """``silu(a) * b`` of ``[a, b]`` side by side."""
    half = ab.shape[-1] // 2
    return jax.nn.silu(ab[..., :half]) * ab[..., half:]


def mamba2(p, x, *, eps, mamba_heads, mamba_head_dim, n_groups, ssm_state):
    """x [R, T, d] (normed) -> [R, T, d]."""
    r, t, _ = x.shape
    h, pd, g, n = mamba_heads, mamba_head_dim, n_groups, ssm_state
    di = h * pd
    proj = x @ _w(p["in_proj"])
    z, xbc, dt = proj[..., :di], proj[..., di:di + di + 2 * g * n], \
        proj[..., 2 * di + 2 * g * n:]
    conv_w = _w(p["conv_w"])  # [K, C]
    k = conv_w.shape[0]
    padded = jnp.pad(xbc, ((0, 0), (k - 1, 0), (0, 0)))
    xbc = jax.nn.silu(sum(padded[:, j:j + t] * conv_w[j] for j in range(k))
                      + _w(p["conv_b"]))
    xs = xbc[..., :di].reshape(r, t, h, pd)
    b = xbc[..., di:di + g * n].reshape(r, t, g, n)
    c = xbc[..., di + g * n:].reshape(r, t, g, n)
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
    y = y.reshape(r, t, di) * jax.nn.silu(z)
    grouped = y.reshape(r, t, g, di // g)
    grouped = grouped * jax.lax.rsqrt(
        jnp.mean(grouped * grouped, axis=-1, keepdims=True) + eps)
    return (grouped.reshape(r, t, di) * _w(p["norm_w"])) @ _w(p["out_proj"])


def attention(p, x, *, n_head, n_kv_head, head_dim, attention_multiplier):
    r, t, _ = x.shape
    q = (x @ _w(p["q_proj"])).reshape(r, t, n_kv_head, n_head // n_kv_head,
                                      head_dim)
    k = (x @ _w(p["k_proj"])).reshape(r, t, n_kv_head, head_dim)
    v = (x @ _w(p["v_proj"])).reshape(r, t, n_kv_head, head_dim)
    out = []
    for at in range(0, t, QUERY_BLOCK):  # a block of queries over all keys
        qb = q[:, at:at + QUERY_BLOCK]
        scores = jnp.einsum("rigqd,rjgd->rgqij", qb, k) * attention_multiplier
        seen = jnp.arange(t)[None, :] <= (at + jnp.arange(qb.shape[1]))[:, None]
        scores = jnp.where(seen, scores, -jnp.inf)
        out.append(jnp.einsum("rgqij,rjgd->rigqd",
                              jax.nn.softmax(scores, axis=-1), v))
    out = jnp.concatenate(out, axis=1)
    return out.reshape(r, t, n_head * head_dim) @ _w(p["o_proj"])


def gating(x, router_w, top_k):
    """x [T, d] -> [T, E_all]: each token's weight on each expert, the
    softmax over its ``top_k`` largest logits, 0 where not chosen."""
    logits = x @ _w(router_w)
    top, chosen = jax.lax.top_k(logits, top_k)
    return jnp.zeros_like(logits).at[
        jnp.arange(x.shape[0])[:, None], chosen].set(
            jax.nn.softmax(top, axis=-1))


def experts(p, x, *, top_k, first_expert):
    """x [R, T, d] (normed) -> [R, T, d]: the held experts' part of the
    routed output, plus the shared expert."""
    shape = x.shape
    x = x.reshape(-1, shape[-1])
    held = p["experts_in"].shape[0]
    mine = gating(x, p["router"], top_k)[:, first_expert:first_expert + held]

    def expert(r, inp):  # every held expert over every token, then masked
        w_in, w_out, g_e = inp
        return r + g_e[:, None] * (gated(x @ _w(w_in)) @ _w(w_out)), None

    routed, _ = jax.lax.scan(expert, jnp.zeros_like(x),
                             (p["experts_in"], p["experts_out"], mine.T))
    out = routed + gated(x @ _w(p["shared_in"])) @ _w(p["shared_out"])
    return out.reshape(shape)


def forward(params, tokens, *, layer_types, eps, n_head, n_kv_head, head_dim,
            mamba_heads, mamba_head_dim, n_groups, ssm_state, top_k,
            first_expert, embedding_multiplier, attention_multiplier,
            residual_multiplier, logits_scaling):
    """Logits [R, T, V] float32 of tokens [R, T]."""
    with jax.default_matmul_precision("highest"):
        x = _w(params["embed_tokens"][tokens]) * embedding_multiplier
        for kind, p in zip(layer_types, params["layers"]):
            y = rms_norm(x, p["input_layernorm"], eps)
            if kind == "mamba":
                y = mamba2(p, y, eps=eps, mamba_heads=mamba_heads,
                           mamba_head_dim=mamba_head_dim, n_groups=n_groups,
                           ssm_state=ssm_state)
            else:
                y = attention(p, y, n_head=n_head, n_kv_head=n_kv_head,
                              head_dim=head_dim,
                              attention_multiplier=attention_multiplier)
            x = x + residual_multiplier * y
            y = rms_norm(x, p["post_attention_layernorm"], eps)
            x = x + residual_multiplier * experts(
                p, y, top_k=top_k, first_expert=first_expert)
        x = rms_norm(x, params["norm"], eps)
        return x @ _w(params["embed_tokens"]).T / logits_scaling


def loss_and_grad_norm(params, tokens, *, remat=False, **kwargs):
    """Mean next-token cross-entropy of rows of T + 1 tokens and the
    global L2 norm of its gradient (the router's choice is not
    differentiated, as ever). No training cell of this family exists: this
    is here because the interface asks, a test runs it at a toy size, and
    ``remat`` changes nothing."""

    def loss(p):
        logp = jax.nn.log_softmax(
            forward(p, tokens[:, :-1], **kwargs), axis=-1)
        return -jnp.mean(jnp.take_along_axis(
            logp, tokens[:, 1:, None], axis=-1))

    value, grads = jax.value_and_grad(loss)(jax.tree.map(_w, params))
    return value, jnp.sqrt(sum(jnp.sum(jnp.square(g))
                               for g in jax.tree.leaves(grads)))
