"""Nemotron-H (``model_type: nemotron_h``), written plainly: float32
``jax.numpy`` at ``highest`` matmul precision, the recurrence as a scan over
tokens, every held expert applied to every token and masked by the routing
weights, no cache, no chunking, no kernels, nothing imported from the
program.

Source: the ``config.json`` of
``nvidia/NVIDIA-Nemotron-3-Super-120B-A12B-BF16`` and the released
``nemotron_h`` modelling code's order of operations.

The equations (d = ``hidden_size``; RMSNorm(x) = ``x / sqrt(mean(x^2) + eps)
* w``; no bias anywhere except the convolution's). Every layer is
``x = x + mixer(RMSNorm(x))``, the mixer chosen by the layer's character in
``pattern``; after the last layer ``norm_f`` and an untied ``lm_head``.

``M``, Mamba-2 mixer (H heads of P channels, d_inner = H P, G groups, state
N, kernel K):
  ``in_proj``: d -> 2 d_inner + 2 G N + H, split z [d_inner], xBC [d_inner +
  2 G N], dt [H].
  ``xBC = silu(causal depthwise conv1d(xBC, K) + conv_bias)``, split
  x [H, P], B [G, N], C [G, N]; head h uses group h // (H / G).
  ``dt = softplus(dt + dt_bias)``; ``A = -exp(A_log)`` [H].
  per head: ``S_t = exp(dt_t A) S_{t-1} + dt_t x_t B_t^T`` (S is P x N),
  ``y_t = S_t C_t + D x_t``.
  ``y = RMSNorm_groups(y * silu(z)) * w``: the norm over each of the G
  groups of d_inner / G channels. ``out_proj``: d_inner -> d.
``*``, attention: q d -> n_head x head_dim, k and v d -> n_kv_head x
  head_dim, causal softmax(q k^T / sqrt(head_dim)), n_head / n_kv_head query
  heads a K/V head, ``o_proj``.
``E``, latent MoE: router on the full-width input, float32:
  ``s = sigmoid(x W_g)`` over all experts; choose the top k of ``s +
  e_score_correction_bias``; ``w = s[chosen]`` (without the bias),
  ``w = w / (sum w + 1e-20)``, ``w = routed_scaling_factor w``.
  ``h = x W_down`` (d -> latent, one matrix for all experts); expert e:
  ``y_e = W2_e relu(W1_e h)^2`` (not gated); ``r = sum_k w_k y_{e_k}``;
  routed output ``r W_up`` (latent -> d). Shared expert on x at full width:
  ``W2_s relu(W1_s x)^2``. Layer output: routed + shared.

Departures, each also under ``assumed`` in the configuration file:
* No position embedding in attention: the released modelling code applies
  none (the Mamba layers carry order); ``rope_theta`` and
  ``partial_rotary_factor`` of the config are unused.
* The order inside ``E`` (router on x, one down-projection before dispatch,
  one up-projection after the combine, the shared expert outside the
  latent) is the released code's ``fc1_latent_proj`` / ``fc2_latent_proj``;
  the catalog says only "experts in 1024-d latent".
* The experts held are a share: ``params["layers"][i]["experts_up"]`` holds
  the experts ``first_expert .. first_expert + E_held`` of the router's
  width, and what the others would add is left out (other chips add it).
  With every expert held this is the whole layer.
* Multi-token prediction (``num_nextn_predict_layers``) is not part of the
  forward pass: a deployment without speculative decoding does not load it.

Leaves may come in bfloat16 (exact to widen); each is widened where it is
used, an expert at a time, so that no float32 copy of all the weights is
ever made.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

F32 = jnp.float32


def _w(x):
    return x.astype(F32)


def rms_norm(x, w, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps) \
        * _w(w)


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
    b = jnp.repeat(xbc[..., di:di + g * n].reshape(r, t, g, n), h // g, 2)
    c = jnp.repeat(xbc[..., di + g * n:].reshape(r, t, g, n), h // g, 2)
    dt = jax.nn.softplus(dt + _w(p["dt_bias"]))  # [R, T, H]
    a = -jnp.exp(_w(p["A_log"]))

    def token(state, inp):  # state [R, H, P, N]
        x_t, b_t, c_t, dt_t = inp
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


def attention(p, x, *, n_head, n_kv_head, head_dim):
    r, t, _ = x.shape
    q = (x @ _w(p["q_proj"])).reshape(r, t, n_kv_head, n_head // n_kv_head,
                                      head_dim)
    k = (x @ _w(p["k_proj"])).reshape(r, t, n_kv_head, head_dim)
    v = (x @ _w(p["v_proj"])).reshape(r, t, n_kv_head, head_dim)
    scores = jnp.einsum("rigqd,rjgd->rgqij", q, k) / (head_dim ** 0.5)
    scores = jnp.where(jnp.tril(jnp.ones((t, t), bool)), scores, -jnp.inf)
    out = jnp.einsum("rgqij,rjgd->rigqd", jax.nn.softmax(scores, axis=-1), v)
    return out.reshape(r, t, n_head * head_dim) @ _w(p["o_proj"])


def latent_moe(p, x, *, top_k, routed_scale, first_expert):
    """x [R, T, d] (normed) -> [R, T, d]: the held experts' part of the
    routed output, plus the shared expert."""
    shape = x.shape
    x = x.reshape(-1, shape[-1])
    s = jax.nn.sigmoid(x @ _w(p["gate_w"]))  # [T, E_all]
    _, chosen = jax.lax.top_k(s + _w(p["e_score_correction_bias"]), top_k)
    w = jnp.take_along_axis(s, chosen, axis=-1)
    w = routed_scale * w / (jnp.sum(w, axis=-1, keepdims=True) + 1e-20)
    # [T, E_all]: each token's weight on each expert, 0 where not chosen
    dense = jnp.zeros_like(s).at[
        jnp.arange(x.shape[0])[:, None], chosen].set(w)
    held = p["experts_up"].shape[0]
    mine = dense[:, first_expert:first_expert + held]
    h = x @ _w(p["fc1_latent_proj"])

    def expert(r, inp):  # every held expert over every token, then masked
        up, down, w_e = inp
        y = jnp.square(jax.nn.relu(h @ _w(up))) @ _w(down)
        return r + w_e[:, None] * y, None

    r, _ = jax.lax.scan(expert, jnp.zeros_like(h),
                        (p["experts_up"], p["experts_down"], mine.T))
    out = r @ _w(p["fc2_latent_proj"]) \
        + jnp.square(jax.nn.relu(x @ _w(p["shared_up"]))) \
        @ _w(p["shared_down"])
    return out.reshape(shape)


def forward(params, tokens, *, pattern, eps, n_head, n_kv_head, head_dim,
            mamba_heads, mamba_head_dim, n_groups, ssm_state, top_k,
            routed_scale, first_expert):
    """Logits [R, T, V] float32 of tokens [R, T]."""
    with jax.default_matmul_precision("highest"):
        x = _w(params["embeddings"][tokens])
        for kind, p in zip(pattern, params["layers"]):
            y = rms_norm(x, p["norm"], eps)
            if kind == "M":
                y = mamba2(p, y, eps=eps, mamba_heads=mamba_heads,
                           mamba_head_dim=mamba_head_dim, n_groups=n_groups,
                           ssm_state=ssm_state)
            elif kind == "*":
                y = attention(p, y, n_head=n_head, n_kv_head=n_kv_head,
                              head_dim=head_dim)
            else:
                y = latent_moe(p, y, top_k=top_k, routed_scale=routed_scale,
                               first_expert=first_expert)
            x = x + y
        return rms_norm(x, params["norm_f"], eps) @ _w(params["lm_head"])


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
