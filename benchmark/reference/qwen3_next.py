"""Qwen3-Next (``model_type: qwen3_next``), written plainly: float32
``jax.numpy`` at ``highest`` matmul precision, the delta rule as the
recurrence one token at a time (``lax.scan``; never a blocked form), every
held expert applied to every token and masked by the routing weights, no
cache, no chunking, no kernels, nothing imported from the program.

Source: the ``config.json`` of ``Qwen/Qwen3-Next-80B-A3B-Instruct`` and the
released ``modeling_qwen3_next``'s order of operations.

The equations (d = ``hidden_size``; ``N(x; w) = x * rsqrt(mean(x^2) + eps)
* (1 + w)``, the model's zero-centred RMSNorm; ``l2(x) = x * rsqrt(sum(x^2)
+ 1e-6)``; no bias anywhere):

  ``x = E[tokens]`` (no multiplier)
  every layer: ``x = x + Mixer(N(x; input_layernorm))`` and then
               ``x = x + Routed(h) + sigmoid(h w_sg) * Shared(h)``,
               ``h = N(x; post_attention_layernorm)``
  ``logits = N(x; norm) W_head^T`` (the head is its own table)

``Mixer`` is, by the layer's entry in ``layer_types``:
``full_attention``: ``q_proj`` d -> n_head x 2 head_dim, per head the query
  and then its output gate; k and v d -> n_kv_head x head_dim; q and k
  normed over each head's lanes (``N``, weights ``q_norm`` / ``k_norm``);
  the FIRST ``rotary_dim`` lanes of a head rotated, the halves of those
  lanes against each other, frequencies ``theta ** (-2 i / rotary_dim)``,
  the other lanes pass; causal ``softmax(q k^T / sqrt(head_dim)) v``,
  n_head / n_kv_head query heads a K/V head; ``o_proj(A * sigmoid(gate))``.
``linear_attention``, Gated DeltaNet (Hk key heads of dk, Hv value heads of
  dv, value head j reads key head ``j // (Hv / Hk)``, kernel K):
  ``q_proj`` d -> Hk dk, ``k_proj`` d -> Hk dk, ``v_proj`` d -> Hv dv,
  ``z_proj`` d -> Hv dv, ``b_proj`` and ``a_proj`` d -> Hv (the released
  checkpoint interleaves these six per key head in two matrices;
  ``families/qwen3_next.to_reference`` takes them apart).
  ``[q, k, v] = silu(causal depthwise conv1d([q, k, v], K))``, no bias,
  channels in that order. ``beta = sigmoid(b)``; ``g = -exp(A_log) *
  softplus(a + dt_bias)``. ``q = l2(q) / sqrt(dk)``, ``k = l2(k)``.
  per value head, S [dk, dv] from zeros: ``S = exp(g_t) S``; ``d_t = beta_t
  (v_t - S^T k_t)``; ``S = S + k_t d_t^T``; ``o_t = S^T q_t``.
  ``y = RMSNorm_dv(o_t) * norm_w * silu(z_t)``: the norm (plain weight, not
  zero-centred) over each head's dv lanes BEFORE the gate; ``out_proj``.
``Routed``: ``softmax(h W_r)`` over all experts; the ``top_k`` largest;
  renormalised over those; expert e is gated: ``y_e = W2_e (silu(a) * b)``,
  ``[a, b] = W1_e h``; ``sum_k w_k y_{e_k}``.
``Shared``: the same gated form at its own width, every token, times the
  scalar ``sigmoid(h w_sg)``.

Departures, each also under ``assumed`` in the configuration file:
* The experts held are a share: ``params["layers"][i]["experts_in"]`` holds
  the experts ``first_expert .. first_expert + E_held`` of the router's
  width, and what the others would add is left out (other chips add it).
  With every expert held this is the whole layer.
* The multi-token-prediction module is not here: the released causal-LM
  class drops its weights on load.

Leaves may come in bfloat16 (exact to widen); each is widened where it is
used, an expert at a time, so that no float32 copy of a layer's experts is
ever made. Attention runs in blocks of ``QUERY_BLOCK`` queries so that a
long row's scores fit: a block's are [heads, block, T], not [heads, T, T].
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np

F32 = jnp.float32
QUERY_BLOCK = 512
LINEAR, FULL = "linear_attention", "full_attention"


def _w(x):
    return x.astype(F32)


def zero_centred_norm(x, w, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps) \
        * (1.0 + _w(w))


def l2(x):
    return x * jax.lax.rsqrt(jnp.sum(x * x, axis=-1, keepdims=True) + 1e-6)


def gated(ab):
    """``silu(a) * b`` of ``[a, b]`` side by side."""
    half = ab.shape[-1] // 2
    return jax.nn.silu(ab[..., :half]) * ab[..., half:]


def partial_rotary(x, theta, rotary_dim):
    """x [R, T, heads, head_dim] at positions 0 .. T-1: the first
    ``rotary_dim`` lanes turned, ``x cos + rotate_half(x) sin`` with the
    halves of THOSE lanes against each other; the rest pass."""
    t = x.shape[1]
    inv = (1.0 / float(theta) ** (np.arange(0, rotary_dim, 2,
                                            dtype=np.float64)
                                  / rotary_dim)).astype(np.float32)
    angles = jnp.arange(t, dtype=F32)[:, None] * inv[None, :]
    cos = jnp.concatenate([jnp.cos(angles)] * 2, -1)[None, :, None, :]
    sin = jnp.concatenate([jnp.sin(angles)] * 2, -1)[None, :, None, :]
    turn, keep = x[..., :rotary_dim], x[..., rotary_dim:]
    half = jnp.concatenate([-turn[..., rotary_dim // 2:],
                            turn[..., :rotary_dim // 2]], -1)
    return jnp.concatenate([turn * cos + half * sin, keep], -1)


def gated_attention(p, x, *, eps, n_head, n_kv_head, head_dim, rope_theta,
                    rotary_dim):
    """x [R, T, d] (normed) -> [R, T, d]."""
    r, t, _ = x.shape
    qg = (x @ _w(p["q_proj"])).reshape(r, t, n_head, 2 * head_dim)
    q, gate = qg[..., :head_dim], qg[..., head_dim:]
    k = (x @ _w(p["k_proj"])).reshape(r, t, n_kv_head, head_dim)
    v = (x @ _w(p["v_proj"])).reshape(r, t, n_kv_head, head_dim)
    q = partial_rotary(zero_centred_norm(q, p["q_norm"], eps), rope_theta,
                       rotary_dim)
    k = partial_rotary(zero_centred_norm(k, p["k_norm"], eps), rope_theta,
                       rotary_dim)
    q = q.reshape(r, t, n_kv_head, n_head // n_kv_head, head_dim)
    out = []
    for at in range(0, t, QUERY_BLOCK):  # a block of queries over all keys
        qb = q[:, at:at + QUERY_BLOCK]
        scores = jnp.einsum("rigqd,rjgd->rgqij", qb, k) / head_dim ** 0.5
        seen = jnp.arange(t)[None, :] <= (at + jnp.arange(qb.shape[1]))[:, None]
        scores = jnp.where(seen, scores, -jnp.inf)
        out.append(jnp.einsum("rgqij,rjgd->rigqd",
                              jax.nn.softmax(scores, axis=-1), v))
    out = jnp.concatenate(out, axis=1).reshape(r, t, n_head, head_dim)
    out = out * jax.nn.sigmoid(gate)
    return out.reshape(r, t, n_head * head_dim) @ _w(p["o_proj"])


def gated_delta_net(p, x, *, eps, key_heads, value_heads, key_dim,
                    value_dim, delta_term=True):
    """x [R, T, d] (normed) -> [R, T, d]. ``delta_term`` False leaves the
    rule out (``d_t = beta_t v_t``: gated linear attention), for a test's
    control."""
    r, t, _ = x.shape
    hk, hv, dk, dv = key_heads, value_heads, key_dim, value_dim
    q, k, v = x @ _w(p["q_proj"]), x @ _w(p["k_proj"]), x @ _w(p["v_proj"])
    z = (x @ _w(p["z_proj"])).reshape(r, t, hv, dv)
    beta = jax.nn.sigmoid(x @ _w(p["b_proj"]))  # [R, T, Hv]
    g = -jnp.exp(_w(p["A_log"])) * jax.nn.softplus(
        x @ _w(p["a_proj"]) + _w(p["dt_bias"]))
    mixed = jnp.concatenate([q, k, v], axis=-1)
    conv_w = _w(p["conv_w"])  # [K, C]
    kk = conv_w.shape[0]
    padded = jnp.pad(mixed, ((0, 0), (kk - 1, 0), (0, 0)))
    mixed = jax.nn.silu(sum(padded[:, j:j + t] * conv_w[j]
                            for j in range(kk)))
    q = l2(mixed[..., :hk * dk].reshape(r, t, hk, dk)) / dk ** 0.5
    k = l2(mixed[..., hk * dk:2 * hk * dk].reshape(r, t, hk, dk))
    v = mixed[..., 2 * hk * dk:].reshape(r, t, hv, dv)
    q = jnp.repeat(q, hv // hk, axis=2)  # value head j reads key head j // rep
    k = jnp.repeat(k, hv // hk, axis=2)

    def token(state, inp):  # state [R, Hv, dk, dv]
        q_t, k_t, v_t, g_t, beta_t = inp
        state = jnp.exp(g_t)[..., None, None] * state
        held = jnp.einsum("rhkv,rhk->rhv", state, k_t) if delta_term else 0.0
        d_t = beta_t[..., None] * (v_t - held)
        state = state + k_t[..., :, None] * d_t[..., None, :]
        return state, jnp.einsum("rhkv,rhk->rhv", state, q_t)

    _, o = jax.lax.scan(
        token, jnp.zeros((r, hv, dk, dv), F32),
        tuple(a.swapaxes(0, 1) for a in (q, k, v, g, beta)))
    o = o.swapaxes(0, 1)  # [R, T, Hv, dv]
    o = o * jax.lax.rsqrt(jnp.mean(o * o, axis=-1, keepdims=True) + eps) \
        * _w(p["norm_w"])
    return (o * jax.nn.silu(z)).reshape(r, t, hv * dv) @ _w(p["out_proj"])


def gating(x, router_w, top_k):
    """x [T, d] -> [T, E_all]: each token's weight on each expert, the
    softmax over all experts, its ``top_k`` largest renormalised over
    themselves, 0 where not chosen."""
    probs = jax.nn.softmax(x @ _w(router_w), axis=-1)
    top, chosen = jax.lax.top_k(probs, top_k)
    return jnp.zeros_like(probs).at[
        jnp.arange(x.shape[0])[:, None], chosen].set(
            top / jnp.sum(top, axis=-1, keepdims=True))


def routed_experts(p, x, *, top_k, first_expert):
    """x [T, d] (normed) -> [T, d]: the held experts' part of the routed
    output."""
    held = p["experts_in"].shape[0]
    mine = gating(x, p["router"], top_k)[:, first_expert:first_expert + held]

    def expert(r, inp):  # every held expert over every token, then masked
        w_in, w_out, g_e = inp
        return r + g_e[:, None] * (gated(x @ _w(w_in)) @ _w(w_out)), None

    routed, _ = jax.lax.scan(expert, jnp.zeros_like(x),
                             (p["experts_in"], p["experts_out"], mine.T))
    return routed


def shared_expert(p, x):
    """x [T, d] (normed) -> [T, d]: the shared expert behind its gate."""
    return jax.nn.sigmoid(x @ _w(p["shared_gate"])) \
        * (gated(x @ _w(p["shared_in"])) @ _w(p["shared_out"]))


def forward(params, tokens, *, layer_types, eps, n_head, n_kv_head, head_dim,
            rope_theta, rotary_dim, key_heads, value_heads, key_dim,
            value_dim, top_k, first_expert):
    """Logits [R, T, V] float32 of tokens [R, T]."""
    with jax.default_matmul_precision("highest"):
        x = _w(params["embed_tokens"][tokens])
        for kind, p in zip(layer_types, params["layers"]):
            y = zero_centred_norm(x, p["input_layernorm"], eps)
            if kind == LINEAR:
                x = x + gated_delta_net(
                    p, y, eps=eps, key_heads=key_heads,
                    value_heads=value_heads, key_dim=key_dim,
                    value_dim=value_dim)
            else:
                x = x + gated_attention(
                    p, y, eps=eps, n_head=n_head, n_kv_head=n_kv_head,
                    head_dim=head_dim, rope_theta=rope_theta,
                    rotary_dim=rotary_dim)
            h = zero_centred_norm(x, p["post_attention_layernorm"], eps)
            flat = h.reshape(-1, h.shape[-1])
            x = x + (routed_experts(p, flat, top_k=top_k,
                                    first_expert=first_expert)
                     + shared_expert(p, flat)).reshape(x.shape)
        x = zero_centred_norm(x, params["norm"], eps)
        return x @ _w(params["lm_head"]).T


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
