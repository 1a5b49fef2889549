"""SmallThinker (``model_name: smallthinker_21b_instruct``), written plainly:
float32 ``jax.numpy`` at ``highest`` matmul precision, a token's attention
as a mask over the whole row, the experts as a loop over all of them, no
cache, no ring, no kernels, nothing imported from the program.

Source: the ``config.json`` of ``PowerInfer/SmallThinker-21BA3B-Instruct``,
arXiv:2507.20984 and the released decoder layer's order of operations.

The equations (d = ``hidden_size``; RMSNorm ``N(x; w) = x / sqrt(mean(x^2) +
eps) * w``; no bias anywhere). Layer ``i`` is GLOBAL where
``sliding_window_layout[i]`` is 0 and WINDOW where it is 1; it rotates its
queries and keys where ``rope_layout[i]`` is 1 (the published lists are the
same list: the global layers have no position embedding at all):

  ``x = E[tokens]``
  every layer, input ``x``:
    ``r = x W_r``                      the router's logits, of the UN-NORMED
                                       layer input, BEFORE attention
    ``a = N(x; input_layernorm)``; ``q = a W_q`` (n_head heads of head_dim),
    ``k = a W_k``, ``v = a W_v`` (n_kv_head heads);
    rotating layer: q and k turned at their position over the WHOLE head,
      lane i paired with lane i + head_dim / 2 (``rotate_half``), by ``pos *
      rope_theta ** (-2 i / head_dim)``;
    the query at position t sees the keys ``<= t``; in a window layer only
      those with ``t - k < sliding_window_size`` (the window's keys with
      the query's own);
    ``h = x + W_o concat_h softmax(q_h k_g(h)^T / sqrt(head_dim)) v_g(h)``,
      n_head / n_kv_head query heads a K/V head;
    ``m = N(h; post_attention_layernorm)``; ``p = softmax(r)`` over ALL
      experts; the ``top_k`` largest chosen, their weights ``p_e`` divided
      by the chosen's sum (``moe_primary_router_apply_softmax`` and
      ``norm_topk_prob`` both true);
    ``out = h + sum_chosen w_e W_down,e (relu(W_gate,e m) * W_up,e m)``
      (gated ReLU, "ReGLU"; no shared expert);
  ``logits = N(x; norm) W_head`` (``tie_word_embeddings`` false).

Departures, each also in the configuration file:
* The vocabulary held is a slice: ``embed_tokens`` and ``lm_head`` hold the
  rows of this chip's slice, token ids are drawn below its length and the
  logits are over it. With the whole tables this is the whole model.
* The rotary frequencies are rounded to float32 once, from float64.
* What the router reads (``router_reads``: ``input`` as above) and the
  window's edge are the configuration file's ``assumed`` and are arguments
  here, so that a test can turn each the other way and see it fail.

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
QUERY_BLOCK = 256


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


def attention(p, a, *, n_head, n_kv_head, head_dim, rope_theta, rotates,
              window):
    """a [R, T, d] (normed) -> [R, T, d]. ``rotates``: q and k are turned;
    ``window``: keys a query sees with its own, or None for every key
    before it."""
    r, t, _ = a.shape
    q = (a @ _w(p["q_proj"])).reshape(r, t, n_head, head_dim)
    k = (a @ _w(p["k_proj"])).reshape(r, t, n_kv_head, head_dim)
    v = (a @ _w(p["v_proj"])).reshape(r, t, n_kv_head, head_dim)
    if rotates:
        q, k = rotary(q, rope_theta), rotary(k, rope_theta)
    q = q.reshape(r, t, n_kv_head, n_head // n_kv_head, head_dim)
    keys = jnp.arange(t)[None, :]
    out = []
    for at in range(0, t, QUERY_BLOCK):  # a block of queries over all keys
        qb = q[:, at:at + QUERY_BLOCK]
        scores = jnp.einsum("rigqd,rjgd->rgqij", qb, k) / head_dim ** 0.5
        at_q = (at + jnp.arange(qb.shape[1]))[:, None]
        seen = keys <= at_q
        if window is not None:
            seen = seen & (at_q - keys < window)
        scores = jnp.where(seen, scores, -jnp.inf)
        out.append(jnp.einsum("rgqij,rjgd->rigqd",
                              jax.nn.softmax(scores, axis=-1), v))
    out = jnp.concatenate(out, axis=1)
    return out.reshape(r, t, n_head * head_dim) @ _w(p["o_proj"])


def gating(logits, top_k):
    """Router logits [T, E] -> [T, E]: each token's weight on each expert,
    the softmax over ALL experts, its ``top_k`` largest divided by their
    sum, 0 where not chosen."""
    probs = jax.nn.softmax(logits, axis=-1)
    top, chosen = jax.lax.top_k(probs, top_k)
    return jnp.zeros_like(probs).at[
        jnp.arange(logits.shape[0])[:, None], chosen].set(
            top / jnp.sum(top, axis=-1, keepdims=True))


def experts(p, m, weights, activation=jax.nn.relu):
    """m [T, d] (normed), weights [T, E] (``gating``) -> [T, d]: every
    expert over every row, weighed by the row's routing weight."""

    def expert(total, inp):
        w_gate, w_up, w_down, w_e = inp
        y = (activation(m @ _w(w_gate)) * (m @ _w(w_up))) @ _w(w_down)
        return total + w_e[:, None] * y, None

    total, _ = jax.lax.scan(
        expert, jnp.zeros_like(m),
        (p["experts_gate"], p["experts_up"], p["experts_down"], weights.T))
    return total


def forward(params, tokens, *, rotates, windows, window, eps, n_head,
            n_kv_head, head_dim, rope_theta, top_k, router_reads="input",
            activation="relu"):
    """Logits [R, T, V] float32 of tokens [R, T]. ``rotates`` and
    ``windows``: a bool a layer (``rope_layout``, ``sliding_window_layout``).
    ``router_reads``: ``input`` (the layer's un-normed input, as released),
    or, for a test's control, ``normed_input`` (``N(x; input_layernorm)``)
    or ``post_attention`` (``h``, un-normed). ``activation``: ``relu`` as
    released, ``silu`` for a control."""
    act = {"relu": jax.nn.relu, "silu": jax.nn.silu}[activation]
    with jax.default_matmul_precision("highest"):
        x = _w(params["embed_tokens"][tokens])
        for p, turns, windowed in zip(params["layers"], rotates, windows):
            a = rms_norm(x, p["input_layernorm"], eps)
            h = x + attention(
                p, a, n_head=n_head, n_kv_head=n_kv_head, head_dim=head_dim,
                rope_theta=rope_theta, rotates=turns,
                window=window if windowed else None)
            read = {"input": x, "normed_input": a,
                    "post_attention": h}[router_reads]
            logits = read.reshape(-1, read.shape[-1]) @ _w(p["router"])
            m = rms_norm(h, p["post_attention_layernorm"], eps)
            x = h + experts(p, m.reshape(-1, m.shape[-1]),
                            gating(logits, top_k), act).reshape(x.shape)
        x = rms_norm(x, params["norm"], eps)
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
