"""K-EXAONE (``model_type: exaone_moe``), written plainly: float32
``jax.numpy`` at ``highest`` matmul precision, a token's attention as a mask
over the whole row, the experts as a loop over all that are held, no cache,
no ring, no kernels, no drafting, nothing imported from the program.

Source: the ``config.json`` of ``LGAI-EXAONE/K-EXAONE-236B-A23B`` for every
width, the ``exaone4`` modelling code of the same family (``transformers``)
for the order of operations in a block, and DeepSeek-V3's multi-token-
prediction module, whose key (``num_nextn_predict_layers``) the config uses.

The equations (d = ``hidden_size``; RMSNorm ``N(x; w) = x / sqrt(mean(x^2) +
eps) * w``; no bias anywhere). Layer ``i`` is a WINDOW layer where
``layer_types[i]`` is ``sliding_attention`` and GLOBAL where it is
``full_attention``; DENSE where ``mlp_layer_types[i]`` is ``dense``:

  ``x = E[tokens]``
  every layer, input ``x`` (``norm_placement: post``, the family's):
    ``q = x W_q`` (n_head heads of head_dim), ``k = x W_k``, ``v = x W_v``
      (n_kv_head heads), of the UN-NORMED stream; ``q`` and ``k`` normed
      over each head's lanes (``q_norm``, ``k_norm``);
    window layer: q and k turned at their position over the WHOLE head,
      lane i paired with lane i + head_dim / 2 (``rotate_half``), by ``pos *
      rope_theta ** (-2 i / head_dim)``; the query at position t sees the
      keys ``t - sliding_window < p <= t`` (the window's keys with the
      query's own);
    global layer: nothing turned; the query sees every key ``<= t``;
    ``h = x + N(W_o concat_h softmax(q_h k_g(h)^T / sqrt(head_dim)) v_g(h);
      post_attention_layernorm)``;
    dense:  ``out = W_down (silu(W_gate h) * W_up h)``;
    sparse: ``s = sigmoid(h W_r)`` over ALL experts; the ``top_k`` largest
      of ``s + e_score_correction_bias`` chosen (``n_group = topk_group =
      1``: no group limit); weights ``s_e`` divided by the chosen's sum
      (``norm_topk_prob``) times ``routed_scaling_factor``;
      ``out = sum_chosen w_e Expert_e(h) + Shared(h)``, each a gated MLP as
      the dense one, the shared one added unweighted;
    ``x = h + N(out; post_feedforward_layernorm)``;
  ``logits = N(x; norm) W_head^T`` (the head is a table of its own).

The module (``module``; position i, ``x_i`` the main stack's stream BEFORE
the last norm): ``u_i = W_eh [N(x_i; hnorm) ; N(E[t_{i+1}]; enorm)]``, one
GLOBAL block of the kind above with a DENSE feed-forward over ``u_0 ..
u_i``, then ``N(.; norm) W_head^T`` with the SHARED head: logits for
``t_{i+2}``.

Departures, each because the configuration file states it:

* **The experts held** (``experts_held``): the router scores every expert of
  the model; the sum runs over the held ids only. What the absent experts
  would have added is left out, as the chips that hold them would add it;
  that partial sum plus the shared expert is what the norm and the next
  layer see. With every expert held this is the whole layer.
* **Depth and vocabulary** are the file's: the layers listed, the rows of
  both tables it holds; token ids are below that and logits are over it.
* The rotary frequencies are rounded to float32 once, from float64.
* Where each sublayer's norm sits, the window's edge, whether a global layer
  rotates and the module's form are the configuration file's ``assumed``
  and are arguments here (``norm_placement``, ``window``, ``rotates``), so
  that a test can turn each the other way and see it fail.

Leaves may come in bfloat16 (exact to widen); each is widened where it is
used, so that no float32 copy of all the weights is ever made. Attention
runs in blocks of ``QUERY_BLOCK`` queries so that a long row's scores fit.
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
              window, eps):
    """a [R, T, d] -> [R, T, d] (before ``W_o``'s norm). ``rotates``: q and
    k are turned; ``window``: keys a query sees with its own, or None for
    every key before it."""
    r, t, _ = a.shape
    q = (a @ _w(p["q_proj"])).reshape(r, t, n_head, head_dim)
    k = (a @ _w(p["k_proj"])).reshape(r, t, n_kv_head, head_dim)
    v = (a @ _w(p["v_proj"])).reshape(r, t, n_kv_head, head_dim)
    q, k = rms_norm(q, p["q_norm"], eps), rms_norm(k, p["k_norm"], eps)
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


def gated_mlp(m, w_gate, w_up, w_down):
    return (jax.nn.silu(m @ _w(w_gate)) * (m @ _w(w_up))) @ _w(w_down)


def gating(scores_in, bias, top_k, scale):
    """Router logits [T, E] -> [T, E]: each token's weight on each expert:
    sigmoid scores, the ``top_k`` largest of score + bias chosen, their OWN
    scores divided by the chosen's sum and times ``scale``, 0 where not
    chosen."""
    s = jax.nn.sigmoid(scores_in)
    _, chosen = jax.lax.top_k(s + _w(bias), top_k)
    rows = jnp.arange(s.shape[0])[:, None]
    top = s[rows, chosen]
    return jnp.zeros_like(s).at[rows, chosen].set(
        scale * top / jnp.sum(top, axis=-1, keepdims=True))


def routed(p, m, weights):
    """m [T, d], weights [T, held] (``gating``'s columns of the held
    experts) -> [T, d]: every held expert over every row, weighed by the
    row's routing weight."""

    def expert(total, inp):
        w_gate, w_up, w_down, w_e = inp
        return total + w_e[:, None] * gated_mlp(m, w_gate, w_up, w_down), None

    total, _ = jax.lax.scan(
        expert, jnp.zeros_like(m),
        (p["experts_gate"], p["experts_up"], p["experts_down"], weights.T))
    return total


def feed_forward(p, m, *, top_k=None, routed_scale=None, first_expert=0):
    """m [T, d] -> [T, d] (before the sublayer's norm): the dense MLP where
    the layer holds one, else the held experts' part of the routed sum plus
    the shared expert."""
    if "gate_proj" in p:
        return gated_mlp(m, p["gate_proj"], p["up_proj"], p["down_proj"])
    weights = gating(m @ _w(p["router"]), p["e_score_correction_bias"],
                     top_k, routed_scale)
    held = p["experts_gate"].shape[0]
    return routed(p, m, weights[:, first_expert:first_expert + held]) \
        + gated_mlp(m, p["shared_gate"], p["shared_up"], p["shared_down"])


def block(p, x, *, rotates, window, norm_placement, eps, attn_kw, ff_kw):
    """One block over x [R, T, d]. ``post``: each sublayer reads the stream
    and its output is normed before it is added (the family's);
    ``pre``, a control: each sublayer reads the normed stream and is added
    as it is."""
    r, t, d = x.shape
    n1, n2 = p["post_attention_layernorm"], p["post_feedforward_layernorm"]
    if norm_placement == "post":
        h = x + rms_norm(attention(p, x, rotates=rotates, window=window,
                                   eps=eps, **attn_kw), n1, eps)
        out = feed_forward(p, h.reshape(r * t, d), **ff_kw)
        return h + rms_norm(out.reshape(r, t, d), n2, eps)
    if norm_placement != "pre":
        raise ValueError(f"norm_placement {norm_placement!r}")
    h = x + attention(p, rms_norm(x, n1, eps), rotates=rotates,
                      window=window, eps=eps, **attn_kw)
    return h + feed_forward(p, rms_norm(h, n2, eps).reshape(r * t, d),
                            **ff_kw).reshape(r, t, d)


def hidden(params, tokens, *, rotates, windows, window, eps, n_head,
           n_kv_head, head_dim, rope_theta, top_k, routed_scale,
           first_expert=0, norm_placement="post"):
    """The main stack's stream [R, T, d] before the last norm."""
    attn_kw = dict(n_head=n_head, n_kv_head=n_kv_head, head_dim=head_dim,
                   rope_theta=rope_theta)
    ff_kw = dict(top_k=top_k, routed_scale=routed_scale,
                 first_expert=first_expert)
    x = _w(params["embed_tokens"][tokens])
    for p, turns, windowed in zip(params["layers"], rotates, windows):
        x = block(p, x, rotates=turns, window=window if windowed else None,
                  norm_placement=norm_placement, eps=eps, attn_kw=attn_kw,
                  ff_kw=ff_kw)
    return x


def forward(params, tokens, **kwargs):
    """Logits [R, T, V] float32 of tokens [R, T]. ``rotates`` and
    ``windows``: a bool a layer (``layer_types``: a sliding layer does
    both); ``norm_placement``: ``post`` as the family has it, ``pre`` for a
    control."""
    with jax.default_matmul_precision("highest"):
        x = hidden(params, tokens, **kwargs)
        return rms_norm(x, params["norm"], kwargs["eps"]) \
            @ _w(params["lm_head"]).T


def module_forward(params, tokens, **kwargs):
    """The prediction module's logits [R, T - 1, V] float32 of tokens [R,
    T]: row i reads ``(x_i, tokens[i + 1])`` and predicts the token at
    ``i + 2``."""
    eps = kwargs["eps"]
    m = params["module"]
    with jax.default_matmul_precision("highest"):
        x = hidden(params, tokens, **kwargs)[:, :-1]
        e = _w(params["embed_tokens"][tokens[:, 1:]])
        u = jnp.concatenate([rms_norm(x, m["hnorm"], eps),
                             rms_norm(e, m["enorm"], eps)], -1) \
            @ _w(m["eh_proj"])
        u = block(
            m["block"], u, rotates=False, window=None,
            norm_placement=kwargs.get("norm_placement", "post"), eps=eps,
            attn_kw={k: kwargs[k] for k in ("n_head", "n_kv_head",
                                            "head_dim", "rope_theta")},
            ff_kw={})
        return rms_norm(u, m["norm"], eps) @ _w(params["lm_head"]).T


def loss_and_grad_norm(params, tokens, *, remat=False, **kwargs):
    """Mean next-token cross-entropy of rows of T + 1 tokens and the
    global L2 norm of its gradient (the router's choice is not
    differentiated, as ever; the module is not in the loss). No training
    cell of this family exists: this is here because the interface asks, a
    test runs it at a toy size, and ``remat`` changes nothing."""

    def loss(p):
        logp = jax.nn.log_softmax(
            forward(p, tokens[:, :-1], **kwargs), axis=-1)
        return -jnp.mean(jnp.take_along_axis(
            logp, tokens[:, 1:, None], axis=-1))

    main = {k: v for k, v in params.items() if k != "module"}
    value, grads = jax.value_and_grad(loss)(jax.tree.map(_w, main))
    return value, jnp.sqrt(sum(jnp.sum(jnp.square(g))
                               for g in jax.tree.leaves(grads)))
