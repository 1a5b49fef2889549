"""Keye-VL-2.0's language model (``model_type: KeyeVL2``), written plainly:
float32 ``jax.numpy`` at ``highest`` matmul precision, a token's key set as
a mask over the whole row made from a plain ``top_k``, the experts as a
loop over all held, no cache, no ring, no kernels, nothing imported from the
program.

Source: the ``config.json`` of ``Kwai-Keye/Keye-VL-2.0-30B-A3B`` (the
language model's keys), whose block is ``qwen3_moe``'s (``transformers``'
``modeling_qwen3_moe.py``: per-head RMSNorm of q and k, softmax router,
``top_k`` renormalised, no shared expert, every layer sparse) and whose
``sa_config`` is the "lightning indexer" of DeepSeek-V3.2-Exp.

The equations (d = ``hidden_size``; RMSNorm ``N(x; w) = x / sqrt(mean(x^2) +
eps) * w``; no bias but the indexer's LayerNorm; ``t`` a query position,
``s`` a key position). Every layer alike:

  ``x = E[tokens]``
  ``a = N(x; input_layernorm)``
  ``q = a W_q`` (n_head heads of head_dim), ``k = a W_k``, ``v = a W_v``
    (n_kv_head heads); ``q = N(q; q_norm)``, ``k = N(k; k_norm)`` over each
    head's lanes; q and k rotated by M-RoPE: lane i pairs with lane i +
    head_dim / 2 (``rotate_half``) and turns by ``pos_c(i)[t] * rope_theta
    ** (-2 i / head_dim)``, where c(i) is the one of THREE position streams
    (temporal, height, width) whose section of ``mrope_section`` frequency
    i lies in. For text the three streams are the same ``0 .. T - 1``;
  indexer, from the same ``a``: ``qI = a W_qI`` (indexer_num_heads heads of
    indexer_head_dim), ``kI = LayerNorm(a W_kI)`` (one head; weight and
    bias), ``w = a W_w`` (a number a head); qI and kI rotated over all their
    lanes (``rotate_half``, ``rope_theta``, one stream);
    ``I[t, s] = sum_j w[t, j] relu(qI[t, j] . kI[s])``;
    ``S_t`` = the ``topk`` positions ``s <= t`` of largest ``I[t, s]`` (all
    of them while ``t < topk``), ties to the lower ``s``;
  ``h = x + W_o concat_h softmax_{s in S_t}(q_h[t] . k_g(h)[s] /
    sqrt(head_dim)) v_g(h)[s]``: ONE set a token a layer, for all heads;
  ``y = N(h; post_attention_layernorm)``; ``p = softmax(y W_r)`` over ALL
    experts, the ``top_k`` largest, divided by their sum (``norm_topk_prob``);
  ``out = h + sum_chosen p_e W_down,e (silu(W_gate,e y) * W_up,e y)``;
  ``logits = N(x; norm) W_head`` (``tie_word_embeddings`` false).

Departures, each also in the configuration file:
* The vocabulary held is a slice, and the experts held are a share
  (``first_expert .. first_expert + E_held`` of the router's; the router
  scores all, what the absent experts would add is left out): as
  ``reference/qwen3_next.py``.
* The vision tower is not here: text only.
* The rotary frequencies are rounded to float32 once, from float64.
* What the config leaves open about the indexer is the file's ``assumed``
  and, where another reading is possible, an argument here, so that a test
  can turn it the other way and see it fail: ``indexer_rotary`` (``all``:
  every lane of qI and kI turns; ``none`` for the control) and
  ``indexer_key_norm`` (``layernorm``; ``none`` for the control).

Leaves may come in bfloat16 (exact to widen); each is widened where it is
used. Attention and the selection run in blocks of ``QUERY_BLOCK`` queries
so that a long row's scores fit: a block's are [heads, block, T].
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


def layer_norm(x, w, b, eps):
    x = x - jnp.mean(x, axis=-1, keepdims=True)
    return x * jax.lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps) \
        * _w(w) + _w(b)


def _turn(x, angles):
    """``x cos + rotate_half(x) sin`` of x [R, T, heads, hd] by angles
    [R, T, hd / 2]."""
    hd = x.shape[-1]
    cos = jnp.concatenate([jnp.cos(angles)] * 2, -1)[:, :, None, :]
    sin = jnp.concatenate([jnp.sin(angles)] * 2, -1)[:, :, None, :]
    half = jnp.concatenate([-x[..., hd // 2:], x[..., :hd // 2]], -1)
    return x * cos + half * sin


def _inv_freq(hd, theta):
    return (1.0 / float(theta) ** (np.arange(0, hd, 2, dtype=np.float64)
                                   / hd)).astype(np.float32)


def rotary(x, theta):
    """x [R, T, heads, hd] at positions 0 .. T - 1, one stream."""
    r, t = x.shape[:2]
    angles = jnp.arange(t, dtype=F32)[:, None] * _inv_freq(x.shape[-1], theta)
    return _turn(x, jnp.broadcast_to(angles, (r,) + angles.shape))


def mrope(x, positions, theta, sections):
    """M-RoPE: x [R, T, heads, hd], positions [3, R, T] (temporal, height,
    width), ``sections`` how many of the hd / 2 frequencies each stream
    turns, in order."""
    hd = x.shape[-1]
    if sum(sections) != hd // 2:
        raise ValueError(f"mrope_section {sections} does not cover the "
                         f"{hd // 2} frequencies of a head of {hd}")
    stream = np.repeat(np.arange(len(sections)), sections)      # [hd / 2]
    pos = positions.astype(F32)[stream]                         # [hd/2, R, T]
    return _turn(x, jnp.moveaxis(pos, 0, -1) * _inv_freq(hd, theta))


def text_positions(r, t):
    """The three streams of a text row: all the token's index."""
    return jnp.broadcast_to(jnp.arange(t), (3, r, t))


def index_scores(p, a, *, indexer_heads, indexer_dim, rope_theta, eps,
                 indexer_rotary="all", indexer_key_norm="layernorm"):
    """a [R, T, d] (normed) -> I [R, T, T]: every query against every key,
    the causal mask not yet applied."""
    r, t, _ = a.shape
    q = (a @ _w(p["indexer_q_proj"])).reshape(r, t, indexer_heads,
                                               indexer_dim)
    k = a @ _w(p["indexer_k_proj"])
    if indexer_key_norm == "layernorm":
        k = layer_norm(k, p["indexer_k_norm"], p["indexer_k_bias"], eps)
    elif indexer_key_norm != "none":
        raise ValueError(f"indexer_key_norm {indexer_key_norm!r}")
    w = a @ _w(p["indexer_weights"])                            # [R, T, J]
    if indexer_rotary == "all":
        q = rotary(q, rope_theta)
        k = rotary(k[:, :, None, :], rope_theta)[:, :, 0]
    elif indexer_rotary != "none":
        raise ValueError(f"indexer_rotary {indexer_rotary!r}")
    out = []
    for at in range(0, t, QUERY_BLOCK):  # a block of queries over all keys
        dots = jax.nn.relu(jnp.einsum(
            "rtjd,rsd->rtjs", q[:, at:at + QUERY_BLOCK], k))
        out.append(jnp.einsum("rtjs,rtj->rts", dots,
                              w[:, at:at + QUERY_BLOCK]))
    return jnp.concatenate(out, axis=1)


def key_sets(scores, topk):
    """I [R, T, T] -> [R, T, T] bool: ``S_t`` as a mask, the ``topk``
    largest ``I[t, s]`` among ``s <= t``, ties to the lower ``s``
    (``lax.top_k`` puts the lower index first among equals)."""
    r, t, _ = scores.shape
    causal = jnp.arange(t)[None, :] <= jnp.arange(t)[:, None]
    # -0.0 is 0.0: equal scores are ties whatever their sign bit
    flat = jnp.where(causal, jnp.where(scores == 0, 0.0, scores),
                     -jnp.inf).reshape(r * t, t)
    _, idx = jax.lax.top_k(flat, min(topk, t))
    picked = jnp.zeros((r * t, t), bool).at[
        jnp.arange(r * t)[:, None], idx].set(True)
    return picked.reshape(r, t, t) & causal


def attention(p, a, sets, *, n_head, n_kv_head, head_dim, rope_theta, eps,
              mrope_section):
    """a [R, T, d] (normed), sets [R, T, T] bool -> [R, T, d]."""
    r, t, _ = a.shape
    q = (a @ _w(p["q_proj"])).reshape(r, t, n_head, head_dim)
    k = (a @ _w(p["k_proj"])).reshape(r, t, n_kv_head, head_dim)
    v = (a @ _w(p["v_proj"])).reshape(r, t, n_kv_head, head_dim)
    q, k = rms_norm(q, p["q_norm"], eps), rms_norm(k, p["k_norm"], eps)
    pos = text_positions(r, t)
    q = mrope(q, pos, rope_theta, mrope_section)
    k = mrope(k, pos, rope_theta, mrope_section)
    q = q.reshape(r, t, n_kv_head, n_head // n_kv_head, head_dim)
    out = []
    for at in range(0, t, QUERY_BLOCK):  # a block of queries over all keys
        qb = q[:, at:at + QUERY_BLOCK]
        scores = jnp.einsum("rigqd,rjgd->rgqij", qb, k) / head_dim ** 0.5
        seen = sets[:, None, None, at:at + QUERY_BLOCK]
        out.append(jnp.einsum(
            "rgqij,rjgd->rigqd",
            jax.nn.softmax(jnp.where(seen, scores, -jnp.inf), axis=-1), v))
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


def experts(p, y, weights):
    """y [T, d] (normed), weights [T, E_held] -> [T, d]: every held expert
    over every row, weighed by the row's routing weight."""

    def expert(total, inp):
        w_gate, w_up, w_down, w_e = inp
        out = (jax.nn.silu(y @ _w(w_gate)) * (y @ _w(w_up))) @ _w(w_down)
        return total + w_e[:, None] * out, None

    total, _ = jax.lax.scan(
        expert, jnp.zeros_like(y),
        (p["experts_gate"], p["experts_up"], p["experts_down"], weights.T))
    return total


def forward(params, tokens, *, eps, n_head, n_kv_head, head_dim, rope_theta,
            mrope_section, indexer_heads, indexer_dim, topk, top_k,
            first_expert, indexer_rotary="all", indexer_key_norm="layernorm",
            with_sets=False, with_streams=False):
    """Logits [R, T, V] float32 of tokens [R, T]; with ``with_sets`` also
    a (I [R, T, T], sets [R, T, T] bool) a layer, and with ``with_streams``
    the stream x [R, T, d] that layer received as the pair's third."""
    kept = []
    with jax.default_matmul_precision("highest"):
        x = _w(params["embed_tokens"][tokens])
        for p in params["layers"]:
            a = rms_norm(x, p["input_layernorm"], eps)
            scores = index_scores(
                p, a, indexer_heads=indexer_heads, indexer_dim=indexer_dim,
                rope_theta=rope_theta, eps=eps, indexer_rotary=indexer_rotary,
                indexer_key_norm=indexer_key_norm)
            sets = key_sets(scores, topk)
            if with_sets:
                kept.append((scores, sets) + (x,) * with_streams)
            h = x + attention(
                p, a, sets, n_head=n_head, n_kv_head=n_kv_head,
                head_dim=head_dim, rope_theta=rope_theta, eps=eps,
                mrope_section=mrope_section)
            y = rms_norm(h, p["post_attention_layernorm"], eps)
            flat = y.reshape(-1, y.shape[-1])
            held = p["experts_gate"].shape[0]
            mine = gating(flat @ _w(p["router"]), top_k)[
                :, first_expert:first_expert + held]
            x = h + experts(p, flat, mine).reshape(x.shape)
        x = rms_norm(x, params["norm"], eps)
        logits = x @ _w(params["lm_head"]).T
    return (logits, kept) if with_sets else logits


def loss_and_grad_norm(params, tokens, *, remat=False, **kwargs):
    """Mean next-token cross-entropy of rows of T + 1 tokens and the
    global L2 norm of its gradient (neither the router's choice nor the
    indexer's is differentiated). No training cell of this family exists:
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
