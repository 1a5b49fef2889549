"""DeepSeek-V2 (``model_type: deepseek_v2``), written plainly: float32
``jax.numpy`` at ``highest`` matmul precision, every key and value
decompressed from its latent, every held expert applied to every token and
masked by the routing weights, no cache, no absorption, no chunking, no
kernels, nothing imported from the program.

Source: the ``config.json`` of ``deepseek-ai/DeepSeek-V2`` and the released
``modeling_deepseek.py``'s order of operations.

The equations (d = ``hidden_size``; RMSNorm(x) = ``x / sqrt(mean(x^2) + eps)
* w``; no bias anywhere; H heads; a head's query is ``[q_nope | q_pe]`` of
``nope + rope`` numbers, its value ``v`` numbers):

  ``x = E[tokens]``
  every layer: ``x = x + Attention(RMSNorm_1(x))`` and then
               ``x = x + FF(RMSNorm_2(x))``
  ``logits = RMSNorm_f(x) W_head^T`` (the head is its own matrix)

``Attention``: ``c_q = RMSNorm(x W_qa)``; ``q = c_q W_qb`` -> H x (nope +
  rope). ``[c_kv | k_pe] = x W_kva`` (rank + rope); ``c_kv = RMSNorm(c_kv)``;
  ``[k_nope | v]_h = c_kv W_kvb`` for each head h. ``q_pe`` and ``k_pe`` are
  rotated by position; ``k_pe`` is ONE vector all heads share.
  ``score_h = (q_nope_h . k_nope_h + q_pe_h . k_pe) * s``, causal softmax,
  ``o_h = sum p v_h``, ``out = concat_h(o_h) W_o``.
Rotation (YaRN): rope / 2 frequencies ``theta^(-2i / rope)``, each blended
  between itself and itself / factor by the linear ramp between the lanes
  at which ``original_max_position_embeddings`` positions make ``beta_fast``
  and ``beta_slow`` turns (the released ``yarn_find_correction_range`` and
  ``yarn_linear_ramp_mask``); cos and sin times ``m(mscale) /
  m(mscale_all_dim)`` with ``m(a) = 0.1 a ln(factor) + 1``; and
  ``s = (nope + rope)^-0.5 * m(mscale_all_dim)^2``.
``FF``, in the first ``first_k_dense_replace`` layers (those whose
  parameters hold ``mlp_in``): ``W_down (silu(a) * b)``, ``[a, b] = W_in h``.
  After them: ``p = softmax(h W_g)`` over all experts, float32; a group's
  score is the largest ``p`` among its E / n_group consecutive experts; the
  ``topk_group`` best groups keep their scores, the rest become 0; the
  ``top_k`` largest are chosen, their weights those ``p`` unchanged
  (``norm_topk_prob`` false) times ``routed_scaling_factor``;
  ``sum_k w_k E_k(h) + S(h)``, each ``E`` a gated MLP, ``S`` one gated MLP
  of ``n_shared_experts`` times that width.

Departures from the released code, each also under ``assumed`` in the
configuration file:
* Rotary lanes: lane i of a rope vector pairs with lane i + rope / 2. The
  released code first de-interleaves the checkpoint's lanes (view [.., rope
  / 2, 2], transpose) into exactly this layout; with seeded weights that
  fixed permutation of columns of ``W_qb`` and ``W_kva`` is a relabelling.
* A gated MLP's two input matrices lie side by side, ``[a, b] = W_in h``
  (the released ``gate_proj`` and ``up_proj`` as one matrix's halves).
* The experts held are a share: ``params["layers"][i]["experts_in"]`` holds
  the experts ``first_expert .. first_expert + E_held`` of the router's
  width, and what the others would add is left out (other chips add it).
  With every expert held this is the whole layer.
* ``seq_aux`` and the balance losses are training's and are not here.

Leaves may come in bfloat16 (exact to widen); each is widened where it is
used, an expert at a time, so that no float32 copy of all the weights is
ever made. Attention runs over ``HEAD_BLOCK`` heads and ``QUERY_BLOCK``
queries at a time so that 8k tokens x 128 heads fit: a block's scores are
[rows, HEAD_BLOCK, QUERY_BLOCK, T], not [rows, H, T, T].
"""

from __future__ import annotations

import math

import jax
import jax.numpy as jnp
import numpy as np

F32 = jnp.float32
QUERY_BLOCK = 512
HEAD_BLOCK = 16


def _w(x):
    return x.astype(F32)


def rms_norm(x, w, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps) \
        * _w(w)


def gated(ab):
    """``silu(a) * b`` of ``[a, b]`` side by side."""
    half = ab.shape[-1] // 2
    return jax.nn.silu(ab[..., :half]) * ab[..., half:]


def yarn_get_mscale(scale, mscale):
    return 1.0 if scale <= 1 else 0.1 * mscale * math.log(scale) + 1.0


def softmax_scale(*, nope, rope, rope_scaling):
    m = yarn_get_mscale(rope_scaling["factor"],
                        rope_scaling["mscale_all_dim"])
    return (nope + rope) ** -0.5 * m * m


def yarn_angles(positions, *, rope, rope_theta, rope_scaling):
    """positions [T] -> (cos, sin) [T, rope / 2], float32, with YaRN's
    magnitude factor on both."""
    rs, dim, base = rope_scaling, rope, rope_theta
    original = rs["original_max_position_embeddings"]

    def correction_dim(rotations):
        return dim * math.log(original / (rotations * 2 * math.pi)) \
            / (2 * math.log(base))

    low = max(math.floor(correction_dim(rs["beta_fast"])), 0)
    high = min(math.ceil(correction_dim(rs["beta_slow"])), dim - 1)
    if low == high:
        high += 0.001
    exponent = np.arange(0, dim, 2, dtype=np.float64) / dim
    freq_extra = 1.0 / base ** exponent
    freq_inter = 1.0 / (rs["factor"] * base ** exponent)
    ramp = np.clip((np.arange(dim // 2, dtype=np.float64) - low)
                   / (high - low), 0, 1)
    inv_freq_mask = 1.0 - ramp
    inv_freq = freq_inter * (1 - inv_freq_mask) + freq_extra * inv_freq_mask
    m = yarn_get_mscale(rs["factor"], rs["mscale"]) \
        / yarn_get_mscale(rs["factor"], rs["mscale_all_dim"])
    freqs = positions.astype(F32)[:, None] * jnp.asarray(inv_freq, F32)
    return jnp.cos(freqs) * m, jnp.sin(freqs) * m


def rotate(x, cos, sin):
    """x [..., T, rope] (or [..., T, H, rope] with cos/sin given an axis
    for H): ``x * cos + rotate_half(x) * sin`` on split halves."""
    half = x.shape[-1] // 2
    a, b = x[..., :half], x[..., half:]
    return jnp.concatenate([a * cos - b * sin, b * cos + a * sin], axis=-1)


def attention(p, x, *, n_head, nope, rope, v_dim, eps, rope_theta,
              rope_scaling):
    """x [R, T, d] (normed) -> [R, T, d]."""
    r, t, _ = x.shape
    rank = p["kv_a_layernorm"].shape[0]
    s = softmax_scale(nope=nope, rope=rope, rope_scaling=rope_scaling)
    cos, sin = yarn_angles(jnp.arange(t), rope=rope, rope_theta=rope_theta,
                           rope_scaling=rope_scaling)
    c_q = rms_norm(x @ _w(p["q_a_proj"]), p["q_a_layernorm"], eps)
    kv = x @ _w(p["kv_a_proj_with_mqa"])
    c_kv = rms_norm(kv[..., :rank], p["kv_a_layernorm"], eps)
    k_pe = rotate(kv[..., rank:], cos, sin)  # [R, T, rope], every head's
    hb = min(HEAD_BLOCK, n_head)
    assert n_head % hb == 0
    q_b = p["q_b_proj"].reshape(-1, n_head // hb, hb, nope + rope)
    kv_b = p["kv_b_proj"].reshape(rank, n_head // hb, hb, nope + v_dim)
    qb_len = min(QUERY_BLOCK, t)
    n_blocks = -(-t // qb_len)
    pad = n_blocks * qb_len - t

    def heads(inp):  # a block of heads, every query block in turn
        w_q, w_kv = inp  # [q_rank, hb, nope + rope], [rank, hb, nope + v]
        q = jnp.einsum("rtc,chd->rthd", c_q, _w(w_q))
        q_pe = rotate(q[..., nope:], cos[:, None], sin[:, None])
        kvh = jnp.einsum("rtc,chd->rthd", c_kv, _w(w_kv))
        k_nope, v = kvh[..., :nope], kvh[..., nope:]
        q_all = jnp.pad(jnp.concatenate([q[..., :nope], q_pe], -1),
                        ((0, 0), (0, pad), (0, 0), (0, 0)))

        def queries(i):
            qs = jax.lax.dynamic_slice_in_dim(q_all, i * qb_len, qb_len, 1)
            scores = (jnp.einsum("rihd,rjhd->rhij", qs[..., :nope], k_nope)
                      + jnp.einsum("rihd,rjd->rhij", qs[..., nope:], k_pe)) \
                * s
            seen = jnp.arange(t)[None, :] \
                <= (i * qb_len + jnp.arange(qb_len))[:, None]
            probs = jax.nn.softmax(jnp.where(seen, scores, -jnp.inf), -1)
            return jnp.einsum("rhij,rjhd->rihd", probs, v)

        out = jax.lax.map(queries, jnp.arange(n_blocks))  # [n, R, qb, hb, v]
        return jnp.moveaxis(out, 0, 1).reshape(r, n_blocks * qb_len, hb,
                                               v_dim)[:, :t]

    out = jax.lax.map(heads, (jnp.moveaxis(q_b, 1, 0),
                              jnp.moveaxis(kv_b, 1, 0)))  # [n, R, T, hb, v]
    out = jnp.moveaxis(out, 0, 2).reshape(r, t, n_head * v_dim)
    return out @ _w(p["o_proj"])


def gating(x, router_w, *, top_k, n_group, topk_group, routed_scale):
    """x [T, d] -> [T, E_all]: each token's weight on each expert: its
    softmax score times ``routed_scale`` where chosen, 0 elsewhere."""
    p = jax.nn.softmax(x @ _w(router_w), axis=-1)
    t, e = p.shape
    group = jnp.max(p.reshape(t, n_group, e // n_group), axis=-1)  # [T, G]
    # a group's rank: how many groups beat it (an equal score: the lower id)
    ids = jnp.arange(n_group)
    beats = (group[:, None, :] > group[:, :, None]) | (
        (group[:, None, :] == group[:, :, None])
        & (ids[None, None, :] < ids[None, :, None]))
    keep = jnp.sum(beats, axis=-1) < topk_group  # [T, G]
    left = jnp.where(jnp.repeat(keep, e // n_group, axis=1), p, 0.0)
    top, chosen = jax.lax.top_k(left, top_k)
    return jnp.zeros_like(p).at[jnp.arange(t)[:, None], chosen].set(
        top * routed_scale)


def experts(p, x, *, first_expert, **router):
    """x [R, T, d] (normed) -> [R, T, d]: the held experts' part of the
    routed output, plus the shared experts."""
    shape = x.shape
    x = x.reshape(-1, shape[-1])
    held = p["experts_in"].shape[0]
    mine = gating(x, p["router"], **router)[
        :, first_expert:first_expert + held]

    def expert(r, inp):  # every held expert over every token, then masked
        w_in, w_out, g_e = inp
        return r + g_e[:, None] * (gated(x @ _w(w_in)) @ _w(w_out)), None

    routed, _ = jax.lax.scan(expert, jnp.zeros_like(x),
                             (p["experts_in"], p["experts_out"], mine.T))
    out = routed + gated(x @ _w(p["shared_in"])) @ _w(p["shared_out"])
    return out.reshape(shape)


def forward(params, tokens, *, n_head, nope, rope, v_dim, eps, rope_theta,
            rope_scaling, top_k, n_group, topk_group, routed_scale,
            first_expert):
    """Logits [R, T, V] float32 of tokens [R, T]."""
    with jax.default_matmul_precision("highest"):
        x = _w(params["embed_tokens"][tokens])
        for p in params["layers"]:
            y = rms_norm(x, p["input_layernorm"], eps)
            x = x + attention(p, y, n_head=n_head, nope=nope, rope=rope,
                              v_dim=v_dim, eps=eps, rope_theta=rope_theta,
                              rope_scaling=rope_scaling)
            y = rms_norm(x, p["post_attention_layernorm"], eps)
            if "mlp_in" in p:
                x = x + gated(y @ _w(p["mlp_in"])) @ _w(p["mlp_down"])
            else:
                x = x + experts(p, y, first_expert=first_expert,
                                top_k=top_k, n_group=n_group,
                                topk_group=topk_group,
                                routed_scale=routed_scale)
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
