"""The Gated DeltaNet mixer of the served linear-attention layers
(``models/qwen3_next.py``): its weights, its share of a serving cache, one
token a slot (``delta_step``) and rows of tokens that begin or continue a
slot's state (``delta_rows`` over the blocked scan ``chunked_delta_rule``).
The interface is ``ops/mamba2.py``'s, so that a reader of one knows the
other; it takes its sizes as ``GatedDeltaDims`` and not a family's
configuration.

The equations (Hk key heads of dk lanes, Hv value heads of dv, Hv a
multiple of Hk: value head j reads key head ``j // (Hv / Hk)``; kernel K;
``l2(x) = x * rsqrt(sum(x^2) + 1e-6)``):

  ``[q, k, v, z] = y W_qkvz``, ``[b, a] = y W_ba``, both laid out per KEY
  head as the released checkpoint has them (``split_qkvz``, ``split_ba``);
  ``[q, k, v] = silu(causal_conv1d([q, k, v]))``, no bias;
  ``beta = sigmoid(b)``, ``g = -exp(a_log) * softplus(a + dt_bias)``;
  ``q = l2(q) / sqrt(dk)``, ``k = l2(k)``; per value head, state S [dk, dv]:
  ``S = exp(g_t) S``; ``d_t = beta_t (v_t - S^T k_t)``; ``S = S + k_t d_t^T``;
  ``o_t = S^T q_t``: the delta rule takes what the state already says of
  ``k_t`` off before it writes;
  ``out_proj(RMSNorm_dv(o_t) * w * silu(z_t))``: the norm over each head's
  own lanes, BEFORE the gate.

The write at ``t`` depends on the state at ``t - 1`` through ``k_t``, so a
block of tokens is no cumulative product as Mamba-2's is: inside a block
the ``d_t`` solve the unit-lower-triangular system ``(I + A) D = beta V -
(beta K e^cum) S_0``, ``A_tj = beta_t e^(cum_t - cum_j) k_t . k_j`` for
``j < t`` (the WY / UT form), and the state goes from block to block.

A layer's weights are a dict: ``in_qkvz`` [D, 2 Hk dk + 2 Hv dv], ``in_ba``
[D, 2 Hv], ``conv_w`` [K, 2 Hk dk + Hv dv], ``dt_bias`` / ``a_log`` [Hv],
``gate_norm`` [dv], ``out_proj`` [Hv dv, D]. The projections multiply in
``dims.dtype``; ``g``, the cumulated decays, the block solve, the scan's
products and the state are float32 (the scan's products are a thousandth
of a layer's operations). In a cache the convolution's tails of all linear
layers are one array [layer, K-1, slot, C] and the state is one array a
layer [slot, Hv, dk, dv], as ``ops/mamba2.init_state`` has them and why.
"""

from __future__ import annotations

import dataclasses
import math
from typing import Any

import jax
import jax.numpy as jnp
import numpy as np

from ray_tpu.ops.mamba2 import conv_rows, conv_step, conv_tail

Params = dict[str, Any]

L2_EPS = 1e-6
_HI = jax.lax.Precision.HIGHEST


@dataclasses.dataclass(frozen=True)
class GatedDeltaDims:
    key_heads: int
    value_heads: int
    key_dim: int
    value_dim: int
    kernel: int
    block: int  # tokens a block of the scan; no result moves with it
    eps: float
    dtype: Any = jnp.bfloat16        # activations and projections
    state_dtype: Any = jnp.float32   # the delta state in a cache

    def __post_init__(self):
        if self.value_heads % self.key_heads:
            raise ValueError("value heads must divide among the key heads")
        if self.block < 1 or self.block & (self.block - 1):
            raise ValueError(f"block {self.block}: the block solve halves "
                             f"a block, so it is a power of two")

    @property
    def per_key(self) -> int:
        """Value heads a key head serves."""
        return self.value_heads // self.key_heads

    @property
    def key_width(self) -> int:
        return self.key_heads * self.key_dim

    @property
    def value_width(self) -> int:
        return self.value_heads * self.value_dim

    @property
    def conv_dim(self) -> int:
        return 2 * self.key_width + self.value_width

    @property
    def qkvz_width(self) -> int:
        return 2 * self.key_width + 2 * self.value_width


def mixer_init(keys, d_model: int, dims: GatedDeltaDims, param_dtype, normal,
               out_std: float, in_std: float = 0.02,
               ba_std: float | None = None) -> Params:
    """A mixer's seeded weights, drawing from the iterator ``keys`` (six of
    them). The decay as ``ops/mamba2.mixer_init`` draws Mamba-2's: the step
    ``softplus(dt_bias)`` log-uniform in [1e-3, 1e-1] through the inverse
    softplus and ``exp(a_log)`` uniform in [1, 16], so that a head's state
    halves in tokens to hundreds of tokens (the released initialisation,
    ``A ~ U(0, 16)`` under ``dt_bias = 1``, forgets within a token, and a
    seeded model would hold nothing of a carried state). Matrices are
    ``normal(key, shape, std, dtype)``."""
    hv, pd = dims.value_heads, param_dtype
    dt = jnp.exp(jax.random.uniform(
        next(keys), (hv,), jnp.float32, math.log(1e-3), math.log(1e-1)))
    dt = jnp.maximum(dt, 1e-4)
    return dict(
        in_qkvz=normal(next(keys), (d_model, dims.qkvz_width), in_std, pd),
        in_ba=normal(next(keys), (d_model, 2 * hv),
                     in_std if ba_std is None else ba_std, pd),
        conv_w=jax.random.uniform(
            next(keys), (dims.kernel, dims.conv_dim), jnp.float32,
            -0.5, 0.5).astype(pd),
        dt_bias=(dt + jnp.log(-jnp.expm1(-dt))).astype(pd),
        a_log=jnp.log(jax.random.uniform(
            next(keys), (hv,), jnp.float32, 1.0, 16.0)).astype(pd),
        gate_norm=jnp.ones((dims.value_dim,), pd),
        out_proj=normal(next(keys), (dims.value_width, d_model), out_std,
                        pd))


def init_state(dims: GatedDeltaDims, layers: int, slots: int) -> Params:
    """The mixers' share of a serving cache: ``conv`` the convolution's
    tails of all ``layers`` [layer, K-1, slot, channel] and ``delta`` the
    float32 state, a matrix a value head, one array a layer (a tuple: a
    decode step rewrites a layer's whole state, ``ops/mamba2.init_state``
    says why that wants a buffer of its own)."""
    return {
        "conv": jnp.zeros((layers, dims.kernel - 1, slots, dims.conv_dim),
                          dims.dtype),
        "delta": tuple(jnp.zeros((slots, dims.value_heads, dims.key_dim,
                                  dims.value_dim), dims.state_dtype)
                       for _ in range(layers)),
    }


def split_qkvz(proj: jax.Array, dims: GatedDeltaDims):
    """``in_qkvz``'s output [..., 2 Hk dk + 2 Hv dv], laid out per KEY head
    (q dk | k dk | v per_key x dv | z per_key x dv, as the released
    checkpoint), -> (the convolution's input [..., conv_dim]: every head's
    q, then every k, then every v; z [..., Hv, dv])."""
    lead = proj.shape[:-1]
    dk, vw = dims.key_dim, dims.per_key * dims.value_dim
    by_head = proj.reshape(*lead, dims.key_heads, 2 * dk + 2 * vw)
    q, k = by_head[..., :dk], by_head[..., dk:2 * dk]
    v, z = by_head[..., 2 * dk:2 * dk + vw], by_head[..., 2 * dk + vw:]
    mixed = jnp.concatenate([q.reshape(*lead, -1), k.reshape(*lead, -1),
                             v.reshape(*lead, -1)], axis=-1)
    return mixed, z.reshape(*lead, dims.value_heads, dims.value_dim)


def split_ba(proj: jax.Array, dims: GatedDeltaDims):
    """``in_ba``'s output [..., 2 Hv], per KEY head (b per_key | a per_key),
    -> (b [..., Hv], a [..., Hv])."""
    lead = proj.shape[:-1]
    by_head = proj.reshape(*lead, dims.key_heads, 2 * dims.per_key)
    return (by_head[..., :dims.per_key].reshape(*lead, -1),
            by_head[..., dims.per_key:].reshape(*lead, -1))


def _l2(x: jax.Array) -> jax.Array:
    return x * jax.lax.rsqrt(jnp.sum(x * x, axis=-1, keepdims=True) + L2_EPS)


def delta_inputs(conv: jax.Array, dims: GatedDeltaDims):
    """The convolution's output (after its silu) split and made ready, in
    float32: q [..., Hv, dk] = ``l2(q) / sqrt(dk)`` and k [..., Hv, dk] =
    ``l2(k)``, each key head's repeated for the value heads it serves, and
    v [..., Hv, dv]."""
    lead = conv.shape[:-1]
    kw = dims.key_width
    conv = conv.astype(jnp.float32)
    q = _l2(conv[..., :kw].reshape(*lead, dims.key_heads, dims.key_dim)) \
        * dims.key_dim ** -0.5
    k = _l2(conv[..., kw:2 * kw].reshape(*lead, dims.key_heads,
                                         dims.key_dim))
    v = conv[..., 2 * kw:].reshape(*lead, dims.value_heads, dims.value_dim)
    return (jnp.repeat(q, dims.per_key, axis=-2),
            jnp.repeat(k, dims.per_key, axis=-2), v)


def beta_and_g(p: Params, b: jax.Array, a: jax.Array):
    """``beta = sigmoid(b)`` and the log of the decay ``g = -exp(a_log) *
    softplus(a + dt_bias)``, float32. b, a [..., Hv]."""
    g = -jnp.exp(p["a_log"].astype(jnp.float32)) * jax.nn.softplus(
        a.astype(jnp.float32) + p["dt_bias"].astype(jnp.float32))
    return jax.nn.sigmoid(b.astype(jnp.float32)), g


def gated_head_norm(o, z, w, dims: GatedDeltaDims):
    """``RMSNorm_dv(o) * w * silu(z)``: the norm over each head's own lanes,
    before the gate. o, z [..., Hv, dv] -> [..., Hv dv] in ``dims.dtype``."""
    of = o.astype(jnp.float32)
    of = of * jax.lax.rsqrt(
        jnp.mean(of * of, axis=-1, keepdims=True) + dims.eps)
    out = of * w.astype(jnp.float32) * jax.nn.silu(z.astype(jnp.float32))
    return out.reshape(*out.shape[:-2], -1).astype(dims.dtype)


def delta_step(p: Params, y: jax.Array, tail: jax.Array, state: jax.Array,
               dims: GatedDeltaDims):
    """One token a slot. y [S, D] (normed), tail [K-1, S, C] the last
    inputs of the convolution, state [S, Hv, dk, dv] float32. -> (the
    mixer's output [S, D], the new tail, the new state)."""
    dt_ = dims.dtype
    with jax.named_scope("gdn_proj"):
        mixed, z = split_qkvz(y @ p["in_qkvz"].astype(dt_), dims)
        b, a = split_ba(y @ p["in_ba"].astype(dt_), dims)
    with jax.named_scope("conv"):
        window, conv = conv_step(tail, mixed, p["conv_w"])
        q, k, v = delta_inputs(jax.nn.silu(conv).astype(dt_), dims)
    with jax.named_scope("gdn_update"):
        beta, g = beta_and_g(p, b, a)  # [S, Hv]
        decayed = state.astype(jnp.float32) * jnp.exp(g)[..., None, None]
        # what the state holds of k, and of q, beside each other: one pass
        held = jnp.sum(decayed * k[..., None], axis=-2)  # [S, Hv, dv]
        read = jnp.sum(decayed * q[..., None], axis=-2)
        d = beta[..., None] * (v - held)
        out = read + jnp.sum(q * k, axis=-1, keepdims=True) * d
        state = decayed + k[..., None] * d[..., None, :]
    with jax.named_scope("gdn_norm"):
        yn = gated_head_norm(out, z, p["gate_norm"], dims)
    with jax.named_scope("gdn_proj"):
        out = yn @ p["out_proj"].astype(dt_)
    return out, window[1:], state.astype(dims.state_dtype)


def _unit_lower_inverse(a: jax.Array) -> jax.Array:
    """``(I + A)^-1`` of strictly lower triangular A [..., n, n] (n a power
    of two), float32: the triangular solve by doubling. With the inverses X
    of the diagonal blocks of size b in hand, a block of 2b is ``[[X11, 0],
    [-X22 A21 X11, X22]]``; masked to the ``A21`` corners and X block
    diagonal, ``X (A * corners) X`` is every block's corner at once, so a
    level is two batched products and there are ``log2 n`` levels."""
    n = a.shape[-1]
    idx = np.arange(n)
    x = jnp.broadcast_to(jnp.eye(n, dtype=a.dtype), a.shape)
    b = 1
    while b < n:
        corners = (idx[:, None] // (2 * b) == idx[None, :] // (2 * b)) \
            & ((idx[:, None] // b) % 2 == 1) & ((idx[None, :] // b) % 2 == 0)
        x = x - jnp.matmul(jnp.matmul(x, jnp.where(corners, a, 0.0),
                                      precision=_HI), x, precision=_HI)
        b *= 2
    return x


def chunked_delta_rule(q, k, v, g, beta, dims: GatedDeltaDims, state=None):
    """The delta rule over rows from ``state`` [R, Hv, dk, dv] float32
    (None: empty), in blocks of ``dims.block`` tokens: inside a block the
    triangular system (solved once, ``_unit_lower_inverse``), between blocks
    the state. q, k [R, T, Hv, dk] (``delta_inputs``'), v [R, T, Hv, dv],
    g and beta [R, T, Hv] float32, both 0 at padded positions: they leave
    the state as it is.
    -> (o [R, T, Hv, dv] float32, the state after the row [R, Hv, dk, dv])."""
    r, t, h, dk = q.shape
    dv = v.shape[-1]
    blk = min(dims.block, 1 << (t - 1).bit_length())  # a power of two
    pad = (-t) % blk
    if pad:
        q, k, v, g, beta = (jnp.pad(x, ((0, 0), (0, pad)) + ((0, 0),) * (
            x.ndim - 2)) for x in (q, k, v, g, beta))
    nc = (t + pad) // blk
    f32 = jnp.float32

    def blocks(x):  # [R, T, H, ...] -> [nc, R, H, Q, ...]
        x = x.astype(f32).reshape(r, nc, blk, *x.shape[2:])
        return jnp.moveaxis(jnp.moveaxis(x, 2, 3), 1, 0)

    q, k, v, g, beta = (blocks(x) for x in (q, k, v, g, beta))
    cum = jnp.cumsum(g, axis=-1)  # [nc, R, H, Q]
    gap = cum[..., :, None] - cum[..., None, :]  # [.., i, j]
    causal = jnp.tril(jnp.ones((blk, blk), bool))
    decay = jnp.where(causal, jnp.exp(jnp.where(causal, gap, 0.0)), 0.0)
    kb, vb = k * beta[..., None], v * beta[..., None]
    a = jnp.einsum("...id,...jd->...ij", kb, k, precision=_HI) * decay
    solve = _unit_lower_inverse(jnp.where(jnp.tril(causal, -1), a, 0.0))
    # D = u - w S_0: what each token writes, by the state the block enters
    u = jnp.matmul(solve, vb, precision=_HI)  # [nc, R, H, Q, dv]
    w = jnp.matmul(solve, kb * jnp.exp(cum)[..., None], precision=_HI)
    inside = jnp.einsum("...id,...jd->...ij", q, k, precision=_HI) * decay
    q_in = q * jnp.exp(cum)[..., None]       # reads the entering state
    to_end = jnp.exp(cum[..., -1:] - cum)    # [nc, R, H, Q]
    k_out = k * to_end[..., None]            # writes, decayed to the end
    through = jnp.exp(cum[..., -1])          # [nc, R, H]

    def block(s, inp):  # s [R, H, dk, dv]
        u_c, w_c, inside_c, q_c, k_c, through_c = inp
        d = u_c - jnp.matmul(w_c, s, precision=_HI)  # [R, H, Q, dv]
        o = jnp.matmul(q_c, s, precision=_HI) \
            + jnp.matmul(inside_c, d, precision=_HI)
        s = s * through_c[..., None, None] + jnp.einsum(
            "...qk,...qv->...kv", k_c, d, precision=_HI)
        return s, o

    state = jnp.zeros((r, h, dk, dv), f32) if state is None \
        else state.astype(f32)
    state, o = jax.lax.scan(block, state,
                            (u, w, inside, q_in, k_out, through))
    # [nc, R, H, Q, dv] -> [R, T, H, dv]
    o = jnp.moveaxis(jnp.moveaxis(o, 0, 1), 2, 3).reshape(
        r, t + pad, h, dv)[:, :t]
    return o, state


def delta_rows(p: Params, y: jax.Array, lengths: jax.Array,
               dims: GatedDeltaDims, tail: jax.Array | None = None,
               state: jax.Array | None = None):
    """Rows of T tokens that continue from ``tail`` [R, K-1, C] (the
    convolution's last inputs) and ``state`` [R, Hv, dk, dv]; None for
    both: rows that begin. y [R, T, D] (normed), lengths [R]: the real
    tokens of each row. -> (the mixer's output [R, T, D], the convolution's
    tail after ``length`` tokens [K-1, R, C], the state after ``length``
    tokens [R, Hv, dk, dv]); with no real token, the tail and state given."""
    dt_ = dims.dtype
    t = y.shape[1]
    with jax.named_scope("gdn_proj"):
        mixed, z = split_qkvz(y @ p["in_qkvz"].astype(dt_), dims)
        b, a = split_ba(y @ p["in_ba"].astype(dt_), dims)
    with jax.named_scope("conv"):
        padded, conv = conv_rows(mixed, tail, p["conv_w"])
        q, k, v = delta_inputs(jax.nn.silu(conv).astype(dt_), dims)
        tail = conv_tail(padded, lengths, dims.kernel)
    with jax.named_scope("gdn_scan"):
        beta, g = beta_and_g(p, b, a)
        real = jnp.arange(t)[None, :, None] < lengths[:, None, None]
        o, state = chunked_delta_rule(
            q, k, v, jnp.where(real, g, 0.0), jnp.where(real, beta, 0.0),
            dims, state)
    with jax.named_scope("gdn_norm"):
        yn = gated_head_norm(o, z, p["gate_norm"], dims)
    with jax.named_scope("gdn_proj"):
        out = yn @ p["out_proj"].astype(dt_)
    return out, tail, state.astype(dims.state_dtype)


# -- a layer's share of the cache ---------------------------------------------


def step_through_cache(p: Params, y: jax.Array, conv_all: jax.Array,
                       delta: jax.Array, layer: int, dims: GatedDeltaDims):
    """``delta_step`` for every slot on linear layer ``layer``'s state.
    -> (out [S, D], ``conv_all`` with the layer's new tail, the new state)."""
    out, tail, state = delta_step(p, y, conv_all[layer], delta, dims)
    with jax.named_scope("state_write"):
        conv_all = jax.lax.dynamic_update_slice(
            conv_all, tail[None].astype(conv_all.dtype), (layer, 0, 0, 0))
    return out, conv_all, state


def rows_through_cache(p: Params, y: jax.Array, lengths: jax.Array,
                       conv_all: jax.Array, delta: jax.Array, layer: int,
                       slots: jax.Array, goes_on: jax.Array,
                       dims: GatedDeltaDims):
    """``delta_rows`` of a chunk: row r continues from what ``slots[r]``
    holds for linear layer ``layer`` where ``goes_on[r]``, else begins, and
    leaves there, in place, its state after the row's real tokens.
    -> (out [R, T, D], ``conv_all``, the layer's state array)."""
    r = y.shape[0]
    with jax.named_scope("conv"):  # its left context, by slot
        tail = jnp.stack([jax.lax.dynamic_slice(
            conv_all, (layer, 0, slots[i], 0),
            (1, dims.kernel - 1, 1, dims.conv_dim))[0, :, 0]
            for i in range(r)])  # [R, K-1, C]
        tail = jnp.where(goes_on[:, None, None], tail, 0)
    with jax.named_scope("gdn_scan"):  # its first state, by slot
        state = jnp.concatenate([jax.lax.dynamic_slice(
            delta, (slots[i], 0, 0, 0), (1,) + delta.shape[1:])
            for i in range(r)])
        state = jnp.where(goes_on[:, None, None, None], state, 0)
    out, tail, state = delta_rows(p, y, lengths, dims, tail, state)
    with jax.named_scope("state_write"):
        tail = tail.astype(conv_all.dtype)
        for i in range(r):  # by slot; distinct but the scratch
            conv_all = jax.lax.dynamic_update_slice(
                conv_all, tail[None, :, i:i + 1], (layer, 0, slots[i], 0))
            delta = jax.lax.dynamic_update_slice(
                delta, state[i:i + 1], (slots[i], 0, 0, 0))
    return out, conv_all, delta
