"""The Mamba-2 mixer the served hybrids share (``models/nemotron_h.py``,
``models/granite_hybrid.py``, ``models/falcon_h1.py``): its weights, its
share of a serving cache, one token a slot (``mamba_step``) and rows of
tokens that begin or continue a slot's state (``mamba_rows`` over the
blocked scan ``ssd_scan``).

It takes its sizes as ``Mamba2Dims`` and not a family's configuration: two
copies of a scan are where a later change speeds one model and forgets the
other. The equations (H heads of P channels, d_inner = H P, G groups of
B / C, state N, kernel K; ``benchmark/reference/`` writes them out plainly
for each family):

  ``[z, xBC, dt] = in_proj(y)`` (each part times its factor where a model
  publishes ``in_multipliers``); ``xBC = silu(causal_conv1d(xBC) + b)``,
  split x [H, P], B and C [G, N]; ``dt = softplus(dt + dt_bias)``,
  ``A = -exp(a_log)``; per head ``S_t = exp(dt_t A) S_{t-1} + dt_t x_t
  B_t^T``, ``y_t = S_t C_t + D x_t``;
  ``out_proj(RMSNorm_groups(y * silu(z)) * w)``.

A layer's weights are a dict: ``in_proj`` [D, 2 d_inner + 2 G N + H],
``conv_w`` [K, C], ``conv_b`` [C], ``dt_bias`` / ``a_log`` / ``d_skip`` [H],
``gate_norm`` [d_inner], ``out_proj`` [d_inner, D]. The matrices multiply in
``dims.dtype``; ``dt`` / ``A`` / decays, the norm's statistics and the state
are float32. In a cache the convolution's tails of all Mamba layers are one
array [layer, K-1, slot, C] and the state is one array a layer
[slot, H, P, N] (``init_state`` says why).
"""

from __future__ import annotations

import dataclasses
import math
from typing import Any

import jax
import jax.numpy as jnp
import numpy as np

Params = dict[str, Any]


@dataclasses.dataclass(frozen=True)
class Mamba2Dims:
    heads: int
    head_dim: int
    groups: int
    state: int
    kernel: int
    block: int  # how a prefill blocks the scan; no result moves with it
    eps: float
    dtype: Any = jnp.bfloat16        # activations and matmuls
    state_dtype: Any = jnp.float32   # the SSM state in a cache
    # What multiplies ``in_proj``'s output columns, by part: (z, x, B, C,
    # dt), a published vector of a model that scales them apart; None: the
    # output as it is, and nothing traced.
    in_multipliers: tuple | None = None

    @property
    def d_inner(self) -> int:
        return self.heads * self.head_dim

    @property
    def conv_dim(self) -> int:
        return self.d_inner + 2 * self.groups * self.state

    @property
    def in_width(self) -> int:
        return self.d_inner + self.conv_dim + self.heads


def mixer_init(keys, d_model: int, dims: Mamba2Dims, param_dtype, normal,
               out_std: float, in_std: float = 0.02) -> Params:
    """A mixer's seeded weights, drawing from the iterator ``keys`` (six of
    them): ``dt`` log-uniform in [1e-3, 1e-1] through the inverse softplus,
    ``A`` uniform in [1, 16], matrices ``normal(key, shape, std, dtype)``
    (``in_proj`` at ``in_std``, ``out_proj`` at ``out_std``)."""
    h, di, pd = dims.heads, dims.d_inner, param_dtype
    dt = jnp.exp(jax.random.uniform(
        next(keys), (h,), jnp.float32, math.log(1e-3), math.log(1e-1)))
    dt = jnp.maximum(dt, 1e-4)
    return dict(
        in_proj=normal(next(keys), (d_model, dims.in_width), in_std, pd),
        conv_w=jax.random.uniform(
            next(keys), (dims.kernel, dims.conv_dim), jnp.float32,
            -0.5, 0.5).astype(pd),
        conv_b=normal(next(keys), (dims.conv_dim,), 0.02, pd),
        dt_bias=(dt + jnp.log(-jnp.expm1(-dt))).astype(pd),
        a_log=jnp.log(jax.random.uniform(
            next(keys), (h,), jnp.float32, 1.0, 16.0)).astype(pd),
        d_skip=jnp.ones((h,), pd),
        gate_norm=jnp.ones((di,), pd),
        out_proj=normal(next(keys), (di, d_model), out_std, pd))


def init_state(dims: Mamba2Dims, layers: int, slots: int) -> Params:
    """The mixers' share of a serving cache: ``conv`` the convolution's
    tails of all ``layers`` [layer, K-1, slot, channel] (slots and channels
    are the minor dimensions, which tile) and ``ssm`` the float32 state,
    one array a layer (a tuple): a decode step rewrites a layer's whole
    state, and only with the layer's state as a buffer of its own does XLA
    fuse the update and the readout ``S_t C_t`` into one pass over it; as
    a slice of a stacked array it is read twice and written once
    (compile-only for a v5e, PR 28)."""
    return {
        "conv": jnp.zeros((layers, dims.kernel - 1, slots, dims.conv_dim),
                          dims.dtype),
        "ssm": tuple(jnp.zeros((slots, dims.heads, dims.head_dim,
                                dims.state), dims.state_dtype)
                     for _ in range(layers)),
    }


def gated_group_norm(y, z, w, dims: Mamba2Dims):
    """``RMSNorm_groups(y * silu(z)) * w``: the norm over each of the
    ``groups`` groups of channels. y, z [..., d_inner]."""
    yf = y.astype(jnp.float32) * jax.nn.silu(z.astype(jnp.float32))
    grouped = yf.reshape(*yf.shape[:-1], dims.groups, -1)
    grouped = grouped * jax.lax.rsqrt(
        jnp.mean(grouped * grouped, axis=-1, keepdims=True) + dims.eps)
    return (grouped.reshape(yf.shape) * w.astype(jnp.float32)).astype(
        dims.dtype)


def in_multiplier_vector(dims: Mamba2Dims):
    """``dims.in_multipliers`` laid over ``in_proj``'s ``in_width`` output
    columns, float32 (numpy): z, x, B, C, dt, each part its own factor."""
    gn = dims.groups * dims.state
    widths = (dims.d_inner, dims.d_inner, gn, gn, dims.heads)
    return np.repeat(np.asarray(dims.in_multipliers, np.float32), widths)


def ssm_inputs(proj: jax.Array, dims: Mamba2Dims):
    """``in_proj``'s output split: z [.., d_inner], xBC [.., conv_dim],
    and ``dt`` before its softplus [.., H]; each part times its entry of
    ``dims.in_multipliers`` where the model has them (in float32, rounded
    once to ``proj``'s type)."""
    if dims.in_multipliers is not None:
        proj = (proj.astype(jnp.float32)
                * in_multiplier_vector(dims)).astype(proj.dtype)
    di = dims.d_inner
    return (proj[..., :di], proj[..., di:di + dims.conv_dim],
            proj[..., di + dims.conv_dim:])


def ssm_split(conv: jax.Array, dims: Mamba2Dims):
    """The convolution's output split: x [.., H, P], B and C [.., G, N]."""
    di, gn = dims.d_inner, dims.groups * dims.state
    lead = conv.shape[:-1]
    return (conv[..., :di].reshape(*lead, dims.heads, dims.head_dim),
            conv[..., di:di + gn].reshape(*lead, dims.groups, dims.state),
            conv[..., di + gn:].reshape(*lead, dims.groups, dims.state))


def dt_and_a(p: Params, dt_raw: jax.Array):
    dt = jax.nn.softplus(dt_raw.astype(jnp.float32)
                         + p["dt_bias"].astype(jnp.float32))
    return dt, -jnp.exp(p["a_log"].astype(jnp.float32))


def conv_step(tail: jax.Array, x: jax.Array, w: jax.Array):
    """The causal depthwise convolution of ONE token a slot: tail [K-1, S, C]
    the last inputs, x [S, C] this token's, w [K, C]. -> (the window
    [K, S, C] in the tail's type, whose rows 1.. are the new tail, and the
    convolution [S, C] float32, no bias). Shared with ``ops/gated_delta.py``."""
    window = jnp.concatenate(
        [tail, x.astype(tail.dtype)[None]], axis=0)  # [K, S, C]
    return window, jnp.einsum("ksc,kc->sc", window.astype(jnp.float32),
                              w.astype(jnp.float32))


def conv_rows(x: jax.Array, tail: jax.Array | None, w: jax.Array):
    """The causal depthwise convolution of rows x [R, T, C] that continue
    from ``tail`` [R, K-1, C] (None: rows that begin), w [K, C]. -> (the
    inputs with their left context [R, K-1 + T, C], the convolution
    [R, T, C] float32, no bias)."""
    k, t = w.shape[0], x.shape[1]
    padded = jnp.pad(x, ((0, 0), (k - 1, 0), (0, 0))) if tail is None \
        else jnp.concatenate([tail.astype(x.dtype), x], axis=1)
    w = w.astype(jnp.float32)
    return padded, sum(padded[:, j:j + t].astype(jnp.float32) * w[j]
                       for j in range(k))


def conv_tail(padded: jax.Array, lengths: jax.Array, k: int) -> jax.Array:
    """The convolution's tail after ``lengths`` [R] real tokens of
    ``conv_rows``' ``padded``: the inputs at length - (K-1) .. length - 1,
    the old tail before 0. -> [K-1, R, C]."""
    at = lengths[None, :] + jnp.arange(k - 1)[:, None]  # into `padded`
    return padded[jnp.arange(padded.shape[0])[None, :], at]


def mamba_step(p: Params, y: jax.Array, tail: jax.Array, state: jax.Array,
               dims: Mamba2Dims):
    """One token a slot. y [S, D] (normed), tail [K-1, S, C] the last
    inputs of the convolution, state [S, H, P, N] float32. -> (the mixer's
    output [S, D], the new tail, the new state)."""
    dt_ = dims.dtype
    s = y.shape[0]
    rep = dims.heads // dims.groups
    with jax.named_scope("ssm_proj"):
        z, xbc, dt_raw = ssm_inputs(y @ p["in_proj"].astype(dt_), dims)
    with jax.named_scope("conv"):
        window, conv = conv_step(tail, xbc, p["conv_w"])
        conv = conv + p["conv_b"].astype(jnp.float32)
        xs, b, c = ssm_split(jax.nn.silu(conv).astype(dt_), dims)
    with jax.named_scope("ssm_update"):
        dt, a = dt_and_a(p, dt_raw)  # [S, H], [H]
        xf = xs.astype(jnp.float32)
        bh = jnp.repeat(b.astype(jnp.float32), rep, axis=1)  # [S, H, N]
        ch = jnp.repeat(c.astype(jnp.float32), rep, axis=1)
        state = state.astype(jnp.float32) \
            * jnp.exp(dt * a)[:, :, None, None] \
            + (dt[..., None] * xf)[..., None] * bh[:, :, None, :]
        # multiply and reduce beside the update: one pass over the state
        yh = jnp.sum(state * ch[:, :, None, :], axis=-1) \
            + p["d_skip"].astype(jnp.float32)[None, :, None] * xf
    with jax.named_scope("ssm_norm"):
        yn = gated_group_norm(yh.reshape(s, dims.d_inner), z,
                              p["gate_norm"], dims)
    with jax.named_scope("ssm_proj"):
        out = yn @ p["out_proj"].astype(dt_)
    return out, window[1:], state.astype(dims.state_dtype)


def ssd_scan(xs, dt, a, b, c, dims: Mamba2Dims, state=None):
    """The recurrence ``S_t = exp(dt_t A) S_{t-1} + dt_t x_t B_t^T``,
    ``y_t = S_t C_t`` over rows from ``state`` [R, H, P, N] float32 (None:
    empty), blocked in chunks of ``dims.block`` (inside a chunk a masked
    product, between chunks the state): xs [R, T, H, P], dt [R, T, H]
    float32 (0 at padded positions: they leave the state as it is), a [H],
    b / c [R, T, G, N].
    -> (y [R, T, H, P] float32, the state after the row [R, H, P, N])."""
    r, t, h, pdim = xs.shape
    g, n = b.shape[2], b.shape[3]
    rep, q = h // g, min(dims.block, t)
    pad = (-t) % q
    if pad:
        xs, dt, b, c = (jnp.pad(v, ((0, 0), (0, pad)) + ((0, 0),) * (
            v.ndim - 2)) for v in (xs, dt, b, c))
    nc = (t + pad) // q
    mm = dims.dtype
    f32 = jnp.float32
    # [R, nc, Q, ...], heads split into (group, heads of the group)
    xdt = (xs.astype(f32) * dt[..., None]).reshape(r, nc, q, g, rep, pdim)
    b = b.reshape(r, nc, q, g, n).astype(mm)
    c = c.reshape(r, nc, q, g, n).astype(mm)
    cum = jnp.cumsum((dt * a).reshape(r, nc, q, g, rep), axis=2)
    # inside a chunk: y_i += sum_{j <= i} (C_i . B_j) exp(cum_i - cum_j) xdt_j
    cb = jnp.einsum("rcign,rcjgn->rcgij", c, b, preferred_element_type=f32)
    gap = cum[:, :, :, None] - cum[:, :, None]  # [R, nc, i, j, G, rep]
    causal = jnp.tril(jnp.ones((q, q), bool))[None, None, :, :, None, None]
    decay = jnp.where(causal, jnp.exp(jnp.where(causal, gap, 0.0)), 0.0)
    mix = cb.transpose(0, 1, 3, 4, 2)[..., None] * decay  # [R,nc,i,j,G,rep]
    y = jnp.einsum("rcijgh,rcjghp->rcighp", mix.astype(mm), xdt.astype(mm),
                   preferred_element_type=f32)
    # what each chunk adds to the state, decayed to the chunk's end
    to_end = jnp.exp(cum[:, :, -1:] - cum)  # [R, nc, Q, G, rep]
    add = jnp.einsum("rcjghp,rcjgn->rcghpn",
                     (xdt * to_end[..., None]).astype(mm), b,
                     preferred_element_type=f32)
    through = jnp.exp(cum[:, :, -1])  # [R, nc, G, rep]: a whole chunk's decay

    def chunk(state, inp):
        add_c, through_c = inp
        return state * through_c[..., None, None] + add_c, state

    state = jnp.zeros((r, g, rep, pdim, n), f32) if state is None \
        else state.astype(f32).reshape(r, g, rep, pdim, n)
    state, before = jax.lax.scan(
        chunk, state,
        (add.transpose(1, 0, 2, 3, 4, 5), through.transpose(1, 0, 2, 3)))
    before = before.transpose(1, 0, 2, 3, 4, 5)  # the state entering a chunk
    y = y + jnp.einsum("rcign,rcghpn->rcighp", c, before.astype(mm),
                       preferred_element_type=f32) * jnp.exp(cum)[..., None]
    y = y.reshape(r, t + pad, h, pdim)[:, :t]
    return y, state.reshape(r, h, pdim, n)


def mamba_rows(p: Params, y: jax.Array, lengths: jax.Array,
               dims: Mamba2Dims, tail: jax.Array | None = None,
               state: jax.Array | None = None):
    """Rows of T tokens that continue from ``tail`` [R, K-1, C] (the
    convolution's last inputs) and ``state`` [R, H, P, N]; None for both:
    rows that begin. y [R, T, D] (normed), lengths [R]: the real tokens of
    each row. -> (the mixer's output [R, T, D], the convolution's tail
    after ``length`` tokens [K-1, R, C], the state after ``length`` tokens
    [R, H, P, N]); with no real token, the tail and state given."""
    dt_ = dims.dtype
    r, t, _ = y.shape
    k = dims.kernel
    with jax.named_scope("ssm_proj"):
        z, xbc, dt_raw = ssm_inputs(y @ p["in_proj"].astype(dt_), dims)
    with jax.named_scope("conv"):
        padded, conv = conv_rows(xbc, tail, p["conv_w"])
        conv = conv + p["conv_b"].astype(jnp.float32)
        xs, b, c = ssm_split(jax.nn.silu(conv).astype(dt_), dims)
        tail = conv_tail(padded, lengths, k)
    with jax.named_scope("ssm_scan"):
        dt, a = dt_and_a(p, dt_raw)
        dt = jnp.where(jnp.arange(t)[None, :, None] < lengths[:, None, None],
                       dt, 0.0)
        yh, state = ssd_scan(xs, dt, a, b, c, dims, state)
        yh = yh + p["d_skip"].astype(jnp.float32)[None, None, :, None] \
            * xs.astype(jnp.float32)
    with jax.named_scope("ssm_norm"):
        yn = gated_group_norm(yh.reshape(r, t, dims.d_inner), z,
                              p["gate_norm"], dims)
    with jax.named_scope("ssm_proj"):
        out = yn @ p["out_proj"].astype(dt_)
    return out, tail, state.astype(dims.state_dtype)


# -- a layer's share of the cache ---------------------------------------------


def step_through_cache(p: Params, y: jax.Array, conv_all: jax.Array,
                       ssm: jax.Array, layer: int, dims: Mamba2Dims):
    """``mamba_step`` for every slot on Mamba layer ``layer``'s state.
    -> (out [S, D], ``conv_all`` with the layer's new tail, the new state)."""
    out, tail, state = mamba_step(p, y, conv_all[layer], ssm, dims)
    with jax.named_scope("state_write"):
        conv_all = jax.lax.dynamic_update_slice(
            conv_all, tail[None].astype(conv_all.dtype), (layer, 0, 0, 0))
    return out, conv_all, state


def rows_through_cache(p: Params, y: jax.Array, lengths: jax.Array,
                       conv_all: jax.Array, ssm: jax.Array, layer: int,
                       slots: jax.Array, goes_on: jax.Array,
                       dims: Mamba2Dims):
    """``mamba_rows`` of a chunk: row r continues from what ``slots[r]``
    holds for Mamba layer ``layer`` where ``goes_on[r]``, else begins, and
    leaves there, in place, its state after the row's real tokens.
    -> (out [R, T, D], ``conv_all``, the layer's state array)."""
    r = y.shape[0]
    with jax.named_scope("conv"):  # its left context, by slot
        tail = jnp.stack([jax.lax.dynamic_slice(
            conv_all, (layer, 0, slots[i], 0),
            (1, dims.kernel - 1, 1, dims.conv_dim))[0, :, 0]
            for i in range(r)])  # [R, K-1, C]
        tail = jnp.where(goes_on[:, None, None], tail, 0)
    with jax.named_scope("ssm_scan"):  # its first state, by slot
        state = jnp.concatenate([jax.lax.dynamic_slice(
            ssm, (slots[i], 0, 0, 0), (1,) + ssm.shape[1:])
            for i in range(r)])
        state = jnp.where(goes_on[:, None, None, None], state, 0)
    out, tail, state = mamba_rows(p, y, lengths, dims, tail, state)
    with jax.named_scope("state_write"):
        tail = tail.astype(conv_all.dtype)
        for i in range(r):  # by slot; distinct but the scratch
            conv_all = jax.lax.dynamic_update_slice(
                conv_all, tail[None, :, i:i + 1], (layer, 0, slots[i], 0))
            ssm = jax.lax.dynamic_update_slice(
                ssm, state[i:i + 1], (slots[i], 0, 0, 0))
    return out, conv_all, ssm
