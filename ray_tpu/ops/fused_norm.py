"""Pallas TPU fused normalization + elementwise-epilogue kernels.

Attacks the third sink of an early profile of the GPT-2 step (~15ms of
128ms, ~1.3ms/layer): the fp32 layernorm/elementwise *backward* fusions
XLA materializes through HBM. Same playbook as the flash-attention backward that took
39%→52% MFU: fuse the backward chain into one Pallas kernel per
row-block grid cell, keep fp32 statistics in VMEM, never round-trip
fp32 intermediates through HBM.

Three op families, each a ``custom_vjp`` with Pallas forward AND
backward:

* ``fused_layer_norm[_residual]`` — forward computes fp32 mean/rstd in
  VMEM and saves ONLY those per-row statistics (2 floats/row) for
  backward; the fp32 x32/mu/var recompute chain XLA would otherwise
  materialize never reaches HBM. The backward kernel fuses dx (the two
  row-reductions and the recentering), the dscale/dbias column
  reductions (fp32 per-row-block partials, one cheap XLA sum after),
  and — in the ``_residual`` variant — the residual-add gradient, in
  ONE kernel per row-block grid cell.
* ``fused_rms_norm[_residual]`` — the RMSNorm twin (no mean, no bias)
  so ``models/llama.py`` rides the same kernel.
* ``fused_gelu`` — tanh-GELU with a fused backward epilogue for the MLP
  path: saves the pre-activation only, recomputes tanh in VMEM.

The ``_residual`` variants return ``(y, x)`` — pass the second output
into the residual add so its cotangent (the residual gradient) enters
the backward kernel and ``dx = d_residual + d_norm`` happens in VMEM.

Shapes the TPU lane layout can't tile (D not a multiple of 128, or a
row count with no usable sublane-aligned block divisor) fall back to
the plain-XLA chain — numerically identical, just unfused. On CPU
(tests) the kernels run in Pallas interpret mode, exactly like
``flash_attention.py``.
"""

from __future__ import annotations

import collections
import functools
import math

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

LN_EPS = 1e-5    # matches models/gpt2.py _layer_norm
RMS_EPS = 1e-6   # matches models/llama.py _rms_norm

# Row-block upper bound; the actual block is the largest divisor of the
# row count that respects the dtype's sublane minimum (see _fit_rows).
_MAX_BLOCK_ROWS = 256
# Per-array fp32 VMEM budget for one block. The backward holds ~4 live
# row-blocks (x, dy, dres, dx); wide rows (GELU's [R, 4D]) shrink the
# row block instead of blowing the ~16 MB VMEM.
_BLOCK_BYTES = 2 * 1024 * 1024

# Trace-time kernel-launch counters, keyed by kernel name. Tests read
# these to assert the Pallas path (vs the XLA fallback) was actually
# taken; machine-independent by construction.
KERNEL_INVOCATIONS: collections.Counter = collections.Counter()


def _sublane(dtype) -> int:
    """Minimum second-to-last-dim tile for the dtype (TPU tiling rule)."""
    return 16 if jnp.dtype(dtype).itemsize < 4 else 8


def _fit_rows(r: int, d: int, dtype) -> int | None:
    """Largest row-block that divides ``r``, is sublane-aligned for
    ``dtype``, and keeps one fp32 block under the VMEM budget. None if
    no such block exists (caller falls back to XLA)."""
    cap = max(_sublane(dtype), _BLOCK_BYTES // (4 * d))
    block = min(_MAX_BLOCK_ROWS, cap, r)
    sub = _sublane(dtype)
    block -= block % sub
    while block >= sub and r % block:
        block -= sub
    return block if block >= sub else None


def _should_fuse(r: int, d: int, dtype) -> int | None:
    """Row block to use, or None when the shape can't tile the TPU lane
    layout (D % 128, degenerate row counts) and XLA should run instead."""
    if d % 128 != 0 or r <= 0:
        return None
    return _fit_rows(r, d, dtype)


def _interpret() -> bool:
    return jax.default_backend() == "cpu"


# -- plain-XLA references (fallback path; also the parity oracle) ----------


def ref_layer_norm(x, scale, bias, eps: float = LN_EPS):
    """Bit-for-bit the model's ``_layer_norm`` chain (fallback path)."""
    x32 = x.astype(jnp.float32)
    mu = jnp.mean(x32, axis=-1, keepdims=True)
    var = jnp.var(x32, axis=-1, keepdims=True)
    y = (x32 - mu) * jax.lax.rsqrt(var + eps)
    return (y * scale + bias).astype(x.dtype)


def ref_rms_norm(x, scale, eps: float = RMS_EPS):
    x32 = x.astype(jnp.float32)
    y = x32 * jax.lax.rsqrt(jnp.mean(x32 * x32, axis=-1, keepdims=True) + eps)
    return (y * scale).astype(x.dtype)


def ref_gelu(x):
    return jax.nn.gelu(x, approximate=True)


# -- forward kernels -------------------------------------------------------


def _ln_fwd_kernel(x_ref, scale_ref, bias_ref, y_ref, mu_ref, rstd_ref,
                   *, eps: float):
    """One row-block: fp32 mean/rstd computed and kept in VMEM; only the
    [block, 1] statistics are written for backward."""
    x32 = x_ref[:].astype(jnp.float32)
    mu = jnp.mean(x32, axis=-1, keepdims=True)
    xc = x32 - mu
    var = jnp.mean(xc * xc, axis=-1, keepdims=True)
    rstd = jax.lax.rsqrt(var + eps)
    y = xc * rstd * scale_ref[:].astype(jnp.float32) \
        + bias_ref[:].astype(jnp.float32)
    y_ref[:] = y.astype(y_ref.dtype)
    mu_ref[:] = mu
    rstd_ref[:] = rstd


def _rms_fwd_kernel(x_ref, scale_ref, y_ref, rstd_ref, *, eps: float):
    x32 = x_ref[:].astype(jnp.float32)
    rstd = jax.lax.rsqrt(jnp.mean(x32 * x32, axis=-1, keepdims=True) + eps)
    y_ref[:] = (x32 * rstd * scale_ref[:].astype(jnp.float32)).astype(
        y_ref.dtype)
    rstd_ref[:] = rstd


def _norm_fwd(x2d, scale, bias, *, block: int, eps: float, rms: bool,
              interpret: bool):
    """x2d [R, D] -> (y [R, D], mu [R, 1] | None, rstd [R, 1])."""
    r, d = x2d.shape
    grid = (r // block,)
    row_spec = pl.BlockSpec((block, d), lambda i: (i, 0))
    vec_spec = pl.BlockSpec((1, d), lambda i: (0, 0))
    stat_spec = pl.BlockSpec((block, 1), lambda i: (i, 0))
    stat_shape = jax.ShapeDtypeStruct((r, 1), jnp.float32)
    params = pltpu.CompilerParams(dimension_semantics=("parallel",))
    if rms:
        KERNEL_INVOCATIONS["rms_fwd"] += 1
        y, rstd = pl.pallas_call(
            functools.partial(_rms_fwd_kernel, eps=eps),
            grid=grid,
            in_specs=[row_spec, vec_spec],
            out_specs=[row_spec, stat_spec],
            out_shape=[jax.ShapeDtypeStruct((r, d), x2d.dtype), stat_shape],
            compiler_params=params,
            interpret=interpret,
        )(x2d, scale.reshape(1, d))
        return y, None, rstd
    KERNEL_INVOCATIONS["ln_fwd"] += 1
    y, mu, rstd = pl.pallas_call(
        functools.partial(_ln_fwd_kernel, eps=eps),
        grid=grid,
        in_specs=[row_spec, vec_spec, vec_spec],
        out_specs=[row_spec, stat_spec, stat_spec],
        out_shape=[jax.ShapeDtypeStruct((r, d), x2d.dtype), stat_shape,
                   stat_shape],
        compiler_params=params,
        interpret=interpret,
    )(x2d, scale.reshape(1, d), bias.reshape(1, d))
    return y, mu, rstd


# -- backward kernel -------------------------------------------------------


def _norm_bwd_kernel(x_ref, mu_ref, rstd_ref, scale_ref, dy_ref, dres_ref,
                     dx_ref, dscale_ref, dbias_ref, *, rms: bool):
    """ONE kernel per row-block: recenters xhat from the saved fp32
    statistics, computes the two row-reductions (c1 = mean(dxhat),
    c2 = mean(dxhat·xhat)), emits dx — fused with the residual-add
    gradient when a dres ref is present — plus the per-block
    dscale/dbias column partials, all without an fp32 HBM round-trip."""
    x32 = x_ref[:].astype(jnp.float32)
    rstd = rstd_ref[:]
    xhat = x32 * rstd if rms else (x32 - mu_ref[:]) * rstd
    dy32 = dy_ref[:].astype(jnp.float32)
    dxhat = dy32 * scale_ref[:].astype(jnp.float32)
    c2 = jnp.mean(dxhat * xhat, axis=-1, keepdims=True)
    if rms:
        dx = rstd * (dxhat - xhat * c2)
    else:
        c1 = jnp.mean(dxhat, axis=-1, keepdims=True)
        dx = rstd * (dxhat - c1 - xhat * c2)
    if dres_ref is not None:
        dx = dx + dres_ref[:].astype(jnp.float32)
    dx_ref[:] = dx.astype(dx_ref.dtype)
    dscale_ref[:] = _column_partials(dy32 * xhat)
    if dbias_ref is not None:
        dbias_ref[:] = _column_partials(dy32)


# Rows of one dscale/dbias partials block: an fp32 sublane tile. A
# one-row block of an [n_blocks, D] array is refused by the TPU lowering
# (the second-to-last block dim must be a multiple of 8), so every
# row-block writes a full tile.
_PARTIAL_ROWS = 8


def _column_partials(v):
    """[block, D] -> [_PARTIAL_ROWS, D]: sums rows that share a sublane,
    so the reduction is plain vector adds with no cross-sublane step;
    the XLA sum after the kernel folds the tile's rows with the rest."""
    return jnp.sum(v.reshape(-1, _PARTIAL_ROWS, v.shape[-1]), axis=0)


def _norm_bwd(x2d, mu, rstd, scale, dy, dres, *, block: int, rms: bool,
              interpret: bool):
    """-> (dx [R, D], dscale [D] fp32, dbias [D] fp32 | None).

    dscale/dbias come back as per-row-block fp32 partials (one sublane
    tile each: [n_blocks * 8, D]) that one XLA sum collapses — the same
    partials-then-reduce shape as the flash backward's dQ path."""
    r, d = x2d.shape
    n_blocks = r // block
    with_res = dres is not None
    with_bias = not rms

    row_spec = pl.BlockSpec((block, d), lambda i: (i, 0))
    vec_spec = pl.BlockSpec((1, d), lambda i: (0, 0))
    stat_spec = pl.BlockSpec((block, 1), lambda i: (i, 0))
    part_spec = pl.BlockSpec((_PARTIAL_ROWS, d), lambda i: (i, 0))
    part_shape = jax.ShapeDtypeStruct(
        (n_blocks * _PARTIAL_ROWS, d), jnp.float32)

    inputs, in_specs = [x2d], [row_spec]
    if not rms:
        inputs.append(mu)
        in_specs.append(stat_spec)
    inputs += [rstd, scale.reshape(1, d), dy]
    in_specs += [stat_spec, vec_spec, row_spec]
    if with_res:
        inputs.append(dres)
        in_specs.append(row_spec)
    out_specs = [row_spec, part_spec]
    out_shape = [jax.ShapeDtypeStruct((r, d), x2d.dtype), part_shape]
    if with_bias:
        out_specs.append(part_spec)
        out_shape.append(part_shape)

    def body(*refs):
        it = iter(refs)
        x_ref = next(it)
        mu_ref = None if rms else next(it)
        rstd_ref, scale_ref, dy_ref = next(it), next(it), next(it)
        dres_ref = next(it) if with_res else None
        dx_ref, dscale_ref = next(it), next(it)
        dbias_ref = next(it) if with_bias else None
        _norm_bwd_kernel(x_ref, mu_ref, rstd_ref, scale_ref, dy_ref,
                         dres_ref, dx_ref, dscale_ref, dbias_ref, rms=rms)

    KERNEL_INVOCATIONS["rms_bwd" if rms else "ln_bwd"] += 1
    out = pl.pallas_call(
        body,
        grid=(n_blocks,),
        in_specs=in_specs,
        out_specs=out_specs,
        out_shape=out_shape,
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel",)),
        interpret=interpret,
    )(*inputs)
    if with_bias:
        dx, dscale_p, dbias_p = out
        return dx, jnp.sum(dscale_p, axis=0), jnp.sum(dbias_p, axis=0)
    dx, dscale_p = out
    return dx, jnp.sum(dscale_p, axis=0), None


# -- GELU kernels ----------------------------------------------------------

_GELU_C = math.sqrt(2.0 / math.pi)
_GELU_A = 0.044715


def _gelu_fwd_kernel(x_ref, y_ref):
    x32 = x_ref[:].astype(jnp.float32)
    t = jnp.tanh(_GELU_C * (x32 + _GELU_A * x32 * x32 * x32))
    y_ref[:] = (0.5 * x32 * (1.0 + t)).astype(y_ref.dtype)


def _gelu_bwd_kernel(x_ref, g_ref, dx_ref):
    """Fused tanh-GELU backward epilogue: recompute tanh from the saved
    pre-activation in VMEM, one multiply-out to dx — no fp32 tanh/sech
    intermediates in HBM."""
    x32 = x_ref[:].astype(jnp.float32)
    g32 = g_ref[:].astype(jnp.float32)
    u = _GELU_C * (x32 + _GELU_A * x32 * x32 * x32)
    t = jnp.tanh(u)
    du = _GELU_C * (1.0 + 3.0 * _GELU_A * x32 * x32)
    dgelu = 0.5 * (1.0 + t) + 0.5 * x32 * (1.0 - t * t) * du
    dx_ref[:] = (g32 * dgelu).astype(dx_ref.dtype)


def _gelu_call(kernel, args, r, d, block, dtype, name, interpret):
    row_spec = pl.BlockSpec((block, d), lambda i: (i, 0))
    KERNEL_INVOCATIONS[name] += 1
    return pl.pallas_call(
        kernel,
        grid=(r // block,),
        in_specs=[row_spec] * len(args),
        out_specs=row_spec,
        out_shape=jax.ShapeDtypeStruct((r, d), dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel",)),
        interpret=interpret,
    )(*args)


# -- custom VJP wiring -----------------------------------------------------
#
# Static args (block, eps, interpret) ride nondiff_argnums, exactly like
# flash attention. The 2D reshape happens in the public wrappers; the
# vjp ops see [R, D].


@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4, 5, 6))
def _norm_op(x2d, scale, bias, block, eps, rms, interpret):
    y, _, _ = _norm_fwd(x2d, scale, bias, block=block, eps=eps, rms=rms,
                        interpret=interpret)
    return y


def _norm_op_fwd(x2d, scale, bias, block, eps, rms, interpret):
    y, mu, rstd = _norm_fwd(x2d, scale, bias, block=block, eps=eps, rms=rms,
                            interpret=interpret)
    return y, (x2d, scale, mu, rstd)


def _norm_op_bwd(block, eps, rms, interpret, res, dy):
    x2d, scale, mu, rstd = res
    dx, dscale, dbias = _norm_bwd(
        x2d, mu, rstd, scale, dy, None, block=block, rms=rms,
        interpret=interpret)
    dscale = dscale.astype(scale.dtype)
    if rms:
        return dx, dscale, None
    return dx, dscale, dbias.astype(scale.dtype)


_norm_op.defvjp(_norm_op_fwd, _norm_op_bwd)


@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4, 5, 6))
def _norm_res_op(x2d, scale, bias, block, eps, rms, interpret):
    """Returns (y, x_passthrough): route the second output into the
    residual add so its cotangent reaches the fused backward kernel."""
    y, _, _ = _norm_fwd(x2d, scale, bias, block=block, eps=eps, rms=rms,
                        interpret=interpret)
    return y, x2d


def _norm_res_op_fwd(x2d, scale, bias, block, eps, rms, interpret):
    y, mu, rstd = _norm_fwd(x2d, scale, bias, block=block, eps=eps, rms=rms,
                            interpret=interpret)
    return (y, x2d), (x2d, scale, mu, rstd)


def _norm_res_op_bwd(block, eps, rms, interpret, res, cts):
    x2d, scale, mu, rstd = res
    dy, dres = cts
    dx, dscale, dbias = _norm_bwd(
        x2d, mu, rstd, scale, dy, dres, block=block, rms=rms,
        interpret=interpret)
    dscale = dscale.astype(scale.dtype)
    if rms:
        return dx, dscale, None
    return dx, dscale, dbias.astype(scale.dtype)


_norm_res_op.defvjp(_norm_res_op_fwd, _norm_res_op_bwd)


@functools.partial(jax.custom_vjp, nondiff_argnums=(1, 2))
def _gelu_op(x2d, block, interpret):
    r, d = x2d.shape
    return _gelu_call(_gelu_fwd_kernel, (x2d,), r, d, block, x2d.dtype,
                      "gelu_fwd", interpret)


def _gelu_op_fwd(x2d, block, interpret):
    return _gelu_op(x2d, block, interpret), x2d


def _gelu_op_bwd(block, interpret, x2d, g):
    r, d = x2d.shape
    dx = _gelu_call(_gelu_bwd_kernel, (x2d, g), r, d, block, x2d.dtype,
                    "gelu_bwd", interpret)
    return (dx,)


_gelu_op.defvjp(_gelu_op_fwd, _gelu_op_bwd)


# -- public API ------------------------------------------------------------


def _to_2d(x):
    d = x.shape[-1]
    return x.reshape(-1, d), x.shape


def fused_layer_norm(x, scale, bias, *, eps: float = LN_EPS):
    """LayerNorm over the last dim of ``x`` [..., D]; fp32 statistics,
    output in ``x.dtype``. Pallas-fused where the shape tiles; plain-XLA
    fallback otherwise."""
    x2d, shape = _to_2d(x)
    block = _should_fuse(x2d.shape[0], x2d.shape[1], x.dtype)
    if block is None:
        return ref_layer_norm(x, scale, bias, eps)
    return _norm_op(x2d, scale, bias, block, eps, False,
                    _interpret()).reshape(shape)


def fused_layer_norm_residual(x, scale, bias, *, eps: float = LN_EPS):
    """(LayerNorm(x), x): feed the second output into the residual add —
    its cotangent is summed into dx inside the one backward kernel."""
    x2d, shape = _to_2d(x)
    block = _should_fuse(x2d.shape[0], x2d.shape[1], x.dtype)
    if block is None:
        return ref_layer_norm(x, scale, bias, eps), x
    y, x_skip = _norm_res_op(x2d, scale, bias, block, eps, False,
                             _interpret())
    return y.reshape(shape), x_skip.reshape(shape)


def fused_rms_norm(x, scale, *, eps: float = RMS_EPS):
    """RMSNorm twin of ``fused_layer_norm`` (no mean, no bias)."""
    x2d, shape = _to_2d(x)
    block = _should_fuse(x2d.shape[0], x2d.shape[1], x.dtype)
    if block is None:
        return ref_rms_norm(x, scale, eps)
    return _norm_op(x2d, scale, None, block, eps, True,
                    _interpret()).reshape(shape)


def fused_rms_norm_residual(x, scale, *, eps: float = RMS_EPS):
    x2d, shape = _to_2d(x)
    block = _should_fuse(x2d.shape[0], x2d.shape[1], x.dtype)
    if block is None:
        return ref_rms_norm(x, scale, eps), x
    y, x_skip = _norm_res_op(x2d, scale, None, block, eps, True,
                             _interpret())
    return y.reshape(shape), x_skip.reshape(shape)


def fused_gelu(x):
    """tanh-GELU with the fused Pallas backward epilogue (MLP path)."""
    x2d, shape = _to_2d(x)
    block = _should_fuse(x2d.shape[0], x2d.shape[1], x.dtype)
    if block is None:
        return ref_gelu(x)
    return _gelu_op(x2d, block, _interpret()).reshape(shape)
