"""Pipeline parallelism over mesh axis ``pp``: GPipe forward streaming and
a 1F1B training schedule.

SURVEY.md §2.4 (absent from the reference, first-class here): layer stacks
shard over ``pp``; microbatches stream through the stages with
``ppermute`` forwarding activations stage->stage each tick; all devices
run the same program (SPMD), with stage identity = ``axis_index``.

Two schedules:

* ``pipeline_apply`` — forward-only GPipe streaming (inference / under
  plain autodiff, which replays the scan in reverse: GPipe-style training
  with all n_micro activations live).
* ``pipeline_value_and_grad`` — 1F1B (one-forward-one-backward): each tick
  a stage runs one microbatch forward AND one backward (vjp with
  rematerialized forward), with backward priority and a per-stage
  in-flight cap of pp - s. Activation memory is O(pp) microbatches per
  stage instead of GPipe's O(n_micro); stage inputs (not residuals) are
  saved, the stage forward recomputes inside the vjp. The fwd/bwd
  schedules are computed in Python (static for XLA) and streamed through
  one ``lax.scan``; activations ride a forward ``ppermute`` ring,
  cotangents a backward one.

Requirements: every stage maps activations [mb, ...] -> [mb, ...] of the
same shape (the transformer-block case), and stage parameters are a pytree
whose leaves have a leading ``pp``-sharded stage dimension.
"""

from __future__ import annotations

import functools
from typing import Callable

import jax
import jax.numpy as jnp
from jax import shard_map
from jax.sharding import Mesh, PartitionSpec as P


def pipeline_apply_local(stage_params, x_micro, *, stage_fn: Callable,
                         axis: str = "pp", axis_size: int):
    """Per-device body (inside shard_map over ``axis``).

    stage_params: this stage's params (leading stage dim of size 1, squeezed
    here). x_micro: [n_micro, mb, ...] (replicated). Returns this device's
    per-tick outputs [n_ticks, mb, ...]; the caller extracts the last
    stage's valid ticks.
    """
    pp = axis_size
    s = jax.lax.axis_index(axis)
    params = jax.tree.map(lambda p: p[0], stage_params)
    n_micro = x_micro.shape[0]
    n_ticks = n_micro + pp - 1
    perm = [(i, (i + 1) % pp) for i in range(pp)]

    def tick(carry, t):
        arriving = carry
        mb_idx = jnp.clip(t, 0, n_micro - 1)
        x0 = jax.lax.dynamic_index_in_dim(x_micro, mb_idx, 0, keepdims=False)
        inp = jnp.where(s == 0, x0, arriving)
        out = stage_fn(params, inp)
        sent = jax.lax.ppermute(out, axis, perm)
        return sent, out

    _, ys = jax.lax.scan(tick, jnp.zeros_like(x_micro[0]), jnp.arange(n_ticks))
    return ys[None]  # restore a device-stacked leading dim for out_specs


def pipeline_apply(stage_params, x, mesh: Mesh, *, stage_fn: Callable,
                   n_micro: int, axis: str = "pp"):
    """Run x [batch, ...] through the pp-sharded stage stack.

    stage_params: pytree with leading dim == mesh.shape[axis] (one slice
    per stage), sharded P(axis, ...). Returns [batch, ...] outputs.
    """
    pp = mesh.shape[axis]
    b = x.shape[0]
    if b % n_micro:
        raise ValueError(f"batch {b} must divide into {n_micro} microbatches")
    x_micro = x.reshape(n_micro, b // n_micro, *x.shape[1:])

    param_specs = jax.tree.map(
        lambda p: P(axis, *([None] * (p.ndim - 1))), stage_params
    )
    fn = shard_map(
        functools.partial(
            pipeline_apply_local, stage_fn=stage_fn, axis=axis, axis_size=pp
        ),
        mesh=mesh,
        in_specs=(param_specs, P()),
        out_specs=P(axis),
        check_vma=False,
    )
    ys = fn(stage_params, x_micro)  # [pp, n_ticks, mb, ...]
    # Valid outputs: last stage (pp-1), ticks pp-1 .. pp-1+n_micro-1.
    outs = ys[pp - 1, pp - 1 : pp - 1 + n_micro]
    return outs.reshape(b, *x.shape[1:])


# ---------------------------------------------------------------------------
# 1F1B
# ---------------------------------------------------------------------------


def build_1f1b_schedule(n_micro: int, pp: int, style: str = "1f1b"):
    """Static pipeline timetable. Returns (fwd, bwd, fwd_arrive,
    bwd_arrive), each a [T, pp] int list: the microbatch index stage s
    handles (or receives) at tick t, -1 for idle.

    Rules (greedy, backward-priority — the canonical 1F1B shape):
      * stage s may forward mb i once stage s-1 forwarded it on an earlier
        tick (ppermute costs one tick); stage 0 is source-fed;
      * stage s may backward mb i once stage s+1 backwarded it on an
        earlier tick; the LAST stage may backward mb i on the same tick it
        forwards it (the fwd slot runs first within a tick);
      * in-flight forwards per stage are capped at pp - s (the 1F1B
        memory bound).

    ``style="gpipe"``: no in-flight cap, and backwards wait for EVERY
    forward to finish (all-fwd-then-all-bwd) — the schedule GPipe runs,
    with O(n_micro) live activations instead of 1F1B's O(pp). Kept for
    the pipeline microbenchmark and as the reference point the 1F1B
    memory claim is measured against.
    """
    f_time = [[None] * n_micro for _ in range(pp)]
    b_time = [[None] * n_micro for _ in range(pp)]
    f_next = [0] * pp
    b_next = [0] * pp
    fwd, bwd = [], []
    t = 0
    while any(b < n_micro for b in b_next):
        frow = [-1] * pp
        for s in range(pp):
            i = f_next[s]
            if i >= n_micro:
                continue
            if style == "1f1b" \
                    and f_next[s] - b_next[s] >= max(1, pp - s):
                continue  # 1F1B in-flight cap
            ready = (s == 0) or (
                f_time[s - 1][i] is not None and f_time[s - 1][i] < t)
            if ready:
                frow[s] = i
                f_time[s][i] = t
                f_next[s] += 1
        brow = [-1] * pp
        all_fwd_done = all(f >= n_micro for f in f_next)
        for s in range(pp):
            i = b_next[s]
            if i >= n_micro:
                continue
            if style == "gpipe" and not all_fwd_done:
                continue  # flush phase: backwards only after every fwd
            if s == pp - 1:
                ready = f_time[s][i] is not None and f_time[s][i] <= t
            else:
                ready = b_time[s + 1][i] is not None and b_time[s + 1][i] < t
            if ready:
                brow[s] = i
                b_time[s][i] = t
                b_next[s] += 1
        fwd.append(frow)
        bwd.append(brow)
        t += 1
        if t > 4 * (n_micro + pp) + 16:  # schedule bug guard
            raise AssertionError("1F1B schedule failed to converge")
    T = len(fwd)
    fwd_arrive = [
        [fwd[t - 1][s - 1] if t >= 1 and s >= 1 else -1 for s in range(pp)]
        for t in range(T)
    ]
    bwd_arrive = [
        [bwd[t - 1][s + 1] if t >= 1 and s < pp - 1 else -1
         for s in range(pp)]
        for t in range(T)
    ]
    return fwd, bwd, fwd_arrive, bwd_arrive


def _1f1b_local(stage_params, x_micro, y_micro, fwd_sched, bwd_sched,
                fwd_arrive, bwd_arrive, *, stage_fn: Callable,
                loss_fn: Callable, axis: str, axis_size: int,
                grad_psum_axes: tuple = (), save_slots: int = 0):
    """Per-device 1F1B body (inside shard_map over ``axis``).

    Every tick executes one (masked) stage forward AND one (masked)
    vjp-with-remat backward — SPMD: all devices run the same ops, validity
    comes from the schedule tables. Returns (loss contribution, this
    stage's param grads with the leading stage dim restored).
    """
    pp = axis_size
    s = jax.lax.axis_index(axis)
    params = jax.tree.map(lambda p: p[0], stage_params)
    n_micro = x_micro.shape[0]
    act_shape = x_micro.shape[1:]
    T = fwd_sched.shape[0]
    is_last = s == pp - 1
    is_first = s == 0
    fperm = [(i, (i + 1) % pp) for i in range(pp)]
    bperm = [(i, (i - 1) % pp) for i in range(pp)]
    zero_act = jnp.zeros(act_shape, x_micro.dtype)
    buf0 = jnp.zeros((pp, *act_shape), x_micro.dtype)
    # Activation stash: pp slots suffice under the 1F1B in-flight cap
    # (THE memory win); a GPipe schedule keeps all n_micro alive.
    n_save = save_slots or pp
    saved0 = jnp.zeros((n_save, *act_shape), x_micro.dtype)

    def tick(carry, t):
        fwd_msg, bwd_msg, in_buf, gbuf, saved, gacc, loss_sum = carry
        # Deliver last tick's ppermute payloads into the mb-ring buffers.
        amb = fwd_arrive[t, s]
        in_buf = jnp.where(
            amb >= 0, in_buf.at[jnp.clip(amb, 0) % pp].set(fwd_msg), in_buf)
        gmb = bwd_arrive[t, s]
        gbuf = jnp.where(
            gmb >= 0, gbuf.at[jnp.clip(gmb, 0) % pp].set(bwd_msg), gbuf)

        # Forward slot.
        fmb = fwd_sched[t, s]
        fvalid = fmb >= 0
        fi = jnp.clip(fmb, 0)
        x_in = jnp.where(is_first, x_micro[fi], in_buf[fi % pp])
        out = stage_fn(params, x_in).astype(x_micro.dtype)
        saved = jnp.where(fvalid, saved.at[fi % n_save].set(x_in), saved)
        fwd_msg = jax.lax.ppermute(
            jnp.where(fvalid, out, zero_act), axis, fperm)

        # Backward slot: vjp with rematerialized forward. One vjp serves
        # every stage: the last stage pulls the cotangent out of the
        # per-microbatch loss (seed 1), earlier stages out of the incoming
        # activation cotangent (seed 0 on the loss output).
        bmb = bwd_sched[t, s]
        bvalid = bmb >= 0
        bi = jnp.clip(bmb, 0)
        x_saved = saved[bi % n_save]
        y_mb = jax.lax.dynamic_index_in_dim(y_micro, bi, 0, keepdims=False)

        def f(p, xx):
            o = stage_fn(p, xx)
            return o, loss_fn(o, y_mb)

        (o, l), vjp_fn = jax.vjp(f, params, x_saved)
        cot_o = jnp.where(is_last, jnp.zeros_like(o),
                          gbuf[bi % pp].astype(o.dtype))
        cot_l = jnp.where(is_last, jnp.ones((), l.dtype),
                          jnp.zeros((), l.dtype))
        dp, dx = vjp_fn((cot_o, cot_l))
        gacc = jax.tree.map(
            lambda a, g: a + jnp.where(bvalid, g, jnp.zeros_like(g)),
            gacc, dp)
        loss_sum = loss_sum + jnp.where(
            bvalid & is_last, l, jnp.zeros((), l.dtype))
        bwd_msg = jax.lax.ppermute(
            jnp.where(bvalid, dx.astype(x_micro.dtype), zero_act),
            axis, bperm)
        return (fwd_msg, bwd_msg, in_buf, gbuf, saved, gacc, loss_sum), None

    grad0 = jax.tree.map(jnp.zeros_like, params)
    init = (zero_act, zero_act, buf0, buf0, saved0, grad0,
            jnp.zeros((), jnp.float32))
    (_, _, _, _, _, gacc, loss_sum), _ = jax.lax.scan(
        tick, init, jnp.arange(T))
    # Mean-over-microbatches semantics for both value and grads.
    loss = jax.lax.psum(loss_sum, axis) / n_micro
    if grad_psum_axes:
        # Data-like in-stage axes (sp sequence shards, dp replicas inside
        # the stage): every param's grad is a partial sum over the tokens
        # that axis split — reduce it here, inside the shard_map, exactly
        # like the reference's grad allreduce over dp x sp. Params
        # SHARDED over one of these axes keep local grads (their tokens
        # are local by construction); callers pass only axes that shard
        # data, not params.
        # pmean, matching the mean-loss convention (loss_fn averages over
        # its LOCAL tokens; the global loss is the mean of shard means).
        gacc = jax.tree.map(
            lambda g: jax.lax.pmean(g, grad_psum_axes), gacc)
        loss = jax.lax.pmean(loss, grad_psum_axes)
    grads = jax.tree.map(lambda g: (g / n_micro)[None], gacc)
    return loss, grads


def pipeline_value_and_grad(stage_params, x, y, mesh: Mesh, *,
                            stage_fn: Callable, loss_fn: Callable,
                            n_micro: int, axis: str = "pp",
                            param_specs=None, data_spec=None,
                            grad_psum_axes: tuple = (),
                            style: str = "1f1b"):
    """1F1B training pass: returns (mean microbatch loss, d loss / d
    stage_params) for ``loss_fn(stage_fn(...last stage...), y)``.

    stage_params: pytree with leading dim == mesh.shape[axis]; x, y:
    [batch, ...] split into ``n_micro`` microbatches. ``param_specs``
    overrides the default ``P(axis, None, ...)`` sharding — pass specs
    naming other mesh axes (e.g. an expert axis) to combine pp with
    in-stage parallelism; collectives over those axes are legal inside
    ``stage_fn``.

    ``data_spec``: PartitionSpec for the POST-microbatching activations
    [n_micro, mb, ...] (and y), e.g. ``P(None, None, "sp")`` to run
    sequence-parallel ring attention inside each stage. Any axis that
    shards data this way must also appear in ``grad_psum_axes`` so param
    grads (partial sums over that axis's token shard) are reduced inside
    the shard_map — the dp x sp grad-allreduce of a classic trainer.
    """
    pp = mesh.shape[axis]
    b = x.shape[0]
    if b % n_micro:
        raise ValueError(f"batch {b} must divide into {n_micro} microbatches")
    x_micro = x.reshape(n_micro, b // n_micro, *x.shape[1:])
    y_micro = y.reshape(n_micro, b // n_micro, *y.shape[1:])
    fwd, bwd, f_arr, b_arr = build_1f1b_schedule(n_micro, pp, style)
    tables = tuple(
        jnp.asarray(a, jnp.int32) for a in (fwd, bwd, f_arr, b_arr))
    if param_specs is None:
        param_specs = jax.tree.map(
            lambda p: P(axis, *([None] * (p.ndim - 1))), stage_params)
    dspec = data_spec if data_spec is not None else P()
    fn = shard_map(
        functools.partial(
            _1f1b_local, stage_fn=stage_fn, loss_fn=loss_fn, axis=axis,
            axis_size=pp, grad_psum_axes=tuple(grad_psum_axes),
            save_slots=(pp if style == "1f1b" else n_micro),
        ),
        mesh=mesh,
        in_specs=(param_specs, dspec, dspec, P(), P(), P(), P()),
        out_specs=(P(), param_specs),
        check_vma=False,
    )
    return fn(stage_params, x_micro, y_micro, *tables)
