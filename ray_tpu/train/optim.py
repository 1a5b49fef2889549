"""Minimal fused AdamW as pure pytree functions.

Written in-tree (rather than via optax) so the optimizer state is a pytree
that mirrors the parameter tree exactly — its shardings are then the param
shardings verbatim, which is what makes ZeRO-style optimizer-state sharding
"fall out of pjit" (SURVEY.md §2.4). optax remains available to user code.
"""

from __future__ import annotations

from typing import Any, NamedTuple

import jax
import jax.numpy as jnp

Params = Any


class AdamWConfig(NamedTuple):
    lr: float = 3e-4
    b1: float = 0.9
    b2: float = 0.95
    eps: float = 1e-8
    weight_decay: float = 0.1
    grad_clip: float = 1.0
    warmup_steps: int = 0


def adamw_init(params: Params) -> dict[str, Params]:
    zeros = lambda p: jnp.zeros_like(p)
    return {"mu": jax.tree.map(zeros, params), "nu": jax.tree.map(zeros, params)}


def _schedule(cfg: AdamWConfig, step: jax.Array) -> jax.Array:
    lr = jnp.asarray(cfg.lr, jnp.float32)
    if cfg.warmup_steps > 0:
        warm = jnp.minimum(1.0, (step + 1) / cfg.warmup_steps)
        lr = lr * warm
    return lr


def adamw_update(
    cfg: AdamWConfig,
    grads: Params,
    params: Params,
    opt_state: dict[str, Params],
    step: jax.Array,
):
    """One AdamW step with global-norm clipping. Returns
    (params, opt_state, lr, gnorm). Traced under the scope ``adamw``."""
    with jax.named_scope("adamw"):
        return _adamw_update(cfg, grads, params, opt_state, step)


def _adamw_update(cfg, grads, params, opt_state, step):
    gnorm = jnp.sqrt(
        sum(jnp.sum(jnp.square(g.astype(jnp.float32))) for g in jax.tree.leaves(grads))
    )
    scale = jnp.where(
        gnorm > cfg.grad_clip, cfg.grad_clip / jnp.maximum(gnorm, 1e-12), 1.0
    )
    lr = _schedule(cfg, step)
    t = (step + 1).astype(jnp.float32)
    bc1 = 1.0 - cfg.b1**t
    bc2 = 1.0 - cfg.b2**t

    def upd(p, g, mu, nu):
        g = g.astype(jnp.float32) * scale
        mu = cfg.b1 * mu + (1 - cfg.b1) * g
        nu = cfg.b2 * nu + (1 - cfg.b2) * jnp.square(g)
        step_ = (mu / bc1) / (jnp.sqrt(nu / bc2) + cfg.eps)
        p32 = p.astype(jnp.float32)
        p32 = p32 - lr * (step_ + cfg.weight_decay * p32)
        return p32.astype(p.dtype), mu, nu

    flat_p, treedef = jax.tree.flatten(params)
    flat_g = treedef.flatten_up_to(grads)
    flat_mu = treedef.flatten_up_to(opt_state["mu"])
    flat_nu = treedef.flatten_up_to(opt_state["nu"])
    out = [upd(p, g, m, n) for p, g, m, n in zip(flat_p, flat_g, flat_mu, flat_nu)]
    new_p = treedef.unflatten([o[0] for o in out])
    new_mu = treedef.unflatten([o[1] for o in out])
    new_nu = treedef.unflatten([o[2] for o in out])
    return new_p, {"mu": new_mu, "nu": new_nu}, lr, gnorm
