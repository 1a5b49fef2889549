"""pjit train-step factory: init + step compiled over an arbitrary mesh.

The whole inner loop — forward, backward, gradient reduction, AdamW — is ONE
jitted program; XLA inserts the dp/fsdp gradient collectives and the tp/sp
activation collectives from the sharding annotations (the "annotate shardings,
let XLA insert collectives" recipe). Contrast with the reference, where the
inner loop is torch DDP and the framework only carries control messages
(SURVEY.md §3.4 HOT LOOP note).
"""

from __future__ import annotations

from typing import Any, Callable

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from ray_tpu.parallel.sharding import logical_sharding
from ray_tpu.train.optim import AdamWConfig, adamw_init, adamw_update
from ray_tpu.util import tracing

Params = Any
TrainState = dict[str, Any]  # {'params', 'opt': {'mu','nu'}, 'step'}


class _MeshBound:
    """A jitted function that traces, lowers and runs under the mesh it
    was built for, so ``get_abstract_mesh()`` inside the traced body is
    that mesh: the models' ``with_logical_constraint`` calls bind to it
    and the flash kernel is ``shard_map``-ped over it. Traced under no
    mesh, both would silently do nothing and every chip would compute
    the global batch."""

    def __init__(self, jitted, mesh: Mesh):
        self._jitted = jitted
        self._mesh = mesh

    def __call__(self, *args, **kwargs):
        with tracing.device_span("train.step.dispatch"), \
                jax.sharding.set_mesh(self._mesh):
            return self._jitted(*args, **kwargs)

    def lower(self, *args, **kwargs):
        with jax.sharding.set_mesh(self._mesh):
            return self._jitted.lower(*args, **kwargs)


def state_shardings(param_shardings: Params, mesh: Mesh) -> TrainState:
    """Optimizer state mirrors the param tree => shardings are shared."""
    return {
        "params": param_shardings,
        "opt": {"mu": param_shardings, "nu": param_shardings},
        "step": NamedSharding(mesh, P()),
    }


def make_init_fn(
    init_params: Callable[[jax.Array], Params],
    param_shardings: Params,
    mesh: Mesh,
):
    """Returns jitted rng -> TrainState, with params initialized *sharded*
    (no host-side full materialization — required for models > host RAM)."""

    def init(rng: jax.Array) -> TrainState:
        params = init_params(rng)
        return {
            "params": params,
            "opt": adamw_init(params),
            "step": jnp.zeros((), jnp.int32),
        }

    return _MeshBound(
        jax.jit(init, out_shardings=state_shardings(param_shardings, mesh)),
        mesh)


def make_train_step(
    loss_fn: Callable[[Params, Any], jax.Array],
    param_shardings: Params,
    mesh: Mesh,
    *,
    optimizer: AdamWConfig | None = None,
    batch_spec: Any = None,
    extra_metrics: Callable[[Params, Any], dict] | None = None,
):
    """Build the jitted (state, batch) -> (state, metrics) step.

    loss_fn(params, batch) -> scalar loss. batch_spec: pytree of
    PartitionSpec for the batch (default: first dim over ('dp','fsdp')).
    """
    opt_cfg = optimizer or AdamWConfig()
    st_shard = state_shardings(param_shardings, mesh)
    if batch_spec is None:
        batch_spec = P(("dp", "fsdp"))
    batch_shardings = jax.tree.map(
        lambda s: NamedSharding(mesh, s),
        batch_spec,
        is_leaf=lambda x: isinstance(x, P),
    )

    def step(state: TrainState, batch) -> tuple[TrainState, dict[str, jax.Array]]:
        loss, grads = jax.value_and_grad(loss_fn)(state["params"], batch)
        new_params, new_opt, lr, gnorm = adamw_update(
            opt_cfg, grads, state["params"], state["opt"], state["step"]
        )
        new_state = {
            "params": new_params,
            "opt": new_opt,
            "step": state["step"] + 1,
        }
        metrics = {"loss": loss, "lr": lr, "grad_norm": gnorm}
        if extra_metrics is not None:
            metrics.update(extra_metrics(new_params, batch))
        return new_state, metrics

    return _MeshBound(
        jax.jit(
            step,
            in_shardings=(st_shard, batch_shardings),
            out_shardings=(st_shard, NamedSharding(mesh, P())),
            donate_argnums=(0,),
        ),
        mesh)


def batch_sharding(mesh: Mesh, spec: P | None = None) -> NamedSharding:
    return NamedSharding(mesh, spec if spec is not None else P(("dp", "fsdp")))
