"""In-mesh XLA collectives: the TPU tensor plane.

This is the TPU-native replacement for the reference's NCCL backend
(``collective_group/nccl_collective_group.py``): dense-tensor collectives
compile into the jitted program and ride ICI, instead of being framework
calls that move buffers between processes (SURVEY.md §5.8).

Two surfaces:

1. **Inside jit / shard_map** — thin aliases over ``jax.lax`` so library
   code can write ``collective.xla.allreduce(x, axis="dp")`` and stay
   backend-agnostic: the op lowers to an XLA collective on the mesh axis.

2. **`DeviceGroup`** — eager helper for code that holds per-device arrays
   OUTSIDE a jitted region: builds a 1D mesh over the chosen devices and
   runs one compiled collective over it. Useful for tests, optimizer-state
   surgery, and host-driven rendezvous steps.
"""

from __future__ import annotations

import functools
from typing import Optional, Sequence

import jax
import jax.numpy as jnp
from jax import shard_map
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P


# -- surface 1: inside jit/shard_map --------------------------------------

def allreduce(x, axis: str):
    return jax.lax.psum(x, axis_name=axis)


def allreduce_mean(x, axis: str):
    return jax.lax.pmean(x, axis_name=axis)


def allgather(x, axis: str, *, concat_axis: int = 0, tiled: bool = True):
    return jax.lax.all_gather(x, axis_name=axis, axis=concat_axis, tiled=tiled)


def reducescatter(x, axis: str, *, scatter_axis: int = 0):
    return jax.lax.psum_scatter(
        x, axis_name=axis, scatter_dimension=scatter_axis, tiled=True
    )


def all_to_all(x, axis: str, *, split_axis: int, concat_axis: int):
    return jax.lax.all_to_all(
        x, axis_name=axis, split_axis=split_axis, concat_axis=concat_axis,
        tiled=True,
    )


def ppermute(x, axis: str, perm: Sequence[tuple[int, int]]):
    return jax.lax.ppermute(x, axis_name=axis, perm=perm)


def broadcast(x, axis: str, src: int = 0):
    """Every rank gets src's value (gather + index — XLA fuses this)."""
    return jax.lax.all_gather(x, axis_name=axis)[src]


def axis_index(axis: str):
    return jax.lax.axis_index(axis)


# -- surface 2: eager collectives over explicit devices -------------------

class DeviceGroup:
    """A 1D mesh over explicit devices with eager compiled collectives.

    The ``world_size``/``rank`` bookkeeping of the reference's group API
    maps to mesh positions here; rendezvous is unnecessary intra-process
    because XLA sees all member devices.
    """

    AXIS = "ranks"

    def __init__(self, devices: Optional[Sequence] = None):
        self.devices = list(devices if devices is not None else jax.devices())
        self.mesh = Mesh(self.devices, (self.AXIS,))
        self.world_size = len(self.devices)
        self._compiled: dict[str, callable] = {}

    def _sharded(self, x, spec: P):
        return jax.device_put(x, NamedSharding(self.mesh, spec))

    def _run(self, name: str, fn, x, in_spec: P, out_spec: P):
        # Cache the jitted collective per op: jax.jit caches by function
        # identity, so a fresh closure per call would recompile every time.
        compiled = self._compiled.get(name)
        if compiled is None:
            compiled = self._compiled[name] = jax.jit(
                shard_map(
                    fn, mesh=self.mesh, in_specs=(in_spec,),
                    out_specs=out_spec, check_vma=False,
                )
            )
        return compiled(self._sharded(x, in_spec))

    def allreduce(self, x):
        """x: (world, ...) stacked per-rank contributions; returns the
        elementwise sum over ranks, replicated."""
        return self._run(
            "allreduce",
            lambda s: jax.lax.psum(s[0], axis_name=self.AXIS),
            x, P(self.AXIS), P(),
        )

    def allgather(self, x):
        """x: (world, ...) stacked per-rank contributions; returns the full
        stack on every rank (i.e. x, replicated)."""
        return self._run(
            "allgather",
            lambda s: jax.lax.all_gather(s, self.AXIS, axis=0, tiled=True),
            x, P(self.AXIS), P(),
        )

    def reducescatter(self, x):
        """x: (world, k*world, ...) stacked per-rank contributions; returns
        (world, k, ...) where row r is rank r's chunk of the reduced sum."""
        return self._run(
            "reducescatter",
            lambda s: jax.lax.psum_scatter(
                s[0], self.AXIS, scatter_dimension=0, tiled=True
            )[None],
            x, P(self.AXIS), P(self.AXIS),
        )

    def barrier(self):
        """Complete a trivial collective on every member device."""
        token = jnp.zeros((self.world_size,), jnp.int32)
        jax.block_until_ready(self.allreduce(token))
