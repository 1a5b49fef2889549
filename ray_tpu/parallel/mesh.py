"""Device mesh construction with named parallelism axes.

The reference framework ships only data parallelism (torch DDP over NCCL,
``python/ray/train/torch/config.py:113``) and a collective-group API
(``python/ray/util/collective/collective.py:120``). Here *all* parallelism
strategies are axes of one `jax.sharding.Mesh`:

    pp    pipeline stages        (DCN-friendly, outermost)
    dp    pure data parallelism  (DCN-friendly)
    fsdp  data parallelism with sharded params/optimizer (ZeRO-3 style)
    sp    sequence/context parallelism (ring attention rides this axis)
    tp    tensor (Megatron-style) parallelism, innermost => fastest ICI hops
    ep    expert parallelism for MoE (aliased onto sp/tp-adjacent axis)

Axis order is chosen so that the innermost axes map to the
fastest-communicating device neighborhoods when `jax.make_mesh` lays devices
out (it uses the physical TPU topology); collectives over ``tp``/``sp`` then
ride short ICI rings while ``pp``/``dp`` tolerate DCN.
"""

from __future__ import annotations

import dataclasses
import math
from typing import Sequence

import jax
from jax.sharding import AxisType, Mesh

from ray_tpu.util.compile_cache import ensure_compile_cache

# Outermost -> innermost. ep shares the dims between sp and tp so MoE models
# can all_to_all over experts without a dedicated physical axis.
AXIS_ORDER: tuple[str, ...] = ("pp", "dp", "fsdp", "ep", "sp", "tp")


@dataclasses.dataclass(frozen=True)
class MeshConfig:
    """Declarative mesh shape. Product of all axes must equal device count.

    ``-1`` on at most one axis means "absorb all remaining devices"
    (same convention as a reshape wildcard).
    """

    pp: int = 1
    dp: int = 1
    fsdp: int = -1
    ep: int = 1
    sp: int = 1
    tp: int = 1
    # Explicit device list (for subsetting / tests); None = all devices.
    devices: Sequence[jax.Device] | None = None

    def axis_sizes(self, n_devices: int) -> dict[str, int]:
        sizes = {a: getattr(self, a) for a in AXIS_ORDER}
        wild = [a for a, s in sizes.items() if s == -1]
        if len(wild) > 1:
            raise ValueError(f"At most one axis may be -1, got {wild}")
        fixed = math.prod(s for s in sizes.values() if s != -1)
        if wild:
            if n_devices % fixed != 0:
                raise ValueError(
                    f"{n_devices} devices not divisible by fixed axes product {fixed}"
                )
            sizes[wild[0]] = n_devices // fixed
        if math.prod(sizes.values()) != n_devices:
            raise ValueError(
                f"Mesh {sizes} needs {math.prod(sizes.values())} devices, "
                f"have {n_devices}"
            )
        return sizes


def local_device_count() -> int:
    return jax.local_device_count()


def auto_mesh_config(n_devices: int | None = None) -> MeshConfig:
    """Default config: pure fsdp (ZeRO-3 data parallelism) over every device.

    This is the safest high-performance default for dense LLM training at
    single-slice scale; callers opt into tp/sp/pp explicitly.
    """
    return MeshConfig(fsdp=n_devices if n_devices is not None else -1)


def build_mesh(
    config: MeshConfig | None = None,
    *,
    axis_types: AxisType = AxisType.Auto,
) -> Mesh:
    """Build a `jax.sharding.Mesh` with the standard axis names.

    Uses Auto axis types by default: shardings are propagated by XLA (GSPMD)
    from the in/out shardings and ``with_sharding_constraint`` hints, which is
    the idiomatic "annotate and let the compiler insert collectives" recipe.
    """
    ensure_compile_cache()
    config = config or auto_mesh_config()
    devices = list(config.devices) if config.devices is not None else jax.devices()
    sizes = config.axis_sizes(len(devices))
    return jax.make_mesh(
        tuple(sizes[a] for a in AXIS_ORDER),
        AXIS_ORDER,
        axis_types=(axis_types,) * len(AXIS_ORDER),
        devices=devices,
    )


def build_hybrid_mesh(
    per_slice: MeshConfig | None = None,
    *,
    dcn_dp: int | None = None,
    dcn_pp: int = 1,
    devices: Sequence[jax.Device] | None = None,
    axis_types: AxisType = AxisType.Auto,
) -> Mesh:
    """Multi-slice mesh: DCN between slices, ICI within (SURVEY §5.8).

    TPU pods beyond one slice have a two-tier network — fast ICI inside a
    slice, slower data-center network (DCN) between slices. The scaling
    recipe ("How to Scale Your Model"; jax ``mesh_utils.create_hybrid_
    device_mesh`` shape) is: put only DCN-tolerant axes across slices —
    pure data parallelism (``dcn_dp``: gradient all-reduce once per step)
    and/or pipeline stages (``dcn_pp``: point-to-point activations) — and
    keep tp/sp/fsdp collectives inside a slice.

    Devices are grouped by ``slice_index``; on hosts without one (CPU
    simulation, single slice) the device list is partitioned evenly into
    ``dcn_dp * dcn_pp`` synthetic slices so the layout is testable
    anywhere. ``per_slice`` shapes the ICI axes of one slice; the result
    is a standard AXIS_ORDER mesh whose ``dp``/``pp`` sizes are the
    DCN-times-ICI products.
    """
    import numpy as np

    devices = list(devices) if devices is not None else jax.devices()
    groups: dict[int, list] = {}
    for d in devices:
        groups.setdefault(getattr(d, "slice_index", 0) or 0, []).append(d)
    n_slices_wanted = (dcn_dp if dcn_dp is not None else
                       max(1, len(groups) // dcn_pp)) * dcn_pp
    if len(groups) == 1 and n_slices_wanted > 1:
        devs = next(iter(groups.values()))
        if len(devs) % n_slices_wanted:
            raise ValueError(
                f"{len(devs)} devices not divisible into "
                f"{n_slices_wanted} synthetic slices")
        per = len(devs) // n_slices_wanted
        groups = {i: devs[i * per:(i + 1) * per]
                  for i in range(n_slices_wanted)}
    slices = [groups[k] for k in sorted(groups)]
    num_slices = len(slices)
    if len({len(s) for s in slices}) != 1:
        raise ValueError("slices have unequal device counts")
    if dcn_dp is None:
        if num_slices % dcn_pp:
            raise ValueError(f"{num_slices} slices not divisible by "
                             f"dcn_pp={dcn_pp}")
        dcn_dp = num_slices // dcn_pp
    if dcn_dp * dcn_pp != num_slices:
        raise ValueError(
            f"dcn_dp({dcn_dp}) * dcn_pp({dcn_pp}) != slices({num_slices})")

    cfg = per_slice or MeshConfig(fsdp=-1)
    sizes = cfg.axis_sizes(len(slices[0]))
    # [dcn_pp, dcn_dp, pp, dp, fsdp, ep, sp, tp] — each slice keeps its
    # devices contiguous over the inner (ICI) dims.
    stacked = np.stack([
        np.array(s, dtype=object).reshape(
            [sizes[a] for a in AXIS_ORDER])
        for s in slices
    ]).reshape(dcn_pp, dcn_dp, *[sizes[a] for a in AXIS_ORDER])
    # Merge DCN dims into their ICI counterparts: pp-total outermost.
    stacked = np.moveaxis(stacked, 2, 1)  # [dcn_pp, pp, dcn_dp, dp, ...]
    final_shape = (
        dcn_pp * sizes["pp"], dcn_dp * sizes["dp"], sizes["fsdp"],
        sizes["ep"], sizes["sp"], sizes["tp"],
    )
    return Mesh(stacked.reshape(final_shape), AXIS_ORDER,
                axis_types=(axis_types,) * len(AXIS_ORDER))


def single_device_mesh() -> Mesh:
    """1-device mesh (all axes size 1) — lets model code be mesh-agnostic."""
    return build_mesh(MeshConfig(fsdp=1, devices=jax.devices()[:1]))
