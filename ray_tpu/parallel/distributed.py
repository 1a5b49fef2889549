"""Multi-host JAX runtime bootstrap via the cluster KV.

The TPU-defining piece of the collective layer (SURVEY.md §7 step 6): a
group of worker processes (one per host) rendezvous through the cluster's
internal KV and call ``jax.distributed.initialize`` so that all hosts'
devices form ONE global mesh and jitted step functions run SPMD across
hosts with XLA collectives on ICI/DCN.

Reference pattern being replaced: NCCL-unique-id rendezvous via a named
actor (``python/ray/util/collective/collective_group/nccl_collective_group.py``
rendezvous) and rank-0 master addr/port fan-out in
``python/ray/train/torch/config.py:129-181``. Here the shared secret is the
coordinator address, published by rank 0 under ``jaxdist/<group>/coordinator``.

On real TPU pods each worker-host simply calls ``initialize()`` with its
rank; the CPU test path forces ``platform="cpu"`` with N virtual devices
per process (Gloo cross-process collectives), which is how multi-host
behavior is validated without a pod (SURVEY.md §4.3).
"""

from __future__ import annotations

import os
import socket
import time
from typing import Optional

_KEY = "jaxdist/{group}/coordinator"


def host_ip() -> str:
    """Best-effort routable IP of this host (falls back to localhost)."""
    try:
        s = socket.socket(socket.AF_INET, socket.SOCK_DGRAM)
        try:
            s.connect(("8.8.8.8", 80))  # no packets sent; picks the route
            return s.getsockname()[0]
        finally:
            s.close()
    except OSError:
        return "127.0.0.1"


def free_port() -> int:
    s = socket.socket()
    s.bind(("", 0))
    port = s.getsockname()[1]
    s.close()
    return port


def publish_coordinator(group: str, address: Optional[str] = None) -> str:
    """Rank 0: publish the coordinator address in the cluster KV."""
    from ray_tpu.experimental import internal_kv

    if address is None:
        address = f"{host_ip()}:{free_port()}"
    internal_kv.kv_put(_KEY.format(group=group), address)
    return address


def wait_coordinator(group: str, timeout: float = 120.0) -> str:
    """Non-zero ranks: poll the KV until rank 0 publishes."""
    from ray_tpu.experimental import internal_kv

    deadline = time.monotonic() + timeout
    key = _KEY.format(group=group)
    while time.monotonic() < deadline:
        addr = internal_kv.kv_get(key)
        if addr is not None:
            return addr
        time.sleep(0.05)
    raise TimeoutError(f"no coordinator published for group {group!r}")


def clear_group(group: str) -> None:
    from ray_tpu.experimental import internal_kv

    internal_kv.kv_del(_KEY.format(group=group))


def initialize(
    group: str,
    rank: int,
    world_size: int,
    *,
    platform: Optional[str] = None,
    num_cpu_devices: Optional[int] = None,
    coordinator_address: Optional[str] = None,
    local_device_ids: Optional[list[int]] = None,
    timeout: float = 120.0,
) -> None:
    """Join the named process group and initialize the JAX runtime.

    Must run before any JAX backend touch in this process. ``platform`` /
    ``num_cpu_devices`` configure the CPU simulation path; on a real pod
    leave them None and the TPU runtime discovers topology itself.
    """
    import jax

    from ray_tpu.util.compile_cache import ensure_compile_cache

    ensure_compile_cache()
    if platform is not None:
        os.environ["JAX_PLATFORMS"] = platform
        jax.config.update("jax_platforms", platform)
    if num_cpu_devices is not None:
        jax.config.update("jax_num_cpu_devices", num_cpu_devices)

    if world_size == 1 and coordinator_address is None:
        return  # single-process: nothing to rendezvous

    if coordinator_address is None:
        if rank == 0:
            coordinator_address = publish_coordinator(group)
        else:
            coordinator_address = wait_coordinator(group, timeout)
    jax.distributed.initialize(
        coordinator_address=coordinator_address,
        num_processes=world_size,
        process_id=rank,
        local_device_ids=local_device_ids,
        initialization_timeout=int(timeout),
    )


def shutdown() -> None:
    import jax

    try:
        jax.distributed.shutdown()
    except (RuntimeError, ValueError):
        pass
