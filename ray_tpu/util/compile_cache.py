"""Where JAX's persistent compilation cache lives.

The cache key includes the directory, so a cache that moves never hits.
One rule, applied wherever the main path first touches JAX
(``build_mesh``, ``LLMEngine``, ``parallel.distributed.initialize``,
``chip_smoke.py``): an operator who sets
``JAX_COMPILATION_CACHE_DIR`` owns the location (JAX reads the variable
itself and nothing here overrides it); otherwise the cache is the fixed
``<checkout>/.jax_cache``. No other path is ever set in code.
"""

from __future__ import annotations

import os

_CHECKOUT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
DEFAULT_CACHE_DIR = os.path.join(_CHECKOUT, ".jax_cache")


def ensure_compile_cache() -> str:
    """Point JAX at the persistent cache; returns the directory in use.
    Every main path comes through here before its first compile, so
    this is also where the compile listeners attach
    (``device_telemetry.compile_log``)."""
    import jax

    from ray_tpu.util import device_telemetry

    device_telemetry.ensure_listeners()
    placed = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if placed:
        return placed

    if jax.config.jax_compilation_cache_dir != DEFAULT_CACHE_DIR:
        jax.config.update("jax_compilation_cache_dir", DEFAULT_CACHE_DIR)
    return DEFAULT_CACHE_DIR
