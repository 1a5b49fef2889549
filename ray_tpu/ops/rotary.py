"""Rotary position embedding of the serving path: one token a slot at its
position, or a chunk of rows at theirs.

``x [..., H, D]`` holds heads of ``D`` lanes and ``pos [...]`` (x's leading
axes) the absolute position of each token. Lane ``i`` pairs with lane
``i + D / 2`` (``rotate_half``: the two halves of a head turned against each
other, as every rotary model this repo holds does) and the pair turns by
``pos * theta ** (-2 i / D)``.

The angles are float32 (at ``theta`` 1e11 the slowest lane turns 1e-11 a
token; the frequencies are rounded once, from float64), the result is in
x's type. A rotated key goes into the ring at the token's true position:
the score of a query against it depends on the distance between the two
positions alone, so a ring that has wrapped is a window.

``models/llama.py`` and ``models/deepseek_v2.py`` keep private copies that
predate this file (ROADMAP.md Queue 3 names the debt: moving them changes
their lowered programs).
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np


def inv_freq(dim: int, theta: float) -> np.ndarray:
    """The ``dim / 2`` frequencies ``theta ** (-2 i / dim)``, float32."""
    return (float(theta) ** (-np.arange(0, dim, 2, dtype=np.float64)
                             / dim)).astype(np.float32)


def rotate(x: jax.Array, pos: jax.Array, theta: float) -> jax.Array:
    """x [..., H, D] at positions pos [...] -> the same shape and type."""
    d = x.shape[-1]
    if d % 2:
        raise ValueError(f"a head of {d} lanes has no pairs")
    angles = pos.astype(jnp.float32)[..., None, None] * inv_freq(d, theta)
    cos, sin = jnp.cos(angles), jnp.sin(angles)  # [..., 1, D / 2]
    xf = x.astype(jnp.float32)
    a, b = xf[..., :d // 2], xf[..., d // 2:]
    return jnp.concatenate([a * cos - b * sin, b * cos + a * sin],
                           -1).astype(x.dtype)
