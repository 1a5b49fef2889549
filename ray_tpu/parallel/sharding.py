"""Logical-axis sharding rules (t5x/MaxText-style).

Model code names tensor dimensions logically ("batch", "embed", "mlp", ...);
a rules table maps logical names to physical mesh axes. Swapping parallelism
strategy = swapping the rules table, with no model changes — the TPU-native
answer to the reference's per-strategy wrapper libraries (SURVEY.md §2.4).
"""

from __future__ import annotations

from typing import Iterable, Mapping, Sequence

import jax
from jax.sharding import Mesh, NamedSharding, PartitionSpec

# logical dim -> physical mesh axis (or tuple of axes, or None = replicated).
# Mirrors the MaxText/t5x convention.
DEFAULT_RULES: dict[str, tuple[str, ...] | None] = {
    "batch": ("dp", "fsdp"),
    "seq": ("sp",),  # activation sequence dim (context parallelism)
    "vocab": ("tp",),
    "embed": ("fsdp",),  # param hidden dim => ZeRO-3 sharding
    "mlp": ("tp",),
    "heads": ("tp",),
    "qkv": ("tp",),
    "kv_seq": ("sp",),
    "layers": ("pp",),  # stacked per-layer params; pp>1 shards stages
    "expert": ("ep",),
    None: None,
}


def logical_spec(
    logical_axes: Sequence[str | None],
    rules: Mapping[str, tuple[str, ...] | None] | None = None,
) -> PartitionSpec:
    """Translate logical dims to a PartitionSpec via the rules table.

    Each physical axis may be used at most once per spec; later logical dims
    that map to an already-used physical axis fall back to replicated — e.g.
    ('batch', 'seq', 'embed') -> PartitionSpec(('dp','fsdp'), 'sp', None)
    because 'batch' already consumed fsdp. This keeps one rules table valid
    for every tensor in the model.
    """
    rules = rules or DEFAULT_RULES
    used: set[str] = set()
    out: list[tuple[str, ...] | str | None] = []
    for name in logical_axes:
        axes = rules.get(name) if name is not None else None
        if axes is None:
            out.append(None)
            continue
        free = tuple(a for a in axes if a not in used)
        if not free:
            out.append(None)
            continue
        used.update(free)
        out.append(free if len(free) > 1 else free[0])
    return PartitionSpec(*out)


def logical_sharding(
    mesh: Mesh,
    logical_axes: Sequence[str | None],
    rules: Mapping[str, tuple[str, ...] | None] | None = None,
) -> NamedSharding:
    return NamedSharding(mesh, logical_spec(logical_axes, rules))


def with_logical_constraint(
    x: jax.Array,
    logical_axes: Sequence[str | None],
    mesh: Mesh | None = None,
    rules: Mapping[str, tuple[str, ...] | None] | None = None,
) -> jax.Array:
    """``with_sharding_constraint`` by logical names.

    Without an explicit ``mesh`` the constraint binds to the mesh the
    computation is traced under (``jax.sharding.set_mesh``, which
    ``make_train_step`` / ``make_init_fn`` enter around trace and call).
    With no mesh in context — a one-device program such as the serving
    engine's step — there is nothing to constrain against and ``x`` is
    returned as is. A jit over several devices that is NOT traced under
    its mesh therefore gets no activation constraints: trace it under
    ``set_mesh``.
    """
    if mesh is None:
        mesh = jax.sharding.get_abstract_mesh()
    if mesh.empty:
        return x
    return jax.lax.with_sharding_constraint(
        x, NamedSharding(mesh, logical_spec(logical_axes, rules))
    )


def shard_pytree(tree, sharding_tree, mesh: Mesh):
    """device_put a pytree of host arrays onto the mesh per a sharding tree."""
    return jax.tree.map(lambda x, s: jax.device_put(x, s), tree, sharding_tree)
