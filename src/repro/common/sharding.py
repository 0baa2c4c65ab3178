"""Logical-axis sharding rules → NamedSharding, MaxText-style.

Every param/activation is annotated with *logical* axis names; a rules table
maps logical names to mesh axes per mesh. This keeps model code mesh-agnostic:
the same model def lowers on 1 CPU device, a (16,16) pod, or a (2,16,16)
multi-pod mesh.
"""
from __future__ import annotations

from typing import Any, Mapping, Sequence

import jax
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

# logical axis -> mesh axis (or tuple of mesh axes, or None = replicated)
# "batch" folds pod+data so multi-pod meshes scale batch across pods.
DEFAULT_RULES: dict[str, Any] = {
    "batch": ("pod", "data"),
    "fsdp": ("pod", "data"),  # ZeRO-3 parameter sharding axis
    "embed": ("pod", "data"),  # 2D weight sharding: d_model dim over data (FSDP)
    "model": "model",
    "vocab": "model",
    "heads": "model",
    "kv_heads": "model",
    "mlp": "model",
    "experts": ("data", "model"),  # full EP: one/few experts per chip
    "seq": None,
    "seq_sharded": "model",  # SP: long-context KV sharding
    "layers": None,  # scanned-layer stack dim
    "opt_state": ("pod", "data", "model"),  # ZeRO: flat int8 moments over all
    "nodes": ("pod", "data", "model"),
    "edges": ("pod", "data", "model"),
    "nodes_sm": ("pod", "data"),  # small graphs: don't pay 256-way collectives
    "edges_sm": ("pod", "data"),
    "table_vocab": "model",  # recsys embedding tables sharded by row
    "candidates": "model",
    "blocks": ("pod", "data"),  # learned-index doc blocks
    "docs": ("pod", "data"),
    "terms": "model",
    None: None,
}


def concrete_mesh(shape: Sequence[int], names: Sequence[str]) -> Mesh:
    """Device mesh with explicit Auto axis types, the shard_map/pjit mix
    these modules use.  Tests and launchers build meshes through this."""
    return jax.make_mesh(
        tuple(shape), tuple(names),
        axis_types=(jax.sharding.AxisType.Auto,) * len(names),
    )


def mesh_context(mesh: Mesh):
    """`with mesh_context(mesh):` installs the ambient mesh."""
    return jax.set_mesh(mesh)


shard_map = jax.shard_map
axis_size = jax.lax.axis_size


def pvary(x, axis_names):
    """Mark ``x`` as varying over ``axis_names`` inside shard_map."""
    return jax.lax.pcast(x, axis_names, to="varying")


def abstract_mesh(shape: Sequence[int], names: Sequence[str]):
    """AbstractMesh((16, 16), ("data", "model")): symbolic axes, no devices."""
    if len(shape) != len(names):
        raise ValueError(f"shape {shape} and names {names} must align")
    return jax.sharding.AbstractMesh(tuple(shape), tuple(names))


def _mesh_axes(mesh: Mesh) -> tuple[str, ...]:
    return tuple(mesh.axis_names)


def resolve_axis(logical: str | None, mesh: Mesh, rules: Mapping[str, Any] | None = None) -> Any:
    rules = rules or DEFAULT_RULES
    target = rules.get(logical, None)
    names = set(_mesh_axes(mesh))
    if target is None:
        return None
    if isinstance(target, tuple):
        present = tuple(a for a in target if a in names)
        if not present:
            return None
        return present if len(present) > 1 else present[0]
    return target if target in names else None


def logical_to_sharding(
    logical_axes: Sequence[str | None],
    mesh: Mesh,
    rules: Mapping[str, Any] | None = None,
) -> NamedSharding:
    """('batch', None, 'model') -> NamedSharding over the given mesh."""
    spec = P(*(resolve_axis(ax, mesh, rules) for ax in logical_axes))
    return NamedSharding(mesh, spec)


def spec_for_shape(
    logical_axes: Sequence[str | None],
    shape: Sequence[int],
    mesh: Mesh,
    rules: Mapping[str, Any] | None = None,
) -> P:
    """Divisibility-aware spec: mesh axes that don't divide a dim are dropped
    (trailing-first), and a mesh axis is never used twice in one spec (the
    first dim that claims it wins) — e.g. MQA's kv_heads=1 falls back to
    replicated, and MoE ('experts','embed','mlp') keeps experts on `model`
    and drops mlp's claim."""
    used: set[str] = set()
    entries: list[Any] = []
    for ax, dim in zip(logical_axes, shape):
        target = resolve_axis(ax, mesh, rules)
        if target is None:
            entries.append(None)
            continue
        t = (target,) if isinstance(target, str) else tuple(target)
        t = tuple(a for a in t if a not in used)
        while t:
            prod = 1
            for a in t:
                prod *= mesh.shape[a]
            if dim % prod == 0:
                break
            t = t[:-1]
        if not t:
            entries.append(None)
            continue
        used.update(t)
        entries.append(t if len(t) > 1 else t[0])
    return P(*entries)


def sharding_for_shape(
    logical_axes: Sequence[str | None],
    shape: Sequence[int],
    mesh: Mesh,
    rules: Mapping[str, Any] | None = None,
) -> NamedSharding:
    return NamedSharding(mesh, spec_for_shape(logical_axes, shape, mesh, rules))


def partition_spec(
    logical_axes: Sequence[str | None],
    mesh: Mesh,
    rules: Mapping[str, Any] | None = None,
) -> P:
    return P(*(resolve_axis(ax, mesh, rules) for ax in logical_axes))


def with_sharding(x: jax.Array, logical_axes: Sequence[str | None], mesh: Mesh) -> jax.Array:
    """In-graph sharding constraint by logical axes."""
    return jax.lax.with_sharding_constraint(x, logical_to_sharding(logical_axes, mesh))


def constrain(x: jax.Array, *logical_axes: str | None) -> jax.Array:
    """Ambient-mesh activation sharding constraint by logical axes.

    Uses the mesh installed by `jax.set_mesh` (the dry-run / launcher
    context); no-op when tracing outside a mesh or on a single device.
    Divisibility-aware like spec_for_shape, so the same model code works on
    any mesh."""
    try:
        mesh = jax.sharding.get_abstract_mesh()
    except Exception:
        return x
    if mesh is None or not mesh.axis_names or mesh.size <= 1:
        return x
    spec = spec_for_shape(logical_axes, x.shape, mesh)
    return jax.lax.with_sharding_constraint(x, spec)


def shard_params(params: Any, axes_tree: Any, mesh: Mesh) -> Any:
    """device_put a param pytree according to a matching logical-axes pytree."""
    return jax.tree.map(
        lambda p, ax: jax.device_put(p, logical_to_sharding(ax, mesh)),
        params,
        axes_tree,
        is_leaf=lambda x: isinstance(x, (np.ndarray, jax.Array)),
    )


def sharding_tree(axes_tree: Any, mesh: Mesh) -> Any:
    """Logical-axes pytree -> NamedSharding pytree (for in_shardings)."""
    return jax.tree.map(
        lambda ax: logical_to_sharding(ax, mesh),
        axes_tree,
        is_leaf=lambda x: isinstance(x, tuple) and all(isinstance(a, (str, type(None))) for a in x),
    )


def abstract_like(params: Any) -> Any:
    return jax.tree.map(lambda p: jax.ShapeDtypeStruct(p.shape, p.dtype), params)
