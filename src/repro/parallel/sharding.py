"""Mesh-aware sharding helpers.

Mesh axes:
  single-pod:  ("data", "model")            = (16, 16)  -> 256 chips
  multi-pod:   ("pod", "data", "model")     = (2, 16, 16) -> 512 chips

Conventions used across every model family:
  * batch-like dims shard over all data axes (pod+data),
  * tensor-parallel dims shard over "model",
  * FSDP ("zero-3") weight sharding uses the data axes on a weight's input
    dim — all-gathered per layer inside lax.scan so XLA's latency-hiding
    scheduler overlaps the gather with the previous layer's compute.
"""

from __future__ import annotations

from typing import Any

import jax
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P, get_abstract_mesh

__all__ = [
    "P",
    "dp_axes",
    "fsdp_axes",
    "named",
    "shard_tree",
    "batch_spec",
    "abstract_with_sharding",
]


def dp_axes(mesh: Mesh) -> tuple[str, ...]:
    """All data-parallel axes present in the mesh (pod first)."""
    return tuple(a for a in ("pod", "data") if a in mesh.axis_names)


def fsdp_axes(mesh: Mesh) -> tuple[str, ...]:
    """Axes used for fully-sharded parameter storage."""
    return dp_axes(mesh)


def batch_spec(mesh: Mesh, *rest: Any) -> P:
    """PartitionSpec with the batch dim sharded over all data axes."""
    return P(dp_axes(mesh), *rest)


def named(mesh: Mesh, spec: P) -> NamedSharding:
    return NamedSharding(mesh, spec)


def shard_tree(mesh: Mesh, spec_tree: Any) -> Any:
    """Map a pytree of PartitionSpec -> pytree of NamedSharding."""
    return jax.tree.map(
        lambda s: NamedSharding(mesh, s),
        spec_tree,
        is_leaf=lambda x: isinstance(x, P),
    )


def abstract_with_sharding(shape_tree: Any, sharding_tree: Any) -> Any:
    """Attach shardings to ShapeDtypeStructs (for .lower() without arrays)."""
    return jax.tree.map(
        lambda s, sh: jax.ShapeDtypeStruct(s.shape, s.dtype, sharding=sh),
        shape_tree,
        sharding_tree,
    )


def _no_active_mesh() -> bool:
    m = get_abstract_mesh()
    return m is None or m.empty


def maybe_constrain(x, spec: P):
    """with_sharding_constraint that is a no-op when no mesh is active
    (lets the same model code run in single-device smoke tests and in
    pjit-partitioned production graphs)."""
    if _no_active_mesh():
        return x
    return jax.lax.with_sharding_constraint(x, spec)


def divisible_or_none(dim: int, mesh: Mesh, axes: tuple[str, ...] | str):
    """Return the axes if ``dim`` divides their product, else None (replicate).

    GSPMD can pad uneven shardings, but padding on a *weight* dim wastes HBM
    and produces ragged collectives; we prefer explicit replication and call
    it out in the roofline notes.
    """
    if isinstance(axes, str):
        axes = (axes,)
    size = 1
    for a in axes:
        size *= mesh.shape[a]
    return axes if dim % size == 0 else None
