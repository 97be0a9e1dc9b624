"""Sharding hints: mesh-aware ``with_sharding_constraint`` that no-ops when
no mesh is active.

Models stay mesh-agnostic (smoke tests run un-sharded on one CPU device), but
under ``jax.set_mesh`` (the launcher/dry-run) these hints pin the layouts the
2D (data, model) strategy intends — most importantly inside attention, where
XLA's propagation otherwise picks a fragmentary head sharding for head counts
that do not divide the model axis (DESIGN.md §5, EXPERIMENTS.md §Perf).

``hint(x, {dim: axis})`` applies an axis to a dim only when the dim size is
divisible by the mesh extent of that axis; everything else is left to the
propagator (PartitionSpec.UNCONSTRAINED on unmentioned dims would be too
strict — None lets XLA refine).
"""
from __future__ import annotations

import math

import jax
from jax.sharding import PartitionSpec as P

Axis = str | tuple[str, ...]


def active_mesh():
    mesh = jax.sharding.get_abstract_mesh()
    if not tuple(getattr(mesh, "axis_names", ())):
        return None
    return mesh


def axis_extent(mesh, axis: Axis) -> int:
    names = (axis,) if isinstance(axis, str) else axis
    sizes = dict(mesh.shape)
    return math.prod(sizes.get(n, 0) or 0 for n in names) or 0


def hint(x, dims: dict[int, Axis]):
    """Constrain ``x`` so dim ``d`` is sharded over ``dims[d]`` when divisible."""
    mesh = active_mesh()
    if mesh is None:
        return x
    spec: list = [None] * x.ndim
    used: set = set()
    for d, axis in dims.items():
        names = (axis,) if isinstance(axis, str) else tuple(axis)
        names = tuple(n for n in names if n in mesh.axis_names and n not in used)
        if not names:
            continue
        ext = axis_extent(mesh, names)
        if ext and x.shape[d] % ext == 0:
            spec[d] = names if len(names) > 1 else names[0]
            used.update(names)
    if all(s is None for s in spec):
        return x
    return jax.lax.with_sharding_constraint(x, P(*spec))


def pick_divisible(mesh, axis: str, *candidates: tuple[int, int]) -> int | None:
    """First candidate (dim_index, dim_size) divisible by the axis extent."""
    ext = axis_extent(mesh, axis)
    if not ext:
        return None
    for idx, size in candidates:
        if size % ext == 0:
            return idx
    return None
