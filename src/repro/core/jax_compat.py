"""The jax mesh / shard_map surface, in one spelling (jax 0.9).

Every call site imports from here, so a jax upgrade that moves one of these
touches one file.
"""

from __future__ import annotations

import jax
from jax.sharding import AxisType


def make_mesh(shape, axes):
    """jax.make_mesh with Auto axis types."""
    return jax.make_mesh(tuple(shape), tuple(axes),
                         axis_types=(AxisType.Auto,) * len(axes))


def set_mesh(mesh):
    """Context manager activating ``mesh`` for sharding constraints."""
    return jax.set_mesh(mesh)


def get_abstract_mesh():
    """The mesh of the current sharding context (empty when absent)."""
    return jax.sharding.get_abstract_mesh()


def manual_axis_names(mesh):
    """Axis names currently in Manual mode (inside a shard_map over them)."""
    return {n for n, t in zip(mesh.axis_names, mesh.axis_types)
            if t == AxisType.Manual}


def cost_analysis(compiled) -> dict:
    """compiled.cost_analysis() as a flat dict."""
    return dict(compiled.cost_analysis())


def axis_size(name):
    """Static size of a named mapped axis (inside shard_map)."""
    return jax.lax.axis_size(name)


def shard_map(f, mesh, in_specs, out_specs, check_vma=False):
    """Map ``f`` over ``mesh``, manual over EVERY mesh axis: an axis that
    ``in_specs`` does not name is replicated into the body.  Partial-auto
    lowering (``jax.shard_map(axis_names=...)``) trips an XLA SPMD
    partitioner CHECK on a ("pod", "data", "model") mesh, so it is not
    used."""
    return jax.shard_map(f, mesh=mesh, in_specs=in_specs,
                         out_specs=out_specs, check_vma=check_vma)
