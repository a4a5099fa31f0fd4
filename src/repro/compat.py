"""The mesh/shard_map surface the codebase uses, over JAX's explicit-mesh
API (``jax.shard_map``, ``jax.make_mesh(axis_types=...)``,
``jax.sharding.set_mesh`` / ``get_abstract_mesh``).

Thin wrappers only: they fix the defaults this repo relies on (all mesh
axes Auto, shard_map's VMA check off) in one place.
"""

from __future__ import annotations

import contextlib

import jax


def manual_axes(mesh=None) -> frozenset[str]:
    """Axis names of ``mesh`` whose axis type is Manual."""
    if mesh is None:
        return frozenset()
    return frozenset(
        name
        for name, ty in zip(mesh.axis_names, mesh.axis_types)
        if ty == jax.sharding.AxisType.Manual
    )


def shard_map(f, mesh, in_specs, out_specs, axis_names=None, check=False):
    """``jax.shard_map`` with the replication (VMA) check off by default
    — the hybrid schedules intentionally let per-team params drift.

    ``axis_names``: the *manual* axes (None = all mesh axes manual).
    """
    kwargs = dict(mesh=mesh, in_specs=in_specs, out_specs=out_specs, check_vma=check)
    if axis_names is not None:
        kwargs["axis_names"] = frozenset(axis_names)
    return jax.shard_map(f, **kwargs)


def axis_size(name) -> int:
    """Static size of a named mesh axis inside shard_map."""
    return jax.lax.axis_size(name)


def abstract_mesh(shape, axes):
    """A ``jax.sharding.AbstractMesh`` of ``shape`` over ``axes``."""
    return jax.sharding.AbstractMesh(tuple(shape), tuple(axes))


def make_mesh(shape, axes, devices=None):
    """``jax.make_mesh`` with every axis Auto."""
    return jax.make_mesh(
        shape, axes, devices=devices,
        axis_types=(jax.sharding.AxisType.Auto,) * len(axes),
    )


def get_abstract_mesh():
    """The ambient mesh (``.empty`` / ``.axis_names`` / ``.axis_sizes``)."""
    return jax.sharding.get_abstract_mesh()


def set_mesh(mesh):
    """Set the ambient mesh (``jax.sharding.set_mesh``)."""
    return jax.sharding.set_mesh(mesh)


@contextlib.contextmanager
def use_mesh(mesh):
    """Scoped ambient mesh — always restores on exit."""
    with jax.sharding.set_mesh(mesh):
        yield mesh
