"""Mesh helpers over jax's public mesh API (jax >= 0.9).

Everything in the repo that touches a mesh context goes through this module,
so the call sites share one definition of "is a mesh active" and one way to
activate one:

* ``get_abstract_mesh`` — the active mesh, or ``None`` (jax returns an
  *empty* ``AbstractMesh`` when no mesh is set; callers here test ``None``);
* ``abstract_mesh`` / ``make_mesh`` — mesh constructors with Auto axis types;
* ``shardings_for`` — ``PartitionSpec`` pytrees to ``NamedSharding``;
* ``use_mesh`` — ``jax.set_mesh``, which sets the concrete and the abstract
  mesh for its dynamic extent.
"""

from __future__ import annotations

import jax
from jax.sharding import AbstractMesh, AxisType, Mesh, NamedSharding, PartitionSpec


def get_abstract_mesh():
    """The mesh of the current mesh context, or ``None`` when there is none."""
    mesh = jax.sharding.get_abstract_mesh()
    return None if mesh.empty else mesh


def abstract_mesh(axis_sizes, axis_names) -> AbstractMesh:
    """``AbstractMesh`` from parallel size/name tuples."""
    axis_sizes = tuple(int(s) for s in axis_sizes)
    axis_names = tuple(axis_names)
    if len(axis_sizes) != len(axis_names):
        raise ValueError(f"{axis_sizes=} vs {axis_names=}")
    return AbstractMesh(axis_sizes, axis_names)


def make_mesh(axis_shapes, axis_names, *, devices=None) -> Mesh:
    """``jax.make_mesh`` with Auto axis types."""
    axis_names = tuple(axis_names)
    return jax.make_mesh(tuple(axis_shapes), axis_names, devices=devices,
                         axis_types=(AxisType.Auto,) * len(axis_names))


def shardings_for(mesh, tree):
    """Resolve a pytree of ``PartitionSpec`` into ``NamedSharding`` on ``mesh``.

    jit call sites that build their spec trees before a mesh is active route
    them through here, so ``in_shardings``/``out_shardings`` never depend on
    the ambient mesh context.
    """
    return jax.tree.map(
        lambda s: NamedSharding(mesh, s) if isinstance(s, PartitionSpec) else s,
        tree,
        is_leaf=lambda s: isinstance(s, PartitionSpec),
    )


def use_mesh(mesh: Mesh):
    """Activate ``mesh`` for the dynamic extent of a ``with`` block."""
    return jax.set_mesh(mesh)
