"""One home for mesh and shard_map construction.

Everything that builds a mesh or a shard_map goes through this module, so
the mesh axis types and the shard_map checking mode are chosen in one place
(written against JAX 0.9).
"""
from __future__ import annotations

import jax


def make_mesh(axis_shapes, axis_names, *, devices=None):
    """``jax.make_mesh`` with Auto axis types."""
    kw = {"devices": devices} if devices is not None else {}
    return jax.make_mesh(axis_shapes, axis_names,
                         axis_types=(jax.sharding.AxisType.Auto,)
                         * len(axis_names), **kw)


def set_mesh(mesh):
    """``jax.set_mesh`` context: installs ``mesh`` as the ambient mesh."""
    return jax.set_mesh(mesh)


def shard_map(f, *, mesh, in_specs, out_specs, check_vma=False,
              axis_names=None):
    """``jax.shard_map``; ``axis_names``: mesh axes the body is manual over
    (all if None)."""
    kw = {"check_vma": check_vma}
    if axis_names is not None:
        kw["axis_names"] = frozenset(axis_names)
    return jax.shard_map(f, mesh=mesh, in_specs=in_specs,
                         out_specs=out_specs, **kw)
