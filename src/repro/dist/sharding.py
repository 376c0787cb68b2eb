"""Mesh-axis helpers shared by the engines and the block placer.

``axes_tuple`` normalises an axis selection, ``mesh_extent`` is the
number of shards over it, and ``pvary`` marks values as varying over it
inside ``shard_map``.
"""

from __future__ import annotations

import jax
from jax import lax
from jax.sharding import Mesh


def axes_tuple(axes) -> tuple:
    """Normalise a mesh-axis selection (None | str | sequence) to a tuple."""
    if axes is None:
        return ()
    if isinstance(axes, (list, tuple)):
        return tuple(axes)
    return (axes,)


def pvary(x, axes):
    """Mark every leaf of ``x`` as varying over ``axes`` inside
    ``shard_map`` (identity for no axes)."""
    if not axes:
        return x
    return jax.tree.map(lambda v: lax.pcast(v, tuple(axes), to="varying"), x)


def mesh_extent(mesh: Mesh | None, axes) -> int:
    """Product of the mesh extents of ``axes`` (1 for no mesh)."""
    if mesh is None:
        return 1
    ext = 1
    for a in axes_tuple(axes):
        ext *= mesh.shape[a]
    return ext

