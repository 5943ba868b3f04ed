"""Two-level collectives for the sharded MTTKRP's node reductions.

:func:`hierarchical_psum` splits one ``psum`` into a reduce-scatter within
the fast intra-node level, a psum of the shard across nodes and an
all-gather back, so only ``1/k`` of each block crosses the slow level.
:func:`reduce_scatter` and :func:`all_gather` are its two legs.  All three
are exact: they change the reduction order, never what is summed.
"""

from __future__ import annotations

import jax
from jax.sharding import Mesh

Array = jax.Array


def reduce_scatter(x: Array, axis_name, *, scatter_axis: int = 0) -> Array:
    """Tiled reduce-scatter of ``x`` over ``axis_name`` (inside shard_map).

    Each of the ``k`` participants ends up with the fully reduced
    ``1/k``-slice of ``x`` along ``scatter_axis`` -- the first half of a ring
    all-reduce, moving ``B (k-1)/k`` bytes per device.  This is the
    intra-node leg of :func:`hierarchical_psum`; ``x.shape[scatter_axis]``
    must be divisible by the axis size.
    """
    return jax.lax.psum_scatter(
        x, axis_name, scatter_dimension=scatter_axis, tiled=True
    )


def all_gather(x: Array, axis_name, *, gather_axis: int = 0) -> Array:
    """Tiled all-gather of ``x`` over ``axis_name`` (inside shard_map).

    Concatenates the participants' blocks along ``gather_axis`` on every
    device -- the second half of a ring all-reduce (``B (k-1)/k`` bytes per
    device), undoing :func:`reduce_scatter`'s split.
    """
    return jax.lax.all_gather(x, axis_name, axis=gather_axis, tiled=True)


def hierarchical_psum(
    x: Array, axes, mesh: Mesh, node_axis: str | None = None,
    *, scatter_axis: int = 0,
) -> Array:
    """Two-level ``psum`` over ``axes``: intra-node traffic on the fast links,
    only a ``1/k`` shard crossing the slow node boundary.

    Must be called inside ``shard_map``.  ``node_axis`` names the mesh axis
    spanning the devices *within* one node (the fast-ICI level); the
    remaining ``axes`` are taken to cross nodes (the slow-DCN level).  The
    decomposition is :func:`reduce_scatter` within ``node_axis`` along
    ``scatter_axis``, a plain ``psum`` of the scattered shard across the
    node-crossing axes, then :func:`all_gather` back within ``node_axis`` --
    so each device moves ``2 B (k-1)/k`` intra-node bytes but only
    ``2 (B/k)(m-1)/m`` inter-node bytes, a factor-``k`` cut of the volume on
    the slow level versus the flat ring (which pays the full ``2 B`` there).

    The result equals ``jax.lax.psum(x, axes)`` up to floating-point
    reduction order.  Falls back to the flat psum whenever the decomposition
    cannot apply: ``node_axis`` is ``None`` or not among ``axes``, it is the
    *only* reduced axis (no slow level to protect), its size is 1, or
    ``x.shape[scatter_axis]`` is not divisible by it.
    """
    axes = (axes,) if isinstance(axes, str) else tuple(axes)
    if node_axis is None or node_axis not in axes:
        return jax.lax.psum(x, axes)
    inter = tuple(a for a in axes if a != node_axis)
    k = int(mesh.shape[node_axis])
    if not inter or k <= 1 or int(x.shape[scatter_axis]) % k:
        return jax.lax.psum(x, axes)
    shard = reduce_scatter(x, node_axis, scatter_axis=scatter_axis)
    shard = jax.lax.psum(shard, inter)
    return all_gather(shard, node_axis, gather_axis=scatter_axis)
