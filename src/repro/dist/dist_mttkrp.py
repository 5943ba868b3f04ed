"""Block-distributed MTTKRP over a device mesh.

The paper's shared-memory parallelization assigns contiguous row blocks of
the (never-materialized) matricization to threads; the distributed-memory
port assigns contiguous *index blocks of the tensor modes* to devices.  A
``mode_axes`` mapping ``{mode: mesh_axis}`` places the dense tensor on an
N-D grid without reordering a single entry -- the defining constraint of
the paper, kept under sharding: every device holds a natural row-major
subtensor (a block of each mapped mode, all of each unmapped mode), and
each factor ``U_k`` is row-distributed over the axis of its mode (or
replicated when mode ``k`` is unmapped).

Per-mode-n MTTKRP then factors exactly as in Ballard/Knight/Rouse's
communication lower-bound analysis:

  * each device runs the *local* shared-memory kernel
    (:func:`repro.core.mttkrp.mttkrp`, 1-step or 2-step) on its block with
    its local factor rows -- a partial sum over the mapped modes != n;
  * one ``psum`` over the mesh axes mapped to modes != n completes the
    contraction (the minimal all-reduce the mode->axis mapping requires);
  * no collective touches the axis mapped to mode ``n`` itself: the output
    rows stay distributed over it, exactly like the factor they update.

Only the X-sized contractions run inside ``shard_map``; the C x C
Gram/Hadamard/solve algebra and the (I_k, C) factor updates run at the
global-array level in :mod:`repro.plan.sweep` (GSPMD inserts the small
factor collectives), whose ``ShardedExecutor`` wraps the placement
primitives below.  A sharded solve therefore matches the single-device
iterates numerically: same update algebra, only the reduction order
differs.  This module imports nothing from :mod:`repro.plan`.
"""

from __future__ import annotations

from typing import Mapping, Sequence

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from repro.core.dimtree import contract_from_partial, partial_mttkrp_range
from repro.core.mttkrp import Method, mttkrp, mttkrp_batched
from repro.core.tensor_ops import mode_letters

from .collectives import hierarchical_psum

Array = jax.Array
ModeAxes = Mapping[int, str]

# Collective strategies the node psum can complete with: "flat" is the plain
# single-level psum; "hierarchical" is the two-level decomposition of
# repro.dist.collectives.hierarchical_psum (reduce-scatter within the node
# axis, cross-node psum of the shard, all-gather back).
COLLECTIVES = ("flat", "hierarchical")


def _validate_collective(collective: str) -> None:
    if collective not in COLLECTIVES:
        raise ValueError(
            f"unknown collective {collective!r} (choose from {COLLECTIVES})"
        )


def _node_psum(
    m: Array,
    reduce_axes: tuple[str, ...],
    mesh: Mesh,
    collective: str,
    node_axis: str | None,
    *,
    scatter_axis: int = 0,
) -> Array:
    """Complete one node contraction's reduction over ``reduce_axes``.

    ``collective="hierarchical"`` routes through
    :func:`repro.dist.collectives.hierarchical_psum` with ``node_axis`` as
    the intra-node level (falling back to the flat psum whenever the
    decomposition cannot apply); ``"flat"`` is the classic single psum.
    """
    _validate_collective(collective)
    if collective == "hierarchical":
        return hierarchical_psum(
            m, reduce_axes, mesh, node_axis=node_axis, scatter_axis=scatter_axis
        )
    return jax.lax.psum(m, reduce_axes)

# Default chunk count of the overlapped psum pipeline (the planner's cost
# model prices the same default): the serialization fraction is
# ~1/n_chunks, so 4 already hides 75% of the hidable term while keeping
# per-chunk GEMMs large enough to stay compute-efficient.
DEFAULT_OVERLAP_CHUNKS = 4


def _validate(shape: Sequence[int], mode_axes: ModeAxes, mesh: Mesh) -> None:
    seen: dict[str, int] = {}
    for mode, axis in mode_axes.items():
        if not 0 <= mode < len(shape):
            raise ValueError(f"mode {mode} out of range for order-{len(shape)} tensor")
        if axis not in mesh.shape:
            raise ValueError(f"mesh has no axis {axis!r} (axes: {mesh.axis_names})")
        if axis in seen:
            raise ValueError(f"mesh axis {axis!r} mapped to modes {seen[axis]} and {mode}")
        seen[axis] = mode
        if shape[mode] % mesh.shape[axis]:
            raise ValueError(
                f"mode {mode} dim {shape[mode]} not divisible by "
                f"axis {axis!r} size {mesh.shape[axis]}"
            )


def _batch_entry(batch_axes: Sequence[str]):
    """PartitionSpec entry of a leading batch axis (``None`` = replicated)."""
    axes = tuple(batch_axes)
    return axes if axes else None


def _validate_batch(
    batch: int, batch_axes: Sequence[str], mode_axes: ModeAxes, mesh: Mesh
) -> None:
    used = set(mode_axes.values())
    seen: set[str] = set()
    shards = 1
    for axis in batch_axes:
        if axis not in mesh.shape:
            raise ValueError(f"mesh has no axis {axis!r} (axes: {mesh.axis_names})")
        if axis in used:
            raise ValueError(
                f"mesh axis {axis!r} cannot shard both a mode and the batch"
            )
        if axis in seen:
            raise ValueError(f"duplicate batch axis {axis!r}")
        seen.add(axis)
        shards *= mesh.shape[axis]
    if batch % shards:
        raise ValueError(
            f"batch {batch} not divisible by batch-axis product {shards}"
        )


def _x_spec(
    ndim: int,
    mode_axes: ModeAxes,
    *,
    batched: bool = False,
    batch_axes: Sequence[str] = (),
) -> P:
    dims = [mode_axes.get(k) for k in range(ndim)]
    if batched:
        return P(_batch_entry(batch_axes), *dims)
    return P(*dims)


def _factor_specs(
    ndim: int,
    mode_axes: ModeAxes,
    *,
    batched: bool = False,
    batch_axes: Sequence[str] = (),
) -> list[P]:
    if batched:
        entry = _batch_entry(batch_axes)
        return [P(entry, mode_axes.get(k), None) for k in range(ndim)]
    return [P(mode_axes.get(k), None) for k in range(ndim)]


def _reduce_axes(mode_axes: ModeAxes, keep_modes: Sequence[int]) -> tuple[str, ...]:
    """Mesh axes whose modes are contracted away (i.e. not in ``keep_modes``)."""
    keep = set(keep_modes)
    return tuple(mode_axes[m] for m in sorted(mode_axes) if m not in keep)


def shard_problem(
    x: Array,
    factors: Sequence[Array],
    mode_axes: ModeAxes,
    mesh: Mesh,
    *,
    batch_axes: Sequence[str] = (),
) -> tuple[Array, list[Array]]:
    """Place tensor + factors on ``mesh`` per ``mode_axes``; no reordering.

    The tensor is block-distributed: device ``(i, j, ...)`` holds the
    row-major subtensor of its index block along each mapped mode (a plain
    ``device_put`` with a NamedSharding -- entries within each block keep
    their natural layout, so the local kernels see exactly the layout the
    paper's algorithms assume).  Factor ``U_k`` is row-sharded over
    ``mode_axes[k]`` when mapped, replicated otherwise.

    A *batched* problem (``x.ndim == len(factors) + 1``: one leading batch
    axis on the tensor and on every factor) is block-distributed along the
    batch over ``batch_axes`` -- each device holds whole problems, so no
    contraction ever needs a collective across the batch.
    """
    batched = x.ndim == len(factors) + 1
    shape = x.shape[1:] if batched else x.shape
    _validate(shape, mode_axes, mesh)
    if batched:
        _validate_batch(x.shape[0], batch_axes, mode_axes, mesh)
    order = len(shape)
    xs = jax.device_put(
        x,
        NamedSharding(
            mesh, _x_spec(order, mode_axes, batched=batched, batch_axes=batch_axes)
        ),
    )
    fs = [
        jax.device_put(u, NamedSharding(mesh, spec))
        for u, spec in zip(
            factors,
            _factor_specs(order, mode_axes, batched=batched, batch_axes=batch_axes),
        )
    ]
    return xs, fs


def dist_mttkrp(
    x: Array,
    factors: Sequence[Array],
    n: int,
    mode_axes: ModeAxes,
    mesh: Mesh,
    method: Method = "auto",
    tiles: Mapping[str, int] | None = None,
    *,
    batch_axes: Sequence[str] = (),
    collective: str = "flat",
    node_axis: str | None = None,
) -> Array:
    """Mode-``n`` MTTKRP of a block-distributed tensor.

    Local shared-memory kernel inside ``shard_map`` + the minimal ``psum``:
    only over axes mapped to contracted modes (``tiles`` threads the tuned
    Pallas tiling into the local kernel for kernel-backed methods).  The
    result is distributed
    over ``mode_axes[n]`` (replicated if mode ``n`` is unmapped) -- the
    sharding of the factor it updates in ALS.

    ``collective="hierarchical"`` completes the reduction with
    :func:`repro.dist.collectives.hierarchical_psum` instead of the flat
    psum: reduce-scatter within ``node_axis`` (the intra-node mesh axis),
    cross-node psum of the ``1/k`` shard, all-gather back -- same value up
    to summation order, a factor-``k`` less volume on the slow level.

    When ``x`` carries a leading batch axis (``x.ndim == len(factors) + 1``),
    the batch is sharded over ``batch_axes`` and each device runs the
    batched local kernel on its slab of whole problems; the psum pattern is
    untouched -- batch axes are never reduced (problems are independent),
    which is exactly why batch-parallel placement costs zero reduce traffic.
    """
    _validate_collective(collective)
    batched = x.ndim == len(factors) + 1
    shape = x.shape[1:] if batched else x.shape
    _validate(shape, mode_axes, mesh)
    if batched:
        _validate_batch(x.shape[0], batch_axes, mode_axes, mesh)
    reduce_axes = _reduce_axes(mode_axes, keep_modes=(n,))
    order = len(shape)
    lead = 1 if batched else 0
    entry = _batch_entry(batch_axes)

    def local_fn(x_blk, *f_blks):
        if batched:
            m = mttkrp_batched(x_blk, list(f_blks), n, method=method, tiles=tiles)
        else:
            m = mttkrp(x_blk, list(f_blks), n, method=method, tiles=tiles)
        if reduce_axes:
            m = _node_psum(
                m, reduce_axes, mesh, collective, node_axis, scatter_axis=lead
            )
        return m

    fn = jax.shard_map(
        local_fn,
        mesh=mesh,
        in_specs=(
            _x_spec(order, mode_axes, batched=batched, batch_axes=batch_axes),
            *_factor_specs(order, mode_axes, batched=batched, batch_axes=batch_axes),
        ),
        out_specs=(
            P(entry, mode_axes.get(n), None) if batched else P(mode_axes.get(n), None)
        ),
        check_vma=False,
    )
    return fn(x, *factors)


def dist_pp_pairs(
    x: Array,
    factors: Sequence[Array],
    mode_axes: ModeAxes,
    mesh: Mesh,
    *,
    batch_axes: Sequence[str] = (),
) -> dict[tuple[int, int], Array]:
    """All pairwise-perturbation intermediates of a block-distributed tensor.

    For every mode pair ``n < m`` the intermediate
    ``M_nm[c, i_n, i_m] = sum X * prod_{k not in {n,m}} U_k[i_k, c]``
    gets exactly the full MTTKRP's treatment with two kept modes instead of
    one: local einsum per device block inside ``shard_map``, then one psum
    over the axes mapped to the contracted modes only -- the kept modes'
    axes carry the output rows/columns, so no collective ever touches them
    (the sharding the PP corrections later consume matches the factors they
    perturb).  A leading batch axis (``x.ndim == len(factors) + 1``) is
    sharded over ``batch_axes`` and never reduced.  Returns ``{(n, m):
    M_nm}`` in the rank-major layout of :class:`repro.plan.schedule.PPPair`
    -- global shapes ``(C, I_n, I_m)`` (batch-led when batched).
    """
    batched = x.ndim == len(factors) + 1
    shape = x.shape[1:] if batched else x.shape
    _validate(shape, mode_axes, mesh)
    if batched:
        _validate_batch(x.shape[0], batch_axes, mode_axes, mesh)
    order = len(shape)
    entry = _batch_entry(batch_axes)
    letters = mode_letters(order)
    out: dict[tuple[int, int], Array] = {}
    for n in range(order):
        for m in range(n + 1, order):
            others = [k for k in range(order) if k not in (n, m)]
            spec = (
                ",".join(
                    ["..." + letters] + ["..." + letters[k] + "c" for k in others]
                )
                + "->..." + letters[n] + letters[m] + "c"
            )
            reduce_axes = _reduce_axes(mode_axes, keep_modes=(n, m))

            def local_fn(x_blk, *f_blks, spec=spec, reduce_axes=reduce_axes):
                # rank-last einsum (the GEMM-friendly orientation), then
                # rank to the front for the PPPair storage layout
                p = jnp.moveaxis(jnp.einsum(spec, x_blk, *f_blks), -1, -3)
                if reduce_axes:
                    p = jax.lax.psum(p, reduce_axes)
                return p

            if batched:
                f_specs = [P(entry, mode_axes.get(k), None) for k in others]
                out_spec = P(entry, None, mode_axes.get(n), mode_axes.get(m))
            else:
                f_specs = [P(mode_axes.get(k), None) for k in others]
                out_spec = P(None, mode_axes.get(n), mode_axes.get(m))
            fn = jax.shard_map(
                local_fn,
                mesh=mesh,
                in_specs=(
                    _x_spec(order, mode_axes, batched=batched, batch_axes=batch_axes),
                    *f_specs,
                ),
                out_specs=out_spec,
                check_vma=False,
            )
            out[(n, m)] = fn(x, *[factors[k] for k in others])
    return out


def _chunk_bounds(extent: int, n_chunks: int) -> list[int]:
    """Split ``[0, extent)`` into ``<= n_chunks`` near-equal static slices."""
    k = max(1, min(int(n_chunks), int(extent)))
    sizes = [extent // k + (1 if i < extent % k else 0) for i in range(k)]
    bounds = [0]
    for s in sizes:
        bounds.append(bounds[-1] + s)
    return bounds


def dist_mttkrp_overlapped(
    x: Array,
    factors: Sequence[Array],
    n: int,
    mode_axes: ModeAxes,
    mesh: Mesh,
    method: Method = "auto",
    n_chunks: int = DEFAULT_OVERLAP_CHUNKS,
    tiles: Mapping[str, int] | None = None,
    *,
    batch_axes: Sequence[str] = (),
    collective: str = "flat",
    node_axis: str | None = None,
) -> Array:
    """Mode-``n`` MTTKRP with the completing psum hidden behind the GEMMs.

    Identical placement and per-element reduction to :func:`dist_mttkrp`
    (equal up to fp32 rounding: XLA may block a row slab's local GEMM
    differently from the whole block's), but the local block is split into ``n_chunks``
    row slabs along mode ``n`` and each slab's psum is issued as soon as
    its local MTTKRP finishes -- a double-buffered pipeline: the collective
    of chunk ``k`` has no data dependency on the GEMM of chunk ``k+1``, so
    XLA's latency-hiding scheduler runs them concurrently and only the
    first GEMM and the last psum stay exposed (the ``1/n_chunks``
    serialization fraction of the cost model).  Chunk psums touch disjoint
    output rows, so concatenating them equals the unchunked psum exactly.

    Falls back to :func:`dist_mttkrp` when the mapping requires no
    collective (nothing to hide) or ``n_chunks <= 1``.  Batched tensors
    (leading batch axis, sharded over ``batch_axes``) chunk along mode
    ``n`` of every problem in the local slab -- the slab axis shifts by one
    but the pipeline structure is identical.

    ``collective="hierarchical"`` completes each slab's reduction with the
    two-level psum (slabs whose row count the ``node_axis`` size does not
    divide fall back to the flat psum individually -- still exact).
    """
    _validate_collective(collective)
    batched = x.ndim == len(factors) + 1
    shape = x.shape[1:] if batched else x.shape
    _validate(shape, mode_axes, mesh)
    reduce_axes = _reduce_axes(mode_axes, keep_modes=(n,))
    local_in = shape[n] // (mesh.shape[mode_axes[n]] if n in mode_axes else 1)
    if not reduce_axes or n_chunks <= 1 or local_in <= 1:
        return dist_mttkrp(
            x, factors, n, mode_axes, mesh,
            method=method, tiles=tiles, batch_axes=batch_axes,
            collective=collective, node_axis=node_axis,
        )
    if batched:
        _validate_batch(x.shape[0], batch_axes, mode_axes, mesh)
    bounds = _chunk_bounds(local_in, n_chunks)
    order = len(shape)
    lead = 1 if batched else 0
    entry = _batch_entry(batch_axes)

    def local_one(x_slab, f_blks):
        if batched:
            return mttkrp_batched(x_slab, list(f_blks), n, method=method, tiles=tiles)
        return mttkrp(x_slab, list(f_blks), n, method=method, tiles=tiles)

    def local_fn(x_blk, *f_blks):
        # issue order GEMM_0, (GEMM_1, psum_0), (GEMM_2, psum_1), ...: each
        # psum depends only on its own slab's GEMM, never on the next one.
        partials = [
            local_one(jax.lax.slice_in_dim(x_blk, i0, i1, axis=n + lead), f_blks)
            for i0, i1 in zip(bounds[:-1], bounds[1:])
        ]
        reduced = [
            _node_psum(
                p, reduce_axes, mesh, collective, node_axis, scatter_axis=lead
            )
            for p in partials
        ]
        return jnp.concatenate(reduced, axis=lead)

    fn = jax.shard_map(
        local_fn,
        mesh=mesh,
        in_specs=(
            _x_spec(order, mode_axes, batched=batched, batch_axes=batch_axes),
            *_factor_specs(order, mode_axes, batched=batched, batch_axes=batch_axes),
        ),
        out_specs=(
            P(entry, mode_axes.get(n), None) if batched else P(mode_axes.get(n), None)
        ),
        check_vma=False,
    )
    return fn(x, *factors)


# --------------------------------------------------------------------------
# Per-node contractions of a general dimension-tree schedule.  Every node of
# repro.plan.schedule is one of two shapes -- a range contraction of the raw
# tensor, or a further contraction of an already-complete partial tensor --
# and each gets the same treatment as the full MTTKRP: local kernel inside
# shard_map + the minimal psum over the axes mapped to the modes contracted
# AT THAT NODE (parents were already reduced when they were built).  The
# overlapped variants chunk along the node's leading kept mode so each
# slab's psum hides behind the next slab's contraction.
# --------------------------------------------------------------------------
def _node_reduce_axes(mode_axes: ModeAxes, contracted: Sequence[int]) -> tuple[str, ...]:
    """Mesh axes of the mapped modes contracted at one node, in mode order."""
    want = set(contracted)
    return tuple(mode_axes[m] for m in sorted(mode_axes) if m in want)


def _dist_contract(
    src: Array,
    factors: Sequence[Array],
    lo: int,
    hi: int,
    parent_lo: int,
    parent_hi: int,
    mode_axes: ModeAxes,
    mesh: Mesh,
    *,
    from_root: bool,
    n_chunks: int = 1,
    batch_axes: Sequence[str] = (),
    collective: str = "flat",
    node_axis: str | None = None,
):
    """Shared core of the two per-node contraction entry points.

    Derives the node's (contracted modes, reduce axes, specs) once, runs
    the matching local contraction -- :func:`partial_mttkrp_range` off the
    raw tensor, :func:`contract_from_partial` off a partial -- and
    completes it with this node's collective: per-slab psums along mode
    ``lo``.  ``collective="hierarchical"`` swaps each psum for the
    two-level decomposition around ``node_axis`` (reduce-scatter /
    cross-node psum / all-gather).

    Batchedness is inferred from ``src.ndim`` (one extra leading axis over
    the unbatched shape for the node's topology); the local contraction is
    then vmapped over the device's batch slab and every spec -- source,
    factors, output -- gains a leading ``batch_axes`` entry.
    Batch axes never appear in ``reduce_axes``: problems are independent.
    """
    _validate_collective(collective)
    order = parent_hi - parent_lo
    expected = order if from_root else order + 1
    batched = src.ndim == expected + 1
    lead = 1 if batched else 0
    if batched:
        _validate_batch(src.shape[0], batch_axes, mode_axes, mesh)
    entry = _batch_entry(batch_axes)
    contracted = [m for m in range(parent_lo, parent_hi) if not lo <= m < hi]
    reduce_axes = _node_reduce_axes(mode_axes, contracted)
    keep_axes = [mode_axes.get(k) for k in range(lo, hi)]
    if batched:
        f_specs = [P(entry, mode_axes.get(m), None) for m in contracted]
        src_spec = (
            _x_spec(order, mode_axes, batched=True, batch_axes=batch_axes)
            if from_root
            else P(entry, *[mode_axes.get(k) for k in range(parent_lo, parent_hi)], None)
        )
        out_spec = P(entry, *keep_axes, None)
    else:
        f_specs = [P(mode_axes.get(m), None) for m in contracted]
        src_spec = (
            _x_spec(order, mode_axes)
            if from_root
            else P(*[mode_axes.get(k) for k in range(parent_lo, parent_hi)], None)
        )
        out_spec = P(*keep_axes, None)
    lo_local = src.shape[lead + lo - parent_lo] // (
        mesh.shape[mode_axes[lo]] if lo in mode_axes else 1
    )
    chunks = max(1, min(int(n_chunks), lo_local)) if reduce_axes else 1
    bounds = _chunk_bounds(lo_local, chunks)

    def contract_local(src_blk, cf):
        if from_root:
            def one(t, *fs):
                fl = list(fs[:lo]) + [None] * (hi - lo) + list(fs[lo:])
                return partial_mttkrp_range(t, fl, lo, hi)
        else:
            def one(t, *fs):
                return contract_from_partial(
                    t, dict(zip(contracted, fs)), lo, hi, parent_lo
                )
        if batched:
            return jax.vmap(one)(src_blk, *cf)
        return one(src_blk, *cf)

    def local_fn(src_blk, *cf):
        out = contract_local(src_blk, cf)
        if not reduce_axes:
            return out
        # slab axis = mode lo of the node output (shifted past the batch)
        slabs = [
            _node_psum(
                jax.lax.slice_in_dim(out, i0, i1, axis=lead),
                reduce_axes, mesh, collective, node_axis, scatter_axis=lead,
            )
            for i0, i1 in zip(bounds[:-1], bounds[1:])
        ]
        return slabs[0] if len(slabs) == 1 else jnp.concatenate(slabs, axis=lead)

    fn = jax.shard_map(
        local_fn,
        mesh=mesh,
        in_specs=(src_spec, *f_specs),
        out_specs=out_spec,
        check_vma=False,
    )
    return fn(src, *[factors[m] for m in contracted])


def dist_contract_range(
    x: Array,
    factors: Sequence[Array],
    lo: int,
    hi: int,
    mode_axes: ModeAxes,
    mesh: Mesh,
    *,
    n_chunks: int = 1,
    batch_axes: Sequence[str] = (),
    collective: str = "flat",
    node_axis: str | None = None,
) -> Array:
    """Distributed range contraction: every mode outside ``[lo, hi)`` of the
    block-distributed tensor is contracted with its (row-sharded) factor.

    Local :func:`repro.core.dimtree.partial_mttkrp_range` per block + one
    psum over the axes mapped to the contracted modes; the partial tensor
    stays distributed over the axes of its surviving modes.  ``n_chunks > 1``
    splits the node's collective into per-slab psums along mode ``lo`` (the
    leading kept mode): slab ``k``'s wire time has no data dependency on
    anything but its own rows, so XLA's latency-hiding scheduler runs the
    later slabs under whatever compute follows.  Slab psums are elementwise
    reductions over disjoint rows of the same local result, so the output is
    *bitwise identical* to the unchunked path by construction.
    """
    order = len(factors)
    _validate(x.shape[1:] if x.ndim == order + 1 else x.shape, mode_axes, mesh)
    return _dist_contract(
        x, factors, lo, hi, 0, order, mode_axes, mesh,
        from_root=True, n_chunks=n_chunks, batch_axes=batch_axes,
        collective=collective, node_axis=node_axis,
    )


def dist_contract_partial(
    t: Array,
    factors: Sequence[Array],
    lo: int,
    hi: int,
    parent_lo: int,
    parent_hi: int,
    mode_axes: ModeAxes,
    mesh: Mesh,
    *,
    n_chunks: int = 1,
    batch_axes: Sequence[str] = (),
    collective: str = "flat",
    node_axis: str | None = None,
) -> Array:
    """Distributed partial-to-partial contraction of one schedule node.

    ``t`` is an already-complete partial tensor carrying modes
    ``[parent_lo, parent_hi)`` plus the rank axis, distributed over those
    modes' axes; the modes outside ``[lo, hi)`` are contracted with their
    row-sharded factors (a multi-TTV, the rank axis shared Hadamard-style).
    The local contraction sums only each device's index block of the
    contracted modes, so one psum over those modes' axes completes it --
    the per-node analogue of the full MTTKRP's minimal collective.  With a
    single kept mode this IS the leaf update off a partial.  ``n_chunks``
    splits the psum into per-slab collectives along mode ``lo`` exactly as
    in :func:`dist_contract_range` -- bitwise identical by construction.
    """
    return _dist_contract(
        t, factors, lo, hi, parent_lo, parent_hi, mode_axes, mesh,
        from_root=False, n_chunks=n_chunks, batch_axes=batch_axes,
        collective=collective, node_axis=node_axis,
    )
