"""Block-distributed MTTKRP and CP-ALS over a device mesh.

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

``dist_cp_als`` / ``dist_dimtree_sweep`` wrap this into sharded ALS
drivers that match the single-device ``cp_als`` / ``als_sweep`` iterates
numerically (same update algebra; only the reduction order differs).
All sweeps route through the single engine in :mod:`repro.plan.sweep`
(``ShardedExecutor`` wraps the shard_map + psum placement below); this
module keeps the placement primitives and the back-compat entry points.
"""

from __future__ import annotations

from typing import Mapping, Sequence

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from repro.core.dimtree import contract_from_partial, partial_mttkrp_range
from repro.core.mttkrp import Method, mttkrp, mttkrp_batched
from repro.core.tensor_ops import mode_letters

from .collectives import compressed_psum, hierarchical_psum

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

# default chunk count of the overlapped psum pipeline; the canonical knob
# the planner uses is repro.plan.cost.DEFAULT_OVERLAP_CHUNKS (same value --
# kept as a plain literal here so repro.dist never imports repro.plan at
# module level).
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


def init_mttkrp_error_state(
    shape: Sequence[int], rank: int, mode_axes: ModeAxes, mesh: Mesh
) -> dict[int, Array]:
    """Zero error-feedback residuals for the compressed factor all-reduce.

    One fp32 array per mode whose MTTKRP needs a psum (mapped modes other
    than the mode itself exist).  Every participating device carries its own
    residual: the global array for mode ``n`` has one leading axis per
    reduced mesh axis (sharded over that axis) followed by the ``(I_n, C)``
    output dims sharded like the factor the MTTKRP updates.  Thread the dict
    through :func:`dist_mttkrp_compressed` calls; it is ordinary sweep state
    (checkpointable, donate-able) exactly like the residuals of
    ``make_compressed_dp_step``.
    """
    _validate(shape, mode_axes, mesh)
    errs: dict[int, Array] = {}
    for n in range(len(shape)):
        reduce_axes = _reduce_axes(mode_axes, keep_modes=(n,))
        if not reduce_axes:
            continue
        lead = tuple(mesh.shape[a] for a in reduce_axes)
        e = jnp.zeros(lead + (shape[n], rank), jnp.float32)
        spec = P(*reduce_axes, mode_axes.get(n), None)
        errs[n] = jax.device_put(e, NamedSharding(mesh, spec))
    return errs


def dist_mttkrp_compressed(
    x: Array,
    factors: Sequence[Array],
    n: int,
    mode_axes: ModeAxes,
    mesh: Mesh,
    err: Array,
    method: Method = "auto",
    tiles: Mapping[str, int] | None = None,
    *,
    batch_axes: Sequence[str] = (),
    collective: str = "flat",
    node_axis: str | None = None,
) -> tuple[Array, Array]:
    """Mode-``n`` MTTKRP completed by the int8 error-feedback collective.

    Same local kernel and placement as :func:`dist_mttkrp`, but the
    completing fp32 psum is replaced by
    :func:`repro.dist.collectives.compressed_psum` over the same mesh axes:
    each device quantizes ``partial + err`` to int8 with a private scale,
    all-gathers the payloads, and dequant-sums locally.  ``err`` is this
    mode's entry of :func:`init_mttkrp_error_state`; returns ``(result,
    new_err)``.  The carried residual keeps the accumulated quantization
    error bounded by one int8 step, which is what lets compressed CP-ALS
    track the exact fit across sweeps.

    Batched tensors thread a batched residual (global layout: reduce-axis
    leads, then the batch axis, then the output dims); the quantize /
    all-gather / dequant path is shape-agnostic, so nothing else changes.

    ``collective="hierarchical"`` splits the levels around the compressor:
    the ``node_axis`` (intra-node) reduction runs as an *exact* psum on the
    fast links first, then only the cross-node exchange is quantized --
    every device in a node compresses the identical node-sum, so the
    residual layout and carry semantics are unchanged while the int8 wire
    traffic spans ``m`` nodes instead of ``k * m`` devices.
    """
    _validate_collective(collective)
    batched = x.ndim == len(factors) + 1
    shape = x.shape[1:] if batched else x.shape
    _validate(shape, mode_axes, mesh)
    reduce_axes = _reduce_axes(mode_axes, keep_modes=(n,))
    if not reduce_axes:
        out = dist_mttkrp(
            x, factors, n, mode_axes, mesh,
            method=method, tiles=tiles, batch_axes=batch_axes,
        )
        return out, err
    if batched:
        _validate_batch(x.shape[0], batch_axes, mode_axes, mesh)
    order = len(shape)
    entry = _batch_entry(batch_axes)
    intra_first = (
        collective == "hierarchical"
        and node_axis in reduce_axes
        and len(reduce_axes) > 1
    )
    gather_axes = (
        tuple(a for a in reduce_axes if a != node_axis)
        if intra_first
        else reduce_axes
    )
    if batched:
        err_spec = P(*reduce_axes, entry, mode_axes.get(n), None)
        out_spec = P(entry, mode_axes.get(n), None)
    else:
        err_spec = P(*reduce_axes, mode_axes.get(n), None)
        out_spec = P(mode_axes.get(n), None)

    def local_fn(x_blk, err_blk, *f_blks):
        if batched:
            m = mttkrp_batched(x_blk, list(f_blks), n, method=method, tiles=tiles)
        else:
            m = mttkrp(x_blk, list(f_blks), n, method=method, tiles=tiles)
        if intra_first:
            m = jax.lax.psum(m, (node_axis,))
        total, new_e = compressed_psum(m, gather_axes, err_blk.reshape(m.shape))
        return total, new_e.reshape(err_blk.shape)

    fn = jax.shard_map(
        local_fn,
        mesh=mesh,
        in_specs=(
            _x_spec(order, mode_axes, batched=batched, batch_axes=batch_axes),
            err_spec,
            *_factor_specs(order, mode_axes, batched=batched, batch_axes=batch_axes),
        ),
        out_specs=(out_spec, err_spec),
        check_vma=False,
    )
    return fn(x, err, *factors)


# --------------------------------------------------------------------------
# Per-node contractions of a general dimension-tree schedule.  Every node of
# repro.plan.schedule is one of two shapes -- a range contraction of the raw
# tensor, or a further contraction of an already-complete partial tensor --
# and each gets the same treatment as the full MTTKRP: local kernel inside
# shard_map + the minimal psum over the axes mapped to the modes contracted
# AT THAT NODE (parents were already reduced when they were built).  The
# overlapped variants chunk along the node's leading kept mode so each
# slab's psum hides behind the next slab's contraction; the compressed
# variants run the node psum through the int8 error-feedback collective.
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
    err: Array | None = None,
    batch_axes: Sequence[str] = (),
    collective: str = "flat",
    node_axis: str | None = None,
):
    """Shared core of the four per-node contraction entry points.

    Derives the node's (contracted modes, reduce axes, specs) once, runs
    the matching local contraction -- :func:`partial_mttkrp_range` off the
    raw tensor, :func:`contract_from_partial` off a partial -- and
    completes it with this node's collective: per-slab psums along mode
    ``lo`` when exact (``err is None``), the int8 error-feedback
    ``compressed_psum`` otherwise.  ``collective="hierarchical"`` swaps
    each exact psum for the two-level decomposition around ``node_axis``
    (reduce-scatter / cross-node psum / all-gather); the compressed path
    runs the intra-node level as an exact psum first and quantizes only
    the cross-node exchange.

    Batchedness is inferred from ``src.ndim`` (one extra leading axis over
    the unbatched shape for the node's topology); the local contraction is
    then vmapped over the device's batch slab and every spec -- source,
    factors, residual, output -- gains a leading ``batch_axes`` entry.
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
        err_spec = P(*reduce_axes, entry, *keep_axes, None)
    else:
        f_specs = [P(mode_axes.get(m), None) for m in contracted]
        src_spec = (
            _x_spec(order, mode_axes)
            if from_root
            else P(*[mode_axes.get(k) for k in range(parent_lo, parent_hi)], None)
        )
        out_spec = P(*keep_axes, None)
        err_spec = P(*reduce_axes, *keep_axes, None)
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

    intra_first = (
        collective == "hierarchical"
        and node_axis in reduce_axes
        and len(reduce_axes) > 1
    )
    gather_axes = (
        tuple(a for a in reduce_axes if a != node_axis)
        if intra_first
        else reduce_axes
    )

    def local_exact(src_blk, *cf):
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

    def local_compressed(src_blk, err_blk, *cf):
        out = contract_local(src_blk, cf)
        if intra_first:
            out = jax.lax.psum(out, (node_axis,))
        total, new_e = compressed_psum(out, gather_axes, err_blk.reshape(out.shape))
        return total, new_e.reshape(err_blk.shape)

    contracted_factors = [factors[m] for m in contracted]
    if err is None:
        fn = jax.shard_map(
            local_exact,
            mesh=mesh,
            in_specs=(src_spec, *f_specs),
            out_specs=out_spec,
            check_vma=False,
        )
        return fn(src, *contracted_factors)
    fn = jax.shard_map(
        local_compressed,
        mesh=mesh,
        in_specs=(src_spec, err_spec, *f_specs),
        out_specs=(out_spec, err_spec),
        check_vma=False,
    )
    return fn(src, err, *contracted_factors)


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


def dist_contract_range_compressed(
    x: Array,
    factors: Sequence[Array],
    lo: int,
    hi: int,
    mode_axes: ModeAxes,
    mesh: Mesh,
    err: Array,
    *,
    batch_axes: Sequence[str] = (),
    collective: str = "flat",
    node_axis: str | None = None,
) -> tuple[Array, Array]:
    """:func:`dist_contract_range` with the node psum compressed.

    The completing fp32 psum of the partial tensor runs through
    :func:`repro.dist.collectives.compressed_psum` over the same axes, with
    ``err`` the node's persistent error-feedback residual (see
    :func:`init_mttkrp_error_state` for the layout convention); returns
    ``(partial, new_err)``.  Falls back to the exact path when the node
    needs no collective.
    """
    order = len(factors)
    _validate(x.shape[1:] if x.ndim == order + 1 else x.shape, mode_axes, mesh)
    contracted = [m for m in range(order) if not lo <= m < hi]
    if not _node_reduce_axes(mode_axes, contracted):
        return (
            dist_contract_range(
                x, factors, lo, hi, mode_axes, mesh, batch_axes=batch_axes
            ),
            err,
        )
    return _dist_contract(
        x, factors, lo, hi, 0, order, mode_axes, mesh,
        from_root=True, err=err, batch_axes=batch_axes,
        collective=collective, node_axis=node_axis,
    )


def dist_contract_partial_compressed(
    t: Array,
    factors: Sequence[Array],
    lo: int,
    hi: int,
    parent_lo: int,
    parent_hi: int,
    mode_axes: ModeAxes,
    mesh: Mesh,
    err: Array,
    *,
    batch_axes: Sequence[str] = (),
    collective: str = "flat",
    node_axis: str | None = None,
) -> tuple[Array, Array]:
    """:func:`dist_contract_partial` with the node psum compressed.

    Same placement, but the node's completing collective is the int8
    error-feedback gather with ``err`` as this node's persistent residual;
    returns ``(result, new_err)``.  Exact path when no collective is needed.
    """
    contracted = [m for m in range(parent_lo, parent_hi) if not lo <= m < hi]
    if not _node_reduce_axes(mode_axes, contracted):
        return (
            dist_contract_partial(
                t, factors, lo, hi, parent_lo, parent_hi, mode_axes, mesh,
                batch_axes=batch_axes,
            ),
            err,
        )
    return _dist_contract(
        t, factors, lo, hi, parent_lo, parent_hi, mode_axes, mesh,
        from_root=False, err=err, batch_axes=batch_axes,
        collective=collective, node_axis=node_axis,
    )


# --------------------------------------------------------------------------
# Sharded ALS sweeps.  Only the X-sized contractions run inside shard_map;
# the C x C Gram/Hadamard/pinv algebra and the (I_k, C) factor updates run
# at the global-array level (GSPMD inserts the small factor collectives),
# which is what keeps the distributed iterates numerically aligned with
# cp_als/als_sweep.  The algebra itself lives ONCE in repro.plan.sweep;
# these wrappers build the sharded plan + executor for the old signatures.
# --------------------------------------------------------------------------
def dist_als_sweep(
    x: Array,
    factors: list[Array],
    weights: Array,
    norm_x: Array,
    it: Array,
    mode_axes: ModeAxes,
    mesh: Mesh,
    method: Method = "auto",
    normalize: bool = True,
) -> tuple[list[Array], Array, Array]:
    """One distributed ALS sweep; mirrors :func:`repro.core.cpals.als_sweep`."""
    from repro import plan as planlib

    return planlib.legacy_sweep(
        x, factors, weights, norm_x, it,
        strategy=method, normalize=normalize, mode_axes=mode_axes, mesh=mesh,
    )


def dist_dimtree_sweep(
    x: Array,
    factors: list[Array],
    weights: Array,
    norm_x: Array,
    it: Array,
    mode_axes: ModeAxes,
    mesh: Mesh,
    *,
    normalize: bool = True,
    split: int | None = None,
) -> tuple[list[Array], Array, Array]:
    """Distributed dimension-tree sweep; same iterates as the standard sweep.

    Two distributed X-sized partial contractions per sweep (instead of N
    full MTTKRPs): ``T_L`` from the old right factors, the per-mode updates
    of the left half from ``T_L``, then ``T_R`` from the *fresh* left
    factors and the right-half updates -- the schedule of the shared engine's
    dimtree path, so it reproduces standard-ALS iterates while reading the
    distributed tensor twice per sweep.
    """
    from repro import plan as planlib

    return planlib.legacy_sweep(
        x, factors, weights, norm_x, it,
        strategy="dimtree", normalize=normalize, split=split,
        mode_axes=mode_axes, mesh=mesh,
    )


def dist_cp_als(
    x: Array,
    rank: int,
    mode_axes: ModeAxes,
    mesh: Mesh,
    n_iters: int = 50,
    tol: float = 1.0e-5,
    *,
    seed: int = 0,
    method: Method = "auto",
    normalize: bool = True,
    dimtree: bool = False,
    init_factors: list[Array] | None = None,
    executor: str = "sharded",
) -> tuple[list[Array], Array, Array]:
    """Sharded CP-ALS driver; same init/stop logic as core ``cp_als``.

    Returns ``(factors, weights, fit)`` with factors row-distributed per
    ``mode_axes``.  ``dimtree=True`` swaps in the distributed
    dimension-tree sweep (identical iterates, 2 tensor reads per sweep).

    ``executor`` picks the communication strategy of the factor all-reduce:
    ``"sharded"`` (the frozen default -- plain psum), ``"overlapping"``
    (chunked psum hidden behind the local GEMMs; exact),
    ``"compressed"`` (int8 error-feedback all-gather; approximate, with the
    per-mode residuals threaded through the sweep), or ``"auto"`` to let
    :func:`repro.plan.select_executor` cost-argmin among them.

    Back-compat wrapper over the single :func:`repro.plan.cp_als` driver.
    """
    from repro import plan as planlib

    problem = planlib.Problem.from_tensor(x, rank, mode_axes=mode_axes, mesh=mesh)
    # the executor kind propagates verbatim (any executor now pairs with any
    # schedule: overlapping chunks and compressed compresses the dimtree
    # partials per node); the tree shape stays pinned to the wrapper's
    # historical behavior -- flat per-mode, or the binary split for dimtree
    sweep_plan = planlib.plan_sweep(
        problem,
        strategy="dimtree" if dimtree else method,
        normalize=normalize,
        executor=executor,
        schedule=None if dimtree else "flat",
    )
    st = planlib.cp_als(
        x,
        sweep_plan,
        executor=planlib.make_executor(sweep_plan.executor, mesh, mode_axes),
        n_iters=n_iters,
        tol=tol,
        seed=seed,
        init_factors=init_factors,
    )
    return st.factors, st.weights, st.fit
