"""Executors: where a planned contraction actually runs.

The sweep engine (:mod:`repro.plan.sweep`) is executor-agnostic: it walks
the plan's contraction schedule asking for "this node's contraction" and
never touches placement.  Three executors implement the protocol, all
exact:

* :class:`LocalExecutor` -- the paper's shared-memory kernels, one device.
* :class:`ShardedExecutor` -- the ``shard_map`` + minimal-``psum`` placement
  of :mod:`repro.dist.dist_mttkrp` (local kernel per device block, one psum
  per node over the axes mapped to the modes contracted there).
* :class:`OverlappingExecutor` -- same numerics, but every node's local
  contraction -- full MTTKRPs *and* the partial contractions of a
  dimension-tree schedule -- is chunked so chunk ``k``'s psum overlaps
  chunk ``k+1``'s GEMM (communication hiding; exact).

``plan_sweep(executor="auto")`` picks among them by predicted cost; use
:func:`make_executor` to turn the chosen ``SweepPlan.executor`` kind into
an instance bound to a concrete mesh.
"""

from __future__ import annotations

from typing import Mapping, Protocol, Sequence, runtime_checkable

import jax
import jax.numpy as jnp

from repro.core.dimtree import (
    contract_from_partial,
    partial_from_view,
    partial_mttkrp_range,
)
from repro.core.mttkrp import mttkrp, mttkrp_batched
from repro.core.tensor_ops import mode_letters
from repro.dist.dist_mttkrp import (
    dist_contract_partial,
    dist_contract_range,
    dist_mttkrp,
    dist_mttkrp_overlapped,
    dist_pp_pairs,
    shard_problem,
)

from .cost import DEFAULT_OVERLAP_CHUNKS, EXECUTORS
from .schedule import ContractionNode

Array = jax.Array


def _node_is_batched(node: ContractionNode, src: Array) -> bool:
    """True when ``src`` carries a leading batch axis over the node's shape.

    The unbatched source of a node has a known rank from the topology alone:
    the raw tensor's order for root contractions, the parent's kept modes
    plus the rank axis for partial-to-partial ones.  One extra axis = batch.
    """
    expected = (node.parent_hi - node.parent_lo) + (0 if node.from_root else 1)
    return src.ndim == expected + 1


@runtime_checkable
class Executor(Protocol):
    """The contractions an ALS sweep needs, placement included.

    The schedule walker drives everything through :meth:`contract` -- one
    entry point per :class:`repro.plan.schedule.ContractionNode`, whether
    the node is a full mode MTTKRP, a root-level partial GEMM, or a
    partial-to-partial multi-TTV.  Executors that can contract a root
    partial from the tensor's matrix view implement ``contract_view(node,
    view, factors)``; :func:`repro.plan.sweep.cp_als` then builds each view
    once a solve and hands it to the node (:class:`LocalExecutor` only; the
    others read the tensor as before).
    """

    def prepare(self, problem, x: Array, factors: Sequence[Array]):
        """Place tensor + factors for this executor (identity when local)."""
        ...

    def contract(
        self, node: ContractionNode, src: Array, factors: Sequence[Array],
        algorithm: str = "auto", tiles: Mapping[str, int] | None = None,
        collective: str = "flat",
    ) -> Array:
        """Run one schedule node's contraction of ``src`` (the parent's
        output; the raw tensor for children of the root).  ``tiles`` is the
        plan's tuned Pallas tile config for kernel-backed algorithms
        (``NodePlan.tiles``); ``None`` keeps the kernel defaults.
        ``collective`` picks the psum decomposition for this node's
        reduction (``NodePlan.collective``): ``"flat"`` is one ring over
        all participating devices, ``"hierarchical"`` reduce-scatters
        within the node axis first so only shards cross the slow level
        (ignored by executors without collectives)."""
        ...


def _over_batch(fn, batched: bool, src: Array, factors: Sequence[Array]) -> Array:
    """``fn(src, factors)``; with ``batched``, vmapped over the leading
    batch axis of ``src`` and of every factor."""
    if batched:
        return jax.vmap(lambda t, *fs: fn(t, list(fs)))(src, *factors)
    return fn(src, list(factors))


class LocalExecutor:
    """Single-device execution of the paper's shared-memory kernels."""

    def prepare(self, problem, x: Array, factors: Sequence[Array]):
        """No placement needed on one device: returns inputs unchanged."""
        return x, list(factors)

    def contract(
        self, node: ContractionNode, src: Array, factors: Sequence[Array],
        algorithm: str = "auto", tiles: Mapping[str, int] | None = None,
        collective: str = "flat",
    ) -> Array:
        """One schedule node locally: planned MTTKRP for leaves off the
        root (tuned Pallas tiles threaded through for the fused kernel),
        range GEMM for internal nodes off the root, multi-TTV einsum
        for anything contracted from a partial.  A leading batch axis on
        ``src`` (and every factor) dispatches the batched kernel for
        leaves and a vmap of the same contraction otherwise.
        ``collective`` is accepted for protocol compatibility and
        ignored: one device runs no psum to decompose."""
        batched = _node_is_batched(node, src)
        if node.from_root:
            if node.is_leaf:
                if batched:
                    return mttkrp_batched(
                        src, list(factors), node.mode, method=algorithm, tiles=tiles
                    )
                return mttkrp(src, list(factors), node.mode, method=algorithm, tiles=tiles)
            return _over_batch(
                lambda t, fs: partial_mttkrp_range(t, fs, node.lo, node.hi),
                batched, src, factors,
            )
        return _over_batch(
            lambda t, fs: contract_from_partial(
                t, dict(zip(node.contracted, fs)), node.lo, node.hi, node.parent_lo
            ),
            batched, src, [factors[m] for m in node.contracted],
        )

    def contract_view(
        self, node: ContractionNode, view: Array, factors: Sequence[Array]
    ) -> Array:
        """One root partial node from the tensor's matrix view ``X_(m)``
        (``m = view_split(node.lo, node.hi, N)``): the same GEMMs as
        :meth:`contract` on the raw tensor, on an operand laid out once a
        solve, each fenced so that the view stays as laid out
        (:func:`repro.core.dimtree.partial_from_view`).  A 3-D view carries
        a leading batch axis, vmapped over with the factors."""
        kept = node.shape[:-1]
        return _over_batch(
            lambda v, fs: partial_from_view(v, fs, node.lo, node.hi, kept),
            view.ndim == 3, view, factors,
        )

    def pp_pairs(
        self, problem, x: Array, factors: Sequence[Array]
    ) -> dict[tuple[int, int], Array]:
        """All pairwise-perturbation intermediates at the current factors:
        ``{(n, m): M_nm}`` for every ``n < m`` with
        ``M_nm[c, i_n, i_m] = sum X * prod_{k not in {n,m}} U_k[i_k, c]``
        in the rank-major layout of :class:`repro.plan.schedule.PPPair`
        -- one einsum per pair; a leading batch axis on ``x`` and the
        factors broadcasts through the ``...`` prefix unchanged."""
        order = problem.ndim
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
                # contract rank-last (the GEMM-friendly orientation), then
                # move rank to the front for the PPPair storage layout --
                # asking einsum for the rank-major output directly makes
                # XLA:CPU emit a far slower fused transpose-GEMM
                p = jnp.einsum(spec, x, *[factors[k] for k in others])
                out[(n, m)] = jnp.moveaxis(p, -1, -3)
        return out


class ShardedExecutor:
    """Block-distributed execution over a device mesh.

    Holds the concrete ``Mesh`` + ``mode_axes`` mapping (the Problem only
    carries their sizes).  Every node contraction is the local
    shared-memory kernel inside ``shard_map`` plus the minimal psum the
    node requires (over the axes mapped to the modes contracted *at that
    node*); the small Gram/pinv algebra stays at the global-array level in
    the engine, exactly as the previous hand-written distributed sweeps did.

    ``batch_axes`` names the mesh axes the leading batch dimension of a
    batched problem is sharded over (empty = batch replicated, or no
    batch).  Batch-parallel placements (``mode_axes`` empty, ``batch_axes``
    set) run every contraction collective-free: each device owns whole
    problems.

    ``node_axis`` names the *intra-node* mesh axis (the fast level of a
    two-level ``make_node_mesh``); it is only consulted when the engine
    passes ``collective="hierarchical"`` for a node, in which case the
    node's psum runs as reduce-scatter over ``node_axis`` + cross-node
    psum of the shard + all-gather back.
    """

    def __init__(self, mesh, mode_axes, batch_axes=(), node_axis=None):
        self.mesh = mesh
        self.mode_axes = dict(mode_axes)
        self.batch_axes = tuple(batch_axes)
        self.node_axis = node_axis

    # chunk count for the node pipeline: 1 = no chunking (plain psum)
    _n_chunks = 1

    def prepare(self, problem, x: Array, factors: Sequence[Array]):
        """Block-distribute tensor + factors per ``mode_axes`` (no reorder);
        a leading batch axis is sharded over ``batch_axes``."""
        return shard_problem(
            x, factors, self.mode_axes, self.mesh, batch_axes=self.batch_axes
        )

    def contract(
        self, node: ContractionNode, src: Array, factors: Sequence[Array],
        algorithm: str = "auto", tiles: Mapping[str, int] | None = None,
        collective: str = "flat",
    ) -> Array:
        """One schedule node on the mesh: local kernel per block + this
        node's psum over the axes mapped to its contracted modes, flat or
        hierarchical per ``collective``."""
        if node.from_root and node.is_leaf:
            return dist_mttkrp(
                src, list(factors), node.mode, self.mode_axes, self.mesh,
                method=algorithm, tiles=tiles, batch_axes=self.batch_axes,
                collective=collective, node_axis=self.node_axis,
            )
        if node.from_root:
            return dist_contract_range(
                src, list(factors), node.lo, node.hi, self.mode_axes, self.mesh,
                n_chunks=self._n_chunks, batch_axes=self.batch_axes,
                collective=collective, node_axis=self.node_axis,
            )
        return dist_contract_partial(
            src, list(factors), node.lo, node.hi, node.parent_lo, node.parent_hi,
            self.mode_axes, self.mesh, n_chunks=self._n_chunks,
            batch_axes=self.batch_axes,
            collective=collective, node_axis=self.node_axis,
        )

    def pp_pairs(
        self, problem, x: Array, factors: Sequence[Array]
    ) -> dict[tuple[int, int], Array]:
        """Pairwise-perturbation intermediates on the mesh: per pair one
        local einsum inside ``shard_map`` + the minimal psum over the axes
        mapped to the contracted modes (both kept modes ride their own
        axes, exactly like the factor rows they later update).  The PP
        cache build is the same on every sharded executor -- overlapping
        changes only psum scheduling, so it inherits this verbatim."""
        return dist_pp_pairs(
            x, list(factors), self.mode_axes, self.mesh,
            batch_axes=self.batch_axes,
        )


class OverlappingExecutor(ShardedExecutor):
    """Communication-hiding sharded executor (exact).

    Identical placement and results to :class:`ShardedExecutor`, but every
    node's communication is pipelined in ``n_chunks`` slabs along its
    leading kept mode: full MTTKRPs run through
    :func:`repro.dist.dist_mttkrp.dist_mttkrp_overlapped` (slab GEMMs with
    per-slab psums -- exact: disjoint output rows of the same reduction),
    and the partial contractions of dimension-tree schedules through the
    chunked ``dist_contract_range`` / ``dist_contract_partial`` pipelines
    (one local contraction, per-slab psums -- *bitwise* identical to the
    plain executor by construction).  Only the schedule changes.
    """

    def __init__(
        self, mesh, mode_axes, n_chunks: int = DEFAULT_OVERLAP_CHUNKS,
        batch_axes=(), node_axis=None,
    ):
        super().__init__(mesh, mode_axes, batch_axes, node_axis)
        self.n_chunks = int(n_chunks)

    @property
    def _n_chunks(self) -> int:
        """Pipeline depth used by the inherited node ``contract``."""
        return self.n_chunks

    def contract(
        self, node: ContractionNode, src: Array, factors: Sequence[Array],
        algorithm: str = "auto", tiles: Mapping[str, int] | None = None,
        collective: str = "flat",
    ) -> Array:
        """One schedule node with its psum hidden behind chunked GEMMs."""
        if node.from_root and node.is_leaf:
            return dist_mttkrp_overlapped(
                src, list(factors), node.mode, self.mode_axes, self.mesh,
                method=algorithm, n_chunks=self.n_chunks, tiles=tiles,
                batch_axes=self.batch_axes,
                collective=collective, node_axis=self.node_axis,
            )
        return super().contract(
            node, src, factors, algorithm, tiles=tiles, collective=collective
        )


def make_executor(
    kind: str,
    mesh=None,
    mode_axes=None,
    *,
    n_chunks: int = DEFAULT_OVERLAP_CHUNKS,
    batch_axes=(),
    node_axis=None,
) -> Executor:
    """Instantiate the executor for a planner-chosen kind.

    ``kind`` is a ``SweepPlan.executor`` value (one of
    :data:`repro.plan.cost.EXECUTORS`); the sharded kinds need the concrete
    ``mesh`` + ``mode_axes``, which the Problem deliberately does not carry
    (plans are pure metadata).  ``n_chunks`` sizes the overlapping
    executor's psum pipeline; ``batch_axes`` names the mesh axes a batched
    problem's leading batch dimension is sharded over (batch-parallel
    placements pass ``mode_axes={}`` plus the batch axes); ``node_axis``
    names the intra-node mesh axis hierarchical collectives decompose over
    (``Problem.node_axis`` for problems built with ``intra_axes``).
    """
    if kind not in EXECUTORS:
        raise ValueError(f"unknown executor kind {kind!r} (choose from {EXECUTORS})")
    if kind == "local":
        return LocalExecutor()
    if mesh is None or mode_axes is None:
        raise ValueError(f"executor {kind!r} needs mesh and mode_axes")
    if kind == "sharded":
        return ShardedExecutor(mesh, mode_axes, batch_axes, node_axis)
    return OverlappingExecutor(
        mesh, mode_axes, n_chunks=n_chunks, batch_axes=batch_axes,
        node_axis=node_axis,
    )
