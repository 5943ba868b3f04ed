"""Analytic per-mode / per-node cost model behind ``plan_sweep``.

Extends the flop/byte model of :func:`repro.core.mttkrp.mttkrp_flops` with
the algorithm-specific intermediate traffic (the full-KRP materialization of
1-step, the partial tensor of 2-step, the partial tensors of a contraction
schedule) and -- for sharded problems -- the per-node psum volume the
``mode_axes`` placement requires (ring all-reduce over the axes mapped to
contracted modes, per Ballard/Knight/Rouse's collective-volume accounting).

Seconds are predicted against the roofline constants of
``repro.analysis.roofline`` with a *bounded-overlap* model:

    predicted_s = max(compute_s, collective_s)
                + serial_fraction * min(compute_s, collective_s)

where ``compute_s = flops/PEAK + bytes/HBM`` and ``collective_s =
collective_bytes/ICI``.  ``serial_fraction`` is the per-executor fraction
of the smaller term that cannot be hidden behind the larger one: 1.0 for
the plain sharded executor (psum strictly after the local GEMM -- the model
degenerates to the old additive sum), ``1/n_chunks`` for the overlapping
executor (chunk ``k``'s psum runs under chunk ``k+1``'s GEMM; only the
first GEMM and the last psum stay exposed).  Measured constants fitted by
``bench_mttkrp --calibrate`` enter through the ``serial_fractions`` mapping
every costing entry point accepts (and ``plan_sweep`` threads through).

:func:`node_cost` is the single coster for schedule nodes -- leaf-off-root
MTTKRPs, root-level partial GEMMs, and partial-to-partial multi-TTVs alike
-- and :func:`validate_executor` is the one validity predicate every
(schedule, executor) pair passes through: a pair is either costed or
rejected here, never special-cased downstream.

Absolute numbers are hardware-nominal; the planner only ever compares
costs of the same contraction across algorithms/executors/schedules, where
shared terms cancel.
"""

from __future__ import annotations

import math
from dataclasses import dataclass, replace
from typing import Mapping

from repro.analysis.roofline import DCN_BW, HBM_BW, ICI_BW, PEAK_FLOPS
from repro.core.mttkrp import mttkrp_flops
from repro.core.tensor_ops import dims_split
from repro.dist.dist_mttkrp import DEFAULT_OVERLAP_CHUNKS

from .problem import Problem
from .schedule import (
    ContractionNode,
    binary_schedule,
    pp_pairs,
    ring_allreduce_bytes,
)

ALGORITHMS = (
    "1step",
    "2step",
    "2step-left",
    "2step-right",
    "dimtree",
    "fused",
    "matrix_free",
    "einsum",
    "baseline",
)

# Executor kinds the planner dispatches over (repro.plan.executor classes).
EXECUTORS = ("local", "sharded", "overlapping")

# Assumed long-run fraction of pairwise-perturbation sweeps that
# re-materialize the cache (factor drift crossing ``pp_tol``).  Late ALS
# sweeps drift little, so re-materialization is rare once past the initial
# transient; 1-in-8 is a conservative planning assumption -- the bench's
# measured exact fraction (``bench_mttkrp --pp``) is the ground truth.
PP_EXACT_FRACTION = 0.125


def validate_executor(problem: Problem, executor: str) -> None:
    """THE validity predicate for (problem, executor) pairings.

    Every (schedule, executor) pair is either costed or rejected here --
    schedules themselves never restrict the executor (any node's psum can
    be overlapped), so validity depends only on the problem's
    placement: ``local`` cannot run sharded problems, and the
    communication-hiding kinds need a sharded problem to have anything to
    hide.  Raises a single-format ``ValueError`` on rejection.
    """
    if executor not in EXECUTORS:
        raise ValueError(f"unknown executor {executor!r} (choose from {EXECUTORS})")
    reason = None
    if executor == "local" and problem.sharded:
        reason = "it runs on one device but the problem maps modes/batch to mesh axes"
    elif executor == "overlapping" and not problem.mode_axes:
        # batch-parallel-only placements have zero reduce traffic: nothing
        # to overlap (mode_axes, not sharded, is the predicate)
        reason = "it reschedules psums but the problem has none"
    if reason is not None:
        raise ValueError(f"executor {executor!r} cannot run this problem: {reason}")


@dataclass(frozen=True)
class ModeCost:
    """Cost terms for one contraction (a mode's MTTKRP or a schedule node).

    ``gemm_flops`` / ``krp_flops`` / ``second_step_flops`` are the terms of
    ``mttkrp_flops`` (local block dims for sharded problems); ``bytes`` is
    total HBM traffic including intermediates; ``collective_bytes`` is the
    per-device wire volume (0 on unsharded problems).  ``serial_fraction``
    is the executor's unhidable share of the smaller of compute/collective
    time (1.0 = no overlap, the additive model).

    ``measured_s`` is a hardware-measured wall time for this exact
    contraction (from :mod:`repro.plan.autotune`'s ``TuningCache``), ``None``
    when never measured.  The analytic prediction is always kept alongside
    it: ``predicted_s`` stays model-only, ``expected_s`` prefers the
    measurement when one exists -- the planner's ``strategy='autotune'``
    argmins over ``expected_s`` (per comparison set; see
    :mod:`repro.plan.planner`).

    ``inter_bytes`` is the share of ``collective_bytes`` that crosses the
    *node* boundary of a two-level mesh and is therefore priced at the slow
    ``DCN_BW`` instead of ``ICI_BW`` (0 on single-level problems, where the
    whole collective rides the fast links and the model reduces to the old
    single-bandwidth form).
    """

    gemm_flops: float
    krp_flops: float
    second_step_flops: float
    bytes: float
    collective_bytes: float = 0.0
    serial_fraction: float = 1.0
    measured_s: float | None = None
    inter_bytes: float = 0.0

    @property
    def flops(self) -> float:
        """Total floating-point operations across all three terms."""
        return self.gemm_flops + self.krp_flops + self.second_step_flops

    @property
    def compute_s(self) -> float:
        """Local roofline time: GEMM/KRP flops + HBM traffic, no collectives."""
        return self.flops / PEAK_FLOPS + self.bytes / HBM_BW

    @property
    def intra_bytes(self) -> float:
        """Wire bytes on the fast (intra-node / ICI) level: the collective
        volume not crossing nodes."""
        return self.collective_bytes - self.inter_bytes

    @property
    def collective_s(self) -> float:
        """Wire time of the completing collective: intra-node bytes at
        nominal ICI bandwidth plus node-crossing bytes at DCN bandwidth."""
        return self.intra_bytes / ICI_BW + self.inter_bytes / DCN_BW

    @property
    def predicted_s(self) -> float:
        """Bounded-overlap roofline: ``max + serial_fraction * min`` of the
        compute and collective terms (``serial_fraction=1`` recovers the
        additive no-overlap model)."""
        c, q = self.compute_s, self.collective_s
        return max(c, q) + self.serial_fraction * min(c, q)

    @property
    def expected_s(self) -> float:
        """Best available time estimate: the hardware measurement when one
        exists (``measured_s``), the analytic ``predicted_s`` otherwise."""
        return self.predicted_s if self.measured_s is None else self.measured_s

    @property
    def predicted_overlap_efficiency(self) -> float:
        """Fraction of the hidable (smaller) term actually hidden:
        ``1 - serial_fraction`` when there is a collective to hide, else 0."""
        if self.collective_bytes <= 0.0:
            return 0.0
        return 1.0 - self.serial_fraction

    def as_dict(self) -> dict:
        """JSON-ready projection of all terms plus the derived predictions."""
        return {
            "gemm_flops": self.gemm_flops,
            "krp_flops": self.krp_flops,
            "second_step_flops": self.second_step_flops,
            "flops": self.flops,
            "bytes": self.bytes,
            "collective_bytes": self.collective_bytes,
            "intra_bytes": self.intra_bytes,
            "inter_bytes": self.inter_bytes,
            "serial_fraction": self.serial_fraction,
            "compute_s": self.compute_s,
            "collective_s": self.collective_s,
            "predicted_overlap_efficiency": self.predicted_overlap_efficiency,
            "predicted_s": self.predicted_s,
            "measured_s": self.measured_s,
            "expected_s": self.expected_s,
        }


def _level_shards(problem: Problem, reduce_axes) -> tuple[int, int]:
    """Split one reduction's participants into (intra k, inter m) shards:
    ``k`` over the axes declared in ``Problem.intra_axes``, ``m`` over the
    node-crossing rest."""
    k = m = 1
    for axis in reduce_axes:
        if axis in problem.intra_axes:
            k *= problem.axis_sizes[axis]
        else:
            m *= problem.axis_sizes[axis]
    return k, m


def collective_level_bytes(
    problem: Problem,
    block_bytes: float,
    reduce_axes,
    collective: str = "flat",
) -> tuple[float, float]:
    """Per-device ``(collective_bytes, inter_bytes)`` of one node's psum.

    Splits the completing all-reduce of a ``block_bytes`` output block over
    ``reduce_axes`` into the two levels of a ``Problem.intra_axes`` mesh:

    * single-level problem (no ``intra_axes``) -- the classic ring volume,
      all of it on the fast links (``inter_bytes = 0``; predictions are
      bit-identical to the old single-bandwidth model);
    * reduction confined to one node (``m <= 1``) -- ring over the intra
      shards, nothing crosses nodes;
    * reduction only across nodes (``k <= 1``) -- the whole ring rides the
      slow level;
    * ``collective="flat"`` spanning both -- one ring over all ``k * m``
      devices; its slowest hops cross nodes, so the full volume is charged
      at DCN rate;
    * ``collective="hierarchical"`` -- reduce-scatter + all-gather within
      the node (``2 B (k-1)/k`` intra) and a ring over the ``1/k`` shard
      across nodes (``2 (B/k)(m-1)/m`` inter): the factor-``k`` cut of
      slow-level volume the two-level psum exists for.
    """
    k, m = _level_shards(problem, reduce_axes)
    if k * m <= 1:
        return 0.0, 0.0
    if not problem.intra_axes:
        return ring_allreduce_bytes(block_bytes, k * m), 0.0
    if m <= 1:
        return ring_allreduce_bytes(block_bytes, k), 0.0
    if k <= 1:
        t = ring_allreduce_bytes(block_bytes, m)
        return t, t
    if collective != "hierarchical":
        t = ring_allreduce_bytes(block_bytes, k * m)
        return t, t
    intra = ring_allreduce_bytes(block_bytes, k)
    inter = ring_allreduce_bytes(block_bytes / k, m)
    return intra + inter, inter


def hierarchical_applicable(problem: Problem, reduce_axes) -> bool:
    """True when a node's reduction spans both levels of a two-level mesh
    (``k > 1`` intra shards *and* ``m > 1`` nodes) -- i.e. the hierarchical
    psum would actually decompose instead of falling back to the flat ring,
    so the planner has a real flat-vs-hierarchical choice to argmin."""
    k, m = _level_shards(problem, reduce_axes)
    return k > 1 and m > 1


def _node_grids(n_modes: int, nodes: int):
    """All integer grids ``(m_1 .. m_N)`` with ``prod m_i == nodes``."""
    if n_modes == 1:
        yield (nodes,)
        return
    d = 1
    while d * d <= nodes:
        if nodes % d == 0:
            for q in (d, nodes // d):
                for rest in _node_grids(n_modes - 1, nodes // q):
                    yield (q,) + rest
                if d * d == nodes:
                    break
        d += 1


def mttkrp_comm_lower_bound(
    shape,
    rank: int,
    mesh_shape,
    *,
    itemsize: float = 4.0,
    per_mode: bool = False,
):
    """Communication lower bound for one full MTTKRP sweep over ``P`` nodes.

    Ballard/Knight/Rouse-style accounting (arXiv 1708.07401): any block
    placement of the dense tensor on ``P`` nodes is an integer grid
    ``(m_1 .. m_N)`` with ``prod m_n = P``, and mode ``n``'s MTTKRP must
    then reduce partial factor blocks across the ``P / m_n`` nodes sharing
    each mode-``n`` slab -- at best a ring all-reduce of the
    ``(I_n / m_n, R)`` block, i.e. ``2 (I_n / m_n) R s (1 - m_n / P)``
    bytes per node.  The bound is the minimum of the per-sweep sum over all
    grids (fractional blocks allowed: grids need not divide the dims, so
    this is a true lower bound for every realizable mapping).

    ``mesh_shape`` is the node count, or a tuple whose product is taken
    (e.g. the inter-node part of a mesh).  Returns bytes per node per
    sweep; with ``per_mode=True`` returns ``(bound, terms, grid)`` where
    ``terms[n]`` is mode ``n``'s contribution at the minimizing grid.
    """
    dims = tuple(int(d) for d in shape)
    if not dims:
        raise ValueError("shape must have at least one mode")
    nodes = mesh_shape
    if not isinstance(nodes, int):
        nodes = math.prod(int(x) for x in mesh_shape)
    nodes = int(nodes)
    if nodes < 1:
        raise ValueError(f"node count must be >= 1, got {nodes}")
    s = float(itemsize)
    best = None
    best_grid = None
    for grid in _node_grids(len(dims), nodes):
        total = 0.0
        for d, m in zip(dims, grid):
            total += 2.0 * (d / m) * rank * s * (1.0 - m / nodes)
        if best is None or total < best:
            best, best_grid = total, grid
    if not per_mode:
        return best
    terms = tuple(
        2.0 * (d / m) * rank * s * (1.0 - m / nodes)
        for d, m in zip(dims, best_grid)
    )
    return best, terms, best_grid


def _fused_krp_dims(local_shape, n: int) -> tuple[int, int]:
    """Row counts of the two partial KRPs the fused Pallas kernel streams
    (internal modes: the L/R sides; external modes: the log-balanced split
    used by ``repro.kernels.ops.fused_mttkrp``)."""
    L, _, R = dims_split(local_shape, n)
    if 0 < n < len(local_shape) - 1:
        return L, R
    from repro.kernels.ops import balanced_split  # lazy: kernels import pallas

    dims = [d for k, d in enumerate(local_shape) if k != n]
    if len(dims) < 2:
        return dims[0] if dims else 1, 1
    s = balanced_split(dims)
    return math.prod(dims[:s]), math.prod(dims[s:])


def mode_cost(
    problem: Problem, n: int, algorithm: str, *, collective: str = "flat"
) -> ModeCost:
    """Cost of one mode-``n`` MTTKRP under ``algorithm``.

    Computed on the per-device block dims; the psum volume for sharded
    problems is the ring all-reduce of the local partial result over the
    axes mapped to contracted modes (no collective when mode ``n`` itself is
    the only mapped mode -- its axis carries the output rows).  On two-level
    problems (``Problem.intra_axes`` set) ``collective`` picks how that
    volume splits across the levels -- see :func:`collective_level_bytes`.
    The ``"dimtree"`` algorithm prices the mode's share of the balanced
    binary schedule via :func:`dimtree_mode_cost` (which folds over
    :func:`node_cost`); general tree shapes are costed per node by
    :func:`node_cost` directly.

    Batched problems scale every flop/byte term by the per-device batch
    extent ``local_batch`` -- including the psum volume, which is why a
    mode-parallel placement of B small tensors pays B times the wire bytes
    while a batch-parallel placement (no mapped modes) pays zero.
    """
    if algorithm not in ALGORITHMS:
        raise ValueError(f"unknown algorithm {algorithm!r} (choose from {ALGORITHMS})")
    if algorithm == "dimtree":
        return dimtree_mode_cost(
            problem, n, (problem.ndim + 1) // 2, collective=collective
        )
    shape = problem.local_shape
    c = problem.rank
    s = problem.itemsize
    lb = problem.local_batch
    base = mttkrp_flops(shape, c, n, itemsize=s, batch=lb)
    L, In, R = dims_split(shape, n)
    out_bytes = In * c * s * lb
    coll, inter = collective_level_bytes(
        problem, out_bytes, problem.reduce_axes_for(n), collective
    )

    if algorithm == "2step" and not problem.external_mode(n):
        # forced 2-step resolves its order by cost, like the Alg. 4 line-4 rule
        left = mode_cost(problem, n, "2step-left", collective=collective)
        right = mode_cost(problem, n, "2step-right", collective=collective)
        return left if left.predicted_s < right.predicted_s else right

    if algorithm == "1step" or (
        problem.external_mode(n) and algorithm in ("2step", "2step-left", "2step-right")
    ):
        # explicit KRP: L*R*C materialized (written once, read once by the GEMM)
        return ModeCost(
            gemm_flops=base["gemm_flops"],
            krp_flops=base["krp_flops"],
            second_step_flops=0.0,
            bytes=base["tensor_bytes"] + 2.0 * base["krp_bytes"] + out_bytes,
            collective_bytes=coll,
            inter_bytes=inter,
        )
    if algorithm in ("2step-left", "2step-right"):
        # left-first contracts K_L in the GEMM, multi-TTVs over R (and vice
        # versa); intermediate is In * contracted-side * C.
        second_side = R if algorithm == "2step-left" else L
        intermediate = In * second_side * c * s * lb
        return ModeCost(
            gemm_flops=base["gemm_flops"],
            krp_flops=float((L + R) * c * lb),  # two small KRPs instead of one huge
            second_step_flops=2.0 * In * second_side * c * lb,
            bytes=base["tensor_bytes"] + 2.0 * intermediate + (L + R) * c * s * lb + out_bytes,
            collective_bytes=coll,
            inter_bytes=inter,
        )
    if algorithm == "fused":
        da, db = _fused_krp_dims(shape, n)
        return ModeCost(
            gemm_flops=base["gemm_flops"],
            krp_flops=float((da + db) * c * lb),
            second_step_flops=0.0,
            # the full KRP never hits HBM -- only the two partials stream in
            bytes=base["tensor_bytes"] + (da + db) * c * s * lb + out_bytes,
            collective_bytes=coll,
            inter_bytes=inter,
        )
    if algorithm == "matrix_free":
        # bytes-read-once model: the tensor streams through VMEM exactly one
        # time, the raw non-target factors ride along (sum of mode extents,
        # not KRP products), and nothing of KRP shape is ever written.  The
        # in-VMEM fold costs one full contraction (== gemm_flops) plus the
        # shrinking broadcast-MAC chain, priced as second_step_flops.
        others = [k for k in range(len(shape)) if k != n]
        spatial = float(math.prod(shape)) / shape[others[-1]]
        fold = 0.0
        for k in reversed(others[:-1]):
            fold += 2.0 * spatial * c * lb
            spatial /= shape[k]
        factor_bytes = float(sum(shape[k] for k in others)) * c * s * lb
        return ModeCost(
            gemm_flops=base["gemm_flops"],
            krp_flops=0.0,
            second_step_flops=fold,
            bytes=base["tensor_bytes"] + factor_bytes + out_bytes,
            collective_bytes=coll,
            inter_bytes=inter,
        )
    if algorithm == "einsum":
        return ModeCost(
            gemm_flops=base["gemm_flops"],
            krp_flops=0.0,
            second_step_flops=0.0,
            bytes=base["tensor_bytes"] + (L + In + R) * c * s * lb + out_bytes,
            collective_bytes=coll,
            inter_bytes=inter,
        )
    assert algorithm == "baseline"
    # reorder (transpose copy: read + write) then one GEMM over the copy
    return ModeCost(
        gemm_flops=base["gemm_flops"],
        krp_flops=base["krp_flops"],
        second_step_flops=0.0,
        bytes=3.0 * base["tensor_bytes"] + 2.0 * base["krp_bytes"] + out_bytes,
        collective_bytes=coll,
        inter_bytes=inter,
    )


def _adjust(
    base: ModeCost,
    executor: str,
    *,
    chunk_extent: int,
    n_chunks: int,
    serial_fractions: Mapping[str, float] | None,
) -> ModeCost:
    """Executor adjustment: the unhidable fraction of the smaller term."""
    fitted = (serial_fractions or {}).get(executor)
    if base.collective_bytes <= 0.0:
        return base
    if executor == "overlapping":
        chunks = max(1, min(int(n_chunks), int(chunk_extent)))
        f = float(fitted) if fitted is not None else 1.0 / chunks
        return replace(base, serial_fraction=f)
    if fitted is not None:
        return replace(base, serial_fraction=float(fitted))
    return base


def executor_mode_cost(
    problem: Problem,
    n: int,
    algorithm: str,
    executor: str = "sharded",
    *,
    n_chunks: int = DEFAULT_OVERLAP_CHUNKS,
    serial_fractions: Mapping[str, float] | None = None,
    collective: str = "flat",
) -> ModeCost:
    """Cost of one mode-``n`` MTTKRP under ``algorithm`` on ``executor``.

    Applies the executor's placement-specific adjustments on top of
    :func:`mode_cost`:

    * ``"local"`` / ``"sharded"`` -- the per-algorithm terms unchanged
      (``serial_fraction`` 1.0: the psum waits for the whole local GEMM).
    * ``"overlapping"`` -- same flops/bytes/wire volume, but the chunked
      double-buffered pipeline hides all but ``1/n_chunks`` of the smaller
      of compute/collective time (chunk count is capped by the local row
      count of mode ``n``).

    ``serial_fractions`` (executor kind -> fitted unhidable fraction, from
    ``bench_mttkrp --calibrate``) overrides the analytic defaults.
    ``collective`` threads the two-level psum choice through (see
    :func:`collective_level_bytes`).
    """
    validate_executor(problem, executor)
    base = mode_cost(problem, n, algorithm, collective=collective)
    return _adjust(
        base,
        executor,
        chunk_extent=problem.local_shape[n],
        n_chunks=n_chunks,
        serial_fractions=serial_fractions,
    )


def node_cost(
    problem: Problem,
    node: ContractionNode,
    executor: str | None = None,
    *,
    algorithm: str = "1step",
    n_chunks: int = DEFAULT_OVERLAP_CHUNKS,
    serial_fractions: Mapping[str, float] | None = None,
    collective: str = "flat",
) -> ModeCost:
    """Cost of one schedule node's contraction on ``executor``.

    ``executor=None`` resolves to the plain kind matching the problem's
    placement (``"sharded"`` when modes are mapped, ``"local"`` otherwise).

    The single coster behind every tree shape (the old per-mode and
    ``dimtree_mode_cost`` special cases fold into it):

    * **leaf off the root** -- a full mode MTTKRP: delegates to
      :func:`executor_mode_cost` with ``algorithm`` (the planner's per-mode
      pick applies only here).
    * **internal node off the root** -- one X-sized GEMM against the KRP of
      the contracted modes, writing the partial tensor, plus its psum over
      the contracted modes' axes.
    * **any node off a partial** -- a multi-TTV: one pass over the parent's
      (much smaller) partial per contracted mode, shrinking as it goes,
      plus this node's own psum.

    ``serial_fractions`` threads calibrated per-executor constants through,
    exactly as in :func:`executor_mode_cost`, and ``collective`` the
    two-level psum choice (the node's stamped flat ring volume is re-split
    per :func:`collective_level_bytes` on two-level problems).
    """
    if executor is None:
        executor = "sharded" if problem.sharded else "local"
    validate_executor(problem, executor)
    if node.is_root:
        raise ValueError("the schedule root is the raw tensor, not a contraction")
    c = problem.rank
    s = problem.itemsize
    lb = problem.local_batch
    if node.from_root and node.is_leaf:
        return executor_mode_cost(
            problem, node.lo, algorithm, executor,
            n_chunks=n_chunks, serial_fractions=serial_fractions,
            collective=collective,
        )
    t_elems = math.prod(node.local_shape) * lb  # kept local dims * rank (x batch)
    t_bytes = t_elems * s
    coll, inter = collective_level_bytes(
        problem, t_bytes, node.reduce_axes, collective
    )
    if node.from_root:
        total = math.prod(problem.local_shape) * lb
        krp_elems = (
            math.prod(problem.local_shape[m] for m in node.contracted) * c * lb
            if node.contracted
            else 0
        )
        base = ModeCost(
            gemm_flops=2.0 * total * c,
            krp_flops=float(krp_elems),
            second_step_flops=0.0,
            bytes=total * s + 2.0 * krp_elems * s + t_bytes,
            collective_bytes=coll,
            inter_bytes=inter,
        )
    else:
        parent_elems = (
            math.prod(problem.local_shape[node.parent_lo : node.parent_hi]) * c * lb
        )
        ttv = 0.0
        elems = float(parent_elems)
        for m in node.contracted:
            ttv += 2.0 * elems
            elems /= problem.local_shape[m]
        base = ModeCost(
            gemm_flops=0.0,
            krp_flops=0.0,
            second_step_flops=ttv,
            bytes=parent_elems * s + t_bytes,
            collective_bytes=coll,
            inter_bytes=inter,
        )
    return _adjust(
        base,
        executor,
        chunk_extent=problem.local_shape[node.lo],
        n_chunks=n_chunks,
        serial_fractions=serial_fractions,
    )


def pp_build_cost(problem: Problem) -> ModeCost:
    """Cost of materializing the pairwise-perturbation cache once.

    One pass over the (local) tensor per pair intermediate ``M_{n,m}`` --
    the naive per-pair einsum the executors run, *not* an amortizing tree --
    each completed by its ring all-reduce over the axes mapped to the
    contracted modes (:func:`repro.plan.schedule.pp_pairs` stamps the
    volume), plus the N tiny base contractions ``M_{n,m} x V_m``.  Paid on
    every exact (re-materialization) sweep, so the planner adds it to the
    exact-sweep term of the amortized PP price.
    """
    c = problem.rank
    s = problem.itemsize
    lb = problem.local_batch
    total = math.prod(problem.local_shape) * lb
    gemm = krp = byts = coll = 0.0
    for pair in pp_pairs(problem):
        t_elems = math.prod(pair.local_shape) * lb
        gemm += 2.0 * total * c
        byts += total * s + t_elems * s
        coll += pair.psum_bytes
    # base terms: one correction-shaped GEMM per mode off its first pair
    for n in range(problem.ndim):
        m = 1 if n == 0 else 0
        ln = problem.local_shape[n]
        lm = problem.local_shape[m]
        gemm += 2.0 * ln * lm * c * lb
        byts += (ln * lm * c + lm * c + ln * c) * s * lb
    return ModeCost(
        gemm_flops=gemm, krp_flops=krp, second_step_flops=0.0,
        bytes=byts, collective_bytes=coll,
    )


def pp_correction_cost(problem: Problem) -> ModeCost:
    """Cost of ONE approximate (correction-only) PP sweep, all modes.

    Each mode's MTTKRP is its cached base plus ``N - 1`` small GEMMs --
    ``(C, I_n, I_m) x (I_m, C) -> (I_n, C)`` against each pairwise
    intermediate -- so an approximate sweep never touches the raw tensor:
    the per-sweep flops drop from ``O(N |X| C)`` to
    ``O(sum I_n I_m C)``, the whole point of pairwise perturbation.  On
    sharded problems the contraction over a mapped mode ``m`` ends in a
    ring all-reduce of the ``(I_n, C)`` block over that mode's axis.
    """
    c = problem.rank
    s = problem.itemsize
    lb = problem.local_batch
    gemm = byts = coll = 0.0
    for n in range(problem.ndim):
        ln = problem.local_shape[n]
        out_bytes = ln * c * s * lb
        for m in range(problem.ndim):
            if m == n:
                continue
            lm = problem.local_shape[m]
            gemm += 2.0 * ln * lm * c * lb
            byts += (ln * lm * c + lm * c) * s * lb + out_bytes
            coll += ring_allreduce_bytes(out_bytes, problem.mode_shards(m))
    return ModeCost(
        gemm_flops=gemm, krp_flops=0.0, second_step_flops=0.0,
        bytes=byts, collective_bytes=coll,
    )


def pp_amortized_cost(
    problem: Problem,
    exact_sweep_s: float,
    *,
    exact_fraction: float = PP_EXACT_FRACTION,
    build_s: float | None = None,
    correction_s: float | None = None,
) -> dict:
    """Amortized per-sweep price of the PP strategy, as a describe() row.

    ``f * (exact_sweep_s + build_s) + (1 - f) * correction_s`` with ``f``
    the assumed exact-sweep fraction: a re-materialization sweep pays the
    full exact sweep plus the cache build, every other sweep only the
    first-order corrections.  This slightly over-prices PP -- the engine
    only pays the build on exact sweeps whose step settled under the
    tolerance, not on every exact sweep -- so the argmin errs toward the
    exact strategy.  ``build_s`` / ``correction_s`` default to the
    analytic predictions; pass hardware measurements (from
    :func:`repro.plan.autotune.tune`) to price on the measured basis.
    """
    if build_s is None:
        build_s = pp_build_cost(problem).predicted_s
    if correction_s is None:
        correction_s = pp_correction_cost(problem).predicted_s
    f = float(exact_fraction)
    amortized = f * (exact_sweep_s + build_s) + (1.0 - f) * correction_s
    return {
        "tol": problem.pp_tol,
        "exact_fraction": f,
        "exact_sweep_s": exact_sweep_s,
        "build_s": build_s,
        "correction_sweep_s": correction_s,
        "amortized_sweep_s": amortized,
    }


def dimtree_mode_cost(
    problem: Problem, n: int, split: int, *, collective: str = "flat"
) -> ModeCost:
    """Dimension-tree cost of mode ``n`` given the half split at ``split``.

    Back-compat per-mode view of the binary schedule, folded over
    :func:`node_cost`: the first mode of each multi-mode half additionally
    carries its half's partial contraction (the X-sized GEMM + psum), every
    mode pays its leaf (a multi-TTV off the half's partial, or the full
    MTTKRP when the half has a single mode).  Summing over modes equals
    summing :func:`node_cost` over the binary schedule's nodes.
    """
    sched = binary_schedule(problem, split)
    leaf = sched.leaf_for_mode(n)
    total = node_cost(problem, leaf, algorithm="1step", collective=collective)
    if not leaf.from_root and n == leaf.parent_lo:
        parent = sched.nodes[leaf.parent]
        head = node_cost(problem, parent, collective=collective)
        total = ModeCost(
            gemm_flops=total.gemm_flops + head.gemm_flops,
            krp_flops=total.krp_flops + head.krp_flops,
            second_step_flops=total.second_step_flops + head.second_step_flops,
            bytes=total.bytes + head.bytes,
            collective_bytes=total.collective_bytes + head.collective_bytes,
            inter_bytes=total.inter_bytes + head.inter_bytes,
        )
    return total
