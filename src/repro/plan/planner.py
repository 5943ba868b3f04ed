"""``plan_sweep``: the single front door for ALS algorithm choice.

The paper's Sec. 5.3.3 finding -- 1-step on external modes, 2-step on
internal modes -- used to be hard-coded inside ``mttkrp(method="auto")`` and
re-derived independently by four sweep implementations.  It now lives here,
driven by the analytic cost model of :mod:`repro.plan.cost`: ``auto`` picks
each root-level mode's algorithm by predicted seconds, breaking near-ties
(within 10%) toward the paper's empirical recommendation, which exactly
reproduces the Sec. 5.3.3 dispatch on the benchmark shapes while letting
genuinely lopsided shapes (e.g. one huge mode flanked by tiny ones) escape
the heuristic.

Beyond the per-mode algorithm, ``plan_sweep`` plans the *contraction
schedule* and the *executor* jointly: ``strategy='auto'`` cost-argmins over
the tree shapes of :func:`repro.plan.schedule.enumerate_schedules` (the
flat per-mode sweep, the binary split at every boundary, and the
multi-level chain for order >= 4) and, via ``executor='auto'``, over the
executor kinds of :data:`repro.plan.cost.EXECUTORS` under the
bounded-overlap model -- so dimension-tree reuse and communication hiding
are planner decisions, not call-site flags.  Every executor kind is exact.
Any (schedule, executor) pair is valid
(:func:`repro.plan.cost.validate_executor` is the one predicate): the
overlapping executor chunks the partial contractions of tree schedules
just like full MTTKRPs.  The chosen kind lands on ``SweepPlan.executor``;
:func:`repro.plan.executor.make_executor` turns it into the matching
executor instance given the concrete mesh.
"""

from __future__ import annotations

import itertools
import math
from dataclasses import dataclass, replace
from typing import Mapping

from .cost import (
    DEFAULT_OVERLAP_CHUNKS,
    EXECUTORS,
    PP_EXACT_FRACTION,
    ModeCost,
    executor_mode_cost,
    hierarchical_applicable,
    mttkrp_comm_lower_bound,
    node_cost,
    pp_amortized_cost,
    validate_executor,
)
from .problem import Problem
from .schedule import (
    ContractionNode,
    Schedule,
    binary_schedule,
    chain_schedule,
    enumerate_schedules,
    flat_schedule,
)

STRATEGIES = (
    "auto",
    "autotune",
    "pp",
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

# Named schedule shapes accepted by ``plan_sweep(schedule=...)``.
SCHEDULE_NAMES = ("flat", "binary", "chain")

# auto prefers 2-step on internal modes unless 1-step is predicted >10%
# cheaper: the flop/byte terms of the two algorithms cross within model noise
# on near-cubic shapes (where the paper measured 2-step ahead), so the model
# alone decides only clear wins.  The same margin breaks schedule near-ties
# toward the flat per-mode sweep (the shape the paper measured).
_NEAR_TIE = 0.9


@dataclass(frozen=True)
class ModePlan:
    """Algorithm choice + predicted cost for one mode's MTTKRP (leaf view)."""

    mode: int
    algorithm: str
    cost: ModeCost

    def as_dict(self) -> dict:
        """JSON-ready row: mode, algorithm, and every cost term."""
        return {"mode": self.mode, "algorithm": self.algorithm, **self.cost.as_dict()}


@dataclass(frozen=True)
class NodePlan:
    """One schedule node's planned contraction: algorithm + predicted cost.

    ``algorithm`` is a per-mode MTTKRP method for leaves off the root,
    ``"partial-krp"`` for root-level partial GEMMs, and ``"partial-ttv"``
    for contractions of an already-computed partial.  ``tiles`` carries the
    hardware-tuned Pallas tile config (``{"block_i": ..., "block_b": ...}``)
    when ``strategy='autotune'`` planned a kernel-backed algorithm; the
    executors thread it into :mod:`repro.kernels.ops`.

    ``collective`` is the planned completing-psum strategy (``"flat"`` or
    ``"hierarchical"``, argmin'd per node on two-level meshes) -- the
    executors thread it into :mod:`repro.dist.dist_mttkrp` exactly like
    ``algorithm``/``tiles``.  ``lower_bound_bytes`` is the leaf's share of
    the Ballard-Knight-Rouse communication lower bound (per node per
    sweep), stamped on certified-planning runs; ``None`` elsewhere.
    """

    node: ContractionNode
    algorithm: str
    cost: ModeCost
    tiles: Mapping[str, int] | None = None
    collective: str = "flat"
    lower_bound_bytes: float | None = None

    def as_dict(self) -> dict:
        """JSON-ready row: node topology/psum metadata + every cost term."""
        return {
            **self.node.as_dict(),
            "algorithm": self.algorithm,
            "tiles": dict(self.tiles) if self.tiles else None,
            "collective": self.collective,
            "lower_bound_bytes": self.lower_bound_bytes,
            **self.cost.as_dict(),
        }


@dataclass(frozen=True)
class SweepPlan:
    """Planned contraction schedule for one full ALS sweep.

    ``schedule`` is the contraction tree the engine walks and ``nodes`` its
    per-node plans in evaluation order; ``modes`` is the per-mode leaf view
    (kept stable for benchmarks and the pre-schedule callers).  ``split`` is
    the binary half boundary when the tree is the classic two-partial split;
    ``normalize`` is carried here because it is part of the sweep recipe the
    executors share; ``serial_fractions`` records calibrated per-executor
    overlap constants when the plan was built with them.  ``describe()`` is
    the JSON-ready prediction surface benchmarks report against
    measurements.

    For batched sharded problems the planner also argmins over *placements*
    (mode-parallel as given vs all-batch-parallel); ``placements`` records
    each candidate's predicted cost and ``problem`` is the winning
    placement -- build the executor from ``plan.problem``'s
    ``mode_axes``/``batch_axes``, not from the pre-planning problem.

    ``pp`` flags the pairwise-perturbation sweep mode: the engine still
    carries this plan's exact schedule (re-materialization sweeps run it
    verbatim), but while factor drift stays under ``problem.pp_tol`` each
    sweep approximates every MTTKRP from the cached pairwise intermediates
    plus first-order corrections.  ``pp_info`` is the pricing row behind
    the decision (see :func:`repro.plan.cost.pp_amortized_cost`), ``None``
    when the problem never opted in (``pp_tol == 0``).

    On two-level meshes (``Problem.intra_axes``) the planner additionally
    argmins over *mesh mappings* (mode -> axis assignments) with the
    flat-vs-hierarchical collective choice folded in per node:
    ``mappings`` records each evaluated candidate with its modeled
    per-node inter-node volume and the Ballard-Knight-Rouse lower bound,
    ``lower_bound_bytes`` is the winning problem's bound (bytes per node
    per sweep), and ``certified_bandwidth_optimal`` flags a winner whose
    modeled inter-node volume is within the planner's ``certify_eps`` of
    that bound -- enumeration stops early once a candidate certifies.
    """

    problem: Problem
    strategy: str
    modes: tuple[ModePlan, ...]
    split: int | None = None
    normalize: bool = True
    executor: str = "local"
    schedule: Schedule | None = None
    nodes: tuple[NodePlan, ...] = ()
    serial_fractions: Mapping[str, float] | None = None
    placements: tuple[Mapping, ...] = ()
    pp: bool = False
    pp_info: Mapping | None = None
    mappings: tuple[Mapping, ...] = ()
    lower_bound_bytes: float | None = None
    certified_bandwidth_optimal: bool = False

    @property
    def kind(self) -> str:
        """``"dimtree"`` for tree schedules, ``"permode"`` for the flat one."""
        if self.schedule is not None:
            return "permode" if self.schedule.is_flat else "dimtree"
        return "dimtree" if self.split is not None else "permode"

    @property
    def resolved_schedule(self) -> Schedule:
        """The plan's schedule, deriving the degenerate tree for plans built
        without one (flat, or the binary split when ``split`` is set)."""
        if self.schedule is not None:
            return self.schedule
        if self.split is not None:
            return binary_schedule(self.problem, self.split)
        return flat_schedule(self.problem)

    def node_plan(self, node_id: int) -> NodePlan:
        """The :class:`NodePlan` of one schedule node."""
        for np_ in self.nodes:
            if np_.node.id == node_id:
                return np_
        raise ValueError(f"no plan for node {node_id}")

    def total_cost(self) -> dict:
        """Sweep-level sums of the per-contraction cost terms/predictions
        (over every schedule node; for flat plans this equals the per-mode
        sum)."""
        rows = self.nodes if self.nodes else self.modes
        return {
            "flops": sum(r.cost.flops for r in rows),
            "bytes": sum(r.cost.bytes for r in rows),
            "collective_bytes": sum(r.cost.collective_bytes for r in rows),
            "intra_bytes": sum(r.cost.intra_bytes for r in rows),
            "inter_bytes": sum(r.cost.inter_bytes for r in rows),
            "predicted_s": sum(r.cost.predicted_s for r in rows),
        }

    def describe(self) -> dict:
        """Predicted flops / HBM bytes / collective bytes per mode and per
        schedule node, plus totals -- and, for batched sharded problems, the
        placement candidates compared (each with its predicted seconds and
        wire bytes, the selected one flagged).  The ``pp`` row prices the
        pairwise-perturbation strategy against the exact sweep (amortized
        per-sweep seconds; ``{"enabled": False}`` when the problem never
        opted in via ``pp_tol``)."""
        return {
            "shape": list(self.problem.shape),
            "rank": self.problem.rank,
            "dtype": self.problem.dtype_str,
            "strategy": self.strategy,
            "kind": self.kind,
            "executor": self.executor,
            "split": self.split,
            "sharded": self.problem.sharded,
            "mode_axes": {str(k): v for k, v in self.problem.mode_axes.items()},
            "batch": self.problem.batch,
            "batch_axes": list(self.problem.batch_axes),
            "local_batch": self.problem.local_batch,
            "placement": _placement_label(self.problem),
            "placements": [dict(p) for p in self.placements],
            "local_shape": list(self.problem.local_shape),
            "schedule": self.resolved_schedule.name,
            "modes": [m.as_dict() for m in self.modes],
            "nodes": [n.as_dict() for n in self.nodes],
            "serial_fractions": dict(self.serial_fractions or {}),
            "pp": {"enabled": self.pp, **dict(self.pp_info or {})},
            "mappings": [dict(m) for m in self.mappings],
            "lower_bound_bytes": self.lower_bound_bytes,
            "certified": self.certified_bandwidth_optimal,
            "totals": self.total_cost(),
        }


def _placement_label(problem: Problem) -> str:
    """Human name of a problem's mesh placement (for describe()/planning)."""
    if problem.mode_axes:
        return "mode-parallel"
    if problem.batch_axes:
        return "batch-parallel"
    return "unsharded"


def _placement_candidates(problem: Problem) -> list[Problem]:
    """Placement candidates the planner argmins over, as-given first.

    A batched mode-parallel problem additionally gets the all-batch-parallel
    remap (no mapped modes, the batch sharded over every mesh axis) whenever
    the batch divides the device count -- the placement with zero reduce
    traffic, which the Ballard-Knight-Rouse accounting predicts to win for
    fleets of small tensors.  Unbatched problems (and problems already
    batch-parallel, whose mode mapping we cannot invent) plan exactly as
    before: one candidate.
    """
    cands = [problem]
    if problem.batched and problem.mode_axes and problem.axis_sizes:
        devices = math.prod(problem.axis_sizes.values())
        if devices > 1 and problem.batch % devices == 0:
            cands.append(
                replace(
                    problem,
                    mode_axes={},
                    batch_axes=tuple(sorted(problem.axis_sizes)),
                )
            )
    return cands


def _mapping_candidates(problem: Problem) -> list[Problem]:
    """Alternative mode->axis assignments of a two-level problem's mesh.

    Every way to hand the axes the as-given mapping uses to distinct tensor
    modes (divisibility-checked), as-given excluded -- the search space of
    the certified mesh planning: same mesh, same tensor, different choice of
    which modes absorb the node / device axes, which is exactly what moves
    the inter-node reduce volume the BKR bound constrains.  Empty for flat
    problems (no ``intra_axes``) so single-level planning never changes.
    """
    if not (problem.intra_axes and problem.mode_axes):
        return []
    axes = sorted(set(problem.mode_axes.values()))
    given = dict(problem.mode_axes)
    out = []
    for modes in itertools.permutations(range(problem.ndim), len(axes)):
        mapping = dict(zip(modes, axes))
        if mapping == given:
            continue
        if any(
            problem.shape[m] % problem.axis_sizes[a] for m, a in mapping.items()
        ):
            continue
        out.append(replace(problem, mode_axes=mapping))
    return out


def _node_bound_bytes(problem: Problem) -> tuple[float, tuple[float, ...]] | None:
    """(BKR bound, per-mode terms) in bytes per node per sweep for a
    two-level mode-parallel problem; ``None`` when certification does not
    apply (flat mesh, single node, or no mapped modes)."""
    if not (problem.mode_axes and problem.intra_axes and problem.n_nodes > 1):
        return None
    bound, terms, _ = mttkrp_comm_lower_bound(
        problem.shape, problem.rank, problem.n_nodes,
        itemsize=problem.itemsize, per_mode=True,
    )
    lb = problem.local_batch
    return bound * lb, tuple(t * lb for t in terms)


def _pick_collective(
    problem: Problem,
    node: ContractionNode,
    alg: str,
    cost: ModeCost,
    executor: str,
    n_chunks: int,
    serial_fractions: Mapping[str, float] | None,
    measured=None,
) -> tuple[str, ModeCost]:
    """Flat-vs-hierarchical argmin for one node's completing collective.

    ``cost`` is the node's flat-collective cost (measurement already
    stamped when available).  When the node's reduction spans both mesh
    levels the hierarchical variant is costed head-to-head: measured
    seconds decide when *both* variants are measured (autotune), the
    analytic prediction otherwise -- measured and analytic never compete.
    """
    if not hierarchical_applicable(problem, node.reduce_axes):
        return "flat", cost
    if node.from_root and node.is_leaf:
        hier = executor_mode_cost(
            problem, node.mode, alg, executor, n_chunks=n_chunks,
            serial_fractions=serial_fractions, collective="hierarchical",
        )
    else:
        hier = node_cost(
            problem, node, executor, n_chunks=n_chunks,
            serial_fractions=serial_fractions, collective="hierarchical",
        )
    if measured is not None:
        m = measured.node_time(node, alg, executor, collective="hierarchical")
        if m is not None:
            hier = replace(hier, measured_s=m)
    if cost.measured_s is not None and hier.measured_s is not None:
        pick_hier = hier.measured_s < cost.measured_s
    else:
        pick_hier = hier.predicted_s < cost.predicted_s
    return ("hierarchical", hier) if pick_hier else ("flat", cost)


def _auto_mode(
    problem: Problem,
    n: int,
    executor: str,
    n_chunks: int,
    serial_fractions: Mapping[str, float] | None = None,
    node: ContractionNode | None = None,
    measured=None,
) -> ModePlan:
    """Cost-model dispatch for one mode (reproduces paper Sec. 5.3.3).

    With ``measured`` (a :class:`repro.plan.autotune.Measurements`, under
    ``strategy='autotune'``) every candidate's hardware measurement is
    stamped on its cost; when the *whole* candidate set is measured the
    choice is a strict argmin over measured seconds (the paper's own Sec. 5
    methodology) and the Pallas kernels (``fused`` and the streaming
    ``matrix_free``) join the candidates.
    Measured and analytic seconds never compete inside one comparison --
    a partially measured set falls back to the analytic near-tie rule.
    """

    def cost(alg: str) -> ModeCost:
        c = executor_mode_cost(
            problem, n, alg, executor, n_chunks=n_chunks,
            serial_fractions=serial_fractions,
        )
        if measured is not None and node is not None:
            m = measured.node_time(node, alg, executor)
            if m is not None:
                c = replace(c, measured_s=m)
        return c

    cands: dict[str, ModeCost] = {"1step": cost("1step")}
    if not problem.external_mode(n):
        cands["2step-left"] = cost("2step-left")
        cands["2step-right"] = cost("2step-right")
    for kernel_alg in ("fused", "matrix_free"):
        if (
            measured is not None
            and node is not None
            and measured.node_time(node, kernel_alg, executor) is not None
        ):
            cands[kernel_alg] = cost(kernel_alg)
    if len(cands) > 1 and all(c.measured_s is not None for c in cands.values()):
        alg = min(cands, key=lambda a: cands[a].measured_s)
        return ModePlan(n, alg, cands[alg])

    if problem.external_mode(n):
        # 2-step degenerates to 1-step here; only 1-step is a real candidate.
        return ModePlan(n, "1step", cands["1step"])
    left, right = cands["2step-left"], cands["2step-right"]
    # strict < keeps the Alg. 4 tie convention (L == R resolves right-first)
    two_alg, two = ("2step-left", left) if left.predicted_s < right.predicted_s else ("2step-right", right)
    one = cands["1step"]
    if one.predicted_s < _NEAR_TIE * two.predicted_s:
        return ModePlan(n, "1step", one)
    return ModePlan(n, two_alg, two)


def _plan_nodes(
    problem: Problem,
    sched: Schedule,
    strategy: str,
    executor: str,
    n_chunks: int,
    serial_fractions: Mapping[str, float] | None,
    measured=None,
) -> tuple[NodePlan, ...]:
    """NodePlans in evaluation order for one (schedule, executor) pair.

    Under ``strategy='autotune'`` (``measured`` set) every node's hardware
    measurement -- leaves and partial contractions alike -- is stamped on
    its cost, and leaves planned onto a kernel-backed algorithm carry the
    tuned Pallas tile config on ``NodePlan.tiles``.  On two-level meshes
    each node's completing collective is additionally argmin'd flat vs
    hierarchical (:func:`_pick_collective`) and stamped on
    ``NodePlan.collective``.
    """
    plans = []
    for node in sched.walk():
        if node.from_root and node.is_leaf:
            if strategy in ("auto", "autotune"):
                mp = _auto_mode(
                    problem, node.mode, executor, n_chunks, serial_fractions,
                    node=node, measured=measured,
                )
                alg, cost = mp.algorithm, mp.cost
            else:
                # forced strategies pin the leaf algorithm verbatim; tree
                # strategies route root leaves through the 1-step GEMM (the
                # arithmetic the binary tree's size-1 halves always used)
                alg = "1step" if strategy == "dimtree" else strategy
                cost = executor_mode_cost(
                    problem, node.mode, alg, executor, n_chunks=n_chunks,
                    serial_fractions=serial_fractions,
                )
            tiles = None
            if measured is not None:
                if alg == "fused":
                    tiles = measured.kernel_tiles("fused_mttkrp")
                elif alg == "matrix_free":
                    tiles = measured.kernel_tiles("matrix_free")
            coll, cost = _pick_collective(
                problem, node, alg, cost, executor, n_chunks,
                serial_fractions, measured,
            )
            plans.append(NodePlan(node, alg, cost, tiles=tiles, collective=coll))
        else:
            alg = "partial-krp" if node.from_root else "partial-ttv"
            cost = node_cost(
                problem, node, executor, n_chunks=n_chunks,
                serial_fractions=serial_fractions,
            )
            if measured is not None:
                m = measured.node_time(node, alg, executor)
                if m is not None:
                    cost = replace(cost, measured_s=m)
            coll, cost = _pick_collective(
                problem, node, alg, cost, executor, n_chunks,
                serial_fractions, measured,
            )
            plans.append(NodePlan(node, alg, cost, collective=coll))
    return tuple(plans)


def _best_executor(
    problem: Problem,
    sched: Schedule,
    strategy: str,
    candidates: tuple[str, ...],
    n_chunks: int,
    serial_fractions: Mapping[str, float] | None,
    measured=None,
) -> tuple[str, tuple[NodePlan, ...], float, float | None]:
    """Cost-argmin executor for one schedule among ``candidates``.

    Every kind is exact, so they compete head-to-head (ties resolve to the
    earlier, plainer kind).  When every candidate's node plan
    is fully measured (autotune), the comparison runs over measured sweep
    seconds instead of the analytic predictions -- mixed sets stay on the
    analytic basis so measured CPU milliseconds never race nominal-constant
    nanoseconds.  Returns ``(kind, node plans, analytic total, measured
    total-or-None)``.
    """
    plans = {
        ex: _plan_nodes(
            problem, sched, strategy, ex, n_chunks, serial_fractions, measured
        )
        for ex in candidates
    }
    pred = {
        ex: sum(np_.cost.predicted_s for np_ in plans[ex]) for ex in candidates
    }
    fully_measured = all(
        np_.cost.measured_s is not None for ex in candidates for np_ in plans[ex]
    ) and measured is not None
    totals = (
        {ex: sum(np_.cost.measured_s for np_ in plans[ex]) for ex in candidates}
        if fully_measured
        else pred
    )
    best = min(candidates, key=totals.__getitem__)
    return best, plans[best], pred[best], totals[best] if fully_measured else None


def _resolve_schedules(
    problem: Problem, strategy: str, split: int | None, schedule
) -> list[Schedule]:
    """Candidate schedules for one plan_sweep call."""
    if isinstance(schedule, Schedule):
        if schedule.problem != problem:
            raise ValueError("schedule was built for a different Problem")
        return [schedule]
    if isinstance(schedule, str):
        if schedule not in SCHEDULE_NAMES:
            raise ValueError(
                f"unknown schedule {schedule!r} (choose from {SCHEDULE_NAMES})"
            )
        if schedule == "flat":
            return [flat_schedule(problem)]
        if schedule == "binary":
            return [binary_schedule(problem, split)]
        return [chain_schedule(problem)]
    assert schedule is None
    if strategy == "dimtree":
        return [binary_schedule(problem, split)]
    if strategy in ("auto", "autotune"):
        return enumerate_schedules(problem)
    return [flat_schedule(problem)]


def select_executor(
    problem: Problem,
    strategy: str = "auto",
    *,
    n_chunks: int = DEFAULT_OVERLAP_CHUNKS,
    schedule=None,
    serial_fractions: Mapping[str, float] | None = None,
    tuning_cache=None,
) -> str:
    """Cost-argmin executor kind for ``problem`` under ``strategy``.

    Unsharded problems run locally.  Sharded plans compare the plain
    ``sharded`` executor against ``overlapping`` (communication hidden
    behind chunked contractions) on total predicted sweep seconds --
    jointly with the schedule shapes the strategy admits, exactly as
    :func:`plan_sweep` does; ties resolve to ``sharded``.  Both are exact.
    Dimension-tree schedules compete on the same footing: their partial
    contractions overlap per node.
    """
    return plan_sweep(
        problem, strategy, executor="auto", n_chunks=n_chunks,
        schedule=schedule, serial_fractions=serial_fractions,
        tuning_cache=tuning_cache,
    ).executor


def plan_sweep(
    problem: Problem,
    strategy: str = "auto",
    *,
    split: int | None = None,
    normalize: bool = True,
    executor: str = "auto",
    n_chunks: int = DEFAULT_OVERLAP_CHUNKS,
    schedule: Schedule | str | None = None,
    serial_fractions: Mapping[str, float] | None = None,
    tuning_cache=None,
    certify_eps: float = 0.25,
) -> SweepPlan:
    """Plan one full ALS sweep for ``problem``.

    ``strategy='auto'`` cost-argmins jointly over contraction-tree shapes
    (flat, the binary split at every boundary, the multi-level chain for
    order >= 4) and -- within each tree -- the per-mode algorithm of every
    leaf off the root (1-step / 2-step-left / 2-step-right by predicted
    cost).  Near-ties (within 10%) break toward the flat per-mode sweep,
    the shape the paper measured.  ``'dimtree'`` forces the classic binary
    tree (``split`` defaults to the balanced half); any other value forces
    that algorithm on every mode of the flat schedule.

    ``schedule`` pins the tree shape regardless of strategy: a
    :class:`repro.plan.schedule.Schedule` built for this problem, or one of
    ``"flat"`` / ``"binary"`` / ``"chain"``.

    ``executor='auto'`` additionally picks the executor kind by the same
    cost argmin (any (schedule, executor) pair is either costed or rejected
    by :func:`repro.plan.cost.validate_executor` -- tree schedules overlap
    per node like everything else); pass an explicit kind from
    :data:`repro.plan.cost.EXECUTORS` to force one.  ``n_chunks`` sizes the
    overlapping executor's psum pipeline; ``serial_fractions`` threads
    calibrated per-executor overlap constants (from ``bench_mttkrp
    --calibrate``) through every cost.  The choice lands on
    ``SweepPlan.executor``; :func:`repro.plan.executor.make_executor`
    builds the matching instance.

    Batched sharded problems (``Problem(batch=B)`` with mapped modes) are
    additionally argmin'd over *placements*: the mode-parallel mapping as
    given vs the all-batch-parallel remap (batch sharded over every mesh
    axis, zero reduce traffic).  The winning placement becomes
    ``SweepPlan.problem`` and both candidates' costs are recorded on
    ``SweepPlan.placements`` (surfaced by ``describe()``) -- the cost model
    proves, rather than assumes, that batch-parallel wins for fleets of
    small tensors.

    Problems with ``pp_tol > 0`` additionally price the pairwise-
    perturbation sweep mode (Ma & Solomonik): ``'auto'``/``'autotune'``
    enable it (``SweepPlan.pp``) when the amortized per-sweep seconds --
    assumed exact-sweep fraction x (exact sweep + cache build) plus the
    correction-only sweeps -- beat the exact sweep, and ``strategy='pp'``
    forces it.  The plan's schedule/executor stay the exact winner's: PP
    re-materialization sweeps run them verbatim.

    Two-level problems (``Problem.intra_axes``) plan against the
    Ballard-Knight-Rouse communication lower bound: every node's psum is
    argmin'd flat vs hierarchical, alternative mode->axis *mappings* of the
    same mesh are enumerated (divisibility-checked permutations), each
    candidate is stamped with its modeled per-node inter-node volume and
    the bound, and enumeration stops early once a candidate's volume is
    within ``certify_eps`` (relative) of the bound -- the winner then
    carries ``certified_bandwidth_optimal`` and per-leaf
    ``lower_bound_bytes`` stamps.

    ``'autotune'`` closes the predict -> measure loop: hardware timings
    recorded by :func:`repro.plan.autotune.tune` (read from
    ``tuning_cache``, defaulting to the process cache -- planning itself
    never measures) are stamped on every node cost, fully measured
    comparison sets are argmin'd on measured seconds (the Pallas ``fused``
    kernel joins the leaf candidates, carrying its tuned tiles on
    ``NodePlan.tiles``), cached ``serial_fractions`` recalibrate the
    overlap constants, and anything unmeasured keeps the analytic
    ``node_cost`` -- an empty cache degrades to exactly ``'auto'``.
    """
    if strategy not in STRATEGIES:
        raise ValueError(f"unknown strategy {strategy!r} (choose from {STRATEGIES})")
    if strategy == "pp" and problem.pp_tol <= 0.0:
        raise ValueError(
            "strategy='pp' needs Problem(pp_tol > 0): the drift threshold is "
            "part of the problem (and its signature), not a planner flag"
        )
    # "pp" forces the approximate sweep mode but still needs a full exact
    # plan (re-materialization sweeps run it verbatim); its schedule /
    # algorithm / executor choices follow the "auto" cost argmin.
    node_strategy = "auto" if strategy == "pp" else strategy
    if split is not None:
        if strategy != "dimtree" and schedule != "binary":
            raise ValueError(
                "split is only meaningful for strategy='dimtree' (or schedule='binary')"
            )
        if not 0 < split < problem.ndim:
            raise ValueError(
                f"split {split} out of range for order-{problem.ndim} tensor"
            )
    if serial_fractions is not None:
        for kind, f in dict(serial_fractions).items():
            if kind not in EXECUTORS:
                raise ValueError(
                    f"unknown executor {kind!r} in serial_fractions "
                    f"(choose from {EXECUTORS})"
                )
            if not 0.0 <= float(f) <= 1.0:
                raise ValueError(f"serial_fractions[{kind!r}] must be in [0, 1], got {f}")
    measured = None
    if strategy in ("autotune", "fused", "matrix_free"):
        # forced kernel strategies reuse the tuned tile stamps (and carry
        # any hardware timings on describe()); only autotune argmins on them
        from .autotune import lookup_measurements  # lazy: autotune plans via us

        measured = lookup_measurements(problem, cache=tuning_cache)
        if (
            measured is not None
            and serial_fractions is None
            and measured.serial_fractions
        ):
            serial_fractions = dict(measured.serial_fractions)

    # a pinned Schedule instance is bound to one Problem, so placement and
    # mapping exploration (which rebuild schedules per candidate) are off
    pinned = isinstance(schedule, Schedule)
    placements = [problem] if pinned else _placement_candidates(problem)

    def evaluate(prob):
        """One candidate problem's best (schedule, executor) row, or None
        when a forced executor kind is invalid on an alternate candidate."""
        if executor != "auto":
            try:
                validate_executor(prob, executor)
            except ValueError:
                if prob is problem:
                    raise
                return None  # forced kind invalid on the alternate candidate
            candidates = (executor,)
        elif prob.mode_axes:
            candidates = ("sharded", "overlapping")
        elif prob.batch_axes:
            # batch-parallel placements have no psums: only the plain
            # sharded executor applies (see validate_executor)
            candidates = ("sharded",)
        else:
            candidates = ("local",)

        schedules = _resolve_schedules(prob, node_strategy, split, schedule)
        results = [
            (sched,)
            + _best_executor(
                prob, sched, node_strategy, candidates, n_chunks,
                serial_fractions, measured,
            )
            for sched in schedules
        ]  # rows: (sched, executor, node_plans, analytic_total, measured_total)
        if measured is not None and all(r[4] is not None for r in results):
            # every candidate schedule fully measured: strict argmin on
            # hardware seconds -- the measurement IS the tie-breaker, so the
            # analytic flat preference does not apply
            best = min(results, key=lambda r: r[4])
        else:
            best = results[0]
            flat_row = next((r for r in results if r[0].is_flat), None)
            for r in results[1:]:
                if r[3] < best[3]:
                    best = r
            # near-tie preference: a tree must beat the flat sweep by >10% to win
            if flat_row is not None and best[0] is not flat_row[0]:
                if best[3] >= _NEAR_TIE * flat_row[3]:
                    best = flat_row
        return (prob,) + best

    def certify(row):
        """(bound, per-node inter volume, certified) of one evaluated row;
        (None, None, False) when the BKR bound does not apply to it."""
        bt = _node_bound_bytes(row[0])
        if bt is None:
            return None, None, False
        bound, _ = bt
        # per-device inter volume x devices-per-node = bytes crossing the
        # node boundary per node per sweep -- the quantity the bound limits
        inter = sum(np_.cost.inter_bytes for np_ in row[3]) * row[0].intra_shards
        return bound, inter, inter <= (1.0 + certify_eps) * bound

    picked = []  # rows: (prob, sched, executor, node_plans, analytic, measured)
    cert_rows = []  # (row, bound, inter, certified) for bound-eligible rows
    certified_found = False
    for prob in placements:
        row = evaluate(prob)
        if row is None:
            continue
        picked.append(row)
        bound, inter, ok = certify(row)
        if bound is not None:
            cert_rows.append((row, bound, inter, ok))
            certified_found = certified_found or ok
    n_placements = len(picked)  # mapping rows appended below are not placements

    # mesh-mapping enumeration (two-level problems only): evaluate
    # alternative mode->axis assignments until one certifies against the
    # communication lower bound -- skipped entirely when the as-given
    # mapping already certifies
    if not pinned and not certified_found:
        for prob in _mapping_candidates(problem):
            row = evaluate(prob)
            if row is None:
                continue
            picked.append(row)
            bound, inter, ok = certify(row)
            cert_rows.append((row, bound, inter, ok))
            if ok:
                break  # within eps of the lower bound: provably near-optimal

    # placement/mapping argmin: strict < keeps the as-given problem on ties
    winner = picked[0]
    for row in picked[1:]:
        if row[4] < winner[4]:
            winner = row
    prob, sched, chosen, node_plans = winner[0], winner[1], winner[2], winner[3]

    # certification + per-leaf lower-bound stamps for the winning problem
    lower_bound = None
    certified = False
    for row, bound, inter, ok in cert_rows:
        if row is winner:
            lower_bound, certified = bound, ok
            break
    if lower_bound is not None:
        _, terms = _node_bound_bytes(prob)
        node_plans = tuple(
            replace(np_, lower_bound_bytes=terms[np_.node.mode])
            if np_.node.is_leaf
            else np_
            for np_ in node_plans
        )
    mapping_rows = tuple(
        {
            "mode_axes": {str(k): v for k, v in row[0].mode_axes.items()},
            "executor": row[2],
            "schedule": row[1].name,
            "predicted_s": row[4],
            "inter_bytes_per_node": inter,
            "lower_bound_bytes": bound,
            "certified": ok,
            "collectives": [np_.collective for np_ in row[3]],
            "selected": row is winner,
        }
        for row, bound, inter, ok in cert_rows
    )

    # pairwise perturbation: price the approximate sweep against the chosen
    # exact plan whenever the problem opted in (pp_tol > 0); strategy="pp"
    # forces it, "auto"/"autotune" argmin the amortized per-sweep seconds.
    # The comparison runs on the measured basis only when BOTH sides are
    # measured (the winner's sweep total and the tuned PP rows) -- measured
    # and analytic seconds never compete inside one comparison.
    pp_enabled = False
    pp_info = None
    if prob.pp_tol > 0.0:
        m_build = measured.pp_second("build_s") if measured is not None else None
        m_corr = (
            measured.pp_second("correct_sweep_s") if measured is not None else None
        )
        if winner[5] is not None and m_build is not None and m_corr is not None:
            pp_info = pp_amortized_cost(
                prob, winner[5], build_s=m_build, correction_s=m_corr
            )
            pp_info["basis"] = "measured"
        else:
            pp_info = pp_amortized_cost(prob, winner[4])
            pp_info["basis"] = "analytic"
        if strategy == "pp":
            pp_enabled = True
        elif strategy in ("auto", "autotune"):
            pp_enabled = pp_info["amortized_sweep_s"] < pp_info["exact_sweep_s"]

    placement_rows = tuple(
        {
            "placement": _placement_label(r[0]),
            "mode_axes": {str(k): v for k, v in r[0].mode_axes.items()},
            "batch_axes": list(r[0].batch_axes),
            "executor": r[2],
            "schedule": r[1].name,
            "predicted_s": r[4],
            "collective_bytes": sum(np_.cost.collective_bytes for np_ in r[3]),
            "selected": r is winner,
        }
        for r in picked[:n_placements]
    ) if n_placements > 1 else ()

    modes = tuple(
        sorted(
            (
                ModePlan(np_.node.mode, np_.algorithm, np_.cost)
                for np_ in node_plans
                if np_.node.is_leaf
            ),
            key=lambda mp: mp.mode,
        )
    )
    return SweepPlan(
        prob,
        strategy,
        modes,
        split=sched.split,
        normalize=normalize,
        executor=chosen,
        schedule=sched,
        nodes=node_plans,
        serial_fractions=dict(serial_fractions) if serial_fractions else None,
        placements=placement_rows,
        pp=pp_enabled,
        pp_info=pp_info,
        mappings=mapping_rows,
        lower_bound_bytes=lower_bound,
        certified_bandwidth_optimal=certified,
    )
