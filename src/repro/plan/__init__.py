"""``repro.plan`` -- one front door: Problem -> SweepPlan -> Executor.

The solver API redesigned around four pieces:

* :class:`Problem` -- immutable descriptor (shape, rank, dtype, optional
  mode->mesh-axis mapping) every planner call keys on.
* :class:`Schedule` -- the contraction-schedule IR: a tree of
  :class:`ContractionNode` GEMMs whose leaves are the N mode updates.  The
  flat per-mode sweep and the binary dimension tree are two degenerate
  shapes (:func:`flat_schedule` / :func:`binary_schedule`); multi-level
  trees (:func:`chain_schedule`, :func:`build_schedule`) reuse partial
  contractions across levels.
* :func:`plan_sweep` -- the cost-model planner: jointly argmins the tree
  shape (:func:`enumerate_schedules`), each root leaf's MTTKRP algorithm
  (1-step / 2-step-left / 2-step-right / fused), and -- via
  :func:`select_executor` -- the executor kind (local / sharded /
  overlapping, all exact) under the bounded-overlap model, per-node
  (:func:`node_cost`); :meth:`SweepPlan.describe` exposes the predictions
  so benchmarks report predicted-vs-measured, and calibrated
  ``serial_fractions`` from ``bench_mttkrp --calibrate`` feed back in.
* :class:`Executor` -- where contractions run: :class:`LocalExecutor`
  (single device), :class:`ShardedExecutor` (``shard_map`` + minimal
  per-node psum over a device mesh), or :class:`OverlappingExecutor`
  (chunked psums hidden behind the local GEMMs -- full MTTKRPs and tree
  partials alike; exact).  :func:`make_executor` builds the instance a
  ``SweepPlan.executor`` kind names.

* :mod:`repro.plan.autotune` -- the measured side of the loop:
  :func:`tune` times candidate Pallas tilings and candidate
  (schedule x executor) plans on the attached device, a persistent
  :class:`TuningCache` (keyed by backend/shape/rank/dtype/device-count)
  remembers the winners, and ``plan_sweep(strategy="autotune")`` argmins
  over the measurements where available (stamping ``measured_s`` into
  ``SweepPlan.describe()`` and tuned tiles onto ``NodePlan.tiles``),
  falling back to the analytic model everywhere else.

* Two-level collectives (Ballard/Knight/Rouse, arXiv 1708.07401): problems
  built with ``intra_axes`` declare a fast intra-node level of the mesh;
  the cost model then prices each node psum's intra/inter traffic
  separately (:func:`collective_level_bytes`), the planner picks flat vs
  hierarchical per node (:func:`hierarchical_applicable` gates it),
  enumerates alternative mode->axis mappings, and certifies the winner
  against the per-node communication lower bound
  (:func:`mttkrp_comm_lower_bound`) -- stamped as
  ``SweepPlan.certified_bandwidth_optimal``.

* Pairwise perturbation (Ma & Solomonik, arXiv 2010.12056):
  ``Problem(pp_tol > 0)`` opts a problem into approximate sweeps that
  reuse cached pairwise intermediates (:func:`pp_pairs` describes them,
  :class:`PPState` carries them) plus first-order corrections while every
  factor's drift stays under tolerance, re-materializing exactly when one
  crosses it.  :func:`pp_amortized_cost` prices the amortized sweep so
  ``plan_sweep`` can argmin PP against the exact strategies
  (``strategy="pp"`` forces it); ``pp_tol=0`` problems never build the
  cache and stay bitwise identical to classic exact ALS.

Exactly one :func:`als_sweep` engine (a schedule walker) and one
:func:`cp_als` driver (sync-free: ``sweeps_per_sync`` sweeps per device
dispatch under ``lax.scan``, bitwise-identical iterates, returning a
:class:`CPState`) consume them.  This package is the only CP-ALS API and
the only owner of the ALS algebra: :mod:`repro.core`, :mod:`repro.dist` and
:mod:`repro.kernels` supply contractions and never import it.
"""

from .autotune import (
    Measurements,
    TuningCache,
    default_tuning_cache,
    lookup_measurements,
    tune,
)
from .cost import (
    ALGORITHMS,
    DEFAULT_OVERLAP_CHUNKS,
    EXECUTORS,
    PP_EXACT_FRACTION,
    ModeCost,
    collective_level_bytes,
    dimtree_mode_cost,
    executor_mode_cost,
    hierarchical_applicable,
    mode_cost,
    mttkrp_comm_lower_bound,
    node_cost,
    pp_amortized_cost,
    pp_build_cost,
    pp_correction_cost,
    ring_allreduce_bytes,
    validate_executor,
)
from .executor import (
    Executor,
    LocalExecutor,
    OverlappingExecutor,
    ShardedExecutor,
    make_executor,
)
from .planner import (
    SCHEDULE_NAMES,
    STRATEGIES,
    ModePlan,
    NodePlan,
    SweepPlan,
    plan_sweep,
    select_executor,
)
from .problem import Problem
from .schedule import (
    ContractionNode,
    PPPair,
    Schedule,
    binary_schedule,
    build_schedule,
    chain_schedule,
    enumerate_schedules,
    flat_schedule,
    pp_pairs,
)
from .sweep import CPState, PPState, SweepState, als_sweep, cp_als

__all__ = [
    "ALGORITHMS",
    "DEFAULT_OVERLAP_CHUNKS",
    "EXECUTORS",
    "PP_EXACT_FRACTION",
    "SCHEDULE_NAMES",
    "STRATEGIES",
    "CPState",
    "ContractionNode",
    "Executor",
    "LocalExecutor",
    "Measurements",
    "ModeCost",
    "ModePlan",
    "NodePlan",
    "OverlappingExecutor",
    "PPPair",
    "PPState",
    "Problem",
    "Schedule",
    "ShardedExecutor",
    "SweepPlan",
    "SweepState",
    "TuningCache",
    "als_sweep",
    "binary_schedule",
    "build_schedule",
    "chain_schedule",
    "collective_level_bytes",
    "cp_als",
    "default_tuning_cache",
    "dimtree_mode_cost",
    "enumerate_schedules",
    "executor_mode_cost",
    "flat_schedule",
    "hierarchical_applicable",
    "lookup_measurements",
    "make_executor",
    "mode_cost",
    "mttkrp_comm_lower_bound",
    "node_cost",
    "plan_sweep",
    "pp_amortized_cost",
    "pp_build_cost",
    "pp_correction_cost",
    "pp_pairs",
    "ring_allreduce_bytes",
    "select_executor",
    "tune",
    "validate_executor",
]
