"""Paper Figs. 5-6: 1-step vs 2-step vs reorder-baseline MTTKRP across modes.

The paper uses cubic tensors of ~750M entries with N in {3,4,5,6} and C=25.
Single-core default here is ~16M entries (--full restores paper scale); the
algorithmic comparisons (2-step beats baseline, 1-step pays the explicit-KRP
tax, baseline pays the reorder copy the paper's methods avoid) are
size-stable.  We additionally time the baseline's reorder (transpose) cost
separately -- the paper's DGEMM baseline *excludes* it, so we report both.

Each shape is also planned through ``repro.plan.plan_sweep``; the measured
rows carry the planner's predicted seconds so perf JSONs record
predicted-vs-measured, and ``--json`` emits the full ``SweepPlan.describe()``
next to the measurements.  ``--smoke`` shrinks to tiny shapes with one rep
(the CI artifact path).

The JSON additionally carries an ``overlap`` section (per-mode
predicted-vs-measured efficiency of the communication-hiding executors on a
small sharded problem) and a ``schedule`` section (per-NODE
predicted-vs-measured seconds of the auto-chosen contraction schedule on an
order-4 sharded problem -- the tree the planner argmin'd over flat / binary
/ chain shapes).  Measurements need >1 device -- run under
``XLA_FLAGS=--xla_force_host_platform_device_count=8`` as CI does;
predicted rows are emitted either way (planning is pure arithmetic).

``--calibrate`` fits per-executor ``serial_fraction`` constants from the
overlap section's measured rows (the unhidable share of the smaller
roofline term implied by each measured sharded/overlapped pair), records
them in the JSON, and re-plans through
``plan_sweep(..., serial_fractions=...)`` so the artifact also carries the
calibrated predictions -- closing the model-calibration loop.

``--autotune`` runs the measured-cost loop of ``repro.plan.autotune`` on
the first benchmark shape: candidate Pallas tilings and every candidate
plan node are timed on the attached device (wall-clock capped by
``--budget-ms``), the winners persist in the tuning cache named by
``--tuning-cache`` (in-memory when omitted; CI uploads the file as an
artifact), and the JSON gains an ``autotune`` section with tuned-vs-default
tile rows plus the measured-vs-predicted node rows of the resulting
``plan_sweep(strategy="autotune")`` plan.  The first CPU-smoke baseline is
committed in-tree as ``benchmarks/BENCH_autotune.json``.

``--pp`` adds a ``pp`` section: a >=20-sweep CP-ALS run on a planted
low-rank tensor, exact vs pairwise perturbation (``Problem.pp_tol``),
reporting end-to-end amortized per-sweep seconds for both, the measured
exact-sweep fraction (``CPState.pp_exact_sweeps / n_iters``) next to the
planner's ``PP_EXACT_FRACTION`` assumption, and the fit gap.  The first
CPU-smoke baseline is committed in-tree as ``benchmarks/BENCH_pp.json``.

``--hierarchical`` adds a ``hierarchical`` section on a 2x4 node mesh:
per mode, the modeled intra/inter bytes and predicted seconds of the flat
ring vs the two-level reduce-scatter/psum/all-gather decomposition, the
Ballard-Knight-Rouse communication lower bound with the planner's
mesh-mapping rows and ``certified`` verdict, and -- when 8 devices are
attached -- measured flat-vs-hierarchical ``dist_mttkrp`` seconds.  The
first CPU-smoke baseline is committed in-tree as
``benchmarks/BENCH_hierarchical.json``.

    PYTHONPATH=src python -m benchmarks.bench_mttkrp --smoke --calibrate \
        --autotune --budget-ms 2000 --json out.json
"""

from __future__ import annotations

import argparse
import json

import jax

from repro.core import (
    matricize,
    mttkrp,
    mttkrp_1step,
    mttkrp_2step,
    mttkrp_baseline,
    random_factors,
    random_tensor,
)
from repro.launch.mesh import make_mesh
from repro.plan import Problem, enumerate_schedules, make_executor, plan_sweep

from .util import row, time_fn

C = 25
DEFAULT_TOTAL = 16e6  # ~16M entries: single-core scale
FULL_TOTAL = 750e6  # the paper's scale (--full)
SMOKE_TOTAL = 4096  # tiny CI-artifact scale (--smoke)

# sharded problem of the overlap section: mode 0 rides the single mesh axis,
# so every other mode's MTTKRP psums over it (the hidable collective)
OVERLAP_SHAPE = (8, 32, 8)
OVERLAP_RANK = 8

# order-4 problem of the schedule section: big enough for the planner to
# enumerate flat / binary@{1,2,3} / chain and pick a real tree
SCHEDULE_SHAPE = (8, 6, 4, 4)
SCHEDULE_RANK = 8

# per-problem tensor of the batched section: deliberately small -- a fleet
# of these is the regime where batch-parallel beats mode-parallel sharding
BATCHED_SHAPE = (16, 16, 16)
BATCHED_RANK = 8
BATCHED_ITERS = 3

# hierarchical section: the CI node mesh -- 2 nodes x 4 devices, mode 0 on
# the inter-node axis, mode 2 on the fast intra-node axis
HIER_SHAPE = (8, 6, 4, 5)
HIER_RANK = 7
HIER_NODES = 2
HIER_DEVICES_PER_NODE = 4

# pp section: big enough that the correction sweep's O(sum I_n*I_m*C) work
# is clearly cheaper than the exact MTTKRP's O(prod I * C); a planted
# low-rank tensor keeps the drift small so most sweeps ride the cache
PP_SHAPE = (128, 128, 128)
PP_RANK = 32
PP_ITERS = 40
PP_TOL = 0.05
PP_INIT_NOISE = 0.05  # refinement regime: init = planted factors + noise


def overlap_section(reps: int) -> dict:
    """Predicted-vs-measured overlap efficiency of the sharded executors.

    Predictions come straight from the bounded-overlap cost model (computed
    even without devices -- capacity-planning style, assuming 8 shards when
    no multi-device runtime is attached).  Measurements time the plain vs
    overlapped dist_mttkrp per mode when the runtime has >1 device.
    """
    from repro.dist.dist_mttkrp import (
        dist_mttkrp,
        dist_mttkrp_overlapped,
        shard_problem,
    )

    n_dev = jax.device_count()
    shards = n_dev if n_dev > 1 and OVERLAP_SHAPE[0] % n_dev == 0 else 8
    mode_axes = {0: "shard"}
    problem = Problem(
        shape=OVERLAP_SHAPE, rank=OVERLAP_RANK,
        mode_axes=mode_axes, axis_sizes={"shard": shards},
    )
    # flat schedule: these are per-MODE rows (tree shapes get their own
    # per-node section below)
    plans = {
        ex: plan_sweep(problem, schedule="flat", executor=ex)
        for ex in ("sharded", "overlapping")
    }
    rows = []
    for n in range(len(OVERLAP_SHAPE)):
        sh, ov = plans["sharded"].modes[n], plans["overlapping"].modes[n]
        pred_sh, pred_ov = sh.cost.predicted_s, ov.cost.predicted_s
        rows.append({
            "mode": n,
            "algorithm": ov.algorithm,
            # model internals: fraction of the hidable (smaller) term hidden
            "predicted_overlap_efficiency": ov.cost.predicted_overlap_efficiency,
            # the directly measurable quantity the saving rows compare against
            "predicted_saving_vs_sharded": (pred_sh - pred_ov) / pred_sh,
            "predicted_s_sharded": pred_sh,
            "predicted_s_overlapping": pred_ov,
            "measured_s_sharded": None,
            "measured_s_overlapping": None,
            "measured_saving_vs_sharded": None,
        })
    measured = n_dev > 1 and OVERLAP_SHAPE[0] % n_dev == 0
    if measured:
        mesh = make_mesh((n_dev,), ("shard",))
        x = random_tensor(jax.random.PRNGKey(2), OVERLAP_SHAPE)
        factors = random_factors(jax.random.PRNGKey(3), OVERLAP_SHAPE, OVERLAP_RANK)
        xs, fs = shard_problem(x, factors, mode_axes, mesh)
        for r in rows:
            n = r["mode"]
            t_sh = time_fn(
                jax.jit(lambda t, fl, m=n: dist_mttkrp(t, fl, m, mode_axes, mesh)),
                xs, fs, reps=reps,
            )["median_s"]
            t_ov = time_fn(
                jax.jit(lambda t, fl, m=n: dist_mttkrp_overlapped(t, fl, m, mode_axes, mesh)),
                xs, fs, reps=reps,
            )["median_s"]
            r["measured_s_sharded"] = t_sh
            r["measured_s_overlapping"] = t_ov
            # realized saving as a fraction of the no-overlap time -- the
            # same quantity predicted_saving_vs_sharded models
            r["measured_saving_vs_sharded"] = (t_sh - t_ov) / t_sh if t_sh > 0 else None
    return {
        "shape": list(OVERLAP_SHAPE),
        "rank": OVERLAP_RANK,
        "shards": shards,
        "measured": measured,
        "selected_executor": plan_sweep(problem).executor,
        "modes": rows,
    }


def schedule_section(reps: int) -> dict:
    """Predicted-vs-measured seconds per contraction-schedule NODE.

    Plans the order-4 sharded problem with the full joint argmin (tree
    shape x executor), then -- when the runtime has a matching multi-device
    mesh -- walks the chosen schedule exactly like the sweep engine does,
    timing each node's ``executor.contract`` against its ``NodePlan``
    prediction.  Internal nodes' outputs are cached so children time the
    real reuse path.
    """
    n_dev = jax.device_count()
    shards = n_dev if n_dev > 1 and SCHEDULE_SHAPE[0] % n_dev == 0 else 8
    mode_axes = {0: "shard"}
    problem = Problem(
        shape=SCHEDULE_SHAPE, rank=SCHEDULE_RANK,
        mode_axes=mode_axes, axis_sizes={"shard": shards},
    )
    plan = plan_sweep(problem)
    sched = plan.resolved_schedule
    rows = [
        {
            "node": np_.node.id,
            "parent": np_.node.parent,
            "modes": list(np_.node.modes),
            "contracted": list(np_.node.contracted),
            "reduce_axes": list(np_.node.reduce_axes),
            "algorithm": np_.algorithm,
            "predicted_s": np_.cost.predicted_s,
            "measured_s": None,
        }
        for np_ in plan.nodes
    ]
    measured = n_dev > 1 and SCHEDULE_SHAPE[0] % n_dev == 0
    if measured:
        from repro.dist.dist_mttkrp import shard_problem

        mesh = make_mesh((n_dev,), ("shard",))
        x = random_tensor(jax.random.PRNGKey(4), SCHEDULE_SHAPE)
        factors = random_factors(jax.random.PRNGKey(5), SCHEDULE_SHAPE, SCHEDULE_RANK)
        xs, fs = shard_problem(x, factors, mode_axes, mesh)
        executor = make_executor(plan.executor, mesh, mode_axes)
        cache = {sched.root.id: xs}
        for r, node in zip(rows, sched.walk()):
            src = cache[node.parent]
            alg = r["algorithm"]
            fn = jax.jit(
                lambda s, f, node=node, alg=alg: executor.contract(node, s, f, alg)
            )
            r["measured_s"] = time_fn(fn, src, fs, reps=reps)["median_s"]
            out = fn(src, fs)
            if not node.is_leaf:
                cache[node.id] = out
    return {
        "shape": list(SCHEDULE_SHAPE),
        "rank": SCHEDULE_RANK,
        "shards": shards,
        "measured": measured,
        "schedule": sched.name,
        "executor": plan.executor,
        "n_candidates": len(enumerate_schedules(problem)),
        "nodes": rows,
    }


def batched_section(batch: int, reps: int) -> dict:
    """Problems/sec of one fused batched ``cp_als`` dispatch over a fleet.

    Plans a fleet of ``batch`` same-shaped small tensors *given* a
    mode-parallel sharding and records the planner's placement argmin (for a
    small-tensor fleet it should re-place batch-parallel: B independent
    problems need zero reduce traffic, vs psum volume x B mode-parallel) --
    the ``placements`` rows carry both candidates' predicted seconds and
    collective bytes straight from ``SweepPlan.describe()``.  Then times the
    batched driver end-to-end (one compiled dispatch per sweep chunk,
    ``sweeps_per_sync`` = all sweeps) and reports amortized per-problem ms
    and problems/sec; when the runtime has a matching device fleet the
    batch-parallel ``shard_map`` run is timed alongside the local one.
    """
    import time as _time

    from repro.core.tensor_ops import random_factors as _rf
    from repro.plan import cp_als

    n_dev = jax.device_count()
    shards = n_dev if n_dev > 1 and batch % n_dev == 0 else 8
    given = Problem(
        shape=BATCHED_SHAPE, rank=BATCHED_RANK, batch=batch,
        mode_axes={0: "shard"}, axis_sizes={"shard": shards},
    )
    plan = plan_sweep(given)
    desc = plan.describe()

    def _time_run(x, run_plan, executor=None):
        init = _rf(jax.random.PRNGKey(9), BATCHED_SHAPE, BATCHED_RANK, batch=batch)
        # warmup compiles; timed runs then measure steady-state dispatches
        cp_als(x, run_plan, executor=executor, n_iters=BATCHED_ITERS, tol=0.0,
               init_factors=init, sweeps_per_sync=BATCHED_ITERS)
        best = None
        for _ in range(max(1, reps)):
            t0 = _time.perf_counter()
            cp_als(x, run_plan, executor=executor, n_iters=BATCHED_ITERS, tol=0.0,
                   init_factors=init, sweeps_per_sync=BATCHED_ITERS)
            dt = _time.perf_counter() - t0
            best = dt if best is None else min(best, dt)
        return best

    x = random_tensor(jax.random.PRNGKey(8), (batch,) + BATCHED_SHAPE)
    local_plan = plan_sweep(Problem(shape=BATCHED_SHAPE, rank=BATCHED_RANK, batch=batch))
    t_local = _time_run(x, local_plan)
    out = {
        "batch": batch,
        "shape": list(BATCHED_SHAPE),
        "rank": BATCHED_RANK,
        "n_iters": BATCHED_ITERS,
        "placement": desc["placement"],
        "placements": desc["placements"],
        "local": {
            "total_s": t_local,
            "problems_per_s": batch / t_local,
            "amortized_ms_per_problem": 1e3 * t_local / batch,
        },
        "batch_parallel": None,
    }
    if n_dev > 1 and batch % n_dev == 0:
        mesh = make_mesh((n_dev,), ("shard",))
        bp = Problem(
            shape=BATCHED_SHAPE, rank=BATCHED_RANK, batch=batch,
            batch_axes=("shard",), axis_sizes={"shard": n_dev},
        )
        bp_plan = plan_sweep(bp)
        executor = make_executor(bp_plan.executor, mesh, {}, batch_axes=("shard",))
        t_bp = _time_run(x, bp_plan, executor=executor)
        out["batch_parallel"] = {
            "devices": n_dev,
            "total_s": t_bp,
            "problems_per_s": batch / t_bp,
            "amortized_ms_per_problem": 1e3 * t_bp / batch,
        }
    return out


def pp_section(reps: int) -> dict:
    """Exact vs pairwise-perturbation CP-ALS on a planted low-rank tensor.

    Times both drivers end-to-end over ``PP_ITERS`` (>= 20) sweeps with the
    hot loop fully sync-free (``sweeps_per_sync`` = all sweeps) and reports
    amortized per-sweep seconds, the *measured* exact-sweep fraction
    (``CPState.pp_exact_sweeps / n_iters``) next to the planner's
    ``PP_EXACT_FRACTION`` planning assumption and its full analytic pricing
    row (``SweepPlan.describe()["pp"]``), and the final-fit gap between the
    two runs -- the accuracy cost of approximating most sweeps.
    """
    import time as _time

    from repro.core import cp_full
    from repro.plan import PP_EXACT_FRACTION, cp_als

    true = random_factors(jax.random.PRNGKey(11), PP_SHAPE, PP_RANK)
    x = cp_full(None, true)
    x = x + 1e-3 * random_tensor(jax.random.PRNGKey(12), PP_SHAPE)
    # start inside the convergence basin (planted factors + small noise):
    # PP targets the refinement phase, where ALS steps settle quickly and
    # nearly every sweep can ride the cached pairwise contractions
    pert = random_factors(jax.random.PRNGKey(13), PP_SHAPE, PP_RANK)
    init = [t + PP_INIT_NOISE * p for t, p in zip(true, pert)]

    exact_plan = plan_sweep(Problem(shape=PP_SHAPE, rank=PP_RANK))
    pp_prob = Problem(shape=PP_SHAPE, rank=PP_RANK, pp_tol=PP_TOL)
    pp_plan = plan_sweep(pp_prob, strategy="pp")

    def _run(run_plan, key):
        state = best = None
        cache: dict = {}
        # first call compiles into the dispatch cache; timed calls reuse
        # the compiled chunk and measure steady-state sweep dispatches
        for i in range(max(1, reps) + 1):
            t0 = _time.perf_counter()
            state = cp_als(
                x, run_plan, n_iters=PP_ITERS, tol=0.0,
                init_factors=list(init), sweeps_per_sync=PP_ITERS,
                dispatch_cache=cache, dispatch_key=key,
            )
            dt = _time.perf_counter() - t0
            if i > 0:
                best = dt if best is None else min(best, dt)
        return state, best

    st_exact, t_exact = _run(exact_plan, "exact")
    st_pp, t_pp = _run(pp_plan, pp_prob.signature())
    exact_fraction = st_pp.pp_exact_sweeps / PP_ITERS
    return {
        "shape": list(PP_SHAPE),
        "rank": PP_RANK,
        "n_iters": PP_ITERS,
        "pp_tol": PP_TOL,
        "exact": {
            "total_s": t_exact,
            "per_sweep_s": t_exact / PP_ITERS,
            "fit": float(st_exact.fit),
        },
        "pp": {
            "total_s": t_pp,
            "per_sweep_s": t_pp / PP_ITERS,
            "fit": float(st_pp.fit),
            "exact_sweeps": int(st_pp.pp_exact_sweeps),
            "exact_fraction_measured": exact_fraction,
            "exact_fraction_assumed": PP_EXACT_FRACTION,
        },
        "speedup": t_exact / t_pp,
        "fit_gap": abs(float(st_exact.fit) - float(st_pp.fit)),
        "plan_pp_info": dict(pp_plan.describe()["pp"]),
    }


def hierarchical_section(reps: int) -> dict:
    """Flat vs hierarchical collectives per mode: measured ms + modeled bytes.

    Plans the order-4 problem on the two-level ``(2 nodes x 4 devices)``
    mesh with ``intra_axes=("device",)`` and records, per mode, the cost
    model's intra/inter byte split under both collectives, the planner's
    per-node pick, the per-mode Ballard-Knight-Rouse lower-bound term, and
    -- when the runtime has the matching 8-device mesh -- the measured
    flat-vs-hierarchical ``dist_mttkrp`` milliseconds head-to-head.  The
    section also carries the plan-level certification verdict and the
    mapping-enumeration rows straight from ``SweepPlan.describe()``.
    """
    from repro.dist.dist_mttkrp import dist_mttkrp, shard_problem
    from repro.plan import mode_cost

    n_dev = jax.device_count()
    mode_axes = {0: "node", 2: "device"}
    problem = Problem(
        shape=HIER_SHAPE, rank=HIER_RANK, mode_axes=mode_axes,
        axis_sizes={"node": HIER_NODES, "device": HIER_DEVICES_PER_NODE},
        intra_axes=("device",),
    )
    # flat schedule: per-MODE rows, one leaf per mode (tree shapes would
    # interleave partial contractions into the comparison)
    plan = plan_sweep(problem, executor="auto", schedule="flat")
    desc = plan.describe()
    rows = []
    for np_ in plan.nodes:
        n = np_.node.mode
        flat_c = mode_cost(problem, n, np_.algorithm)
        hier_c = mode_cost(problem, n, np_.algorithm, collective="hierarchical")
        rows.append({
            "mode": n,
            "algorithm": np_.algorithm,
            "collective": np_.collective,  # the planner's per-node pick
            "lower_bound_bytes": np_.lower_bound_bytes,
            "flat": {
                "intra_bytes": flat_c.intra_bytes,
                "inter_bytes": flat_c.inter_bytes,
                "predicted_s": flat_c.predicted_s,
                "measured_s": None,
            },
            "hierarchical": {
                "intra_bytes": hier_c.intra_bytes,
                "inter_bytes": hier_c.inter_bytes,
                "predicted_s": hier_c.predicted_s,
                "measured_s": None,
            },
        })
    measured = n_dev == HIER_NODES * HIER_DEVICES_PER_NODE
    if measured:
        from repro.launch.mesh import make_node_mesh

        mesh = make_node_mesh(HIER_NODES, HIER_DEVICES_PER_NODE)
        x = random_tensor(jax.random.PRNGKey(14), HIER_SHAPE)
        factors = random_factors(jax.random.PRNGKey(15), HIER_SHAPE, HIER_RANK)
        xs, fs = shard_problem(x, factors, mode_axes, mesh)
        for r in rows:
            n = r["mode"]
            r["flat"]["measured_s"] = time_fn(
                jax.jit(lambda t, fl, m=n: dist_mttkrp(t, fl, m, mode_axes, mesh)),
                xs, fs, reps=reps,
            )["median_s"]
            r["hierarchical"]["measured_s"] = time_fn(
                jax.jit(
                    lambda t, fl, m=n: dist_mttkrp(
                        t, fl, m, mode_axes, mesh,
                        collective="hierarchical", node_axis="device",
                    )
                ),
                xs, fs, reps=reps,
            )["median_s"]
    return {
        "shape": list(HIER_SHAPE),
        "rank": HIER_RANK,
        "mesh": {"nodes": HIER_NODES, "devices_per_node": HIER_DEVICES_PER_NODE},
        "mode_axes": {str(k): v for k, v in mode_axes.items()},
        "measured": measured,
        "executor": plan.executor,
        "lower_bound_bytes": desc["lower_bound_bytes"],
        "certified": desc["certified"],
        "mappings": desc["mappings"],
        "modes": rows,
    }


def calibrate_serial_fractions(overlap: dict) -> dict:
    """Fit per-executor ``serial_fraction`` from measured overlap rows.

    The bounded-overlap model says ``t_sharded - t_overlapped =
    (1 - f) * min(compute_s, collective_s)``: each measured mode pair gives
    one estimate of the overlapping executor's unhidable fraction ``f``
    (clamped to [0, 1]; on CPU test fleets the collective is a memcpy and
    the fit mostly documents noise -- on real ICI it is the constant the
    model needs).  Returns ``{executor: fitted}`` with the plain sharded
    executor pinned at its defining 1.0; empty when nothing was measured.
    """
    fits = []
    for r in overlap["modes"]:
        t_sh, t_ov = r.get("measured_s_sharded"), r.get("measured_s_overlapping")
        if t_sh is None or t_ov is None:
            continue
        # recover the model's hidable term min(compute, collective) from the
        # two predictions: pred_sh = max + min and pred_ov = max + f*min, so
        # pred_sh - pred_ov = (1 - f) * min -- and (1 - f) is exactly the
        # row's predicted_overlap_efficiency
        efficiency = r["predicted_overlap_efficiency"]
        if efficiency <= 0.0:
            continue
        min_term = (r["predicted_s_sharded"] - r["predicted_s_overlapping"]) / efficiency
        if min_term <= 0.0:
            continue
        f = 1.0 - (t_sh - t_ov) / min_term
        fits.append(min(1.0, max(0.0, f)))
    if not fits:
        return {}
    fits.sort()
    fitted = fits[len(fits) // 2]  # median: robust to one noisy mode
    return {"sharded": 1.0, "overlapping": fitted}


def _dims(n: int, total: float) -> tuple[int, ...]:
    d = round(total ** (1.0 / n))
    return (d,) * n


def autotune_section(
    total: float, reps: int, budget_ms: float, cache_path: str | None
) -> dict:
    """Tuned-vs-default tile rows + measured-vs-predicted autotune-plan rows.

    Runs :func:`repro.plan.autotune.tune` on the order-3 benchmark shape
    (tile candidates for both Pallas kernels, then every node of every
    candidate (schedule x executor) plan, budget-capped), persists the
    winners in ``cache_path`` when given, and re-plans through
    ``plan_sweep(strategy="autotune")`` so the section records exactly what
    the measured argmin chose -- per node, with the analytic prediction
    kept alongside the measurement.
    """
    from repro.plan.autotune import TuningCache, problem_key, tune

    shape = _dims(3, total)
    cache = TuningCache(cache_path)
    x = random_tensor(jax.random.PRNGKey(6), shape)
    factors = random_factors(jax.random.PRNGKey(7), shape, C)
    entry = tune(x, C, factors=factors, cache=cache, budget_ms=budget_ms, reps=reps)
    problem = Problem(shape=shape, rank=C, dtype="float32")
    plan = plan_sweep(problem, strategy="autotune", tuning_cache=cache)
    tile_rows = {
        kernel: {
            "tuned": {
                k: v for k, v in info.items() if k in ("block_i", "block_b")
            },
            "default_s": info["default_s"],
            "tuned_s": info["tuned_s"],
            "speedup_vs_default": info["speedup_vs_default"],
            "rows": info["rows"],
        }
        for kernel, info in entry["tiles"].items()
    }
    node_rows = [
        {
            "node": np_.node.id,
            "modes": list(np_.node.modes),
            "algorithm": np_.algorithm,
            "tiles": dict(np_.tiles) if np_.tiles else None,
            "predicted_s": np_.cost.predicted_s,
            "measured_s": np_.cost.measured_s,
        }
        for np_ in plan.nodes
    ]
    return {
        "shape": list(shape),
        "rank": C,
        "budget_ms": budget_ms,
        "elapsed_ms": entry["elapsed_ms"],
        "cache_key": problem_key(problem),
        "cache_path": cache_path,
        "n_measured_nodes": len(entry["nodes"]),
        "serial_fractions": entry["serial_fractions"],
        "tiles": tile_rows,
        "plan": {
            "strategy": "autotune",
            "schedule": plan.resolved_schedule.name,
            "executor": plan.executor,
            "nodes": node_rows,
        },
    }


def collect(
    full: bool = False,
    smoke: bool = False,
    calibrate: bool = False,
    autotune: bool = False,
    budget_ms: float = 2000.0,
    tuning_cache: str | None = None,
    batch: int = 0,
    pp: bool = False,
    hierarchical: bool = False,
) -> dict:
    """Measure all shapes; returns {"plans": [...], "results": [...]}."""
    if full and smoke:
        raise ValueError("--full and --smoke are mutually exclusive")
    total = FULL_TOTAL if full else (SMOKE_TOTAL if smoke else DEFAULT_TOTAL)
    reps = 1 if smoke else 3
    plans: list[dict] = []
    results: list[dict] = []

    def rec(name: str, seconds: float, derived: str = "") -> None:
        results.append({"name": name, "median_s": seconds, "derived": derived})

    for n_modes in (3, 4, 5, 6):
        shape = _dims(n_modes, total)
        # flat schedule: the rows below time per-mode MTTKRP algorithms
        # head-to-head (tree schedules get the dedicated section)
        plan = plan_sweep(Problem(shape=shape, rank=C, dtype="float32"), schedule="flat")
        plans.append(plan.describe())
        x = random_tensor(jax.random.PRNGKey(0), shape)
        factors = random_factors(jax.random.PRNGKey(1), shape, C)
        # reorder cost: what the straightforward approach pays before DGEMM
        for mp in plan.modes:
            mode = mp.mode
            reorder = jax.jit(lambda t, m=mode: matricize(t, m))
            t_reorder = time_fn(reorder, x, reps=reps)["median_s"]
            t_base = time_fn(
                jax.jit(lambda t, fs, m=mode: mttkrp_baseline(t, fs, m)),
                x, factors, reps=reps,
            )["median_s"]
            t_1step = time_fn(
                jax.jit(lambda t, fs, m=mode: mttkrp_1step(t, fs, m)),
                x, factors, reps=reps,
            )["median_s"]
            rec(f"mttkrp_N{n_modes}_mode{mode}_baseline", t_base,
                f"reorder_s={t_reorder:.4f}")
            rec(f"mttkrp_N{n_modes}_mode{mode}_1step", t_1step,
                f"vs_baseline={t_base/t_1step:.2f}x")
            t_2step = None
            if 0 < mode < n_modes - 1:
                t_2step = time_fn(
                    jax.jit(lambda t, fs, m=mode: mttkrp_2step(t, fs, m)),
                    x, factors, reps=reps,
                )["median_s"]
                rec(f"mttkrp_N{n_modes}_mode{mode}_2step", t_2step,
                    f"vs_baseline={t_base/t_2step:.2f}x")
            # the planner's pick, with its prediction alongside the measurement
            # (reuse the timing above when the pick is a variant already timed:
            # auto's 2step order equals mttkrp_2step's own order rule)
            if mp.algorithm == "1step":
                t_plan = t_1step
            elif mp.algorithm.startswith("2step") and t_2step is not None:
                t_plan = t_2step
            else:
                t_plan = time_fn(
                    jax.jit(lambda t, fs, m=mode, a=mp.algorithm: mttkrp(t, fs, m, method=a)),
                    x, factors, reps=reps,
                )["median_s"]
            rec(f"mttkrp_N{n_modes}_mode{mode}_planned", t_plan,
                f"alg={mp.algorithm};predicted_s={mp.cost.predicted_s:.3e}")
    overlap = overlap_section(reps)
    for r in overlap["modes"]:
        if r["measured_saving_vs_sharded"] is not None:
            rec(
                f"dist_mttkrp_overlap_mode{r['mode']}",
                r["measured_s_overlapping"],
                f"measured_saving={r['measured_saving_vs_sharded']:.2f};"
                f"predicted_saving={r['predicted_saving_vs_sharded']:.2f}",
            )
    schedule = schedule_section(reps)
    for r in schedule["nodes"]:
        if r["measured_s"] is not None:
            rec(
                f"schedule_{schedule['schedule']}_node{r['node']}",
                r["measured_s"],
                f"alg={r['algorithm']};predicted_s={r['predicted_s']:.3e}",
            )
    data = {
        "smoke": smoke, "full": full, "rank": C,
        "plans": plans, "results": results, "overlap": overlap,
        "schedule": schedule,
    }
    if batch > 1:
        bt = batched_section(batch, reps)
        rec(
            f"batched_cp_als_B{batch}_local",
            bt["local"]["total_s"],
            f"problems_per_s={bt['local']['problems_per_s']:.1f};"
            f"amortized_ms={bt['local']['amortized_ms_per_problem']:.3f};"
            f"placement={bt['placement']}",
        )
        if bt["batch_parallel"] is not None:
            rec(
                f"batched_cp_als_B{batch}_batch_parallel",
                bt["batch_parallel"]["total_s"],
                f"problems_per_s={bt['batch_parallel']['problems_per_s']:.1f};"
                f"amortized_ms={bt['batch_parallel']['amortized_ms_per_problem']:.3f}",
            )
        data["batched"] = bt
    if hierarchical:
        hs = hierarchical_section(reps)
        for r in hs["modes"]:
            if r["hierarchical"]["measured_s"] is not None:
                rec(
                    f"dist_mttkrp_hier_mode{r['mode']}",
                    r["hierarchical"]["measured_s"],
                    f"flat_s={r['flat']['measured_s']:.3e};"
                    f"picked={r['collective']};"
                    f"inter_bytes={r['hierarchical']['inter_bytes']:.0f}"
                    f"_vs_{r['flat']['inter_bytes']:.0f}",
                )
        data["hierarchical"] = hs
    if pp:
        ps = pp_section(reps)
        rec(
            "cp_als_exact_sweep", ps["exact"]["per_sweep_s"],
            f"fit={ps['exact']['fit']:.5f}",
        )
        rec(
            "cp_als_pp_sweep_amortized", ps["pp"]["per_sweep_s"],
            f"vs_exact={ps['speedup']:.2f}x;"
            f"exact_fraction={ps['pp']['exact_fraction_measured']:.3f};"
            f"fit_gap={ps['fit_gap']:.2e}",
        )
        data["pp"] = ps
    if autotune:
        at = autotune_section(total, reps, budget_ms, tuning_cache)
        for kernel, info in at["tiles"].items():
            rec(
                f"autotune_{kernel}_tuned",
                info["tuned_s"],
                f"tiles={info['tuned']};default_s={info['default_s']:.3e};"
                f"speedup={info['speedup_vs_default']:.2f}x",
            )
        for r in at["plan"]["nodes"]:
            if r["measured_s"] is not None:
                rec(
                    f"autotune_plan_node{r['node']}",
                    r["measured_s"],
                    f"alg={r['algorithm']};predicted_s={r['predicted_s']:.3e}",
                )
        data["autotune"] = at
    if calibrate:
        fitted = calibrate_serial_fractions(overlap)
        calibration = {"serial_fractions": fitted, "source": "overlap.modes measured rows"}
        if fitted:
            # the acceptance loop: fitted constants feed straight back into
            # the planner and the calibrated predictions land in the artifact
            problem = Problem(
                shape=tuple(overlap["shape"]), rank=overlap["rank"],
                mode_axes={0: "shard"}, axis_sizes={"shard": overlap["shards"]},
            )
            replanned = plan_sweep(
                problem, schedule="flat", executor="overlapping",
                serial_fractions=fitted,
            )
            calibration["replanned"] = {
                "executor": replanned.executor,
                "serial_fractions": dict(replanned.serial_fractions),
                "predicted_s_overlapping_fitted": [
                    m.cost.predicted_s for m in replanned.modes
                ],
            }
        data["calibration"] = calibration
    return data


def run(full: bool = False, smoke: bool = False) -> list[str]:
    data = collect(full, smoke)
    return [row(r["name"], r["median_s"], r["derived"]) for r in data["results"]]


def main() -> None:
    ap = argparse.ArgumentParser()
    scale = ap.add_mutually_exclusive_group()
    scale.add_argument("--full", action="store_true", help="paper-scale shapes")
    scale.add_argument("--smoke", action="store_true", help="tiny shapes, 1 rep")
    ap.add_argument("--calibrate", action="store_true",
                    help="fit per-executor serial_fraction from the measured "
                         "overlap rows and record it (with calibrated "
                         "re-predictions) in the JSON")
    ap.add_argument("--autotune", action="store_true",
                    help="run the measured-cost loop (tile + plan-node "
                         "timings via repro.plan.autotune.tune) and record "
                         "tuned-vs-default / measured-vs-predicted rows")
    ap.add_argument("--budget-ms", type=float, default=2000.0, metavar="MS",
                    help="wall-clock cap for --autotune measurements "
                         "(compile time included; default 2000)")
    ap.add_argument("--tuning-cache", default=None, metavar="PATH",
                    help="persist --autotune winners in this TuningCache "
                         "file (in-memory when omitted)")
    ap.add_argument("--batch", type=int, default=0, metavar="B",
                    help="time one fused batched cp_als dispatch over a fleet "
                         "of B small tensors (problems/sec + amortized "
                         "per-problem ms; records the planner's "
                         "batch-vs-mode placement argmin in the JSON)")
    ap.add_argument("--pp", action="store_true",
                    help="time a >=20-sweep exact-vs-pairwise-perturbation "
                         "cp_als run (amortized per-sweep seconds, measured "
                         "exact-sweep fraction, fit gap)")
    ap.add_argument("--hierarchical", action="store_true",
                    help="price + (on an 8-device mesh) time flat vs "
                         "hierarchical two-level collectives per mode, with "
                         "the BKR lower bound and mapping certification")
    ap.add_argument("--json", default=None, metavar="PATH",
                    help="also write measurements + SweepPlan.describe() as JSON")
    args = ap.parse_args()
    data = collect(
        full=args.full, smoke=args.smoke, calibrate=args.calibrate,
        autotune=args.autotune, budget_ms=args.budget_ms,
        tuning_cache=args.tuning_cache, batch=args.batch, pp=args.pp,
        hierarchical=args.hierarchical,
    )
    for r in data["results"]:
        print(row(r["name"], r["median_s"], r["derived"]))
    if args.calibrate:
        fitted = data["calibration"]["serial_fractions"]
        print(f"# calibrated serial_fractions: {fitted or 'n/a (no measurements)'}")
    if args.json:
        with open(args.json, "w") as f:
            json.dump(data, f, indent=1)
        print(f"# wrote {args.json}")


if __name__ == "__main__":
    main()
