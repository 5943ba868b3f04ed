"""Hardware-measured autotuning: the predict -> measure loop, closed.

The analytic model of :mod:`repro.plan.cost` compares candidates against
*nominal* roofline constants -- good enough to reproduce the paper's
Sec. 5.3.3 dispatch, blind to everything the constants miss (cache effects,
interpreter overhead, real collective latency, Pallas tile efficiency).
This module measures instead of predicting, the way the paper's Sec. 5
benchmarking drives its recommendation:

* :func:`tune` times, on the actual attached device, (a) candidate Pallas
  tilings for ``fused_mttkrp`` / ``multi_ttv`` and (b) every contraction
  node of every candidate (schedule x executor) plan, under a wall-clock
  ``budget_ms`` cap;
* :class:`TuningCache` persists the winners on disk -- keyed by
  ``(backend, shape, rank, dtype, n_devices)`` via :func:`problem_key` --
  with in-memory memoization, so tuning cost is paid once per
  (hardware, problem) pair;
* ``plan_sweep(strategy="autotune")`` resolves the cache through
  :func:`lookup_measurements` and argmins over measurements where a
  comparison set is fully measured, the analytic ``node_cost`` elsewhere;
  measured node times are stamped on ``ModeCost.measured_s`` (and therefore
  in ``SweepPlan.describe()``), tuned tile configs land on
  ``NodePlan.tiles``, and measured sharded/overlapping pairs recalibrate
  the ``serial_fractions`` overlap constants.

Measurement never happens implicitly: ``plan_sweep`` only ever *reads* the
cache (CI and cold starts fall back cleanly to the analytic model); only an
explicit :func:`tune` call runs kernels.
"""

from __future__ import annotations

import json
import math
import os
import time
from dataclasses import dataclass, field
from pathlib import Path
from typing import Any, Callable, Mapping, Sequence

import jax
import jax.numpy as jnp

from repro.core.tensor_ops import dims_split, random_factors, tensor_norm

from .problem import Problem
from .schedule import ROOT, ContractionNode

Array = jax.Array

# Environment variable naming the on-disk cache file of the process-default
# cache (see default_tuning_cache); unset/empty means in-memory only.
CACHE_ENV = "REPRO_TUNING_CACHE"

# Candidate (block_i, block_b, block_batch) tilings for the fused MTTKRP
# kernel.  The default (128, 256, 8) is always measured first; the rest
# bracket it along both matmul axes (MXU-aligned multiples of 128 plus the
# half-tile 64, the small end for short modes).  block_batch sizes the
# batched kernel's leading grid axis and is inert for unbatched problems
# (effective batch tile 1), so dedup on the effective tile keeps unbatched
# tuning timing exactly the same candidate set as before.  Candidates are
# capped by the actual dims and deduped on the effective tile, so tiny
# problems time only what differs.
FUSED_TILE_CANDIDATES = (
    (128, 256, 8),  # the long-standing hard-coded default
    (64, 128, 8),
    (128, 128, 8),
    (256, 256, 8),
    (128, 512, 8),
    (256, 512, 8),
)

# Candidate (block_i, block_r, block_batch) tilings for the matrix-free
# MTTKRP kernel (default (128, 8, 8) first).  block_i sizes the target-mode
# output block held in VMEM, block_r caps every reduction-mode block (the
# wrapper shrinks further when the tensor tile would blow the VMEM budget),
# block_batch slabs the batched kernel's leading grid axis (inert unbatched).
MATRIX_FREE_TILE_CANDIDATES = (
    (128, 8, 8),
    (64, 8, 8),
    (128, 16, 8),
    (256, 8, 8),
    (64, 16, 8),
    (128, 4, 8),
)

# Candidate block_i tilings for the multi-TTV kernel (default 256 first).
TTV_TILE_CANDIDATES = (256, 64, 128, 512)

# Leaf algorithms the tuner measures head-to-head for a full mode-n MTTKRP.
# "fused" and "matrix_free" are measured only on the local executor (the
# Pallas kernels are single-device objects; sharded executors dispatch
# per-mode methods).
_LEAF_ALGORITHMS = ("1step", "2step-left", "2step-right", "fused", "matrix_free")
_EXTERNAL_LEAF_ALGORITHMS = ("1step", "fused", "matrix_free")
_KERNEL_LEAF_ALGORITHMS = ("fused", "matrix_free")


def backend_name() -> str:
    """The jax backend measurements are valid for (``cpu``/``gpu``/``tpu``)."""
    return str(jax.default_backend())


def problem_key(
    problem: Problem, *, backend: str | None = None, n_devices: int | None = None
) -> str:
    """Cache key of one (hardware, problem) pair.

    ``backend|shape|rank|dtype|devices``: measurements are only comparable
    on the same backend, for the same global shape/rank/dtype, on the same
    device count (the per-device blocks and collectives change with it).
    ``n_devices`` defaults to the product of the problem's mesh axis sizes
    (1 when unsharded) -- NOT the runtime device count, so plans for
    detached hardware key consistently.

    The construction itself is :meth:`repro.plan.problem.Problem.signature`
    (the one canonical key, shared with the serving engine's batch buckets);
    this wrapper only fills in the live jax backend.  Batched problems
    append a ``|b{B}`` field; unbatched keys keep the historical 5-field
    layout, so entries tuned before the batch dimension existed keep
    resolving for B=1.
    """
    backend = backend_name() if backend is None else str(backend)
    return problem.signature(backend=backend, n_devices=n_devices)


def node_key(
    node: ContractionNode, algorithm: str, executor: str, collective: str = "flat"
) -> str:
    """Measurement key of one schedule node's contraction.

    Keys on the contraction itself -- executor kind, algorithm, kept range,
    parent range, and whether the source is the raw tensor -- not on the
    schedule it appeared in, so identical nodes shared by several candidate
    trees (e.g. a root leaf present in both the flat and a binary schedule)
    are measured once and recognized everywhere.  Hierarchical-collective
    measurements append a ``|coll=hierarchical`` field; flat keys keep the
    historical layout so entries tuned before two-level collectives existed
    keep resolving.
    """
    src = "root" if node.from_root else "partial"
    key = (
        f"{executor}|{algorithm}|{src}|keep={node.lo}:{node.hi}"
        f"|parent={node.parent_lo}:{node.parent_hi}"
    )
    if collective != "flat":
        key += f"|coll={collective}"
    return key


@dataclass(frozen=True)
class Measurements:
    """One problem's resolved tuning entry, as the planner consumes it.

    ``node_s`` maps :func:`node_key` strings to measured median seconds;
    ``tiles`` maps kernel name (``"fused_mttkrp"`` / ``"matrix_free"`` /
    ``"multi_ttv"``) to its tuned tile config (``{"block_i": ...,
    "block_b": ...}`` / ``{"block_i": ..., "block_r": ...}`` subsets);
    ``serial_fractions`` are the overlap constants recalibrated from
    measured sharded/overlapping node pairs (empty when nothing paired);
    ``pp`` holds the pairwise-perturbation rows (``"build_s"`` for the
    cache materialization, ``"correct_sweep_s"`` for one correction-only
    sweep) when the tuned problem opted in via ``pp_tol``.
    """

    node_s: Mapping[str, float] = field(default_factory=dict)
    tiles: Mapping[str, Mapping[str, int]] = field(default_factory=dict)
    serial_fractions: Mapping[str, float] = field(default_factory=dict)
    pp: Mapping[str, float] = field(default_factory=dict)

    def node_time(
        self,
        node: ContractionNode,
        algorithm: str,
        executor: str,
        collective: str = "flat",
    ) -> float | None:
        """Measured seconds for one node contraction under one collective
        decomposition (``"flat"`` / ``"hierarchical"``), ``None`` if
        unmeasured."""
        return self.node_s.get(node_key(node, algorithm, executor, collective))

    def kernel_tiles(self, kernel: str) -> dict[str, int] | None:
        """Tuned tile config for one kernel name, ``None`` if untuned."""
        t = self.tiles.get(kernel)
        return {k: int(v) for k, v in t.items()} if t else None

    def pp_second(self, key: str) -> float | None:
        """Measured seconds of one PP row (``"build_s"`` /
        ``"correct_sweep_s"``), ``None`` when the entry was tuned without
        pairwise perturbation."""
        v = self.pp.get(key)
        return float(v) if v is not None else None


class TuningCache:
    """Persistent ``{problem_key: entry}`` store with in-memory memoization.

    Entries are plain JSON dicts (see :func:`tune` for the layout).  A cache
    built with ``path=None`` lives in memory only; with a path, every
    :meth:`put` rewrites the file atomically-enough for the single-writer
    tuning workflow, and construction loads whatever the file already holds
    -- so winners measured in one process are visible to the next
    (``REPRO_TUNING_CACHE`` names the process-default file; CI uploads it
    as an artifact next to the benchmark JSON).
    """

    def __init__(self, path: str | os.PathLike | None = None):
        """Load ``path`` if it exists; ``None`` -> in-memory only."""
        self.path = Path(path) if path else None
        self._entries: dict[str, dict] = {}
        if self.path is not None and self.path.exists():
            text = self.path.read_text()
            # a pre-created empty file (mkstemp, `touch`) is an empty store;
            # anything else must parse -- a corrupt cache should be loud
            self._entries = json.loads(text) if text.strip() else {}

    def get(self, key: str) -> dict | None:
        """The entry stored under ``key``, or ``None`` (memoized in memory)."""
        return self._entries.get(key)

    def put(self, key: str, entry: dict) -> None:
        """Store ``entry`` under ``key`` and persist to disk when backed."""
        self._entries[key] = entry
        self.save()

    def save(self) -> None:
        """Write the full store to ``self.path`` (no-op when memory-only)."""
        if self.path is None:
            return
        self.path.parent.mkdir(parents=True, exist_ok=True)
        self.path.write_text(json.dumps(self._entries, indent=1))

    def keys(self) -> list[str]:
        """All problem keys currently held (in-memory view)."""
        return list(self._entries)


_default_cache: TuningCache | None = None


def default_tuning_cache() -> TuningCache:
    """The process-default cache ``plan_sweep(strategy="autotune")`` reads.

    Backed by the file named in ``$REPRO_TUNING_CACHE`` when set (created
    lazily), in-memory otherwise.  Built once per process.
    """
    global _default_cache
    if _default_cache is None:
        _default_cache = TuningCache(os.environ.get(CACHE_ENV) or None)
    return _default_cache


def lookup_measurements(
    problem: Problem, cache: TuningCache | None = None
) -> Measurements | None:
    """Resolve ``problem``'s tuning entry into planner-ready Measurements.

    Reads ``cache`` (the process default when ``None``); returns ``None``
    when the problem was never tuned on this backend/device-count -- the
    planner then falls back to the purely analytic model, which is the CI
    default (measurement never happens implicitly).
    """
    cache = cache or default_tuning_cache()
    entry = cache.get(problem_key(problem))
    if not entry:
        return None
    node_s = {r["key"]: float(r["measured_s"]) for r in entry.get("nodes", [])}
    tiles = {
        k: {
            kk: int(vv)
            for kk, vv in v.items()
            if kk in ("block_i", "block_b", "block_r", "block_batch")
        }
        for k, v in entry.get("tiles", {}).items()
        if v
    }
    return Measurements(
        node_s=node_s,
        tiles=tiles,
        serial_fractions={
            str(k): float(v)
            for k, v in entry.get("serial_fractions", {}).items()
        },
        pp={str(k): float(v) for k, v in entry.get("pp", {}).items()},
    )


# ------------------------------------------------------------ measurement
class _Budget:
    """Wall-clock budget for one tune() call (compile time counts too)."""

    def __init__(self, budget_ms: float | None):
        self.budget_ms = budget_ms
        self.t0 = time.perf_counter()

    def exhausted(self) -> bool:
        if self.budget_ms is None:
            return False
        return (time.perf_counter() - self.t0) * 1e3 >= self.budget_ms


def _time(fn: Callable[[], Any], reps: int) -> float:
    """Median wall seconds of ``fn()`` with one compile/warmup call excluded."""
    jax.block_until_ready(fn())
    times = []
    for _ in range(max(1, reps)):
        t0 = time.perf_counter()
        jax.block_until_ready(fn())
        times.append(time.perf_counter() - t0)
    times.sort()
    return times[len(times) // 2]


def _tile_rows(
    candidates: Sequence[tuple[int, ...]],
    effective: Callable[[tuple[int, ...]], tuple[int, ...]],
    run: Callable[[tuple[int, ...]], Any],
    reps: int,
    budget: _Budget,
) -> list[dict]:
    """Time deduped tile candidates; the default candidate is always first."""
    rows: list[dict] = []
    seen: set[tuple[int, ...]] = set()
    for i, cand in enumerate(candidates):
        eff = effective(cand)
        if eff in seen:
            continue
        if i > 0 and budget.exhausted():
            break
        seen.add(eff)
        rows.append(
            {
                "candidate": list(cand),
                "effective": list(eff),
                "is_default": i == 0,
                "measured_s": _time(lambda c=cand: run(c), reps),
            }
        )
    return rows


def _summarize_tiles(rows: list[dict], names: tuple[str, ...], mode: int) -> dict:
    """Best/default summary of one kernel's measured tile rows."""
    best = min(rows, key=lambda r: r["measured_s"])
    default = rows[0]  # the default candidate is always measured first
    out = {nm: best["candidate"][k] for k, nm in enumerate(names)}
    out.update(
        {
            "mode": mode,
            "default_s": default["measured_s"],
            "tuned_s": best["measured_s"],
            "speedup_vs_default": (
                default["measured_s"] / best["measured_s"]
                if best["measured_s"] > 0
                else 1.0
            ),
            "rows": rows,
        }
    )
    return out


def _tune_fused_tiles(
    x: Array, factors: Sequence[Array], *, reps: int, budget: _Budget
) -> dict:
    """Measure candidate fused-MTTKRP tilings on a representative internal
    mode; the winner feeds both ``NodePlan.tiles`` and the tuner's own
    ``fused`` node measurements (so the argmin times what will execute)."""
    from repro.kernels import ops as kops  # lazy: kernels import pallas

    n = x.ndim // 2  # internal mode: the kernel's primary bilinear layout
    _, in_dim, big_r = dims_split(x.shape, n)
    # tuning runs unbatched (batch tile effectively 1), so block_batch never
    # splits the candidate set here; the tuned value rides along for the
    # batched kernel to consume
    rows = _tile_rows(
        FUSED_TILE_CANDIDATES,
        lambda cand: (min(in_dim, cand[0]), min(big_r, cand[1]), 1),
        lambda cand: kops.fused_mttkrp(
            x, list(factors), n, block_i=cand[0], block_b=cand[1]
        ),
        reps,
        budget,
    )
    return _summarize_tiles(rows, ("block_i", "block_b", "block_batch"), n)


def _tune_matrix_free_tiles(
    x: Array, factors: Sequence[Array], *, reps: int, budget: _Budget
) -> dict:
    """Measure candidate matrix-free tilings on the same representative
    internal mode as the fused tuner; the winner feeds ``NodePlan.tiles``
    and the tuner's ``matrix_free`` node measurements."""
    from repro.kernels import ops as kops  # lazy: kernels import pallas

    n = x.ndim // 2
    in_dim = x.shape[n]
    red_max = max(d for k, d in enumerate(x.shape) if k != n)
    # effective tile: block_i clamped to the mode, block_r to the largest
    # reduction extent (batch tile effectively 1; the tuned block_batch
    # rides along for the batched kernel, exactly as with fused)
    rows = _tile_rows(
        MATRIX_FREE_TILE_CANDIDATES,
        lambda cand: (min(in_dim, cand[0]), min(red_max, cand[1]), 1),
        lambda cand: kops.matrix_free_mttkrp(
            x, list(factors), n, block_i=cand[0], block_r=cand[1]
        ),
        reps,
        budget,
    )
    return _summarize_tiles(rows, ("block_i", "block_r", "block_batch"), n)


def _tune_ttv_tiles(
    x: Array, factors: Sequence[Array], *, reps: int, budget: _Budget
) -> dict:
    """Measure candidate multi-TTV tilings (the 2nd step of Alg. 4).

    The winner parameterizes the public kernelized entry point
    ``repro.kernels.ops.mttkrp_2step_kernel(block_i=...)`` -- the planner's
    ``2step-*`` algorithms use the XLA einsum second step, so this runs
    *after* node timing in :func:`tune` and only spends leftover budget.
    """
    from repro.kernels import ops as kops  # lazy: kernels import pallas

    n = x.ndim // 2
    c = factors[0].shape[1]
    big_l, in_dim, big_r = dims_split(x.shape, n)
    # multi-TTV operands at this mode's 2-step shapes: the partial tensor is
    # (min(L,R), I_n, C) and the complementary KRP (min(L,R), C); random
    # payloads -- timing depends on shapes/tiles, not values.
    small = min(big_l, big_r)
    t3 = jax.random.normal(jax.random.PRNGKey(0), (small, in_dim, c), jnp.float32)
    w2 = jax.random.normal(jax.random.PRNGKey(1), (small, c), jnp.float32)
    rows = _tile_rows(
        tuple((b,) for b in TTV_TILE_CANDIDATES),
        lambda cand: (min(in_dim, cand[0]),),
        lambda cand: kops.multi_ttv(t3, w2, block_i=cand[0]),
        reps,
        budget,
    )
    return _summarize_tiles(rows, ("block_i",), n)


def _leaf_algorithms(problem: Problem, node: ContractionNode, kind: str) -> tuple[str, ...]:
    """Algorithm candidates the tuner measures for one root-leaf MTTKRP."""
    algs = (
        _EXTERNAL_LEAF_ALGORITHMS
        if problem.external_mode(node.mode)
        else _LEAF_ALGORITHMS
    )
    # the Pallas kernels are single-device objects; measure them locally only
    if kind == "local":
        return algs
    return tuple(a for a in algs if a not in _KERNEL_LEAF_ALGORITHMS)


def _tune_nodes(
    problem: Problem,
    x: Array,
    factors: Sequence[Array],
    *,
    mesh,
    mode_axes,
    reps: int,
    budget: _Budget,
    fused_tiles: Mapping[str, int] | None = None,
    matrix_free_tiles: Mapping[str, int] | None = None,
) -> list[dict]:
    """Measure every node of every candidate (schedule x executor) plan.

    Walks each candidate schedule exactly like the sweep engine (parents'
    outputs cached for their children, carry-bearing executors measured
    through their carry path), timing each deduped :func:`node_key` once.
    Root leaves are measured under every competing algorithm -- ``fused``
    with ``fused_tiles`` and ``matrix_free`` with ``matrix_free_tiles``
    (the already-tuned tilings), so the argmin times exactly the
    configuration the resulting plan will execute.  On two-level problems
    (``Problem.intra_axes``) every node whose reduction spans both levels
    is additionally measured under ``collective="hierarchical"``, so the
    planner's per-node flat-vs-hierarchical pick argmins over measured
    head-to-head times rather than modeled bandwidths.  Root partials
    on an executor with ``contract_view`` are timed as
    :func:`repro.plan.sweep.cp_als` runs them: on the matrix view its
    set-up program builds, which is built here too but not timed (a solve
    pays it once, not once a sweep).  Stops
    cleanly when ``budget`` runs out -- unmeasured nodes simply keep their
    analytic costs at plan time.
    """
    from repro.core.dimtree import view_split

    from .cost import hierarchical_applicable  # lazy: cost imports schedule
    from .executor import make_executor  # lazy: avoids an import cycle
    from .planner import plan_sweep
    from .schedule import enumerate_schedules
    from .sweep import _reads_view, prepare_operands, view_splits

    kinds = (
        ("sharded", "overlapping", "compressed") if problem.sharded else ("local",)
    )
    # flat first: its leaves are the full per-mode MTTKRPs every tree shares,
    # so a tight budget still measures the comparisons that matter most
    schedules = sorted(enumerate_schedules(problem), key=lambda s: not s.is_flat)
    rows: list[dict] = []
    seen: set[str] = set()
    for kind in kinds:
        ex = make_executor(kind, mesh, mode_axes, node_axis=problem.node_axis)
        xs, fs = ex.prepare(problem, x, list(factors))
        for sched in schedules:
            plan = plan_sweep(problem, schedule=sched, executor=kind)
            carry = (
                ex.init_carry(plan, xs, fs) if hasattr(ex, "init_carry") else None
            )
            splits = view_splits(plan, ex)
            views = (
                prepare_operands(xs, splits=splits, batched=problem.batched)[1]
                if splits
                else {}
            )
            cache: dict[int, Array] = {ROOT: xs}
            for node in sched.walk():
                src = cache[node.parent]
                reads_view = bool(views) and _reads_view(node)
                if reads_view:
                    src = views[view_split(node.lo, node.hi, problem.ndim)]
                planned = plan.node_plan(node.id).algorithm
                algs = (
                    _leaf_algorithms(problem, node, kind)
                    if node.from_root and node.is_leaf
                    else (planned,)
                )
                colls = (
                    ("flat", "hierarchical")
                    if kind != "local"
                    and hierarchical_applicable(problem, node.reduce_axes)
                    else ("flat",)
                )
                out = None
                for alg in algs:
                    if alg == "fused":
                        tl = fused_tiles
                    elif alg == "matrix_free":
                        tl = matrix_free_tiles
                    else:
                        tl = None
                    run_out = None
                    for coll in colls:
                        key = node_key(node, alg, kind, coll)
                        if carry is not None:
                            fn = jax.jit(
                                lambda s, f, c, node=node, alg=alg, tl=tl, coll=coll: (
                                    ex.contract_carry(
                                        node, s, f, alg, c, tiles=tl, collective=coll
                                    )
                                )
                            )
                            if key not in seen and not budget.exhausted():
                                seen.add(key)
                                rows.append(
                                    {
                                        "key": key,
                                        "executor": kind,
                                        "algorithm": alg,
                                        "collective": coll,
                                        "schedule": sched.name,
                                        "node": node.id,
                                        "measured_s": _time(
                                            lambda: fn(src, fs, carry)[0], reps
                                        ),
                                    }
                                )
                            if alg == planned and coll == "flat":
                                run_out, carry = fn(src, fs, carry)
                        else:
                            if reads_view:
                                fn = jax.jit(
                                    lambda v, f, node=node: ex.contract_view(node, v, f)
                                )
                            else:
                                fn = jax.jit(
                                    lambda s, f, node=node, alg=alg, tl=tl, coll=coll: (
                                        ex.contract(
                                            node, s, f, alg, tiles=tl, collective=coll
                                        )
                                    )
                                )
                            if key not in seen and not budget.exhausted():
                                seen.add(key)
                                rows.append(
                                    {
                                        "key": key,
                                        "executor": kind,
                                        "algorithm": alg,
                                        "collective": coll,
                                        "schedule": sched.name,
                                        "node": node.id,
                                        "measured_s": _time(
                                            lambda: fn(src, fs), reps
                                        ),
                                    }
                                )
                            if alg == planned and coll == "flat":
                                run_out = fn(src, fs)
                    if run_out is not None:
                        out = run_out
                if not node.is_leaf:
                    cache[node.id] = out
    return rows


def _recalibrate_serial_fractions(
    problem: Problem, rows: Sequence[Mapping[str, Any]]
) -> dict[str, float]:
    """Fit the overlapping executor's unhidable fraction from measured pairs.

    For every node measured under both ``sharded`` and ``overlapping`` the
    bounded-overlap model says ``t_sh - t_ov = (1 - f) * min(compute,
    collective)``; the hidable term comes from the analytic predictions of
    the same node (``(pred_sh - pred_ov) / predicted_overlap_efficiency``).
    Median over pairs, clamped to [0, 1]; empty when nothing paired (e.g.
    local problems).  Same estimator as ``bench_mttkrp --calibrate``, fed by
    node measurements instead of the bench's dedicated overlap loop.
    """
    from .cost import node_cost  # lazy: cost imports schedule, not us
    from .schedule import enumerate_schedules

    if not problem.sharded:
        return {}
    by_key = {r["key"]: float(r["measured_s"]) for r in rows}
    nodes_by_sig: dict[str, ContractionNode] = {}
    for sched in enumerate_schedules(problem):
        for node in sched.walk():
            if node.is_root:
                continue
            sig = node_key(node, "x", "x")
            nodes_by_sig.setdefault(sig, node)
    fits: list[float] = []
    for r in rows:
        if r["executor"] != "sharded":
            continue
        ov_key = r["key"].replace("sharded|", "overlapping|", 1)
        t_ov = by_key.get(ov_key)
        if t_ov is None:
            continue
        node = nodes_by_sig.get(node_key_from(r["key"]))
        if node is None:
            continue
        alg = r["algorithm"]
        kw = dict(algorithm=alg) if node.from_root and node.is_leaf else {}
        pred_sh = node_cost(problem, node, "sharded", **kw)
        pred_ov = node_cost(problem, node, "overlapping", **kw)
        eff = pred_ov.predicted_overlap_efficiency
        if eff <= 0.0:
            continue
        min_term = (pred_sh.predicted_s - pred_ov.predicted_s) / eff
        if min_term <= 0.0:
            continue
        f = 1.0 - (float(r["measured_s"]) - t_ov) / min_term
        fits.append(min(1.0, max(0.0, f)))
    if not fits:
        return {}
    fits.sort()
    return {"sharded": 1.0, "overlapping": fits[len(fits) // 2]}


def node_key_from(key: str) -> str:
    """Normalize a measurement key to its executor/algorithm-free signature
    (the node topology part), for pairing measurements across executors."""
    _, _, rest = key.split("|", 2)
    return f"x|x|{rest}"


def _tune_pp(
    problem: Problem,
    x: Array,
    factors: Sequence[Array],
    *,
    mesh,
    mode_axes,
    reps: int,
    budget: _Budget,
) -> dict[str, float]:
    """Measure the two pairwise-perturbation phases for a ``pp_tol > 0``
    problem: ``build_s`` (cache materialization -- pairwise intermediates +
    bases, i.e. what every exact sweep additionally pays) and
    ``correct_sweep_s`` (one correction-only approximate sweep -- what
    replaces the exact sweep while drifts stay under tolerance).  These are
    the measured inputs of :func:`repro.plan.cost.pp_amortized_cost`."""
    from . import sweep as sweeplib  # lazy: sweep imports planner/executor
    from .executor import make_executor
    from .planner import plan_sweep

    kind = "sharded" if problem.sharded else "local"
    ex = make_executor(kind, mesh, mode_axes)
    xs, fs = ex.prepare(problem, x, list(factors))
    build = jax.jit(lambda t, f: sweeplib._pp_materialize(problem, ex, t, f, 0))
    rows: dict[str, float] = {}
    if budget.exhausted():
        return rows
    rows["build_s"] = _time(lambda: build(xs, fs), reps)
    if budget.exhausted():
        return rows
    plan = plan_sweep(problem, executor=kind, schedule="flat")
    state = sweeplib.SweepState(
        x=xs,
        factors=list(fs),
        weights=jnp.ones((problem.rank,), xs.dtype),
        norm_x=tensor_norm(xs).astype(xs.dtype),
        it=jnp.asarray(0),
        grams=sweeplib.grams(fs),
        pp=build(xs, fs),
    )
    corr = jax.jit(lambda st: sweeplib._pp_sweep(problem, plan, st))
    rows["correct_sweep_s"] = _time(lambda: corr(state), reps)
    return rows


def tune(
    x: Array,
    rank: int,
    *,
    factors: Sequence[Array] | None = None,
    mesh=None,
    mode_axes: Mapping[int, str] | None = None,
    cache: TuningCache | None = None,
    budget_ms: float | None = 2000.0,
    reps: int = 3,
    seed: int = 0,
    pp_tol: float = 0.0,
    intra_axes: Sequence[str] = (),
    batch: int = 1,
) -> dict:
    """Measure tiles + candidate plans for ``x``'s problem; persist winners.

    The one measuring entry point (nothing else runs kernels): in budget
    priority order, times candidate fused-MTTKRP tilings
    (:data:`FUSED_TILE_CANDIDATES`) and matrix-free tilings
    (:data:`MATRIX_FREE_TILE_CANDIDATES`), then every contraction node of
    every candidate (schedule x executor) plan -- ``fused`` /
    ``matrix_free`` leaves under the just-tuned tilings, so the argmin
    times what will execute -- then candidate multi-TTV tilings
    (:data:`TTV_TILE_CANDIDATES`; consumed by the public
    ``mttkrp_2step_kernel``, so it only spends leftover budget).
    Capped by ``budget_ms`` of wall clock (compile time included; ``None``
    = no cap); recalibrates ``serial_fractions`` from measured
    sharded/overlapping pairs, and stores the entry in ``cache`` (the
    process default when ``None``) under :func:`problem_key`.  Pass
    ``mesh`` + ``mode_axes`` to tune a sharded problem; ``factors`` default
    to random ones (timing depends on shapes, not values).  ``pp_tol > 0``
    tunes the pairwise-perturbation variant of the problem (its own cache
    key, via the signature's ``|pp`` field) and additionally measures the
    PP cache build and one correction-only sweep into the entry's ``pp``
    rows, which ``plan_sweep`` then prefers over the analytic PP estimates.
    ``intra_axes`` declares the fast (intra-node) mesh axes of a two-level
    mesh, exactly as on :class:`Problem`: nodes whose reductions span both
    levels are then measured under flat AND hierarchical collectives, and
    the resulting entry keys include the node-topology field so two-level
    measurements never collide with single-level ones.  ``batch=B > 1``
    tunes the batched problem a :class:`repro.serve.CPService` of
    ``batch_size=B`` dispatches: ``x`` is ``(B, *shape)``, the entry lands
    under that batched signature (the service's warm-plan key), nodes are
    timed on the whole batch, and kernel tiles on its first problem.
    Returns the stored entry dict.
    """
    cache = cache or default_tuning_cache()
    problem = Problem.from_tensor(
        x, rank, mode_axes=mode_axes, mesh=mesh, pp_tol=pp_tol,
        intra_axes=intra_axes, batch=batch,
    )
    if factors is None:
        factors = random_factors(
            jax.random.PRNGKey(seed), problem.shape, rank, x.dtype,
            batch=problem.batch,
        )
    # kernel tiles key on the mode dims: tune them on one problem
    x1 = x[0] if problem.batched else x
    f1 = [u[0] for u in factors] if problem.batched else factors
    from .sweep import SWEEP_PRECISION  # lazy: sweep imports the planner

    budget = _Budget(budget_ms)
    # measure at the precision the sweep executes at
    with jax.default_matmul_precision(SWEEP_PRECISION):
        entry = _measure(
            problem, x, factors, x1, f1, mesh=mesh, mode_axes=mode_axes,
            reps=reps, budget=budget,
        )
    entry.update(budget_ms=budget_ms, reps=reps)
    cache.put(problem_key(problem), entry)
    return entry


def _measure(
    problem: Problem, x, factors, x1, f1, *, mesh, mode_axes, reps: int,
    budget: _Budget,
) -> dict:
    """The measuring body of :func:`tune`: tiles on one problem (``x1``,
    ``f1``), nodes and PP rows on the whole (possibly batched) problem."""
    fused = _tune_fused_tiles(x1, f1, reps=reps, budget=budget)
    mfree = _tune_matrix_free_tiles(x1, f1, reps=reps, budget=budget)
    rows = _tune_nodes(
        problem, x, factors, mesh=mesh, mode_axes=mode_axes, reps=reps,
        budget=budget,
        fused_tiles={
            "block_i": fused["block_i"],
            "block_b": fused["block_b"],
            "block_batch": fused["block_batch"],
        },
        matrix_free_tiles={
            "block_i": mfree["block_i"],
            "block_r": mfree["block_r"],
            "block_batch": mfree["block_batch"],
        },
    )
    tiles = {
        "fused_mttkrp": fused,
        "matrix_free": mfree,
        "multi_ttv": _tune_ttv_tiles(x1, f1, reps=reps, budget=budget),
    }
    pp_rows = (
        _tune_pp(
            problem, x, factors, mesh=mesh, mode_axes=mode_axes,
            reps=reps, budget=budget,
        )
        if problem.pp_tol > 0.0
        else {}
    )
    return {
        "backend": backend_name(),
        "n_devices": (
            math.prod(problem.axis_sizes.values()) if problem.axis_sizes else 1
        ),
        "elapsed_ms": (time.perf_counter() - budget.t0) * 1e3,
        "tiles": tiles,
        "nodes": rows,
        "serial_fractions": _recalibrate_serial_fractions(problem, rows),
        "pp": pp_rows,
    }
