"""THE ALS sweep: the one copy of the update algebra, plan- and executor-driven.

Per mode-n update (alternating least squares, paper Sec. 2.2):
    M   = MTTKRP(X, {U_k}, n)               (bottleneck; executor + plan decide how)
    H   = *_{k != n} (U_k^T U_k)            (Hadamard of Gram matrices)
    U_n = M @ inv(H) (by LU);  column-normalize -> lambda
with the fit tracked through the factored identity reusing the last MTTKRP.

The engine walks the plan's contraction schedule (:mod:`repro.plan.schedule`)
node by node -- the flat per-mode sweep and every dimension-tree shape are
the same walk over different trees.  This module replaces the four
hand-written sweeps (``core.cpals.als_sweep``, ``core.dimtree.dimtree_sweep``,
``dist.dist_mttkrp.dist_als_sweep`` and ``dist_dimtree_sweep``), which
survive as thin wrappers building the corresponding plan + executor.  The
Gram/Hadamard/solve/normalize/fit algebra exists ONLY here.
"""

from __future__ import annotations

import functools
import math
import time
from dataclasses import dataclass
from typing import Any, Callable, MutableMapping, Sequence

import jax
import jax.numpy as jnp
import numpy as np

from repro.core.cpals import (
    CPState,
    fit_from_last_mttkrp,
    grams,
    hadamard_except,
    normalize_columns,
)
from repro.core.dimtree import matrix_view, view_split
from repro.core.tensor_ops import random_factors, tensor_norm

from .executor import Executor, LocalExecutor, ShardedExecutor
from .planner import SweepPlan, plan_sweep
from .problem import Problem
from .schedule import ROOT, ContractionNode, pp_pairs as pp_pair_meta

Array = jax.Array

# THE precision policy of the solver: every contraction of a sweep (MTTKRP,
# partial contractions, Grams, solve, fit) at Precision.HIGHEST, as the
# Pallas kernels already contract.  On a TPU an fp32 matmul at default
# precision takes one bf16 pass; on the paper's 225x59x200x200 tensor that
# stalled ALS at fit 0.738 where the HIGHEST reference reached 0.950.  At
# the paper's ranks MTTKRP stays memory-bound even at HIGHEST's passes.
SWEEP_PRECISION = "highest"

# THE host-synchronization point of the cp_als driver: exactly one call per
# dispatched chunk of sweeps, reading the chunk's per-sweep fits into host
# memory.  Module-level so tests can count syncs.
_read_fits = np.asarray


@dataclass
class SweepState:
    """Pytree carried across sweeps: the tensor rides along unchanged so the
    jitted sweep is a pure ``state -> state`` function.

    ``carry`` is executor-private state threaded through the sweep (e.g. the
    per-mode error-feedback residuals of
    :class:`repro.plan.executor.CompressedShardedExecutor`); ``None`` for
    stateless executors.  ``grams`` carries the per-factor Gram matrices
    ``U_k^T U_k`` across sweeps: each mode's update refreshes its own Gram,
    so the next sweep starts from exact values without recomputing all N --
    ``None`` (the single-shot default) recomputes them from the factors.

    ``pp`` is the pairwise-perturbation cache (:class:`PPState`) when the
    plan enabled PP sweeps, ``None`` otherwise -- and ``None`` keeps the
    sweep graph literally the classic exact one (the ``pp_tol=0`` bitwise
    guarantee is *by construction*, not by tolerance).

    ``views`` maps a split point ``m`` to the tensor's matrix view
    ``X_(m)`` (:func:`repro.core.dimtree.matrix_view`): root partial nodes
    then read their view through the executor's ``contract_view`` instead
    of ``x``, and ``x`` may be ``None`` when nothing else reads it.
    ``None`` (every direct caller) reads ``x``.
    """

    x: Array | None
    factors: list[Array]
    weights: Array
    norm_x: Array
    it: Array
    fit: Array | float = 0.0
    carry: Any = None
    grams: list[Array] | None = None
    pp: Any = None
    views: dict[int, Array] | None = None


jax.tree_util.register_pytree_node(
    SweepState,
    lambda s: (
        (s.x, s.factors, s.weights, s.norm_x, s.it, s.fit, s.carry, s.grams, s.pp,
         s.views),
        None,
    ),
    lambda _, c: SweepState(*c),
)


@dataclass
class PPState:
    """Pairwise-perturbation cache (Ma & Solomonik, arXiv 2010.12056).

    Captured at the end of every *exact* sweep and carried across the
    approximate ones: ``ref`` are the factor iterates the cache was built
    from, ``pairs`` maps ``(n, m)`` (``n < m``) to the pairwise intermediate
    ``M_{n,m}[i_n, i_m, c] = sum X * prod_{k not in {n,m}} V_k[i_k, c]``,
    and ``base`` is each mode's exact MTTKRP at the reference point
    (``pairs`` contracted with one more reference factor).  ``drift`` is the
    per-factor relative drift ``||U_n - V_n||_F / ||V_n||_F`` since the
    capture (float32, max over the batch for batched problems; +inf before
    the first capture so the run always opens with an exact sweep), and
    ``n_exact`` counts exact (re-materializing) sweeps -- the measured
    exact-sweep fraction the bench reports against the planner's assumption.
    """

    ref: list[Array]
    pairs: dict[tuple[int, int], Array]
    base: list[Array]
    drift: Array
    n_exact: Array


jax.tree_util.register_pytree_node(
    PPState,
    lambda s: ((s.ref, s.pairs, s.base, s.drift, s.n_exact), None),
    lambda _, c: PPState(*c),
)


def _pp_drift(factors: Sequence[Array], ref: Sequence[Array]) -> Array:
    """Per-factor relative drift ``||U_n - V_n||_F / ||V_n||_F`` as an
    ``(ndim,)`` float32 vector (max over the batch when batched) -- the
    quantity the PP gate compares against ``Problem.pp_tol``."""
    ds = []
    for u, v in zip(factors, ref):
        du = (u - v).astype(jnp.float32)
        num = jnp.sqrt(jnp.sum(du * du, axis=(-2, -1)))
        den = jnp.sqrt(jnp.sum(v.astype(jnp.float32) ** 2, axis=(-2, -1)))
        ds.append(jnp.max(num / jnp.maximum(den, 1e-30)))
    return jnp.stack(ds)


def _pp_contract_second(pair: Array, v: Array) -> Array:
    """``M_{n,m} . v_m -> (I_n, C)``: contract the rank-major pair
    ``(..., C, I_n, I_m)`` with a factor ``(..., I_m, C)`` over the m
    index.  The stored layout makes this one stride-1 batched GEMM over
    the rank axis -- an index-major pair would force a transpose of the
    (large) pair per correction, which on CPU costs more than the GEMM."""
    vt = jnp.swapaxes(v, -1, -2)  # (..., C, I_m)
    out = jnp.matmul(pair, vt[..., :, :, None])[..., 0]  # (..., C, I_n)
    return jnp.swapaxes(out, -1, -2)


def _pp_contract_first(pair: Array, v: Array) -> Array:
    """``M_{m,n} . v_m -> (I_n, C)`` when the partner is the pair's FIRST
    index (``m < n``): same stride-1 batched GEMM, contracting the
    ``(..., C, I_m, I_n)`` pair with ``(..., I_m, C)`` over ``I_m``."""
    vt = jnp.swapaxes(v, -1, -2)  # (..., C, I_m)
    out = jnp.matmul(vt[..., :, None, :], pair)[..., 0, :]  # (..., C, I_n)
    return jnp.swapaxes(out, -1, -2)


def _pp_base(
    pairs: dict[tuple[int, int], Array], ref: Sequence[Array], n: int
) -> Array:
    """Mode-``n`` exact MTTKRP at the reference point, recovered from one
    pairwise intermediate: contract ``M_{n,m}`` with reference factor
    ``V_m`` (any partner ``m`` works; the smallest index is used)."""
    m = 1 if n == 0 else 0
    if n < m:
        return _pp_contract_second(pairs[(n, m)], ref[m])
    return _pp_contract_first(pairs[(m, n)], ref[m])


def _pp_materialize(problem: Problem, executor, x, factors, n_exact) -> "PPState":
    """Build the PP cache at the current iterates: pairwise intermediates
    via ``executor.pp_pairs`` (local einsum, or shard_map + per-pair psum),
    per-mode bases, zero drift, ``n_exact`` exact-sweep count."""
    pairs = executor.pp_pairs(problem, x, factors)
    base = [_pp_base(pairs, factors, n) for n in range(problem.ndim)]
    return PPState(
        ref=list(factors),
        pairs=pairs,
        base=base,
        drift=jnp.zeros((problem.ndim,), jnp.float32),
        n_exact=jnp.asarray(n_exact, jnp.int32),
    )


def _pp_init(problem: Problem, x, factors) -> "PPState":
    """Zero-filled PP cache with +inf drift: structurally identical to a
    materialized one (so ``lax.cond``/``scan`` carry one pytree shape) but
    guaranteed to route the first sweep through the exact branch."""
    lead = (problem.batch,) if problem.batched else ()
    pairs = {
        (p.n, p.m): jnp.zeros(lead + p.shape, x.dtype)
        for p in pp_pair_meta(problem)
    }
    return PPState(
        ref=[jnp.zeros_like(u) for u in factors],
        pairs=pairs,
        base=[jnp.zeros_like(u) for u in factors],
        drift=jnp.full((problem.ndim,), jnp.inf, jnp.float32),
        n_exact=jnp.asarray(0, jnp.int32),
    )


def _update_factor(
    plan: SweepPlan, factors: list[Array], gs: list[Array], weights: Array,
    n: int, m_n: Array, it: Array,
) -> Array:
    """THE per-mode factor update (paper Sec. 2.2), shared by the exact and
    the pairwise-perturbation sweeps: solve ``U H = M`` exactly on the
    C x C Gram-Hadamard, optionally column-normalize into the lambdas, and
    refresh exactly the changed factor's Gram.  Mutates ``factors``/``gs``
    in place; returns the (possibly updated) weights.

    ``H^{-1}`` comes from an LU factorization with partial pivoting.  An
    SVD pseudo-inverse in its place put the answers about six times
    farther from a plain LU-solving CP-ALS (float32 on a CPU, rank-25
    tensors of 45 x 40 x 40, 20 sweeps from nvecs)."""
    with jax.named_scope(f"update.mode{n}"):
        h = hadamard_except(gs, n)
        u = m_n @ jnp.linalg.inv(h)
        if plan.normalize:
            u, norms = normalize_columns(u, it)
            weights = norms
        factors[n] = u
        gs[n] = jnp.swapaxes(u, -1, -2) @ u
    return weights


def _exact_sweep(
    problem: Problem, plan: SweepPlan, executor: Executor, state: SweepState
) -> SweepState:
    """The exact schedule-walking sweep (see :func:`als_sweep`); passes
    ``state.pp`` through untouched."""
    x = state.x
    factors = list(state.factors)
    weights = state.weights
    it = state.it
    carry = state.carry
    views = state.views or {}
    use_carry = hasattr(executor, "contract_carry")
    gs = list(state.grams) if state.grams is not None else grams(factors)
    m_last = None

    sched = plan.resolved_schedule
    cache: dict[int, Array] = {ROOT: x}
    for node in sched.walk():
        src = cache[node.parent]
        if plan.nodes:
            np_ = plan.node_plan(node.id)
            alg, tiles, coll = np_.algorithm, np_.tiles, np_.collective
        else:
            alg, tiles, coll = "auto", None, "flat"
        with jax.named_scope(f"mttkrp.node{node.id}"):
            if views and _reads_view(node):
                out = executor.contract_view(
                    node, views[view_split(node.lo, node.hi, problem.ndim)], factors
                )
            elif use_carry:
                out, carry = executor.contract_carry(
                    node, src, factors, alg, carry, tiles=tiles, collective=coll
                )
            else:
                out = executor.contract(
                    node, src, factors, alg, tiles=tiles, collective=coll
                )
        if node.is_leaf:
            m_last = out
            weights = _update_factor(plan, factors, gs, weights, node.mode, m_last, it)
        else:
            cache[node.id] = out

    # Fit from the last MTTKRP (standard trick; avoids forming the model).
    with jax.named_scope("fit"):
        fit = fit_from_last_mttkrp(gs, weights, m_last, factors[-1], state.norm_x)
    return SweepState(
        x=x, factors=factors, weights=weights, norm_x=state.norm_x, it=it, fit=fit,
        carry=carry, grams=gs, pp=state.pp, views=state.views,
    )


def _pp_sweep(
    problem: Problem, plan: SweepPlan, state: SweepState
) -> SweepState:
    """One approximate sweep from the PP cache: per mode ``n`` the MTTKRP is
    the cached base plus one small GEMM per perturbed factor,
    ``M_n ~= base_n + sum_{m != n} M_{n,m} . (U_m - V_m)``
    (first order in the drifts -- the neglected terms are products of two or
    more deltas, hence the O(drift^2) error the property suite checks).  The
    factor update itself is the shared exact algebra; the tensor is never
    touched, which is the whole point.  Returns the state with refreshed
    drifts; the cache (``ref``/``pairs``/``base``/``n_exact``) rides along
    unchanged.
    """
    pp = state.pp
    factors = list(state.factors)
    weights = state.weights
    it = state.it
    gs = list(state.grams) if state.grams is not None else grams(factors)
    m_last = None
    for n in range(problem.ndim):
        m_n = pp.base[n]
        for m in range(problem.ndim):
            if m == n:
                continue
            du = factors[m] - pp.ref[m]
            if n < m:
                m_n = m_n + _pp_contract_second(pp.pairs[(n, m)], du)
            else:
                m_n = m_n + _pp_contract_first(pp.pairs[(m, n)], du)
        m_last = m_n
        weights = _update_factor(plan, factors, gs, weights, n, m_n, it)
    with jax.named_scope("fit"):
        fit = fit_from_last_mttkrp(gs, weights, m_last, factors[-1], state.norm_x)
    new_pp = PPState(
        ref=pp.ref, pairs=pp.pairs, base=pp.base,
        drift=_pp_drift(factors, pp.ref), n_exact=pp.n_exact,
    )
    return SweepState(
        x=state.x, factors=factors, weights=weights, norm_x=state.norm_x,
        it=it, fit=fit, carry=state.carry, grams=gs, pp=new_pp, views=state.views,
    )


def _with_payload(state: SweepState, payload) -> SweepState:
    """Rebuild a :class:`SweepState` from the sweep-mutable payload tuple
    (the ``lax.cond`` outputs of the PP gate), keeping the tensor, the PP
    cache, and the other sweep-invariant fields from ``state``."""
    factors, weights, fit, carry, gs = payload
    return SweepState(
        x=state.x, factors=list(factors), weights=weights, norm_x=state.norm_x,
        it=state.it, fit=fit, carry=carry, grams=gs, pp=state.pp, views=state.views,
    )


def als_sweep(
    problem: Problem, plan: SweepPlan, executor: Executor, state: SweepState
) -> SweepState:
    """One full ALS sweep over all modes, following ``plan`` on ``executor``.

    The engine is a *schedule walker*: it visits the plan's contraction
    tree in evaluation order (pre-order), materializing each internal
    node's partial tensor through ``executor.contract`` and caching it for
    its children (the reuse that makes dimension trees pay), and updating
    one factor at each leaf.  The flat per-mode sweep and the classic
    binary two-partial split are just two tree shapes; because children
    partition their parent's range in order and nodes materialize right
    before their first descendant leaf, every contracted factor is exactly
    as fresh as standard ALS requires -- any valid schedule reproduces the
    standard iterates (see :mod:`repro.plan.schedule`).

    Executors implementing the carry extension (``contract_carry``; see the
    :class:`repro.plan.executor.Executor` protocol) have their private state
    -- e.g. per-node error-feedback residuals -- threaded through
    ``state.carry`` across every node contraction, partials included.

    Gram matrices ride ``state.grams`` when the caller threads them across
    sweeps (``cp_als`` does): each update refreshes exactly the changed
    factor's Gram, so carried Grams are identical to recomputing all N from
    the factors -- which is what happens when ``state.grams is None``.

    With a PP cache on ``state.pp`` the sweep becomes a traced two-way
    gate (``lax.cond``): while every factor's drift since the last exact
    sweep stays below ``problem.pp_tol``, the approximate
    :func:`_pp_sweep` runs (no tensor contraction at all); once any drift
    crosses the threshold, the exact walk above runs verbatim and the
    cache is re-materialized at the fresh iterates.  ``state.pp is None``
    (every ``pp_tol=0`` plan) skips the gate entirely -- the graph is the
    classic exact sweep, bitwise.

    Gate structure: cond outputs cannot alias their operands, so everything
    routed through a cond's output is a fresh buffer every sweep.  The
    per-sweep gate therefore carries only what a sweep actually rewrites --
    factors, weights, fit, carry, grams; the tensor (and norm_x/it) and the
    pair cache stay outside.  The cache (``ref``/``pairs``/``base``, by far
    the largest conditional state) crosses exactly one minimal cond whose
    predicate -- "this was an exact sweep whose own step settled under the
    tolerance" -- is false on every approximate sweep, with a pure identity
    keep-branch, instead of riding the two-way sweep gate's carry on every
    iteration.  The drift/n_exact bookkeeping is recomputed outside the
    gate from the same quantities the branches used, bitwise identical to
    the nested-cond formulation (``test_property.py`` pins this).

    Every contraction of the sweep runs at :data:`SWEEP_PRECISION`.
    """
    with jax.default_matmul_precision(SWEEP_PRECISION):
        return _gated_sweep(problem, plan, executor, state)


def _gated_sweep(
    problem: Problem, plan: SweepPlan, executor: Executor, state: SweepState
) -> SweepState:
    """The body of :func:`als_sweep`: the exact walk, or the PP gate."""
    if state.pp is None:
        return _exact_sweep(problem, plan, executor, state)

    pp0 = state.pp
    use_pp = jnp.max(pp0.drift) < problem.pp_tol

    def _payload(st: SweepState):
        return (st.factors, st.weights, st.fit, st.carry, st.grams)

    def exact_branch(payload):
        out = _exact_sweep(problem, plan, executor, _with_payload(state, payload))
        return _payload(out)

    def pp_branch(payload):
        out = _pp_sweep(problem, plan, _with_payload(state, payload))
        return _payload(out)

    payload = jax.lax.cond(use_pp, pp_branch, exact_branch, _payload(state))
    new_factors = list(payload[0])

    # rebuild the cache only when an exact sweep's own step settled under
    # the tolerance -- i.e. the next sweeps would actually stay in the PP
    # regime.  During the early large-step phase the build would be
    # invalidated immediately, so keep the stale cache (drift = inf keeps
    # routing through the exact branch) and pay nothing extra.
    step = _pp_drift(new_factors, state.factors)
    rebuild = jnp.logical_and(
        jnp.logical_not(use_pp), jnp.max(step) < problem.pp_tol
    )

    def build(_):
        new = _pp_materialize(problem, executor, state.x, new_factors, 0)
        return (new.ref, new.pairs, new.base)

    def keep(_):
        return (pp0.ref, pp0.pairs, pp0.base)

    ref, pairs, base = jax.lax.cond(rebuild, build, keep, None)
    # drift after the sweep: vs the (kept) reference on approximate sweeps
    # (what _pp_sweep refreshes), exactly zero right after a rebuild (the
    # reference IS the fresh iterate), +inf while the cache is stale.
    drift = jnp.where(
        use_pp,
        _pp_drift(new_factors, pp0.ref),
        jnp.where(
            rebuild,
            jnp.zeros_like(pp0.drift),
            jnp.full_like(pp0.drift, jnp.inf),
        ),
    )
    n_exact = pp0.n_exact + jnp.where(use_pp, 0, 1).astype(pp0.n_exact.dtype)
    out = _with_payload(state, payload)
    return SweepState(
        x=out.x, factors=out.factors, weights=out.weights, norm_x=out.norm_x,
        it=out.it, fit=out.fit, carry=out.carry, grams=out.grams,
        pp=PPState(ref=ref, pairs=pairs, base=base, drift=drift, n_exact=n_exact),
        views=out.views,
    )


def legacy_sweep(
    x: Array,
    factors: Sequence[Array],
    weights: Array,
    norm_x: Array,
    it,
    *,
    strategy: str,
    normalize: bool = True,
    split: int | None = None,
    mode_axes=None,
    mesh=None,
) -> tuple[list[Array], Array, Array]:
    """The one bridge behind the pre-redesign sweep signatures.

    Builds the Problem/plan/executor for an old-style ``(x, factors,
    weights, norm_x, it)`` call -- sharded when ``mesh`` is given -- runs
    the engine, and returns the historical ``(factors, weights, fit)``
    triple.  All four back-compat wrappers delegate here so the legacy
    plumbing exists once.
    """
    problem = Problem.from_tensor(
        x, factors[0].shape[1], mode_axes=mode_axes, mesh=mesh
    )
    # legacy wrappers are frozen on the exact executors AND the pre-schedule
    # tree shapes (flat per-mode, or the binary split for dimtree): plan and
    # execution must keep matching the pre-redesign behavior.
    plan = plan_sweep(
        problem, strategy=strategy, split=split, normalize=normalize,
        executor="sharded" if mesh is not None else "local",
        schedule=None if strategy == "dimtree" else "flat",
    )
    executor = ShardedExecutor(mesh, mode_axes) if mesh is not None else LocalExecutor()
    state = SweepState(
        x=x, factors=list(factors), weights=weights, norm_x=norm_x, it=jnp.asarray(it)
    )
    out = als_sweep(problem, plan, executor, state)
    return out.factors, out.weights, out.fit


def _reads_view(node: ContractionNode) -> bool:
    """Whether ``node`` reads a matrix view of the tensor where views are
    built: a root partial, contracted from the tensor and not a leaf."""
    return node.from_root and not node.is_leaf


def view_splits(plan: SweepPlan, executor: Executor) -> tuple[int, ...]:
    """The split points of the matrix views :func:`cp_als` builds for
    ``plan`` on ``executor``: one per distinct view the schedule's root
    partials read (:func:`repro.core.dimtree.view_split`).  Empty where the
    executor has no ``contract_view`` or the schedule no root partial."""
    if not hasattr(executor, "contract_view"):
        return ()
    n = plan.problem.ndim
    return tuple(sorted({
        view_split(node.lo, node.hi, n)
        for node in plan.resolved_schedule.walk()
        if _reads_view(node)
    }))


@functools.partial(jax.jit, static_argnames=("splits", "batched"))
def prepare_operands(x: Array, *, splits: tuple[int, ...], batched: bool):
    """The set-up program :func:`cp_als` runs once a solve, before its
    first dispatch: the tensor's norm and its matrix views ``{m: X_(m)}``
    for each split point in ``splits``, from one read of ``x`` under the
    scope ``prepare``.  On a TPU each view is a relayout of the whole
    tensor (:func:`repro.core.dimtree.matrix_view`), paid here once a
    solve instead of inside every sweep."""
    with jax.named_scope("prepare"):
        norm_x = tensor_norm(x, batched=batched).astype(x.dtype)
        views = {m: matrix_view(x, m, batched=batched) for m in splits}
    return norm_x, views


def sweep_chunk(plan: SweepPlan, executor: Executor, donate: tuple[int, ...] = ()):
    """The jitted program one :func:`cp_als` dispatch runs:
    ``chunk(x, norm_x, it0, factors, weights, gs, carry, pp, views=None, *,
    length)`` runs ``length`` sweeps under ``lax.scan`` and returns
    ``(factors, weights, gs, carry, pp, it, fits, fit)``: ``it`` is
    ``it0 + length``, the next chunk's ``it0``, and ``fit`` the last
    sweep's entry of the per-sweep ``fits``, so the host loop takes no
    slice of ``fits`` and sends no counter to the device.

    Only the evolving buffers go out (returning ``x`` would make XLA emit a
    full-tensor copy every chunk); ``donate`` names the arguments donated
    in, so off-CPU backends update factors/Grams/carry/PP-cache in place.
    With ``views`` the root partials read them, and ``x`` is ``None``
    unless a root leaf or the PP cache reads it.
    """
    problem = plan.problem

    def _chunk(x, norm_x, it0, factors, weights, gs, carry, pp, views=None, *, length):
        def body(c, _):
            factors, weights, gs, carry, pp, it = c
            state = SweepState(
                x=x, factors=factors, weights=weights, norm_x=norm_x,
                it=it, carry=carry, grams=gs, pp=pp, views=views,
            )
            out = als_sweep(problem, plan, executor, state)
            return (
                (out.factors, out.weights, out.grams, out.carry, out.pp, it + 1),
                out.fit,
            )

        init = (factors, weights, gs, carry, pp, it0)
        (factors, weights, gs, carry, pp, it), fits = jax.lax.scan(
            body, init, None, length=length
        )
        return factors, weights, gs, carry, pp, it, fits, fits[-1]

    return jax.jit(_chunk, static_argnames=("length",), donate_argnums=donate)


def cp_als(
    x: Array,
    plan: SweepPlan,
    *,
    executor: Executor | None = None,
    n_iters: int = 50,
    tol: float = 1.0e-5,
    seed: int = 0,
    track_fit: bool = True,
    init_factors: list[Array] | None = None,
    callback: Callable[[int, float, float], None] | None = None,
    sweeps_per_sync: int = 1,
    dispatch_cache: MutableMapping[Any, Callable] | None = None,
    dispatch_key: Any = None,
) -> CPState:
    """THE CP-ALS driver: init, sync-free chunked sweep loop, convergence stop.

    Replaces both ``core.cpals.cp_als`` and ``dist.dist_mttkrp.dist_cp_als``
    (which wrap it).  ``executor`` defaults to :class:`LocalExecutor` for
    local plans; for sharded plans pass the matching instance (build one
    from ``plan.executor`` with :func:`repro.plan.executor.make_executor`)
    -- ``prepare`` places the tensor/factors before the loop, and executors
    with carry state (compressed collectives) have it initialized here and
    threaded across iterations.  Per-iteration wall times go through
    ``callback(it, fit, seconds)`` so benchmarks can record them.

    ``sweeps_per_sync`` makes the hot loop sync-free: each device dispatch
    runs that many sweeps inside one compiled ``lax.scan`` (factor, Gram,
    weight and carry buffers donated off-CPU) and the host blocks exactly
    once per chunk -- the per-sweep iterates are bitwise identical to
    ``sweeps_per_sync=1``, only the host round-trips change (one per chunk
    instead of one per sweep).  That one block is the chunk's sync: the read
    of its per-sweep fits into host memory, started as the chunk is
    enqueued.  Convergence is checked against that host copy, so a run may
    execute up to ``sweeps_per_sync - 1`` sweeps past the first converged
    one; the callback still fires once per executed sweep (with the chunk's
    mean per-sweep seconds).  The chunk itself returns the last sweep's fit
    and the advanced sweep counter, so between dispatches the host makes
    no other device call.

    Batched problems (``plan.problem.batched``) expect ``x`` of shape
    ``(batch, *problem.shape)`` and run ALL problems through the same
    compiled dispatches: factors/weights/Grams gain a leading batch axis,
    the fit is per-problem (``CPState.fit`` has shape ``(batch,)``), the
    callback receives the batch-mean fit, and convergence requires every
    problem's fit delta below ``tol``, taken in the fits' dtype as the
    device computes it (problems are independent, so the shared stop is
    the price of one fused dispatch -- at most a few extra sweeps for the
    fastest converger).

    Plans with ``plan.pp`` (built from a ``Problem(pp_tol > 0)``) run the
    pairwise-perturbation loop: the scan carries the PP cache next to the
    factors, each sweep gates exact-vs-approximate on the traced drifts (so
    chunks stay sync-free), and ``CPState.pp_exact_sweeps`` reports how many
    sweeps re-materialized the cache -- ``pp_exact_sweeps / it`` is the
    measured exact-sweep fraction the bench compares against the planner's
    amortization assumption.  ``pp_tol=0`` plans never build the cache, so
    their iterates are bitwise identical to classic exact ALS.

    ``dispatch_cache`` (with ``dispatch_key``) lets a caller that drives
    many same-signature runs -- the serving engine of
    :mod:`repro.serve.cp_service` -- reuse ONE jitted sweep-chunk across
    calls: each ``cp_als`` call otherwise builds a fresh ``jax.jit`` wrapper
    and recompiles.  The compiled chunk closes over ``(plan, executor)``, so
    the caller must key the cache such that one key never maps two distinct
    plans/executors (the service keys on the problem signature and memoizes
    plan + executor under the same key).  A cache hit makes the call
    compile-free for shapes already traced.

    Executors with the ``contract_view`` hook (:class:`LocalExecutor`)
    have the tensor's matrix views built once a call, in the set-up
    program :func:`prepare_operands` that also takes the norm, and each
    root partial of the schedule reads its view; ``CPState.prepared_views``
    counts them.  Views are not kept across calls.

    Observability costs nothing unless a profiler trace is being taken.
    Device ops carry the named scopes ``prepare`` (the set-up program),
    ``mttkrp.node<id>`` (one schedule node's contraction), ``update.mode<n>``
    and ``fit`` in their op names.  The host loop opens the
    ``jax.profiler.TraceAnnotation`` spans ``cp_als.init`` (entry to the
    first dispatch), ``cp_als.dispatch`` (argument preparation and enqueue),
    ``cp_als.wait`` (the chunk's block: the read of its fits) and
    ``cp_als.check`` (the convergence test and the callback, on the host
    copy).  ``CPState.host_syncs`` counts every time the host blocked on
    the device: one read a chunk, plus one read of the exact-sweep count on
    a pairwise-perturbation plan.
    """
    problem = plan.problem
    if executor is None:
        if plan.executor != "local":
            raise ValueError(
                f"plan.executor={plan.executor!r} needs an executor instance: "
                "the Problem carries only axis sizes, so build one with "
                "repro.plan.make_executor(plan.executor, mesh, mode_axes)"
            )
        executor = LocalExecutor()
    k = int(sweeps_per_sync)
    if k < 1:
        raise ValueError(f"sweeps_per_sync must be >= 1, got {sweeps_per_sync}")
    with jax.profiler.TraceAnnotation("cp_als.init"), jax.named_scope("init"):
        key = jax.random.PRNGKey(seed)
        if problem.batched:
            expected = (problem.batch,) + problem.shape
            if tuple(x.shape) != expected:
                raise ValueError(
                    f"batched problem expects x.shape {expected}, got {tuple(x.shape)}"
                )
            factors = init_factors or random_factors(
                key, problem.shape, problem.rank, x.dtype, batch=problem.batch
            )
        else:
            factors = init_factors or random_factors(key, x.shape, problem.rank, x.dtype)
        x, factors = executor.prepare(problem, x, factors)
        # donated buffers are deleted after the first dispatch; prepare() may
        # pass caller arrays through unchanged (LocalExecutor), so donation is
        # keyed off the backend (a no-op-with-warning on CPU) and caller-owned
        # init_factors are copied once rather than invalidated under the caller.
        donate = (3, 4, 5, 6, 7) if jax.default_backend() != "cpu" else ()
        if donate and init_factors is not None:
            factors = [jnp.array(u, copy=True) for u in factors]
        lead = (problem.batch,) if problem.batched else ()
        weights = jnp.ones(lead + (problem.rank,), x.dtype)
        splits = view_splits(plan, executor)
        norm_x, views = prepare_operands(x, splits=splits, batched=problem.batched)
        carry = (
            executor.init_carry(plan, x, factors)
            if hasattr(executor, "init_carry")
            else None
        )
        # Grams are computed once here and carried across sweeps (each update
        # refreshes exactly the changed factor's Gram inside the sweep), at
        # the sweep's precision: the first sweep's updates solve with them,
        # and at a TPU's default precision (one bf16 pass) they put the
        # answers of slowly converging problems percents off exact ALS.
        with jax.default_matmul_precision(SWEEP_PRECISION):
            gs = grams(factors)
        # PP plans carry the cache through the same scan (zeros + inf drift,
        # so the first sweep is exact); pp stays None otherwise and the chunk
        # graph is the classic exact one, bitwise.
        pp = _pp_init(problem, x, factors) if plan.pp else None
        # the chunk takes x only where something still reads it: a node
        # off the root that gets no view, or the PP cache's pairwise build
        reads_x = plan.pp or any(
            node.from_root and not (views and _reads_view(node))
            for node in plan.resolved_schedule.walk()
        )
        x_arg = x if reads_x else None

        if dispatch_cache is not None and dispatch_key in dispatch_cache:
            chunk = dispatch_cache[dispatch_key]
        else:
            chunk = sweep_chunk(plan, executor, donate)
            if dispatch_cache is not None:
                dispatch_cache[dispatch_key] = chunk
        # the sweep counter's one transfer a solve: each chunk returns its
        # advanced counter, which the next chunk takes as it is
        it_dev = jnp.asarray(0)

    syncs = 0
    fit_prev = -math.inf
    fit = None
    it = 0
    done = False
    while it < n_iters and not done:
        length = min(k, n_iters - it)
        t0 = time.perf_counter()
        with jax.profiler.TraceAnnotation("cp_als.dispatch"):
            factors, weights, gs, carry, pp, it_dev, fits, fit = chunk(
                x_arg, norm_x, it_dev, factors, weights, gs, carry, pp,
                views, length=length,
            )
            fits.copy_to_host_async()
        with jax.profiler.TraceAnnotation("cp_als.wait"):
            fits = _read_fits(fits)  # the chunk's one wait
        syncs += 1
        dt = time.perf_counter() - t0
        with jax.profiler.TraceAnnotation("cp_als.check"):
            if problem.batched:
                # per-problem fits (B,), in the device's dtype; stop only
                # when EVERY problem's fit delta clears tol (one fused
                # dispatch, shared stop).
                tol_f = fits.dtype.type(tol)
                for j, f in enumerate(fits):
                    if callback is not None:
                        callback(it + j, float(f.mean()), dt / length)
                    if track_fit and np.max(np.abs(f - fit_prev)) < tol_f:
                        done = True
                    fit_prev = f
            else:
                for j, f in enumerate(fits.tolist()):
                    if callback is not None:
                        callback(it + j, f, dt / length)
                    if track_fit and abs(f - fit_prev) < tol:
                        done = True
                    fit_prev = f
            it += length
    if fit is None:
        fit = jnp.asarray(0.0, x.dtype)
    pp_exact_sweeps = None
    if pp is not None:
        pp_exact_sweeps = int(pp.n_exact)
        syncs += 1
    return CPState(
        factors=factors, weights=weights, fit=fit, it=it,
        pp_exact_sweeps=pp_exact_sweeps, host_syncs=syncs,
        prepared_views=len(views),
    )
