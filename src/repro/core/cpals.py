"""CP-ALS entry points + the shared per-update algebra (paper Sec. 2.2).

Per mode-n update (alternating least squares):
    M   = MTTKRP(X, {U_k}, n)                      (the bottleneck; Algs. 2-4)
    H   = *_{k != n} (U_k^T U_k)                   (Hadamard of Gram matrices)
    U_n = M @ inv(H) (by LU);  column-normalize -> lambda

Fit is tracked with the standard factored identity (no residual tensor):
    ||X - Y||^2 = ||X||^2 - 2 <X, Y> + ||Y||^2
    <X, Y>      = sum(M_last * (U_last * lambda))   (reuses the last MTTKRP)
    ||Y||^2     = lambda^T ( *_k U_k^T U_k ) lambda

The sweep itself lives in ONE place -- :func:`repro.plan.sweep.als_sweep` --
driven by a ``SweepPlan`` (per-mode algorithm choice from the analytic cost
model) and an ``Executor`` (local or sharded).  ``als_sweep`` / ``cp_als``
below are thin back-compat wrappers that build the plan for the old
``method=`` argument; this module keeps the small algebra helpers
(:func:`grams`, :func:`hadamard_except`, :func:`fit_from_last_mttkrp`,
:func:`normalize_columns`) the engine imports.
"""

from __future__ import annotations

from dataclasses import dataclass
from typing import Callable, Sequence

import jax
import jax.numpy as jnp

from .mttkrp import Method

Array = jax.Array


@dataclass
class CPState:
    factors: list[Array]
    weights: Array  # lambda, shape (C,) -- or (B, C) for batched problems
    fit: Array  # scalar in [.., 1] -- or shape (B,) for batched problems
    it: int = 0
    # Exact (re-materializing) sweeps executed when the run used pairwise
    # perturbation (Problem.pp_tol > 0); None for classic exact-only runs.
    pp_exact_sweeps: int | None = None
    # Times cp_als blocked the host on the device: the one read of each
    # chunk's fits, which is its wait, and the read of pp_exact_sweeps.
    host_syncs: int = 0
    # Tensor-sized matrix views of the tensor cp_als built for the solve
    # (once, before the first sweep); 0 where the sweeps read the tensor.
    prepared_views: int = 0


@dataclass
class CPConfig:
    rank: int
    n_iters: int = 50
    tol: float = 1.0e-5
    method: Method = "auto"
    seed: int = 0
    normalize: bool = True
    track_fit: bool = True


def grams(factors: Sequence[Array]) -> list[Array]:
    # rank-polymorphic: (I, C) -> (C, C), and (B, I, C) -> (B, C, C); for the
    # unbatched 2-D case swapaxes @ is exactly u.T @ u
    return [jnp.swapaxes(u, -1, -2) @ u for u in factors]


def hadamard_except(gs: Sequence[Array], n: int) -> Array:
    out = None
    for k, g in enumerate(gs):
        if k == n:
            continue
        out = g if out is None else out * g
    assert out is not None
    return out


def fit_from_last_mttkrp(
    gs: Sequence[Array],
    weights: Array,
    m_last: Array,
    last_factor: Array,
    norm_x: Array,
) -> Array:
    """Fit via the factored identity, reusing the final mode's MTTKRP:
    ||X - Y||^2 = ||X||^2 - 2 <X, Y> + ||Y||^2  with
    <X, Y> = sum(M_last * (U_last * lambda)) and
    ||Y|| ^2 = lambda^T ( *_k U_k^T U_k ) lambda.

    Rank-polymorphic: with batched arguments (leading ``B`` axis on every
    operand, ``norm_x`` of shape ``(B,)``) the return is the per-problem fit
    vector ``(B,)``; unbatched it stays the classic scalar."""
    n_modes = len(gs)
    full_h = gs[-1] * hadamard_except(gs, n_modes - 1)
    norm_y_sq = jnp.einsum("...c,...cd,...d->...", weights, full_h, weights)
    inner = jnp.sum(
        m_last * (last_factor * weights[..., None, :]), axis=(-2, -1)
    )
    resid_sq = jnp.maximum(norm_x**2 - 2.0 * inner + norm_y_sq, 0.0)
    return 1.0 - jnp.sqrt(resid_sq) / norm_x


def normalize_columns(u: Array, it: int) -> tuple[Array, Array]:
    """Column norms -> lambda.  First sweep uses 2-norm, later sweeps use
    max(1, norm) (the Tensor Toolbox convention that keeps lambdas stable).
    Rank-polymorphic: norms are taken over the row axis (``-2``), so a
    batched ``(B, I, C)`` factor yields ``(B, C)`` lambdas."""
    norms = jnp.linalg.norm(u, axis=-2)
    norms = jnp.where(it == 0, norms, jnp.maximum(norms, 1.0))
    return u / norms[..., None, :], norms


# Historical private name; dimtree.py and dist_mttkrp.py used to import it.
_normalize_columns = normalize_columns


def als_sweep(
    x: Array,
    factors: list[Array],
    weights: Array,
    norm_x: Array,
    it: int,
    method: Method,
    normalize: bool,
) -> tuple[list[Array], Array, Array]:
    """One full ALS sweep over all modes; returns (factors, weights, fit).

    Back-compat wrapper: builds the :class:`repro.plan.SweepPlan` for
    ``method`` and runs the single shared sweep engine on a LocalExecutor.
    """
    from repro import plan as planlib

    return planlib.legacy_sweep(
        x, factors, weights, norm_x, it, strategy=method, normalize=normalize
    )


def cp_als(
    x: Array,
    config: CPConfig,
    init_factors: list[Array] | None = None,
    callback: Callable[[int, float, float], None] | None = None,
) -> CPState:
    """Run CP-ALS.  Returns the final CPState; per-iteration times go through
    ``callback(it, fit, seconds)`` so benchmarks can record them.

    Back-compat wrapper over the single :func:`repro.plan.cp_als` driver.
    """
    from repro import plan as planlib

    problem = planlib.Problem.from_tensor(x, config.rank)
    sweep_plan = planlib.plan_sweep(
        problem, strategy=config.method, normalize=config.normalize
    )
    return planlib.cp_als(
        x,
        sweep_plan,
        n_iters=config.n_iters,
        tol=config.tol,
        seed=config.seed,
        track_fit=config.track_fit,
        init_factors=init_factors,
        callback=callback,
    )
