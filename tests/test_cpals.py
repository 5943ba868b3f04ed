"""CP-ALS behaviour tests: recovery of planted low-rank tensors, fit monotonicity."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import cp_full, random_factors
from repro.plan import Problem, cp_als, plan_sweep


def planted_tensor(shape, rank, seed=0, noise=0.0):
    key = jax.random.PRNGKey(seed)
    kf, kn = jax.random.split(key)
    factors = random_factors(kf, shape, rank)
    x = cp_full(None, factors)
    if noise:
        x = x + noise * jax.random.normal(kn, x.shape)
    return x


def solve(x, rank, strategy="auto", **kw):
    """``repro.plan.cp_als`` on the plan of ``strategy`` for ``x``."""
    plan = plan_sweep(Problem.from_tensor(x, rank), strategy=strategy)
    return cp_als(x, plan, **kw)


@pytest.mark.parametrize("method", ["auto", "1step", "2step", "einsum"])
def test_cpals_recovers_planted_rank(method):
    x = planted_tensor((12, 9, 10), rank=3, seed=1)
    state = solve(x, 3, method, n_iters=150, tol=1e-8, seed=4)
    assert float(state.fit) > 0.99, f"fit={float(state.fit)} for {method}"


def test_cpals_fit_monotone_nondecreasing():
    x = planted_tensor((10, 8, 6, 4), rank=2, seed=2, noise=0.05)
    fits = []
    solve(x, 4, n_iters=25, tol=0.0, callback=lambda it, fit, dt: fits.append(fit))
    fits = np.asarray(fits)
    # ALS monotonically decreases the residual (tiny numerical slack).
    assert np.all(np.diff(fits) > -1e-4), fits


def test_cpals_4way_matches_across_methods():
    x = planted_tensor((6, 5, 4, 3), rank=2, seed=3)
    f1 = solve(x, 2, "1step", n_iters=60, seed=9).fit
    f2 = solve(x, 2, "2step", n_iters=60, seed=9).fit
    np.testing.assert_allclose(float(f1), float(f2), atol=1e-3)


def test_cpals_reconstruction_error_matches_fit():
    x = planted_tensor((8, 7, 6), rank=2, seed=5)
    st = solve(x, 2, n_iters=100, tol=1e-9, seed=11)
    recon = cp_full(st.weights, st.factors)
    true_fit = 1.0 - float(jnp.linalg.norm((x - recon).ravel()) / jnp.linalg.norm(x.ravel()))
    # The factored fit formula (normX^2 - 2<X,Y> + normY^2) loses ~sqrt(eps)
    # precision near zero residual in fp32 -- allow that slack.
    np.testing.assert_allclose(float(st.fit), true_fit, atol=2e-3)


def test_cpals_weights_positive_and_sorted_magnitudes():
    x = planted_tensor((9, 9, 9), rank=3, seed=6)
    st = solve(x, 3, n_iters=80, seed=2)
    assert np.all(np.asarray(st.weights) > 0)
