"""Dimension-tree CP-ALS: exact equivalence with the standard sweep."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core.dimtree import (
    mttkrp_from_partial,
    partial_mttkrp_left,
    partial_mttkrp_right,
)
from repro.core import mttkrp_einsum, random_factors, random_tensor, tensor_norm
from repro.plan import LocalExecutor, Problem, SweepState, als_sweep, plan_sweep


def _problem(shape, c=4, seed=0):
    x = random_tensor(jax.random.PRNGKey(seed), shape)
    factors = random_factors(jax.random.PRNGKey(seed + 1), shape, c)
    return x, factors


def _sweep(x, factors, w, norm_x, it, strategy, schedule=None):
    """One ``repro.plan.als_sweep`` on the plan of ``strategy``; returns
    ``(factors, weights, fit)``."""
    plan = plan_sweep(
        Problem.from_tensor(x, factors[0].shape[1]), strategy=strategy,
        schedule=schedule,
    )
    state = SweepState(
        x=x, factors=list(factors), weights=w, norm_x=norm_x, it=jnp.asarray(it)
    )
    out = als_sweep(plan.problem, plan, LocalExecutor(), state)
    return out.factors, out.weights, out.fit


@pytest.mark.parametrize("shape", [(5, 6, 7), (4, 5, 6, 3), (3, 4, 2, 3, 4)])
def test_partials_give_correct_mttkrps(shape):
    x, factors = _problem(shape)
    n_modes = len(shape)
    m = (n_modes + 1) // 2
    t_left = partial_mttkrp_right(x, factors[m:])
    for n in range(m):
        sib = [factors[k] for k in range(m) if k != n]
        out = np.asarray(mttkrp_from_partial(t_left, sib, n))
        ref = np.asarray(mttkrp_einsum(x, factors, n))
        np.testing.assert_allclose(out, ref, rtol=2e-4, atol=1e-4)
    t_right = partial_mttkrp_left(x, factors[:m])
    for n in range(m, n_modes):
        sib = [factors[k] for k in range(m, n_modes) if k != n]
        out = np.asarray(mttkrp_from_partial(t_right, sib, n - m))
        ref = np.asarray(mttkrp_einsum(x, factors, n))
        np.testing.assert_allclose(out, ref, rtol=2e-4, atol=1e-4)


@pytest.mark.parametrize("shape", [(6, 5, 4), (4, 5, 3, 4)])
def test_dimtree_sweep_matches_standard_als_exactly(shape):
    """Same iterates: factor-by-factor agreement over multiple sweeps."""
    x, factors = _problem(shape, c=3, seed=7)
    w = jnp.ones((3,), x.dtype)
    norm_x = tensor_norm(x)
    f_a, w_a = list(factors), w
    f_b, w_b = list(factors), w
    for it in range(4):
        f_a, w_a, fit_a = _sweep(x, f_a, w_a, norm_x, it, "2step", "flat")
        f_b, w_b, fit_b = _sweep(x, f_b, w_b, norm_x, it, "dimtree")
        for ua, ub in zip(f_a, f_b):
            np.testing.assert_allclose(
                np.asarray(ua), np.asarray(ub), rtol=2e-3, atol=2e-4
            )
        np.testing.assert_allclose(float(fit_a), float(fit_b), atol=1e-4)


def test_dimtree_converges_on_planted():
    from repro.core import cp_full

    planted = random_factors(jax.random.PRNGKey(2), (8, 7, 6, 5), 2)
    x = cp_full(None, planted)
    factors = random_factors(jax.random.PRNGKey(3), x.shape, 2)
    w = jnp.ones((2,), x.dtype)
    norm_x = tensor_norm(x)
    fit = 0.0
    for it in range(60):
        factors, w, fit = _sweep(x, factors, w, norm_x, it, "dimtree")
    assert float(fit) > 0.99, float(fit)
