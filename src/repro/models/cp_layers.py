"""CP-factorized layers: the paper's technique as an LM compression hook.

A dense weight W (d_in x d_out) is a 2-way tensor; its rank-r CP model is
W ~= A @ B (A: d_in x r, B: r x d_out) with the rank-1 terms as columns --
fit here with the solver's CP-ALS, :mod:`repro.plan` (for matrices, ALS
converges to the truncated-SVD subspace).  3-way weights (MoE expert
stacks (E, d, f)) use the full 3-way CP decomposition, whose factor
updates are exactly our MTTKRP.

``cfg.cp_rank > 0`` switches models/ffn.py to the factorized parameterization
(trainable end to end); :func:`factorize_linear` / :func:`compress_ffn`
convert a trained dense checkpoint into that parameterization.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

from repro.plan import Problem, cp_als, plan_sweep

Array = jax.Array


def _cp(w: Array, rank: int, n_iters: int):
    """CP-ALS of ``w`` at ``rank`` on the planner's automatic plan."""
    plan = plan_sweep(Problem.from_tensor(w, rank), strategy="auto")
    return cp_als(w, plan, n_iters=n_iters, tol=1e-7)


def factorize_linear(w: Array, rank: int, *, n_iters: int = 60) -> tuple[Array, Array]:
    """Rank-r CP (== low-rank) factorization of a matrix:  W ~= A @ B."""
    st = _cp(w, rank, n_iters)
    a, b = st.factors
    return a * st.weights[None, :], b.T  # fold lambda into A


def factorize_expert_stack(w: Array, rank: int, *, n_iters: int = 60):
    """3-way CP of an (E, d_in, d_out) expert stack -> (E-, in-, out-) factors."""
    st = _cp(w, rank, n_iters)
    e, a, b = st.factors
    return e * st.weights[None, :], a, b


def reconstruction_error(w: Array, a: Array, b: Array) -> float:
    approx = a @ b
    return float(jnp.linalg.norm(w - approx) / jnp.linalg.norm(w))


def compress_ffn(ffn_params: dict, rank: int) -> dict:
    """Dense FFN params {gate, up, down} -> CP-factorized {._a, ._b} tree
    matching models/ffn.py's cp_rank parameterization."""
    out = {}
    for name in ("gate", "up", "down"):
        if name not in ffn_params:
            continue
        a, b = factorize_linear(ffn_params[name], rank)
        out[f"{name}_a"] = a
        out[f"{name}_b"] = b
    return out
