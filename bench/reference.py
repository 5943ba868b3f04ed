"""The plain CP-ALS reference the benchmark compares the program with.

Straightforward ``jax.numpy``: per mode, the MTTKRP as a chain of pairwise
contractions, the Hadamard of the other Grams, an LU solve, and the column
norms moved into the weights; the fit from the model formed explicitly.
It imports nothing of the program.

``precision="highest"`` is the reference: every contraction at
``Precision.HIGHEST``, the precision the configurations state.
``precision="high"`` is the control, the nearest precision below it:
every contraction of two operands is the three-pass bf16 product
(``hi*hi + hi*lo + lo*hi``, each operand split into two bf16 parts), the
arithmetic of ``Precision.HIGH``, written out so that it is the same on a
CPU as on a TPU.
"""

from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp
import numpy as np

HI = jax.lax.Precision.HIGHEST
PRECISIONS = ("highest", "high")


def _split(a):
    hi = a.astype(jnp.bfloat16).astype(jnp.float32)
    lo = (a - hi).astype(jnp.bfloat16).astype(jnp.float32)
    return hi, lo


def contract(spec: str, a, b, precision: str):
    """``einsum(spec, a, b)`` at the named precision."""
    if precision == "highest":
        return jnp.einsum(spec, a, b, precision=HI)
    if precision == "high":
        (ah, al), (bh, bl) = _split(a), _split(b)
        return (
            jnp.einsum(spec, ah, bh, precision=HI)
            + jnp.einsum(spec, ah, bl, precision=HI)
            + jnp.einsum(spec, al, bh, precision=HI)
        )
    raise ValueError(f"precision {precision!r} not in {PRECISIONS}")


def mttkrp(x, factors, n: int, precision: str):
    """``X_(n) (U_{N-1} kr ... kr U_0)``: contract the other modes one at a
    time, the last first; the rank index rides along after the first."""
    letters = "abcdefgh"[: x.ndim]
    t, cur = x, letters
    for k in reversed(range(x.ndim)):
        if k == n:
            continue
        lhs = cur if cur == letters else cur + "z"
        cur = cur.replace(letters[k], "")
        t = contract(f"{lhs},{letters[k]}z->{cur}z", t, factors[k], precision)
    return t


def sweep(x, factors, precision: str):
    """One ALS sweep; returns the new factors and the weights."""
    factors = list(factors)
    weights = None
    for n in range(x.ndim):
        h = None
        for k, u in enumerate(factors):
            if k != n:
                g = contract("iz,iy->zy", u, u, precision)
                h = g if h is None else h * g
        m = mttkrp(x, factors, n, precision)
        u = jnp.linalg.solve(h, m.T).T
        weights = jnp.linalg.norm(u, axis=0)
        factors[n] = u / weights
    return factors, weights


def fit(x, factors, weights):
    """``1 - ||X - model|| / ||X||``, the model formed explicitly."""
    letters = "abcdefgh"[: x.ndim]
    subs = ",".join(c + "z" for c in letters)
    model = jnp.einsum(f"z,{subs}->{letters}", weights, *factors, precision=HI)
    return 1.0 - jnp.linalg.norm(x - model) / jnp.linalg.norm(x)


@partial(jax.jit, static_argnames=("precision",))
def _step(x, factors, *, precision):
    with jax.default_matmul_precision("highest"):
        factors, weights = sweep(x, factors, precision)
        return factors, weights, fit(x, factors, weights)


def als(x, init, *, n_iters: int, tol: float, precision: str = "highest",
        keep=()):
    """CP-ALS from ``init`` under the driver's rule: stop after the first
    sweep whose fit differs from the last by less than ``tol``, or after
    ``n_iters`` (``tol=0`` runs them all).  Returns that answer,
    ``(factors, weights, fit, sweeps)``, and the same after each count in
    ``keep`` up to ``n_iters``, sweeping on past the stop where ``keep``
    asks for more."""
    keep = {int(k) for k in keep if 0 < int(k) <= n_iters}
    factors, prev, it = list(init), -np.inf, 0
    final, snaps = None, {}
    while it < n_iters and (final is None or it < max(keep, default=0)):
        factors, weights, f = _step(x, factors, precision=precision)
        it += 1
        f = float(f)
        if it in keep:
            snaps[it] = (factors, weights, f, it)
        if final is None and (abs(f - prev) < tol or it == n_iters):
            final = (factors, weights, f, it)
        prev = f
    return final, snaps


def model_diff(w1, f1, w2, f2) -> float:
    """``||M1 - M2|| / ||M2||`` between two CP models, in float64 on the
    host through the Gram identity (no tensor is formed)."""
    w1, w2 = np.asarray(w1, np.float64), np.asarray(w2, np.float64)
    f1 = [np.asarray(u, np.float64) for u in f1]
    f2 = [np.asarray(u, np.float64) for u in f2]

    def inner(wa, fa, wb, fb):
        h = np.ones((wa.size, wb.size))
        for a, b in zip(fa, fb):
            h *= a.T @ b
        return float(wa @ h @ wb)

    n11, n22, n12 = inner(w1, f1, w1, f1), inner(w2, f2, w2, f2), inner(w1, f1, w2, f2)
    return float(np.sqrt(max(n11 + n22 - 2.0 * n12, 0.0) / n22))
