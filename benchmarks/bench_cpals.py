"""Paper Figs. 7-8: CP-ALS per-iteration time on fMRI-shaped tensors.

The application tensors are 225 x 59 x 200 x 200 (4D) and, linearizing the
symmetric region-region modes, 225 x 59 x 19900 (3D).  Default here scales
regions down 2x (100x100 / 4950) for single-core wall times; --full restores
paper shapes.  We compare the paper's recommended mixed method ('auto':
1-step external + 2-step internal) against the reorder-baseline and the
plain einsum formulation, for C in {10, 25}.
"""

from __future__ import annotations

import time

import jax
import jax.numpy as jnp

from repro.core import random_tensor
from repro.plan import Problem, cp_als, plan_sweep

from .util import row


def _tensors(full: bool):
    r = 200 if full else 100
    key = jax.random.PRNGKey(3)
    x4 = random_tensor(key, (225, 59, r, r))
    # symmetrize region modes then linearize upper triangle incl. diagonal
    x4 = 0.5 * (x4 + jnp.swapaxes(x4, 2, 3))
    iu = jnp.triu_indices(r)
    x3 = x4[:, :, iu[0], iu[1]]
    return {"4d": x4, "3d": x3}


def _per_iter_seconds(x, rank: int, method: str, iters: int = 3) -> float:
    times: list[float] = []
    plan = plan_sweep(Problem.from_tensor(x, rank), strategy=method)
    cp_als(
        x, plan, n_iters=iters, tol=0.0, track_fit=False,
        callback=lambda it, fit, dt: times.append(dt),
    )
    return min(times[1:]) if len(times) > 1 else times[0]  # skip compile iter


def run(full: bool = False) -> list[str]:
    out = []
    for name, x in _tensors(full).items():
        for rank in (10, 25):
            t_auto = _per_iter_seconds(x, rank, "auto")
            t_base = _per_iter_seconds(x, rank, "baseline")
            t_1 = _per_iter_seconds(x, rank, "1step")
            t_2 = _per_iter_seconds(x, rank, "2step")
            out.append(
                row(
                    f"cpals_{name}_C{rank}_auto",
                    t_auto,
                    f"shape={tuple(x.shape)};baseline_s={t_base:.3f};"
                    f"speedup={t_base/t_auto:.2f}x;"
                    f"pure_1step_s={t_1:.3f};pure_2step_s={t_2:.3f}",
                )
            )
    return out


if __name__ == "__main__":
    for line in run():
        print(line)
