"""Beyond-paper: dimension-tree ALS sweep vs standard sweep (wall clock).

The paper's Sec. 6 predicts ~2x per-iteration CP-ALS gain in 4-D from reusing
partial MTTKRPs across modes (Phan et al. III.C).  The dry-run confirms the
byte/flop model at pod scale (EXPERIMENTS SPerf cell 1); this benchmark
confirms it in real single-core time.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

from repro.core import random_factors, random_tensor, tensor_norm
from repro.plan import LocalExecutor, Problem, SweepState, als_sweep, plan_sweep

from .util import row, time_fn

C = 16


def _sweep(plan, norm_x, it):
    """One jitted ``repro.plan.als_sweep`` of ``plan``: ``(x, factors,
    weights) -> fit``."""

    def sweep(x, factors, weights):
        state = SweepState(x=x, factors=factors, weights=weights, norm_x=norm_x, it=it)
        return als_sweep(plan.problem, plan, LocalExecutor(), state).fit

    return jax.jit(sweep)


def run(full: bool = False) -> list[str]:
    out = []
    shapes = [(64, 64, 64, 64), (32, 32, 32, 32, 32)]
    if full:
        shapes = [(160, 160, 160, 160), (64,) * 5]
    for shape in shapes:
        x = random_tensor(jax.random.PRNGKey(0), shape)
        factors = random_factors(jax.random.PRNGKey(1), shape, C)
        w = jnp.ones((C,), x.dtype)
        norm_x = tensor_norm(x)
        it = jnp.asarray(1)

        problem = Problem.from_tensor(x, C)
        std = _sweep(plan_sweep(problem, schedule="flat"), norm_x, it)
        dt = _sweep(plan_sweep(problem, strategy="dimtree"), norm_x, it)
        t_std = time_fn(std, x, factors, w, reps=3)["median_s"]
        t_dt = time_fn(dt, x, factors, w, reps=3)["median_s"]
        out.append(
            row(
                f"dimtree_N{len(shape)}_{shape[0]}",
                t_dt,
                f"standard_sweep_s={t_std:.4f};speedup={t_std/t_dt:.2f}x",
            )
        )
    return out


if __name__ == "__main__":
    for line in run():
        print(line)
