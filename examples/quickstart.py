"""Quickstart: CP decomposition of a dense tensor with the paper's MTTKRP.

Builds a rank-4 planted tensor + noise, plans the sweep through the
``Problem -> SweepPlan -> Executor`` front door (the planner argmins over
contraction schedules -- on an order-4 tensor it picks a dimension tree,
reading X twice per sweep instead of four times; full MTTKRPs inside any
schedule follow the paper's Sec. 5.3.3 method mix), runs CP-ALS, prints
fit trajectory and per-iteration timing, and cross-checks the fused Pallas
kernel against the einsum oracle on one MTTKRP.

    PYTHONPATH=src python examples/quickstart.py
"""

import jax
import jax.numpy as jnp
import numpy as np

from repro.core import cp_full, mttkrp_einsum, random_factors
from repro.kernels import ops
from repro.plan import Problem, cp_als, plan_sweep


def main():
    key = jax.random.PRNGKey(0)
    shape, rank = (60, 48, 36, 24), 4
    planted = random_factors(key, shape, rank)
    x = cp_full(None, planted)
    x = x + 0.05 * jnp.std(x) * jax.random.normal(jax.random.PRNGKey(1), x.shape)
    print(f"tensor {shape}, planted rank {rank}, noise 5% of signal std")

    # the front door: plan the sweep, see what the cost model picked per mode
    plan = plan_sweep(Problem.from_tensor(x, rank))
    for mp in plan.modes:
        print(f"  mode {mp.mode}: {mp.algorithm:12s} "
              f"predicted {mp.cost.predicted_s*1e6:8.1f} us "
              f"({mp.cost.flops:.2e} flops, {mp.cost.bytes:.2e} B)")

    history = []
    state = cp_als(
        x, plan, n_iters=40, tol=1e-7,
        callback=lambda it, fit, dt: history.append((it, fit, dt)),
    )
    for it, fit, dt in history[:3] + history[-2:]:
        print(f"  iter {it:2d}  fit={fit:.6f}  {dt*1e3:7.1f} ms")
    print(f"final fit {float(state.fit):.6f} after {state.it} sweeps")
    assert float(state.fit) > 0.95

    # fused Pallas kernel (interpret mode on CPU) vs oracle
    m_kernel = ops.fused_mttkrp(x, state.factors, 1)
    m_ref = mttkrp_einsum(x, state.factors, 1)
    err = float(jnp.max(jnp.abs(m_kernel - m_ref)))
    print(f"fused-kernel MTTKRP max|err| vs einsum oracle: {err:.2e}")
    assert err < 1e-2
    print("OK")


if __name__ == "__main__":
    main()
