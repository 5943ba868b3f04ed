"""Distributed CP-ALS demo: paper Alg. 3 across a device mesh.

Runs on 8 simulated host devices (mesh 2x4), tensor block-distributed over
two modes, full ALS inside one shard_map (local MTTKRP + psum reductions --
the device-for-thread port of the paper's parallelization).

    PYTHONPATH=src python examples/distributed_cpals.py
"""

import os

os.environ.setdefault("XLA_FLAGS", "--xla_force_host_platform_device_count=8")

import time  # noqa: E402

import jax  # noqa: E402

from repro.core import CPConfig, cp_als, cp_full, random_factors  # noqa: E402
from repro.dist.dist_mttkrp import dist_cp_als  # noqa: E402
from repro.launch.mesh import make_mesh  # noqa: E402
from repro.plan import Problem, plan_sweep  # noqa: E402


def main():
    print(f"devices: {jax.device_count()}")
    mesh = make_mesh((2, 4), ("data", "model"))
    key = jax.random.PRNGKey(0)
    shape, rank = (64, 48, 40), 6
    x = cp_full(None, random_factors(key, shape, rank))

    # plan the sharded sweep: per-mode algorithm + predicted psum volume,
    # plus the cost-argmin executor pick (overlapping hides the psums behind
    # the chunked local GEMMs; see docs/distributed.md)
    plan = plan_sweep(Problem.from_tensor(x, rank, mode_axes={0: "data", 1: "model"},
                                          mesh=mesh))
    print(f"  planner picked executor: {plan.executor}")
    for mp in plan.modes:
        print(f"  mode {mp.mode}: {mp.algorithm:12s} "
              f"psum {mp.cost.collective_bytes/1e3:8.1f} kB/device "
              f"overlap_eff {mp.cost.predicted_overlap_efficiency:.2f}")

    t0 = time.perf_counter()
    factors, weights, fit = dist_cp_als(
        x, rank=rank, mode_axes={0: "data", 1: "model"}, mesh=mesh, n_iters=60
    )
    t_dist = time.perf_counter() - t0
    print(f"distributed CP-ALS: fit={float(fit):.5f} in {t_dist:.2f}s "
          f"(tensor sharded {mesh.shape} over modes 0,1)")

    t0 = time.perf_counter()
    st = cp_als(x, CPConfig(rank=rank, n_iters=60))
    t_local = time.perf_counter() - t0
    print(f"single-device reference: fit={float(st.fit):.5f} in {t_local:.2f}s")
    assert abs(float(fit) - float(st.fit)) < 1e-2
    print("OK: distributed result matches")


if __name__ == "__main__":
    main()
