"""The paper's neuroimaging application (Sec. 3 / Sec. 5.3.3), synthetic data.

Builds a time x subject x region x region functional-connectivity tensor with
planted network components (rank-1 outer products of temporal envelopes,
subject loadings, and symmetric network maps), then:
  1. runs CP-ALS on the 4-way tensor,
  2. linearizes the symmetric region-region modes (upper triangle, as the
     paper does -- halves the entries) and runs CP-ALS on the 3-way tensor,
  3. reports per-iteration times for the paper's method mix vs the
     reorder-baseline, and the recovered component count.

    PYTHONPATH=src python examples/fmri_cpals.py [--regions 60] [--rank 5]
"""

import argparse
import time

import jax
import jax.numpy as jnp
import numpy as np

from repro.plan import Problem, cp_als, plan_sweep


def synth_fmri(t=120, subjects=30, regions=60, rank=5, seed=0):
    key = jax.random.PRNGKey(seed)
    ks = jax.random.split(key, 4)
    # temporal envelopes: smooth positive signals
    tt = jnp.linspace(0, 8 * jnp.pi, t)[:, None]
    phases = jax.random.uniform(ks[0], (1, rank)) * 2 * jnp.pi
    temporal = 1.0 + jnp.sin(tt / (1 + jnp.arange(rank)) + phases)
    subj = jax.nn.softplus(jax.random.normal(ks[1], (subjects, rank)))
    seeds = jax.random.normal(ks[2], (regions, rank))
    networks = jnp.einsum("ir,jr->rij", seeds, seeds)  # symmetric maps
    x = jnp.einsum("tr,sr,rij->tsij", temporal, subj, networks)
    x = x / jnp.max(jnp.abs(x))
    noise = 0.05 * jax.random.normal(ks[3], x.shape)
    return x + noise


def run(x, rank, label, method="auto", iters=15):
    times = []
    plan = plan_sweep(Problem.from_tensor(x, rank), strategy=method)
    st = cp_als(
        x, plan, n_iters=iters, tol=1e-6,
        callback=lambda it, fit, dt: times.append(dt),
    )
    per_iter = float(np.min(times[1:])) if len(times) > 1 else times[0]
    print(
        f"  {label:28s} fit={float(st.fit):.4f}  per-iter={per_iter*1e3:8.1f} ms"
        f"  ({st.it} sweeps)"
    )
    return st, per_iter


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--regions", type=int, default=60)
    ap.add_argument("--subjects", type=int, default=30)
    ap.add_argument("--time", type=int, default=120)
    ap.add_argument("--rank", type=int, default=5)
    args = ap.parse_args()

    x4 = synth_fmri(args.time, args.subjects, args.regions, args.rank)
    print(f"4-way tensor {tuple(x4.shape)} ({x4.size:,} entries)")
    _, t_auto = run(x4, args.rank, "4D paper methods (auto)")
    _, t_base = run(x4, args.rank, "4D reorder-baseline", method="baseline")
    print(f"  4D speedup over baseline: {t_base / t_auto:.2f}x")

    # linearize symmetric region modes (paper: halves entries, 3-way tensor)
    r = args.regions
    iu = jnp.triu_indices(r)
    x3 = x4[:, :, iu[0], iu[1]]
    print(f"3-way linearized tensor {tuple(x3.shape)}")
    st3, t3_auto = run(x3, args.rank, "3D paper methods (auto)")
    _, t3_base = run(x3, args.rank, "3D reorder-baseline", method="baseline")
    print(f"  3D speedup over baseline: {t3_base / t3_auto:.2f}x")

    # component summary: temporal factor column norms = component energies
    w = np.asarray(st3.weights)
    print(f"recovered component weights: {np.sort(w)[::-1][:args.rank].round(3)}")


if __name__ == "__main__":
    main()
