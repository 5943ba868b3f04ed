#!/usr/bin/env python3
"""Bring-up smoke of the CP solver and CPService on one TPU chip.

Drives the system's main path once, through the entry points a user calls,
at the paper's neuroimaging size (arXiv 1708.08976 Sec. 6): a
225 x 59 x 200 x 200 fp32 tensor (2.1 GB) and its linearized
225 x 59 x 19900 form, rank 25.  The tensor is a planted rank-25 CP model
plus ~5% Gaussian noise, made on the device from ``--seed``.  Phases, all in
this one process:

* solver:  ``Problem.from_tensor -> plan_sweep(strategy="auto") -> cp_als``
  on the 4-way and the 3-way tensor, each fit checked against a plain
  ``jax.numpy`` ALS (einsum MTTKRP, ``Precision.HIGHEST``) from the same
  initial factors;
* kernels: ``core.mttkrp(method="fused" | "matrix_free")`` on every mode of
  the 4-way tensor, each checked against an einsum MTTKRP, with the compiled
  program shown to hold a ``tpu_custom_call`` (no kernel ran interpreted);
* service: ``tune`` one per-subject signature, then ``CPService`` serves 16
  per-subject 225 x 200 x 200 tensors over two signatures.

``--four-chips`` runs only the sharded phase instead: the 4-way tensor
sharded mode-parallel over a 2 x 2 mesh through ``ShardedExecutor``,
compared with the single-device ``LocalExecutor`` run of the same problem.

Run from the checkout root on a TPU host:  ``python chip_smoke.py``.  The
script fails unless JAX finds a TPU; it never falls back to the CPU.  The
last line of its output is a JSON object naming the device.  Compiles
persist in ``<checkout>/.jax_cache`` unless ``JAX_COMPILATION_CACHE_DIR``
names another directory.
"""

from __future__ import annotations

import argparse
import gc
import json
import sys
import time
from functools import partial
from pathlib import Path

ROOT = Path(__file__).resolve().parent
sys.path.insert(0, str(ROOT / "src"))

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402
import numpy as np  # noqa: E402

FMRI = (225, 59, 200, 200)  # time x subjects x region x region
RANK = 25
NOISE = 0.05
SWEEPS = 25
SUBJECTS = 16
SERVICE_BATCH = 8
SERVICE_RANKS = (25, 10)  # the paper's two ranks: two service signatures

# Fit tolerance against the HIGHEST-precision reference.  The solver
# contracts at Precision.HIGHEST too (repro.plan.sweep.SWEEP_PRECISION); the
# two differ in contraction order (dimension trees vs einsum), the solve
# (pinv vs LU) and the fit (factored identity vs explicit residual): fp32
# rounding only, ~1e-5 in fit on a converged planted problem.  1e-3 leaves
# that two orders of headroom and still fails a solver that rounds its
# matmuls to one bf16 pass (it stalled 2.1e-1 below the reference here).
FIT_TOL = 1e-3
# The Pallas kernels contract at Precision.HIGHEST (fp32 via several bf16
# passes) and accumulate in fp32: agreement with the einsum oracle to a
# small multiple of fp32 rounding over the reduction's length.
KERNEL_RTOL = 1e-4
# Sharded vs single-device factors after SWEEPS sweeps (relative Frobenius
# per mode): the two runs differ only in reduction order and the psums'
# rounding, which ALS carries from sweep to sweep; 10x the fit tolerance.
FACTOR_TOL = 10 * FIT_TOL
# What a compiled program holds where a Pallas kernel was lowered for the
# chip (interpret mode leaves plain XLA ops instead).
KERNEL_MARK = "tpu_custom_call"

HI = jax.lax.Precision.HIGHEST


def log(msg: str) -> None:
    print(msg, flush=True)


class SmokeFailure(AssertionError):
    """A phase produced a wrong, missing or non-finite result."""


def check(ok: bool, msg: str) -> None:
    if not ok:
        raise SmokeFailure(msg)


# ----------------------------------------------------------- the reference
def ref_mttkrp(x, factors, n):
    """Einsum MTTKRP at HIGHEST precision (the oracle)."""
    letters = "abcdefgh"[: x.ndim]
    subs = [letters] + [letters[k] + "z" for k in range(x.ndim) if k != n]
    ops = [x] + [u for k, u in enumerate(factors) if k != n]
    return jnp.einsum(",".join(subs) + f"->{letters[n]}z", *ops, precision=HI)


@jax.jit
def ref_sweep(x, factors):
    """One plain ALS sweep: per mode, solve U_n H = M with H the Hadamard of
    the other Grams, then move the column norms into the weights."""
    factors = list(factors)
    weights = None
    for n in range(len(factors)):
        h = jnp.ones((factors[0].shape[1],) * 2, jnp.float32)
        for k, u in enumerate(factors):
            if k != n:
                h = h * jnp.matmul(u.T, u, precision=HI)
        m = ref_mttkrp(x, factors, n)
        u = jnp.linalg.solve(h, m.T).T
        weights = jnp.linalg.norm(u, axis=0)
        factors[n] = u / weights
    return factors, weights


@jax.jit
def ref_fit(x, factors, weights):
    """1 - ||X - model|| / ||X||, the model formed explicitly."""
    letters = "abcdefgh"[: x.ndim]
    subs = ",".join(letters[k] + "z" for k in range(x.ndim))
    model = jnp.einsum(f"z,{subs}->{letters}", weights, *factors, precision=HI)
    return 1.0 - jnp.linalg.norm(x - model) / jnp.linalg.norm(x)


def ref_als(x, init, sweeps):
    """The reference's fit after each of ``sweeps`` sweeps."""
    factors = list(init)
    fits = []
    for _ in range(sweeps):
        factors, weights = ref_sweep(x, factors)
        fits.append(float(ref_fit(x, factors, weights)))
    return fits


# ---------------------------------------------------------------- the data
def planted_fmri(seed: int, shape=FMRI, rank=RANK):
    """Planted rank-``rank`` tensor plus ~NOISE relative Gaussian noise,
    made on the device.  The two region modes share one factor, as the
    symmetric region x region correlation data of the paper do, so the
    linearized 3-way form stays rank-``rank`` too."""
    k = jax.random.split(jax.random.PRNGKey(seed), 4)
    a = jax.random.normal(k[0], (shape[0], rank), jnp.float32)
    b = jax.random.normal(k[1], (shape[1], rank), jnp.float32)
    r = jax.random.normal(k[2], (shape[2], rank), jnp.float32)

    @jax.jit
    def build(a, b, r, key):
        clean = jnp.einsum("ic,jc,kc,lc->ijkl", a, b, r, r, precision=HI)
        scale = NOISE * jnp.linalg.norm(clean) / np.sqrt(clean.size)
        return clean + scale * jax.random.normal(key, clean.shape, jnp.float32)

    return jax.block_until_ready(build(a, b, r, k[3]))


def linearize(x4):
    """225 x 59 x 200 x 200 -> 225 x 59 x 19900: the strict upper triangle
    of each symmetric region x region slice (the paper's 3-way form)."""
    iu, ju = np.triu_indices(x4.shape[2], k=1)
    return jax.block_until_ready(jax.jit(lambda x: x[:, :, iu, ju])(x4))


def init_factors(x, rank, seed: int):
    """Leading left singular vectors of each unfolding X_(n) (the usual
    'nvecs' CP-ALS start), by a randomized range finder: two passes over
    the tensor per mode, no unfolding copy.  From a random start, ALS on
    this tensor stalls at different stationary points depending on the
    rounding of its first sweeps, and a fit comparison would measure the
    trajectory, not the solver; from here both solvers converge to the
    planted solution."""
    key = jax.random.PRNGKey(seed)
    p = rank + 10  # oversampling

    @partial(jax.jit, static_argnums=2)
    def one(x, key, n):
        other = [k for k in range(x.ndim) if k != n]
        omega = jax.random.normal(key, tuple(x.shape[k] for k in other) + (p,))
        y = jnp.tensordot(x, omega, axes=(other, list(range(len(other)))), precision=HI)
        q, _ = jnp.linalg.qr(y)  # (I_n, p) basis of X_(n)'s range
        b = jnp.tensordot(q, x, axes=([0], [n]), precision=HI).reshape(q.shape[1], -1)
        _, v = jnp.linalg.eigh(jnp.matmul(b, b.T, precision=HI))  # ascending
        return jnp.matmul(q, v[:, ::-1][:, :rank], precision=HI)

    return [
        one(x, jax.random.fold_in(key, n), n) for n in range(x.ndim)
    ]


# ------------------------------------------------------------------ phases
def solve(x, init, *, executor=None, plan=None, dispatch=None, fits=None):
    """The user's path: Problem -> plan_sweep -> cp_als, ``SWEEPS`` sweeps
    in one dispatch; per-sweep fits are appended to ``fits`` if given."""
    from repro.plan import Problem, cp_als, plan_sweep

    if plan is None:
        plan = plan_sweep(Problem.from_tensor(x, RANK), strategy="auto")
    return cp_als(
        x, plan, executor=executor, n_iters=SWEEPS, tol=0.0,
        init_factors=list(init), sweeps_per_sync=SWEEPS,
        dispatch_cache=dispatch, dispatch_key=0,
        callback=None if fits is None else (lambda it, f, dt: fits.append(f)),
    ), plan


def trajectory(fits) -> str:
    """Fits after sweeps 1, 2, 5, 10, 20, ... and the last one."""
    marks = sorted({1, 2, 5, *range(10, len(fits), 10), len(fits)})
    return " ".join(f"{k}:{fits[k - 1]:.4f}" for k in marks if k <= len(fits))


def phase_solver(x, name: str, seed: int) -> None:
    init = init_factors(x, RANK, seed)
    dispatch: dict = {}
    fits: list[float] = []
    t0 = time.perf_counter()
    st, plan = solve(x, init, dispatch=dispatch, fits=fits)  # compiles the chunk
    fit = float(st.fit)
    t_first = time.perf_counter() - t0
    t0 = time.perf_counter()
    st2, _ = solve(x, init, plan=plan, dispatch=dispatch)  # compile-free
    float(st2.fit)
    t_warm = time.perf_counter() - t0
    ref_fits = ref_als(x, init, SWEEPS)
    ref = ref_fits[-1]
    gap = abs(fit - ref)
    log(
        f"solver[{name}] shape={tuple(x.shape)} rank={RANK} sweeps={st.it} "
        f"plan={[m.algorithm for m in plan.modes]} schedule={plan.resolved_schedule.name} "
        f"fit={fit:.6f} ref_fit={ref:.6f} gap={gap:.3e} tol={FIT_TOL:g} "
        f"wall_s={t_first:.3f} run_s={t_warm:.3f} compile_s={t_first - t_warm:.3f}"
    )
    log(f"solver[{name}] fits   {trajectory(fits)}")
    log(f"solver[{name}] ref    {trajectory(ref_fits)}")
    check(np.isfinite(fit), f"solver[{name}] fit is not finite")
    check(gap <= FIT_TOL, f"solver[{name}] fit gap {gap:.3e} > {FIT_TOL:g}")


def phase_kernels(x, seed: int) -> None:
    from repro.core import mttkrp

    keys = jax.random.split(jax.random.PRNGKey(seed), x.ndim)
    factors = [jax.random.normal(k, (d, RANK)) for k, d in zip(keys, x.shape)]
    for method in ("fused", "matrix_free"):
        for n in range(x.ndim):
            t0 = time.perf_counter()
            compiled = (
                jax.jit(lambda x, fs, n=n, m=method: mttkrp(x, fs, n, method=m))
                .lower(x, factors)
                .compile()
            )
            t_compile = time.perf_counter() - t0
            check(
                KERNEL_MARK in compiled.as_text(),
                f"{method} mode {n}: no {KERNEL_MARK} in the compiled program",
            )
            t0 = time.perf_counter()
            out = jax.block_until_ready(compiled(x, factors))
            t_run = time.perf_counter() - t0
            ref = ref_mttkrp(x, factors, n)
            err = float(jnp.max(jnp.abs(out - ref)) / jnp.max(jnp.abs(ref)))
            log(
                f"kernel[{method} mode {n}] out={tuple(out.shape)} tpu_custom_call=yes "
                f"rel_err={err:.3e} tol={KERNEL_RTOL:g} compile_s={t_compile:.3f} "
                f"run_s={t_run:.3f}"
            )
            check(out.shape == (x.shape[n], RANK), f"{method} mode {n}: shape {out.shape}")
            check(err <= KERNEL_RTOL, f"{method} mode {n}: rel err {err:.3e}")


def phase_service(subjects, seed: int) -> None:
    from repro.plan.autotune import TuningCache, tune
    from repro.serve import CPService

    cache = TuningCache()  # in memory: this process is the whole deployment
    rank0 = SERVICE_RANKS[0]
    t0 = time.perf_counter()
    entry = tune(
        jnp.stack(subjects[:SERVICE_BATCH]), rank0, batch=SERVICE_BATCH,
        cache=cache, budget_ms=20000, reps=1, seed=seed,
    )
    log(
        f"service tune: signature rank={rank0} batch={SERVICE_BATCH} "
        f"nodes_measured={len(entry['nodes'])} "
        f"fused_tiles={[entry['tiles']['fused_mttkrp'][k] for k in ('block_i', 'block_b')]} "
        f"matrix_free_tiles={[entry['tiles']['matrix_free'][k] for k in ('block_i', 'block_r')]} "
        f"wall_s={time.perf_counter() - t0:.3f}"
    )
    svc = CPService(batch_size=SERVICE_BATCH, n_iters=SWEEPS, tuning_cache=cache)
    ranks = [SERVICE_RANKS[i % 2] for i in range(len(subjects))]
    inits = [init_factors(x, r, seed + 100 + i) for i, (x, r) in enumerate(zip(subjects, ranks))]
    walls = []
    for _ in range(2):  # the second round must reuse both compiled dispatches
        futures = [
            svc.submit(x, r, init_factors=u) for x, r, u in zip(subjects, ranks, inits)
        ]
        t0 = time.perf_counter()
        svc.flush()
        walls.append(time.perf_counter() - t0)
    stats = svc.stats()
    log(
        f"service: served={stats['completed']} signatures={stats['signatures']} "
        f"compiles={stats['compiles']} warm_plan_hits={stats['warm_plan_hits']} "
        f"batches={stats['batches']} occupancy={stats['batch_occupancy']:.3f} "
        f"wall_s={walls[0]:.3f} warm_wall_s={walls[1]:.3f} "
        f"compile_s={walls[0] - walls[1]:.3f}"
    )
    check(stats["completed"] == 2 * len(subjects), f"served {stats['completed']}")
    check(stats["compiles"] == len(SERVICE_RANKS), f"{stats['compiles']} compiles")
    check(stats["warm_plan_hits"] >= 1, "the tuned signature did not plan warm")
    for i in (0, 1):  # one request of each signature
        res = futures[i].result()
        ref = ref_als(subjects[i], inits[i], SWEEPS)[-1]
        gap = abs(res.fit - ref)
        log(
            f"service result[{i}] rank={ranks[i]} fit={res.fit:.6f} "
            f"ref_fit={ref:.6f} gap={gap:.3e} tol={FIT_TOL:g}"
        )
        check(np.isfinite(res.fit) and gap <= FIT_TOL, f"request {i}: gap {gap:.3e}")


def phase_four_chips(seed: int) -> None:
    from jax.sharding import NamedSharding, PartitionSpec as P

    from repro.launch.mesh import make_mesh
    from repro.plan import LocalExecutor, Problem, make_executor, plan_sweep

    devices = jax.devices()
    check(len(devices) >= 4, f"--four-chips needs 4 devices, found {len(devices)}")
    mesh = make_mesh((2, 2), ("data", "model"))
    mode_axes = {2: "data", 3: "model"}
    with jax.default_device(devices[0]):
        x = planted_fmri(seed)
        init = init_factors(x, RANK, seed + 1)
        t0 = time.perf_counter()
        local, _ = solve(x, init, executor=LocalExecutor())
        fit_local = float(local.fit)
        t_local = time.perf_counter() - t0
    problem = Problem.from_tensor(x, RANK, mode_axes=mode_axes, mesh=mesh)
    plan = plan_sweep(problem, strategy="auto", executor="sharded")
    ex = make_executor("sharded", mesh, mode_axes)
    xs = jax.device_put(x, NamedSharding(mesh, P(None, None, "data", "model")))
    del x
    gc.collect()
    shard_devs = {s.device for s in xs.addressable_shards}
    check(len(shard_devs) == 4, f"tensor shards sit on {len(shard_devs)} devices")
    t0 = time.perf_counter()
    sharded, _ = solve(xs, init, executor=ex, plan=plan)
    fit_sharded = float(sharded.fit)
    t_sharded = time.perf_counter() - t0
    gap = abs(fit_sharded - fit_local)
    fac = max(
        float(np.linalg.norm(a - b) / np.linalg.norm(a))
        for a, b in zip(
            map(np.asarray, jax.device_get(local.factors)),
            map(np.asarray, jax.device_get(sharded.factors)),
        )
    )
    log(
        f"four_chips: mesh={dict(mesh.shape)} mode_axes={mode_axes} "
        f"shard_devices={sorted(d.id for d in shard_devs)} executor={plan.executor} "
        f"plan={[m.algorithm for m in plan.modes]} fit_sharded={fit_sharded:.6f} "
        f"fit_local={fit_local:.6f} gap={gap:.3e} factor_rel_diff={fac:.3e} "
        f"local_wall_s={t_local:.3f} sharded_wall_s={t_sharded:.3f}"
    )
    check(gap <= FIT_TOL, f"sharded vs local fit gap {gap:.3e}")
    check(fac <= FACTOR_TOL, f"sharded vs local factors differ by {fac:.3e}")


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument(
        "--four-chips", action="store_true",
        help="run only the sharded 2x2 mesh phase and its local comparison",
    )
    args = ap.parse_args(argv)

    dev = jax.devices()[0]
    if dev.platform != "tpu":
        print(
            f"chip_smoke: needs a TPU, but JAX found platform {dev.platform!r} "
            f"({dev.device_kind}); refusing to fall back",
            file=sys.stderr,
        )
        return 2
    from repro.launch.compile_cache import use_checkout_cache

    cache_dir = use_checkout_cache()
    log(
        f"device: platform={dev.platform} kind={dev.device_kind} "
        f"count={len(jax.devices())} jax={jax.__version__} compile_cache={cache_dir}"
    )
    t_start = time.perf_counter()
    if args.four_chips:
        phase_four_chips(args.seed)
    else:
        t0 = time.perf_counter()
        x4 = planted_fmri(args.seed)
        log(f"data: planted {x4.shape} fp32 ({x4.nbytes / 1e9:.2f} GB) wall_s={time.perf_counter() - t0:.3f}")
        phase_solver(x4, "4-way", args.seed + 1)
        x3 = linearize(x4)
        phase_solver(x3, "3-way", args.seed + 2)
        del x3
        gc.collect()
        phase_kernels(x4, args.seed + 3)
        subjects = [x4[:, s] for s in range(SUBJECTS)]
        del x4
        gc.collect()
        phase_service(subjects, args.seed + 4)
    log(f"all phases passed in {time.perf_counter() - t_start:.1f}s")
    print(json.dumps({
        "ok": True,
        "device": {"platform": dev.platform, "kind": dev.device_kind, "count": len(jax.devices())},
    }))
    return 0


if __name__ == "__main__":
    sys.exit(main())
