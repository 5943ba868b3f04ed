"""Batched CP end-to-end: one compiled dispatch amortized over a fleet.

Covers the leading-batch-dimension path through every layer: batched
``cp_als`` against a per-tensor Python loop (and B=1 bitwise against the
unbatched path), batch-parallel ``shard_map`` execution against the local
run, the planner's batch-vs-mode placement argmin, the sync-free driver's
one-dispatch-per-chunk guarantee at B >= 64, property sweeps over
(order, B, ragged batch chunk), and the tuning cache's backward-compatible
batch key field.
"""

import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import mttkrp_einsum, random_factors, random_tensor
from repro.core.mttkrp import mttkrp_batched
from repro.launch.mesh import make_mesh
from repro.plan import Problem, cp_als, make_executor, plan_sweep
from repro.plan.autotune import TuningCache, problem_key

ON_CPU = jax.default_backend() == "cpu"
N_DEV = jax.device_count()


def _fleet(batch, shape, rank, seed=0):
    x = random_tensor(jax.random.PRNGKey(seed), (batch,) + shape)
    init = random_factors(jax.random.PRNGKey(seed + 1), shape, rank, batch=batch)
    return x, init


# ----------------------------------------------------------- driver numerics
def test_batched_cp_als_matches_per_tensor_loop():
    """Acceptance: batched cp_als over B stacked tensors matches running
    the unbatched driver on each tensor with the same init, allclose at
    highest precision; the fit is per-problem."""
    B, shape, rank = 6, (8, 9, 10), 4
    x, init = _fleet(B, shape, rank)
    prob = Problem.from_tensor(x, rank, batch=B)
    plan = plan_sweep(prob)
    st = cp_als(x, plan, n_iters=5, tol=0.0, init_factors=init)
    assert st.fit.shape == (B,)
    assert all(u.shape == (B, d, rank) for u, d in zip(st.factors, shape))
    for b in range(B):
        plb = plan_sweep(Problem.from_tensor(x[b], rank))
        stb = cp_als(
            x[b], plb, n_iters=5, tol=0.0, init_factors=[u[b] for u in init]
        )
        for u_batched, u_loop in zip(st.factors, stb.factors):
            np.testing.assert_allclose(
                np.asarray(u_batched[b]), np.asarray(u_loop), rtol=2e-4, atol=2e-5
            )
        np.testing.assert_allclose(
            float(st.fit[b]), float(stb.fit), rtol=1e-4, atol=1e-5
        )


def test_batch_one_bitwise_identical_to_unbatched():
    """B=1 problems keep arrays with no batch axis and run the exact old
    code path -- factors, weights, and fit are bitwise identical."""
    shape, rank = (8, 9, 10), 4
    x = random_tensor(jax.random.PRNGKey(3), shape)
    st1 = cp_als(x, plan_sweep(Problem.from_tensor(x, rank, batch=1)),
                 n_iters=5, tol=0.0, seed=2)
    st0 = cp_als(x, plan_sweep(Problem.from_tensor(x, rank)),
                 n_iters=5, tol=0.0, seed=2)
    for a, b in zip(st1.factors, st0.factors):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
    np.testing.assert_array_equal(np.asarray(st1.weights), np.asarray(st0.weights))
    assert float(st1.fit) == float(st0.fit)


def test_batched_dimtree_schedule_matches_flat():
    """Tree schedules walk the same batched contractions: dimtree iterates
    equal the flat schedule's on a batched problem."""
    B, shape, rank = 4, (6, 7, 8, 5), 3
    x, init = _fleet(B, shape, rank, seed=5)
    prob = Problem.from_tensor(x, rank, batch=B)
    st_flat = cp_als(x, plan_sweep(prob, schedule="flat"),
                     n_iters=4, tol=0.0, init_factors=init)
    st_tree = cp_als(x, plan_sweep(prob, strategy="dimtree"),
                     n_iters=4, tol=0.0, init_factors=init)
    for a, b in zip(st_flat.factors, st_tree.factors):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b), rtol=2e-4, atol=2e-5)


# ------------------------------------------------------- sharded placements
@pytest.mark.skipif(N_DEV < 2, reason="needs a multi-device runtime")
def test_batch_parallel_shard_map_equals_local():
    """Acceptance: a batch-sharded run (batch_axes over the mesh, no mode
    sharding, zero collectives) produces the local iterates."""
    B, shape, rank = 2 * N_DEV, (8, 8, 6), 4
    x, init = _fleet(B, shape, rank, seed=7)
    mesh = make_mesh((N_DEV,), ("b",))
    prob = Problem(
        shape=shape, rank=rank, batch=B,
        batch_axes=("b",), axis_sizes={"b": N_DEV},
    )
    plan = plan_sweep(prob)
    assert plan.executor == "sharded"
    ex = make_executor(plan.executor, mesh, {}, batch_axes=("b",))
    st_sh = cp_als(x, plan, executor=ex, n_iters=4, tol=0.0, init_factors=init)
    st_lo = cp_als(x, plan_sweep(Problem.from_tensor(x, rank, batch=B)),
                   n_iters=4, tol=0.0, init_factors=init)
    for a, b in zip(st_sh.factors, st_lo.factors):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b), rtol=2e-4, atol=2e-5)
    np.testing.assert_allclose(
        np.asarray(st_sh.fit), np.asarray(st_lo.fit), rtol=1e-4, atol=1e-5
    )


@pytest.mark.skipif(N_DEV < 2, reason="needs a multi-device runtime")
def test_mode_parallel_batched_equals_local():
    """Mode-parallel sharding with the batch replicated: dist_mttkrp* accept
    the leading batch axis inside shard_map and reproduce the local run."""
    B, shape, rank = 4, (8, 8, 6), 4
    x, init = _fleet(B, shape, rank, seed=9)
    mesh = make_mesh((N_DEV,), ("s",))
    mode_axes = {0: "s"}
    prob = Problem(
        shape=shape, rank=rank, batch=B,
        mode_axes=mode_axes, axis_sizes={"s": N_DEV},
    )
    st_lo = cp_als(x, plan_sweep(Problem.from_tensor(x, rank, batch=B)),
                   n_iters=3, tol=0.0, init_factors=init)
    for kind in ("sharded", "overlapping"):
        plan = plan_sweep(prob, executor=kind)
        ex = make_executor(kind, mesh, mode_axes)
        st = cp_als(x, plan, executor=ex, n_iters=3, tol=0.0, init_factors=init)
        for a, b in zip(st.factors, st_lo.factors):
            np.testing.assert_allclose(
                np.asarray(a), np.asarray(b), rtol=2e-4, atol=2e-5,
                err_msg=f"executor={kind}",
            )


@pytest.mark.skipif(N_DEV < 2, reason="needs a multi-device runtime")
def test_compressed_batched_tracks_fit():
    """The int8 error-feedback collective threads batched residuals: the
    compressed run's per-problem fits track the exact ones."""
    B, shape, rank = 4, (8, 8, 6), 4
    x, init = _fleet(B, shape, rank, seed=11)
    mesh = make_mesh((N_DEV,), ("s",))
    mode_axes = {0: "s"}
    prob = Problem(
        shape=shape, rank=rank, batch=B,
        mode_axes=mode_axes, axis_sizes={"s": N_DEV},
    )
    plan = plan_sweep(prob, executor="compressed")
    ex = make_executor("compressed", mesh, mode_axes)
    st = cp_als(x, plan, executor=ex, n_iters=4, tol=0.0, init_factors=init)
    st_lo = cp_als(x, plan_sweep(Problem.from_tensor(x, rank, batch=B)),
                   n_iters=4, tol=0.0, init_factors=init)
    np.testing.assert_allclose(
        np.asarray(st.fit), np.asarray(st_lo.fit), rtol=0.05, atol=0.05
    )


# -------------------------------------------------------- placement argmin
def test_plan_sweep_selects_batch_parallel_for_fleet():
    """Acceptance: for a fleet of small tensors given mode-parallel, the
    placement argmin re-places batch-parallel -- zero reduce traffic beats
    psum volume x B -- and describe() records both candidates' costs."""
    prob = Problem(
        shape=(16, 16, 16), rank=8, batch=64,
        mode_axes={0: "s"}, axis_sizes={"s": 8},
    )
    plan = plan_sweep(prob)
    desc = plan.describe()
    assert desc["placement"] == "batch-parallel"
    assert plan.problem.mode_axes == {}
    assert plan.problem.batch_axes == ("s",)
    rows = {r["placement"]: r for r in desc["placements"]}
    assert rows["batch-parallel"]["selected"]
    assert not rows["mode-parallel"]["selected"]
    assert rows["batch-parallel"]["collective_bytes"] == 0.0
    assert rows["mode-parallel"]["collective_bytes"] > 0.0
    assert rows["batch-parallel"]["predicted_s"] < rows["mode-parallel"]["predicted_s"]


def test_plan_sweep_keeps_explicit_batch_parallel():
    """A problem given batch-parallel stays as-given (no placement rows:
    there is nothing to argmin against)."""
    prob = Problem(
        shape=(16, 16, 16), rank=8, batch=64,
        batch_axes=("s",), axis_sizes={"s": 8},
    )
    plan = plan_sweep(prob)
    assert plan.describe()["placement"] == "batch-parallel"
    assert plan.describe()["placements"] == []
    assert plan.executor == "sharded"


def test_problem_batch_validation():
    """Batch fields validate: dual-role axes and indivisible batches raise,
    and the batch folds into the problem hash."""
    with pytest.raises(ValueError, match="cannot shard both"):
        Problem(shape=(8, 8), rank=2, batch=8,
                mode_axes={0: "s"}, batch_axes=("s",), axis_sizes={"s": 2})
    with pytest.raises(ValueError, match="divisible"):
        Problem(shape=(8, 8), rank=2, batch=3,
                batch_axes=("s",), axis_sizes={"s": 2})
    a = Problem(shape=(8, 8), rank=2)
    b = Problem(shape=(8, 8), rank=2, batch=4)
    assert hash(a) != hash(b)


# ------------------------------------------------------ one fused dispatch
def test_batched_one_dispatch_per_chunk(monkeypatch):
    """Acceptance: cp_als on Problem(batch=64) runs as ONE compiled
    dispatch per sweep chunk -- the host blocks once per chunk regardless
    of B (counted at the driver's single sync point)."""
    import repro.plan.sweep as sweeplib

    B, shape, rank = 64, (6, 6, 6), 3
    x, init = _fleet(B, shape, rank, seed=13)
    plan = plan_sweep(Problem.from_tensor(x, rank, batch=B))
    counts = {"n": 0}
    real = jax.block_until_ready

    def counting(tree):
        counts["n"] += 1
        return real(tree)

    monkeypatch.setattr(sweeplib, "_block_until_ready", counting)
    cp_als(x, plan, n_iters=6, track_fit=False, init_factors=init,
           sweeps_per_sync=3)
    assert counts["n"] == 2  # two chunks of 3 sweeps, B=64 notwithstanding
    counts["n"] = 0
    cp_als(x, plan, n_iters=6, track_fit=False, init_factors=init,
           sweeps_per_sync=6)
    assert counts["n"] == 1  # the whole run in one dispatch


def test_batched_convergence_stops_all_problems():
    """Convergence requires every problem's fit delta below tol; the chunk
    loop stops once the batch-max delta clears it.

    Planted rank-3 problems under noise of about their own size: ALS finds
    the planted components in a few sweeps, and a fit near 0.5 keeps the
    float32 fit estimate's jitter far below tol (near a fit of 1 the
    estimate's cancellation alone moves it by ~1e-5 per sweep)."""
    B, shape, rank = 3, (16, 16, 16), 3
    true = random_factors(jax.random.PRNGKey(15), shape, rank, batch=B)
    x = jnp.einsum("bic,bjc,bkc->bijk", *true)
    x = x + 0.5 * rank**0.5 * random_tensor(jax.random.PRNGKey(17), x.shape)
    init = random_factors(jax.random.PRNGKey(16), shape, rank, batch=B)
    plan = plan_sweep(Problem.from_tensor(x, rank, batch=B))
    fits = []
    st = cp_als(x, plan, n_iters=40, tol=1e-6, init_factors=init,
                callback=lambda it, fit, dt: fits.append(fit))
    assert st.it < 40  # actually converged
    assert len(fits) == st.it  # callback once per sweep, batch-mean fit
    assert st.fit.shape == (B,)


# ------------------------------------------------------- property sweeps
def _check_mttkrp_batched(order, batch, mode, method="auto", tiles=None):
    mode = mode % order
    shape = tuple(5 + k for k in range(order))
    rank = 3
    x = random_tensor(jax.random.PRNGKey(order * 13 + batch), (batch,) + shape)
    factors = random_factors(
        jax.random.PRNGKey(order * 29 + batch), shape, rank, batch=batch
    )
    if batch == 1:  # the kernel-level API always takes an explicit lead axis
        factors = [u[None] for u in factors]
    got = mttkrp_batched(x, factors, mode, method=method, tiles=tiles)
    want = jnp.stack([
        mttkrp_einsum(x[b], [u[b] for u in factors], mode) for b in range(batch)
    ])
    np.testing.assert_allclose(
        np.asarray(got), np.asarray(want), rtol=2e-4, atol=2e-5
    )


@pytest.mark.parametrize("order", [3, 4])
@pytest.mark.parametrize("batch", [1, 3, 5])
def test_mttkrp_batched_ragged_grid(order, batch):
    """mttkrp_batched == per-item mttkrp_einsum over (order, B) including
    ragged batches; the fused kernel's batch grid axis pads + slices
    (block_batch=2 never divides B=1,3,5)."""
    for mode in range(order):
        _check_mttkrp_batched(order, batch, mode)
    _check_mttkrp_batched(
        order, batch, 0, method="fused",
        tiles={"block_i": 4, "block_b": 8, "block_batch": 2},
    )
    _check_mttkrp_batched(
        order, batch, order - 1, method="fused",
        tiles={"block_i": 4, "block_b": 8, "block_batch": 2},
    )


from conftest import given, settings, st  # noqa: E402  (shared optional-dep shim)


@settings(max_examples=12, deadline=None)
@given(
    order=st.integers(min_value=3, max_value=4),
    batch=st.integers(min_value=1, max_value=5),
    mode=st.integers(min_value=0, max_value=3),
    fused=st.booleans(),
)
def test_mttkrp_batched_property(order, batch, mode, fused):
    """Hypothesis sweep over (order, B, mode, kernel) -- small B forces
    the ragged last chunk of the batch grid axis."""
    if fused:
        _check_mttkrp_batched(
            order, batch, mode, method="fused",
            tiles={"block_i": 4, "block_b": 8, "block_batch": 2},
        )
    else:
        _check_mttkrp_batched(order, batch, mode)


# --------------------------------------------------------- tuning cache
def test_tuning_cache_batch_key_backward_compat(tmp_path):
    """Old 5-field cache keys (written before the batch dimension existed)
    keep resolving for B=1 problems; batched problems get a distinct
    ``|b{B}`` key that round-trips through the on-disk cache."""
    p1 = Problem(shape=(16, 16, 16), rank=8)
    p1b = Problem(shape=(16, 16, 16), rank=8, batch=1)
    pB = Problem(shape=(16, 16, 16), rank=8, batch=64)
    k1 = problem_key(p1, backend="cpu")
    assert problem_key(p1b, backend="cpu") == k1  # B=1 == historical layout
    assert "|b" not in k1
    kB = problem_key(pB, backend="cpu")
    assert kB == k1 + "|b64"

    path = os.fspath(tmp_path / "tuning.json")
    cache = TuningCache(path)
    # an entry written under the old (pre-batch) key format...
    cache.put(k1, {"tiles": {}, "nodes": [], "serial_fractions": {}})
    cache.put(kB, {"tiles": {}, "nodes": [], "serial_fractions": {"sharded": 1.0}})
    reloaded = TuningCache(path)
    # ...still resolves for today's B=1 problem, and the batched entry is
    # separate (a fleet's measured timings never shadow the single-tensor's)
    assert reloaded.get(problem_key(p1b, backend="cpu")) is not None
    got = reloaded.get(problem_key(pB, backend="cpu"))
    assert got is not None and got["serial_fractions"] == {"sharded": 1.0}
