"""cp_als builds the tensor's matrix views once a solve and the sweeps read
them: the same iterates as sweeps on the raw tensor, and a count of the
views built.

Each case runs ``cp_als`` (views built in its set-up program, handed to
the root partials of the schedule) and, from the same start, a loop of
jitted ``als_sweep`` calls on the raw tensor (no views: each root partial
reshapes ``x`` itself).  On XLA:CPU the factors and weights agree bit for
bit: the GEMMs are the same contractions on the same row-major data.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import cp_full, random_factors, random_tensor
from repro.core.cpals import grams
from repro.core.dimtree import matrix_view, partial_from_view, partial_mttkrp_range, view_split
from repro.core.tensor_ops import tensor_norm
from repro.plan import LocalExecutor, Problem, ShardedExecutor, cp_als, plan_sweep
from repro.plan.schedule import build_schedule
from repro.plan.sweep import SweepState, _pp_init, als_sweep, prepare_operands, view_splits

N_SWEEPS = 4


def _planted(shape, rank, batch=1, seed=0):
    lead = (batch,) if batch > 1 else ()
    key = jax.random.PRNGKey(seed)
    if batch > 1:
        true = random_factors(key, shape, rank, batch=batch)
        x = jax.vmap(lambda *fs: cp_full(None, list(fs)))(*true)
    else:
        x = cp_full(None, random_factors(key, shape, rank))
    x = x + 0.05 * random_tensor(jax.random.PRNGKey(seed + 1), lead + shape)
    init = random_factors(
        jax.random.PRNGKey(seed + 2), shape, rank, **({"batch": batch} if batch > 1 else {})
    )
    return x, list(init)


def _raw_sweeps(plan, x, init, n):
    """``n`` jitted ``als_sweep`` calls on the raw tensor, carrying what
    cp_als carries (Grams, PP cache, sweep counter) from the norm its set-up
    program takes; returns the last state and the fits."""
    problem = plan.problem
    ex = LocalExecutor()
    step = jax.jit(lambda st: als_sweep(problem, plan, ex, st))
    lead = (problem.batch,) if problem.batched else ()
    st = SweepState(
        x=x, factors=list(init), weights=jnp.ones(lead + (problem.rank,), x.dtype),
        norm_x=prepare_operands(x, splits=(), batched=problem.batched)[0],
        it=jnp.asarray(0), grams=grams(init),
        pp=_pp_init(problem, x, init) if plan.pp else None,
    )
    fits = []
    for _ in range(n):
        out = step(st)
        fits.append(out.fit)
        st = SweepState(
            x=x, factors=out.factors, weights=out.weights, norm_x=st.norm_x,
            it=st.it + 1, grams=out.grams, pp=out.pp,
        )
    return st, fits


def _case(name):
    """(plan, x, init) for a named case."""
    if name == "binary-4way":
        x, init = _planted((8, 7, 6, 5), 3)
        return plan_sweep(Problem.from_tensor(x, 3), strategy="dimtree"), x, init
    if name == "binary-3way":
        x, init = _planted((9, 7, 6), 3)
        return plan_sweep(Problem.from_tensor(x, 3), strategy="dimtree"), x, init
    if name == "middle-range":
        # root leaves 0 and 3 around the middle partial [1, 3): mixed tree
        x, init = _planted((8, 7, 6, 5), 3)
        prob = Problem.from_tensor(x, 3)
        return plan_sweep(prob, schedule=build_schedule(prob, [0, [1, 2], 3])), x, init
    if name == "two-views":
        # root partials [0, 2) and [2, 4) read X_(2) and X_(4)
        x, init = _planted((5, 4, 6, 3, 4), 2)
        prob = Problem.from_tensor(x, 2)
        return plan_sweep(prob, schedule=build_schedule(prob, [[0, 1], [2, 3], 4])), x, init
    if name == "batched-4":
        x, init = _planted((8, 7, 6), 3, batch=4)
        return plan_sweep(Problem.from_tensor(x, 3, batch=4), strategy="dimtree"), x, init
    if name == "batched-1":
        x, init = _planted((8, 7, 6), 3)
        return plan_sweep(Problem.from_tensor(x, 3, batch=1), strategy="dimtree"), x, init
    if name == "pp":
        x, init = _planted((8, 7, 6, 5), 3)
        prob = Problem.from_tensor(x, 3, pp_tol=0.2)
        return plan_sweep(prob, strategy="pp"), x, init
    raise KeyError(name)


CASES = {
    "binary-4way": 1, "binary-3way": 1, "middle-range": 1, "two-views": 2,
    "batched-4": 1, "batched-1": 1, "pp": 1,
}


@pytest.mark.parametrize("name", sorted(CASES))
def test_views_give_the_raw_tensor_iterates_bitwise(name):
    """cp_als on views reproduces the raw-tensor sweeps bit for bit on
    XLA:CPU: factors, weights and fit."""
    plan, x, init = _case(name)
    st = cp_als(x, plan, n_iters=N_SWEEPS, tol=0.0, init_factors=list(init))
    ref, fits = _raw_sweeps(plan, x, init, N_SWEEPS)
    assert st.it == N_SWEEPS
    assert st.prepared_views == CASES[name]
    for a, b in zip(st.factors, ref.factors):
        assert np.array_equal(np.asarray(a), np.asarray(b))
    assert np.array_equal(np.asarray(st.weights), np.asarray(ref.weights))
    assert np.array_equal(np.asarray(st.fit), np.asarray(fits[-1]))


@pytest.mark.parametrize("batched", [False, True])
def test_set_up_program_takes_the_norm_and_the_views(batched):
    """One program: the norm (to 1 ulp of the eager one; only its
    reduction order may differ) and each view, in the tensor's order."""
    x = random_tensor(jax.random.PRNGKey(6), (3, 8, 7, 6, 5))
    if not batched:
        x = x[0]
    norm_x, views = prepare_operands(x, splits=(1, 2), batched=batched)
    np.testing.assert_array_max_ulp(
        np.asarray(norm_x), np.asarray(tensor_norm(x, batched=batched)), maxulp=1
    )
    assert sorted(views) == [1, 2]
    for m, v in views.items():
        assert np.array_equal(np.asarray(v), np.asarray(matrix_view(x, m, batched=batched)))
        assert v.shape[-1] * v.shape[-2] == x.size // (3 if batched else 1)


def test_pp_plan_reads_views_and_the_tensor():
    """A PP plan's exact sweeps read the view; its pairwise build reads x,
    so the chunk still takes the tensor."""
    plan, x, init = _case("pp")
    assert view_splits(plan, LocalExecutor()) != ()
    st = cp_als(x, plan, n_iters=6, tol=0.0, init_factors=list(init))
    assert st.prepared_views == 1 and st.pp_exact_sweeps >= 1


def test_flat_plan_builds_no_view():
    x, init = _planted((8, 7, 6), 3)
    plan = plan_sweep(Problem.from_tensor(x, 3), schedule="flat")
    assert view_splits(plan, LocalExecutor()) == ()
    st = cp_als(x, plan, n_iters=2, tol=0.0, init_factors=list(init))
    assert st.prepared_views == 0


def test_sharded_executor_builds_no_view():
    """Executors without ``contract_view`` read the tensor as before."""
    from repro.launch import mesh as meshlib

    mesh = meshlib.make_host_mesh(1, 1)
    mode_axes = {0: "data", 1: "model"}
    x, init = _planted((6, 4, 4), 3)
    problem = Problem.from_tensor(x, 3, mode_axes=mode_axes, mesh=mesh)
    plan = plan_sweep(problem, strategy="dimtree", executor="sharded")
    ex = ShardedExecutor(mesh, mode_axes)
    assert view_splits(plan, ex) == ()
    st = cp_als(x, plan, executor=ex, n_iters=2, tol=0.0, init_factors=list(init))
    assert st.prepared_views == 0
    local = cp_als(x, plan_sweep(Problem.from_tensor(x, 3), strategy="dimtree"),
                   n_iters=2, tol=0.0, init_factors=list(init))
    assert local.prepared_views == 1
    for a, b in zip(st.factors, local.factors):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b), rtol=1e-5, atol=1e-6)


@pytest.mark.parametrize("lo,hi", [(0, 1), (0, 2), (0, 3), (1, 4), (2, 4), (3, 4), (1, 3), (1, 2)])
def test_partial_from_view_is_the_range_partial(lo, hi):
    """Every root range from its view is the einsum of the tensor with the
    factors of the modes outside the range, and what the raw-tensor
    partial gives."""
    shape, rank = (5, 4, 6, 3), 2
    x = random_tensor(jax.random.PRNGKey(3), shape)
    fs = random_factors(jax.random.PRNGKey(4), shape, rank)
    xm = matrix_view(x, view_split(lo, hi, len(shape)))
    assert xm.ndim == 2 and xm.size == x.size
    got = partial_from_view(xm, fs, lo, hi, shape[lo:hi])
    out = [m for m in range(len(shape)) if not lo <= m < hi]
    spec = "abcd," + ",".join("abcd"[m] + "r" for m in out) + "->" + "abcd"[lo:hi] + "r"
    want = jnp.einsum(spec, x, *[fs[m] for m in out], precision="highest")
    assert got.shape == shape[lo:hi] + (rank,)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), rtol=1e-5, atol=1e-5)
    assert np.array_equal(np.asarray(got), np.asarray(partial_mttkrp_range(x, fs, lo, hi)))


def test_matrix_view_keeps_the_batch_lead():
    x = random_tensor(jax.random.PRNGKey(5), (3, 4, 5, 6))
    xm = matrix_view(x, 1, batched=True)
    assert xm.shape == (3, 4, 30)
    assert np.array_equal(np.asarray(xm[1]), np.asarray(x[1].reshape(4, 30)))


def test_views_are_not_kept_across_calls():
    """Each cp_als call builds its own view (a reused dispatch included)."""
    x, init = _planted((8, 7, 6, 5), 3)
    plan = plan_sweep(Problem.from_tensor(x, 3), strategy="dimtree")
    cache = {}
    a = cp_als(x, plan, n_iters=2, tol=0.0, init_factors=list(init),
               dispatch_cache=cache, dispatch_key=0)
    y = 2.0 * x
    b = cp_als(y, plan, n_iters=2, tol=0.0, init_factors=list(init),
               dispatch_cache=cache, dispatch_key=0)
    assert a.prepared_views == b.prepared_views == 1
    ref, _ = _raw_sweeps(plan, y, init, 2)
    for u, v in zip(b.factors, ref.factors):
        assert np.array_equal(np.asarray(u), np.asarray(v))


def _holds_barrier(fn, *args) -> bool:
    return "optimization_barrier" in jax.jit(fn).lower(*args).as_text()


@pytest.mark.parametrize("kind", ["local", "sharded"])
def test_only_the_view_path_is_fenced(kind):
    """The optimization barrier sits on the GEMMs that read a prepared view
    and nowhere else: a sweep on the raw tensor, on the executors with or
    without ``contract_view``, lowers as it did before views existed."""
    from repro.launch import mesh as meshlib
    from repro.plan.executor import make_executor

    x, init = _planted((6, 4, 4, 5), 3)
    if kind == "local":
        problem, ex = Problem.from_tensor(x, 3), LocalExecutor()
    else:
        mesh = meshlib.make_host_mesh(1, 1)
        mode_axes = {0: "data", 1: "model"}
        problem = Problem.from_tensor(x, 3, mode_axes=mode_axes, mesh=mesh)
        ex = make_executor("sharded", mesh, mode_axes)
    plan = plan_sweep(problem, strategy="dimtree", executor=kind)
    xs, fs = ex.prepare(problem, x, init)

    def sweep(xs, fs):
        st = SweepState(x=xs, factors=list(fs), weights=jnp.ones(3), norm_x=jnp.float32(1.0),
                        it=jnp.asarray(0), grams=grams(fs))
        return als_sweep(problem, plan, ex, st).factors

    assert not _holds_barrier(sweep, xs, fs)
    for lo, hi in [(0, 2), (2, 4), (1, 3)]:
        assert not _holds_barrier(lambda x, f: partial_mttkrp_range(x, f, lo, hi), x, init)
        xm = matrix_view(x, view_split(lo, hi, 4))
        assert _holds_barrier(
            lambda v, f: partial_from_view(v, f, lo, hi, x.shape[lo:hi]), xm, init
        )


def test_tune_times_root_partials_on_their_view(monkeypatch):
    """The tuner times each root partial the way cp_als runs it, through
    ``contract_view`` on a prepared view, and never through ``contract``
    on the raw tensor."""
    from repro.plan.autotune import TuningCache, node_key, tune

    seen = {"view": [], "raw": []}
    contract, contract_view = LocalExecutor.contract, LocalExecutor.contract_view

    def spy_contract(self, node, src, *a, **k):
        if node.from_root and not node.is_leaf:
            seen["raw"].append(node.id)
        return contract(self, node, src, *a, **k)

    def spy_view(self, node, view, factors):
        assert view.ndim == 2
        seen["view"].append(node.id)
        return contract_view(self, node, view, factors)

    monkeypatch.setattr(LocalExecutor, "contract", spy_contract)
    monkeypatch.setattr(LocalExecutor, "contract_view", spy_view)
    x, _ = _planted((8, 7, 6, 5), 3)
    entry = tune(x, 3, cache=TuningCache(), budget_ms=None, reps=1)
    assert seen["view"] and not seen["raw"]
    plan = plan_sweep(Problem.from_tensor(x, 3), strategy="dimtree")
    partials = [n for n in plan.resolved_schedule.walk() if n.from_root and not n.is_leaf]
    measured = {r["key"] for r in entry["nodes"]}
    for node in partials:
        assert node_key(node, plan.node_plan(node.id).algorithm, "local") in measured
