"""The plan front door: dispatch, cost-model agreement, plan-vs-direct
numerics for every algorithm, and sharded == local on a size-1 mesh."""

import functools
import json
import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import (
    mttkrp_einsum,
    mttkrp_flops,
    random_factors,
    random_tensor,
    tensor_norm,
)
from repro.core.tensor_ops import dims_split
from repro.plan import (
    EXECUTORS,
    LocalExecutor,
    Problem,
    SweepState,
    als_sweep,
    cp_als,
    make_executor,
    mode_cost,
    plan_sweep,
)
from repro.plan.sweep import (
    fit_from_last_mttkrp,
    grams,
    hadamard_except,
    normalize_columns,
)

# paper bench shapes: cubic, N in {3..6}, default (~16M) and paper (~750M) scale
BENCH_SHAPES = [
    tuple([round(total ** (1.0 / n))] * n)
    for total in (16e6, 750e6)
    for n in (3, 4, 5, 6)
]


# ------------------------------------------------------------------ planner
@pytest.mark.parametrize(
    "shape", BENCH_SHAPES, ids=["x".join(map(str, s)) for s in BENCH_SHAPES]
)
def test_auto_reproduces_paper_dispatch_on_bench_shapes(shape):
    """Sec. 5.3.3: 1-step on external modes, 2-step on internal modes.

    The per-mode dispatch property lives on the flat schedule (tree plans
    replace most full MTTKRPs with partial contractions -- see
    test_schedule.py for those invariants)."""
    plan = plan_sweep(Problem(shape=shape, rank=25), schedule="flat")
    algs = [m.algorithm for m in plan.modes]
    assert algs[0] == "1step" and algs[-1] == "1step", algs
    assert all(a.startswith("2step") for a in algs[1:-1]), algs


def test_auto_2step_order_matches_smaller_second_step_rule():
    """Alg. 4 line 4: contract the bigger side first (left-first iff L > R)."""
    shape = (4, 6, 8, 2)
    plan = plan_sweep(Problem(shape=shape, rank=5), schedule="flat")
    for mp in plan.modes[1:-1]:
        L, _, R = dims_split(shape, mp.mode)
        expect = "2step-left" if L > R else "2step-right"
        assert mp.algorithm == expect, (mp.mode, mp.algorithm, L, R)


def test_cost_model_agrees_with_mttkrp_flops():
    """Acceptance: GEMM/KRP/second-step terms come straight from mttkrp_flops."""
    shape, rank = (12, 10, 8, 6), 7
    problem = Problem(shape=shape, rank=rank)
    for n in range(len(shape)):
        f = mttkrp_flops(shape, rank, n)
        one = mode_cost(problem, n, "1step")
        assert one.gemm_flops == f["gemm_flops"]
        assert one.krp_flops == f["krp_flops"]
        assert one.second_step_flops == 0.0
        if 0 < n < len(shape) - 1:
            two = mode_cost(problem, n, "2step")
            assert two.gemm_flops == f["gemm_flops"]
            # the cost-picked order contracts min(L, R) in the 2nd step
            assert two.second_step_flops == f["second_step_flops"]


def test_mttkrp_flops_dtype_threading():
    shape, rank, n = (8, 6, 4), 5, 1
    f32 = mttkrp_flops(shape, rank, n)
    bf16 = mttkrp_flops(shape, rank, n, dtype=jnp.bfloat16)
    f64 = mttkrp_flops(shape, rank, n, dtype="float64")
    for key in ("tensor_bytes", "krp_bytes"):
        assert bf16[key] * 2 == f32[key]
        assert f64[key] == f32[key] * 2
    for key in ("gemm_flops", "krp_flops", "second_step_flops"):
        assert bf16[key] == f32[key] == f64[key]
    # Problem carries the dtype into the planner's byte terms
    b16 = plan_sweep(Problem(shape=shape, rank=rank, dtype=jnp.bfloat16))
    b32 = plan_sweep(Problem(shape=shape, rank=rank))
    assert b16.modes[n].cost.bytes * 2 == b32.modes[n].cost.bytes


def test_describe_is_json_ready_and_totals_sum():
    problem = Problem(
        shape=(8, 6, 4, 4),
        rank=3,
        mode_axes={0: "data", 2: "model"},
        axis_sizes={"data": 2, "model": 4},
    )
    # totals sum over every schedule node, whatever tree auto picked
    plan = plan_sweep(problem)
    d = json.loads(json.dumps(plan.describe()))
    assert d["sharded"] and d["local_shape"] == [4, 6, 1, 4]
    assert len(d["modes"]) == 4
    assert len(d["nodes"]) >= 4  # the 4 leaves, plus any partials
    for key in ("flops", "bytes", "collective_bytes", "predicted_s"):
        assert d["totals"][key] == pytest.approx(sum(n[key] for n in d["nodes"]))
    # on the flat schedule the node rows ARE the per-mode rows, and every
    # mode psums over the *other* mapped mode's axis; none is free
    flat = json.loads(json.dumps(plan_sweep(problem, schedule="flat").describe()))
    assert flat["schedule"] == "flat" and len(flat["nodes"]) == 4
    for key in ("flops", "bytes", "collective_bytes", "predicted_s"):
        assert flat["totals"][key] == pytest.approx(sum(m[key] for m in flat["modes"]))
    assert all(m["collective_bytes"] > 0 for m in flat["modes"])
    # unsharded problems predict zero collective traffic
    local = plan_sweep(Problem(shape=(8, 6, 4, 4), rank=3)).describe()
    assert local["totals"]["collective_bytes"] == 0.0


def test_problem_validation_errors():
    with pytest.raises(ValueError):  # unknown axis size
        Problem(shape=(4, 4), rank=2, mode_axes={0: "data"})
    with pytest.raises(ValueError):  # not divisible
        Problem(shape=(5, 4), rank=2, mode_axes={0: "data"}, axis_sizes={"data": 2})
    with pytest.raises(ValueError):  # axis mapped twice
        Problem(
            shape=(4, 4), rank=2,
            mode_axes={0: "data", 1: "data"}, axis_sizes={"data": 2},
        )
    with pytest.raises(ValueError):
        plan_sweep(Problem(shape=(4, 4, 4), rank=2), strategy="nope")
    with pytest.raises(ValueError):  # split only for dimtree
        plan_sweep(Problem(shape=(4, 4, 4), rank=2), strategy="1step", split=1)


# ----------------------------------------------------- plan-vs-direct sweeps
def _reference_sweep(x, factors, weights, norm_x, it):
    """Independent oracle sweep: einsum MTTKRP + the textbook update algebra."""
    factors = list(factors)
    gs = grams(factors)
    m_last = None
    for n in range(len(factors)):
        m_last = mttkrp_einsum(x, factors, n)
        h = hadamard_except(gs, n)
        u = m_last @ jnp.linalg.pinv(h)
        u, weights = normalize_columns(u, it)
        factors[n] = u
        gs[n] = u.T @ u
    fit = fit_from_last_mttkrp(gs, weights, m_last, factors[-1], norm_x)
    return factors, weights, fit


STRATEGIES_UNDER_TEST = [
    "auto", "1step", "2step", "2step-left", "2step-right",
    "einsum", "baseline", "dimtree", "fused",
]


@pytest.mark.parametrize("strategy", STRATEGIES_UNDER_TEST)
def test_planned_sweep_matches_reference_for_every_algorithm(strategy):
    shape, rank = (5, 4, 6, 3), 3
    x = random_tensor(jax.random.PRNGKey(0), shape)
    factors = random_factors(jax.random.PRNGKey(1), shape, rank)
    w = jnp.ones((rank,), x.dtype)
    norm_x = tensor_norm(x)
    problem = Problem.from_tensor(x, rank)
    plan = plan_sweep(problem, strategy=strategy)
    state = SweepState(
        x=x, factors=list(factors), weights=w, norm_x=norm_x, it=jnp.asarray(0)
    )
    out = als_sweep(problem, plan, LocalExecutor(), state)
    f_ref, w_ref, fit_ref = _reference_sweep(x, list(factors), w, norm_x, jnp.asarray(0))
    tol = dict(rtol=5e-3, atol=1e-3) if strategy == "fused" else dict(rtol=1e-3, atol=1e-4)
    for a, b in zip(out.factors, f_ref):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b), **tol)
    np.testing.assert_allclose(float(out.fit), float(fit_ref), atol=1e-3)


@pytest.mark.parametrize("kind", ["sharded", "overlapping"])
def test_sharded_executor_equals_local_on_size1_mesh(kind):
    """Every sharded executor the planner can return == LocalExecutor
    when every axis has 1 device: all of them are exact.  ``sharded`` is
    bitwise; ``overlapping`` runs its MTTKRP as slab GEMMs, which reorder
    the float sum, so it agrees to rounding."""
    from repro.launch import mesh as meshlib

    mesh = meshlib.make_host_mesh(1, 1)
    mode_axes = {0: "data", 1: "model"}
    shape, rank = (6, 4, 4), 3
    x = random_tensor(jax.random.PRNGKey(4), shape)
    factors = random_factors(jax.random.PRNGKey(5), shape, rank)
    w = jnp.ones((rank,), x.dtype)
    norm_x = tensor_norm(x)
    problem = Problem.from_tensor(x, rank, mode_axes=mode_axes, mesh=mesh)
    assert problem.local_shape == shape  # size-1 axes shard nothing
    for strategy in ("auto", "dimtree"):
        plan = plan_sweep(problem, strategy=strategy, executor=kind)
        assert plan.total_cost()["collective_bytes"] == 0.0

        def state():
            return SweepState(
                x=x, factors=list(factors), weights=w,
                norm_x=norm_x, it=jnp.asarray(0),
            )

        sharded_ex = make_executor(kind, mesh, mode_axes)
        xs, fss = sharded_ex.prepare(problem, x, factors)
        st_sharded = SweepState(
            x=xs, factors=fss, weights=w, norm_x=norm_x, it=jnp.asarray(0)
        )
        out_local = als_sweep(problem, plan, LocalExecutor(), state())
        out_sharded = als_sweep(problem, plan, sharded_ex, st_sharded)
        if kind == "sharded":
            check = np.testing.assert_array_equal
        else:
            check = functools.partial(np.testing.assert_allclose, rtol=1e-5, atol=1e-6)
        for a, b in zip(out_local.factors, out_sharded.factors):
            check(np.asarray(a), np.asarray(b))
        check(np.asarray(out_local.fit), np.asarray(out_sharded.fit))


def test_plan_cp_als_driver_converges_with_dimtree():
    from repro.core import cp_full

    planted = random_factors(jax.random.PRNGKey(2), (10, 8, 6, 5), 2)
    x = cp_full(None, planted)
    fits = []
    plan = plan_sweep(Problem.from_tensor(x, 2), strategy="dimtree")
    st = cp_als(x, plan, n_iters=80, tol=1e-9, seed=3,
                callback=lambda it, fit, dt: fits.append(fit))
    assert float(st.fit) > 0.99, float(st.fit)
    assert len(fits) == st.it


def test_mode_letters_rejects_unsupported_order():
    from repro.core import mode_letters

    assert mode_letters(3) == "abd"
    with pytest.raises(ValueError, match="order"):
        mode_letters(13)
    with pytest.raises(ValueError, match="order"):
        mode_letters(0)


# ------------------------------------------------- executor selection / cost
def test_overlap_model_degenerates_to_additive_sum():
    """serial_fraction=1 (sharded/local): max + min == the old additive model."""
    from repro.analysis.roofline import HBM_BW, ICI_BW, PEAK_FLOPS

    problem = Problem(
        shape=(8, 6, 4, 4), rank=3,
        mode_axes={0: "data", 2: "model"}, axis_sizes={"data": 2, "model": 4},
    )
    for n in range(4):
        c = mode_cost(problem, n, "1step")
        assert c.serial_fraction == 1.0
        additive = c.flops / PEAK_FLOPS + c.bytes / HBM_BW + c.collective_bytes / ICI_BW
        assert c.predicted_s == pytest.approx(additive)
        assert c.predicted_s >= max(c.compute_s, c.collective_s)


def test_overlapping_cost_hides_all_but_one_chunk():
    from repro.plan import DEFAULT_OVERLAP_CHUNKS, executor_mode_cost

    # every mode keeps local extent >= DEFAULT_OVERLAP_CHUNKS (local (4,16,4))
    problem = Problem(
        shape=(8, 16, 16), rank=5,
        mode_axes={0: "data", 2: "model"}, axis_sizes={"data": 2, "model": 4},
    )
    for n in range(3):
        sh = executor_mode_cost(problem, n, "1step", "sharded")
        ov = executor_mode_cost(problem, n, "1step", "overlapping")
        # same physical terms, only the schedule differs
        assert ov.flops == sh.flops and ov.bytes == sh.bytes
        assert ov.collective_bytes == sh.collective_bytes
        assert ov.serial_fraction == pytest.approx(1.0 / DEFAULT_OVERLAP_CHUNKS)
        assert ov.predicted_s < sh.predicted_s  # every mode psums here
        assert ov.predicted_overlap_efficiency == pytest.approx(
            1.0 - 1.0 / DEFAULT_OVERLAP_CHUNKS
        )
    # chunk count is capped by the local row count of the mode
    tiny = Problem(
        shape=(4, 2, 4), rank=5,
        mode_axes={0: "data", 2: "model"}, axis_sizes={"data": 2, "model": 4},
    )
    ov = executor_mode_cost(tiny, 1, "1step", "overlapping", n_chunks=8)
    assert ov.serial_fraction == pytest.approx(1.0 / 2)


def test_select_executor_cost_argmin():
    from repro.plan import select_executor

    assert EXECUTORS == ("local", "sharded", "overlapping")
    # unsharded -> local
    assert select_executor(Problem(shape=(8, 8, 8), rank=4)) == "local"
    # sharded with real collectives -> overlapping (hiding is free in the model)
    sharded = Problem(
        shape=(8, 6, 4, 4), rank=3,
        mode_axes={0: "data", 2: "model"}, axis_sizes={"data": 2, "model": 4},
    )
    assert select_executor(sharded) == "overlapping"
    # few participants + collective-bound: still an exact executor
    bound = Problem(
        shape=(2, 64, 2), rank=4096, mode_axes={0: "data"}, axis_sizes={"data": 2}
    )
    assert select_executor(bound) == "overlapping"
    # dimtree plans compete on the same footing: their per-node psums can be
    # overlapped too, so selection is a cost argmin, not a forced "sharded"
    assert select_executor(sharded, "dimtree") == "overlapping"
    assert plan_sweep(sharded, strategy="dimtree", executor="overlapping").executor == "overlapping"
    # plan_sweep agrees and stamps the choice on the plan
    for problem in (sharded, bound):
        plan = plan_sweep(problem)
        assert plan.executor == select_executor(problem)
        assert json.loads(json.dumps(plan.describe()))["executor"] == plan.executor


def test_plan_executor_validation():
    from repro.plan import make_executor

    sharded = Problem(
        shape=(4, 4), rank=2, mode_axes={0: "data"}, axis_sizes={"data": 2}
    )
    with pytest.raises(ValueError, match="cannot run this problem"):
        plan_sweep(sharded, executor="local")  # local cannot run sharded problems
    with pytest.raises(ValueError, match="cannot run this problem"):
        plan_sweep(Problem(shape=(4, 4), rank=2), executor="overlapping")
    # any (schedule, executor) pair is costed or rejected by the one shared
    # predicate: dimtree + overlapping is a valid pairing...
    sharded3 = Problem(
        shape=(4, 4, 4), rank=2, mode_axes={0: "data"}, axis_sizes={"data": 2}
    )
    plan = plan_sweep(sharded3, strategy="dimtree", executor="overlapping")
    assert plan.executor == "overlapping" and plan.kind == "dimtree"
    # ...and overlapping on an unsharded problem is rejected with the same
    # message the flat schedule gets
    with pytest.raises(ValueError, match="cannot run this problem"):
        plan_sweep(
            Problem(shape=(4, 4, 4), rank=2), strategy="dimtree", executor="overlapping"
        )
    with pytest.raises(ValueError):
        plan_sweep(Problem(shape=(4, 4), rank=2), executor="nope")
    with pytest.raises(ValueError):  # sharded kinds need the concrete mesh
        make_executor("overlapping")
    with pytest.raises(ValueError):
        make_executor("nope")
    # a sharded plan refuses to run on the default LocalExecutor
    plan = plan_sweep(sharded)
    with pytest.raises(ValueError, match="make_executor"):
        cp_als(jnp.zeros((4, 4)), plan)


def test_make_executor_builds_matching_kinds():
    from repro.launch import mesh as meshlib
    from repro.plan import OverlappingExecutor, ShardedExecutor

    mesh = meshlib.make_host_mesh(1, 1)
    mode_axes = {0: "data"}
    assert isinstance(make_executor("local"), LocalExecutor)
    sh = make_executor("sharded", mesh, mode_axes)
    assert isinstance(sh, ShardedExecutor) and not isinstance(sh, OverlappingExecutor)
    ov = make_executor("overlapping", mesh, mode_axes, n_chunks=7)
    assert isinstance(ov, OverlappingExecutor) and ov.n_chunks == 7


def _compressed_plan():
    return plan_sweep(
        Problem(shape=(4, 4, 4), rank=2, mode_axes={0: "data"}, axis_sizes={"data": 2}),
        executor="compressed",
    )


def _compressed_executor():
    from repro.launch import mesh as meshlib

    return make_executor("compressed", meshlib.make_host_mesh(1, 1), {0: "data"})


@pytest.mark.parametrize("call", [_compressed_plan, _compressed_executor])
def test_no_approximate_executor_is_reachable(call):
    """The int8 error-feedback executor is gone: asking for it by name
    fails with the unknown-executor error, which names the exact kinds."""
    with pytest.raises(ValueError, match="unknown executor") as err:
        call()
    assert str(EXECUTORS) in str(err.value)


# ------------------------------------------------------------- dispatch cache
class _CountingCache(dict):
    """dict that counts lookups so tests can distinguish hit from rebuild."""

    def __init__(self):
        super().__init__()
        self.hits = 0

    def __getitem__(self, key):
        self.hits += 1
        return super().__getitem__(key)


def test_dispatch_cache_reuses_compiled_chunk():
    """A second ``cp_als`` call with the same key reuses the cached dispatch
    (no new entry, one hit) and reproduces the first run bit for bit."""
    shape, rank = (6, 5, 4), 3
    x = random_tensor(jax.random.PRNGKey(50), shape)
    init = random_factors(jax.random.PRNGKey(51), shape, rank)
    problem = Problem(shape=shape, rank=rank)
    plan = plan_sweep(problem)
    cache = _CountingCache()
    key = problem.signature()

    a = cp_als(x, plan, n_iters=4, tol=0.0, init_factors=list(init),
               dispatch_cache=cache, dispatch_key=key)
    assert len(cache) == 1 and cache.hits == 0  # cold: built, not looked up
    b = cp_als(x, plan, n_iters=4, tol=0.0, init_factors=list(init),
               dispatch_cache=cache, dispatch_key=key)
    assert len(cache) == 1 and cache.hits == 1  # warm: reused, nothing built
    for fa, fb in zip(a.factors, b.factors):
        assert np.array_equal(np.asarray(fa), np.asarray(fb))
    assert np.array_equal(np.asarray(a.weights), np.asarray(b.weights))


def test_dispatch_cache_isolates_signatures():
    """Different problems -- including the same shape with PP enabled -- get
    their own cache entries when keyed by ``Problem.signature()``; the PP
    signature is distinct by construction (``|pp`` suffix)."""
    shape, rank = (6, 5, 4), 3
    x = random_tensor(jax.random.PRNGKey(52), shape)
    cache = _CountingCache()

    exact = Problem(shape=shape, rank=rank)
    cp_als(x, plan_sweep(exact), n_iters=3, tol=0.0,
           dispatch_cache=cache, dispatch_key=exact.signature())
    assert len(cache) == 1

    other = Problem(shape=(5, 5, 5), rank=rank)
    cp_als(random_tensor(jax.random.PRNGKey(53), (5, 5, 5)), plan_sweep(other),
           n_iters=3, tol=0.0, dispatch_cache=cache,
           dispatch_key=other.signature())
    assert len(cache) == 2

    pp = Problem(shape=shape, rank=rank, pp_tol=0.1)
    assert pp.signature() != exact.signature() and "|pp" in pp.signature()
    cp_als(x, plan_sweep(pp, strategy="pp"), n_iters=3, tol=0.0,
           dispatch_cache=cache, dispatch_key=pp.signature())
    assert len(cache) == 3 and cache.hits == 0  # three builds, zero collisions


# --------------------------------------------- hypothesis planner invariants
from conftest import given, settings, st  # noqa: E402  (shared optional-dep shim)


@settings(max_examples=30, deadline=None)
@given(
    shape=st.lists(st.integers(2, 30), min_size=3, max_size=6),
    rank=st.integers(1, 32),
)
def test_auto_plan_invariants(shape, rank):
    # the per-mode invariants live on the flat schedule; tree-schedule
    # invariants are property-tested in test_schedule.py
    plan = plan_sweep(Problem(shape=tuple(shape), rank=rank), schedule="flat")
    assert [m.mode for m in plan.modes] == list(range(len(shape)))
    # external modes are always 1-step (2-step degenerates there)
    assert plan.modes[0].algorithm == "1step"
    assert plan.modes[-1].algorithm == "1step"
    for m in plan.modes:
        assert m.algorithm in ("1step", "2step-left", "2step-right")
        assert m.cost.predicted_s > 0.0
        assert m.cost.collective_bytes == 0.0


@settings(max_examples=15, deadline=None)
@given(
    shape=st.lists(st.integers(2, 12), min_size=3, max_size=5),
    strategy=st.sampled_from(["1step", "einsum", "baseline", "fused"]),
)
def test_forced_strategy_is_verbatim(shape, strategy):
    plan = plan_sweep(Problem(shape=tuple(shape), rank=4), strategy=strategy)
    assert all(m.algorithm == strategy for m in plan.modes)


def test_sweep_contracts_at_the_solver_precision():
    """Every dot_general a traced sweep emits -- MTTKRPs, partial
    contractions, Grams, solve, fit -- carries SWEEP_PRECISION: on a TPU a
    default-precision fp32 matmul takes one bf16 pass, which stalls ALS on
    the paper's fMRI tensor."""
    from repro.plan.sweep import SWEEP_PRECISION

    shape, rank = (6, 5, 4, 3), 3
    x = random_tensor(jax.random.PRNGKey(0), shape)
    fs = random_factors(jax.random.PRNGKey(1), shape, rank)
    want = f"Precision.{SWEEP_PRECISION.upper()}"
    for strategy in ("auto", "1step", "2step", "dimtree"):
        plan = plan_sweep(Problem.from_tensor(x, rank), strategy=strategy)
        state = SweepState(
            x=x, factors=fs, weights=jnp.ones((rank,)), norm_x=jnp.float32(1.0),
            it=jnp.asarray(0),
        )
        text = str(jax.make_jaxpr(
            lambda s, plan=plan: als_sweep(plan.problem, plan, LocalExecutor(), s)
        )(state))
        precisions = re.findall(r"precision=\(?([^,\)\]]*)", text)
        assert precisions, strategy
        assert set(p.strip() for p in precisions) == {want}, (strategy, set(precisions))
