"""Contraction-schedule IR: builders, validity, costing, and the invariant
that ANY valid schedule reproduces the flat ALS iterates on LocalExecutor."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import random_factors, random_tensor, tensor_norm
from repro.plan import (
    LocalExecutor,
    Problem,
    Schedule,
    SweepState,
    als_sweep,
    binary_schedule,
    build_schedule,
    chain_schedule,
    dimtree_mode_cost,
    enumerate_schedules,
    flat_schedule,
    node_cost,
    plan_sweep,
    select_executor,
    validate_executor,
)


# ------------------------------------------------------------- IR / builders
def test_builders_and_degenerate_shapes():
    p = Problem(shape=(6, 5, 4, 3), rank=3)
    flat = flat_schedule(p)
    assert flat.is_flat and flat.split is None
    assert [leaf.mode for leaf in flat.leaves()] == [0, 1, 2, 3]
    assert all(node.from_root for node in flat.walk())

    b = binary_schedule(p, 2)
    assert not b.is_flat and b.split == 2
    # two internal halves + four leaves, leaves in increasing mode order
    assert len(b.walk()) == 6
    assert [leaf.mode for leaf in b.leaves()] == [0, 1, 2, 3]
    # the left half contracts the right modes from the raw tensor
    left = b.nodes[b.root.children[0]]
    assert left.modes == (0, 1) and left.contracted == (2, 3) and left.from_root

    chain = chain_schedule(p)
    assert not chain.is_flat and chain.split is None
    # the chain reuses each partial: every internal node contracts ONE mode
    internals = [n for n in chain.walk() if not n.is_leaf]
    assert all(len(n.contracted) == 1 for n in internals)

    # size-1 halves degenerate to leaves off the root
    b1 = binary_schedule(Problem(shape=(4, 4, 4), rank=2), 1)
    assert b1.leaf_for_mode(0).from_root
    assert not b1.leaf_for_mode(2).from_root


def test_enumerate_schedules_counts():
    """Acceptance: an order-4 problem enumerates >= 3 distinct tree shapes."""
    order4 = enumerate_schedules(Problem(shape=(8, 8, 8, 8), rank=4))
    names = [s.name for s in order4]
    assert len(set(names)) == len(names)
    trees = [s for s in order4 if not s.is_flat]
    assert len(trees) >= 3, names  # binary@1..3 + chain
    assert any(s.name == "chain" for s in order4)
    order3 = enumerate_schedules(Problem(shape=(8, 8, 8), rank=4))
    assert sum(not s.is_flat for s in order3) >= 2


def test_build_schedule_rejects_invalid_specs():
    p = Problem(shape=(4, 4, 4, 4), rank=2)
    with pytest.raises(ValueError):  # gap / wrong order
        build_schedule(p, [[0, 2], [1, 3]])
    with pytest.raises(ValueError):  # missing a mode
        build_schedule(p, [0, 1, 2])
    with pytest.raises(ValueError):  # single-child internal node
        build_schedule(p, [[0, 1, 2, 3]])
    with pytest.raises(ValueError):  # duplicated mode breaks contiguity
        build_schedule(p, [0, 0, 1, 2, 3])


def test_node_metadata_matches_placement():
    p = Problem(
        shape=(8, 6, 4, 4), rank=3,
        mode_axes={0: "data", 2: "model"}, axis_sizes={"data": 2, "model": 4},
    )
    b = binary_schedule(p, 2)
    left = b.nodes[b.root.children[0]]  # keeps {0,1}, contracts {2,3}
    assert left.reduce_axes == ("model",) and left.psum_participants == 4
    assert left.local_shape == (4, 6, 3)
    right = b.nodes[b.root.children[1]]  # keeps {2,3}, contracts {0,1}
    assert right.reduce_axes == ("data",) and right.psum_participants == 2
    # leaf 0 contracts mode 1 (unmapped) from the left partial: no psum
    assert b.leaf_for_mode(0).reduce_axes == ()
    # leaf 1 contracts mode 0 (mapped): psum over its axis
    assert b.leaf_for_mode(1).reduce_axes == ("data",)
    assert left.psum_bytes > 0.0 and b.leaf_for_mode(0).psum_bytes == 0.0


# ------------------------------------------------------------------- costing
def test_dimtree_mode_cost_folds_over_node_cost():
    """Summing the per-mode back-compat view == summing node_cost over the
    binary schedule: one coster, two projections."""
    p = Problem(
        shape=(8, 6, 4, 4), rank=3,
        mode_axes={0: "data", 2: "model"}, axis_sizes={"data": 2, "model": 4},
    )
    for split in (1, 2, 3):
        sched = binary_schedule(p, split)
        node_total = sum(
            node_cost(p, node).predicted_s for node in sched.walk()
        )
        mode_total = sum(
            dimtree_mode_cost(p, n, split).predicted_s for n in range(4)
        )
        assert node_total == pytest.approx(mode_total)
    # the old special-case raise is gone: "dimtree" is a costed algorithm
    from repro.plan import mode_cost

    assert mode_cost(p, 1, "dimtree").predicted_s > 0.0


def test_validate_executor_is_the_single_predicate():
    sharded = Problem(
        shape=(4, 4), rank=2, mode_axes={0: "data"}, axis_sizes={"data": 2}
    )
    local = Problem(shape=(4, 4), rank=2)
    validate_executor(sharded, "sharded")  # no raise
    validate_executor(local, "local")
    msgs = []
    for problem, executor in ((sharded, "local"), (local, "overlapping")):
        with pytest.raises(ValueError, match="cannot run this problem") as ei:
            validate_executor(problem, executor)
        msgs.append(str(ei.value))
    assert all("cannot run this problem" in m for m in msgs)
    with pytest.raises(ValueError, match="unknown executor"):
        validate_executor(local, "nope")


def test_serial_fractions_thread_through_plan():
    """Calibrated constants override the analytic defaults everywhere."""
    p = Problem(
        shape=(8, 16, 16), rank=5,
        mode_axes={0: "data", 2: "model"}, axis_sizes={"data": 2, "model": 4},
    )
    base = plan_sweep(p, schedule="flat", executor="overlapping")
    fitted = plan_sweep(
        p, schedule="flat", executor="overlapping",
        serial_fractions={"overlapping": 0.5},
    )
    for mb, mf in zip(base.modes, fitted.modes):
        assert mf.cost.serial_fraction == pytest.approx(0.5)
        assert mf.cost.predicted_s > mb.cost.predicted_s  # 0.5 > 1/4 default
    assert fitted.serial_fractions == {"overlapping": 0.5}
    assert fitted.describe()["serial_fractions"] == {"overlapping": 0.5}
    # and fitted "sharded" fractions bend the exact executor's prediction
    sh = plan_sweep(p, schedule="flat", executor="sharded",
                    serial_fractions={"sharded": 0.9})
    assert all(m.cost.serial_fraction == pytest.approx(0.9) for m in sh.modes)
    with pytest.raises(ValueError):
        plan_sweep(p, serial_fractions={"nope": 0.5})
    with pytest.raises(ValueError):
        plan_sweep(p, serial_fractions={"overlapping": 1.5})


# ------------------------------------------------------- planner integration
def test_auto_enumerates_trees_and_can_pick_overlapping_dimtree():
    """Acceptance: order-4 bench shape -> >= 3 tree candidates; a dimtree
    schedule can land on the overlapping executor."""
    bench = Problem(shape=(63, 63, 63, 63), rank=25)
    assert sum(not s.is_flat for s in enumerate_schedules(bench)) >= 3
    # on the order-4 bench shape the binary tree's two X-reads beat the
    # flat sweep's four by far more than the 10% near-tie margin
    plan = plan_sweep(bench)
    assert plan.kind == "dimtree", plan.resolved_schedule.name
    # sharded order-3 with an 8-way psum: the argmin lands on the
    # overlapping executor -- for the dimtree schedule too
    p = Problem(
        shape=(8, 32, 8), rank=8, mode_axes={0: "shard"}, axis_sizes={"shard": 8}
    )
    assert select_executor(p, "dimtree") == "overlapping"
    plan = plan_sweep(p, strategy="dimtree")
    assert plan.kind == "dimtree" and plan.executor == "overlapping"


def test_plan_sweep_accepts_explicit_and_named_schedules():
    p = Problem(shape=(5, 4, 6, 3), rank=3)
    custom = build_schedule(p, [0, [1, 2], 3], name="mixed")
    plan = plan_sweep(p, schedule=custom)
    assert plan.resolved_schedule is custom and plan.kind == "dimtree"
    assert plan_sweep(p, schedule="chain").resolved_schedule.name == "chain"
    assert plan_sweep(p, schedule="binary", split=1).split == 1
    with pytest.raises(ValueError, match="different Problem"):
        plan_sweep(Problem(shape=(5, 4, 6, 3), rank=4), schedule=custom)
    with pytest.raises(ValueError, match="unknown schedule"):
        plan_sweep(p, schedule="nope")


# ----------------------------------------- any schedule == flat ALS iterates
def _reference(x, factors, w, norm_x, problem, sweeps=2):
    plan = plan_sweep(problem, schedule="flat")
    fs, ws = list(factors), w
    for it in range(sweeps):
        st = SweepState(x=x, factors=fs, weights=ws, norm_x=norm_x, it=jnp.asarray(it))
        out = als_sweep(problem, plan, LocalExecutor(), st)
        fs, ws = out.factors, out.weights
    return fs, ws, out.fit


def _run_schedule(x, factors, w, norm_x, problem, sched, sweeps=2):
    plan = plan_sweep(problem, schedule=sched)
    fs, ws = list(factors), w
    for it in range(sweeps):
        st = SweepState(x=x, factors=fs, weights=ws, norm_x=norm_x, it=jnp.asarray(it))
        out = als_sweep(problem, plan, LocalExecutor(), st)
        fs, ws = out.factors, out.weights
    return fs, ws, out.fit


def _assert_matches_flat(shape, sched_or_spec, seed=0):
    rank = 3
    x = random_tensor(jax.random.PRNGKey(seed), shape)
    factors = random_factors(jax.random.PRNGKey(seed + 1), shape, rank)
    w = jnp.ones((rank,), x.dtype)
    norm_x = tensor_norm(x)
    problem = Problem.from_tensor(x, rank)
    sched = (
        sched_or_spec
        if isinstance(sched_or_spec, Schedule)
        else build_schedule(problem, sched_or_spec)
    )
    f_ref, w_ref, fit_ref = _reference(x, factors, w, norm_x, problem)
    f_s, w_s, fit_s = _run_schedule(x, factors, w, norm_x, problem, sched)
    for a, b in zip(f_s, f_ref):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b), rtol=2e-3, atol=2e-4)
    np.testing.assert_allclose(float(fit_s), float(fit_ref), atol=1e-3)


@pytest.mark.parametrize(
    "shape,spec",
    [
        ((5, 6, 7), [[0, 1], 2]),
        ((4, 5, 6, 3), [[0, 1], [2, 3]]),
        ((4, 5, 6, 3), [[[0, 1], 2], 3]),
        ((3, 4, 2, 3, 4), [[0, 1], [2, [3, 4]]]),
        ((3, 4, 2, 3, 4), [0, [[1, 2], [3, 4]]]),
        ((3, 3, 2, 2, 3, 2), [[[0, 1], [2, 3]], [4, 5]]),
    ],
)
def test_schedules_match_flat_iterates(shape, spec):
    """Deterministic spot checks across orders 3..6 and tree depths."""
    _assert_matches_flat(shape, spec)


def test_every_enumerated_schedule_matches_flat_on_order4():
    problem = Problem(shape=(4, 5, 6, 3), rank=3)
    for sched in enumerate_schedules(problem):
        _assert_matches_flat((4, 5, 6, 3), sched)


# --------------------------------------------- hypothesis: random tree shapes
from conftest import given, settings, st  # noqa: E402  (shared optional-dep shim)


@st.composite
def _spec(draw, lo, hi):
    """A random valid nested spec over modes [lo, hi)."""
    if hi - lo == 1:
        return lo
    k = draw(st.integers(2, hi - lo))
    cuts = sorted(
        draw(
            st.sets(
                st.integers(lo + 1, hi - 1), min_size=k - 1, max_size=k - 1
            )
        )
    )
    bounds = [lo, *cuts, hi]
    return [
        a if b - a == 1 else draw(_spec(a, b))
        for a, b in zip(bounds[:-1], bounds[1:])
    ]


@st.composite
def _problem_and_spec(draw):
    order = draw(st.integers(3, 6))
    shape = tuple(draw(st.integers(2, 5)) for _ in range(order))
    spec = draw(_spec(0, order))
    return shape, spec


@settings(max_examples=15, deadline=None)
@given(case=_problem_and_spec())
def test_random_schedule_matches_flat_iterates(case):
    """Property (the ALS-exactness invariant of the IR): ANY valid tree
    over a random order-3..6 shape reproduces the flat sweep."""
    shape, spec = case
    _assert_matches_flat(shape, spec, seed=11)
