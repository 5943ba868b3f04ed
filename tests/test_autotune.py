"""The measured-cost loop: TuningCache persistence/keying, tuned-tile
numerics, plan_sweep(strategy="autotune") stamping + analytic fallback, and
the sync-free cp_als chunked driver (bitwise iterates, one sync per chunk)."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import cp_full, random_factors, random_tensor
from repro.kernels import ops, ref
from repro.plan import (
    Problem,
    TuningCache,
    cp_als,
    lookup_measurements,
    plan_sweep,
    tune,
)
from repro.plan.autotune import node_key, problem_key

SHAPE, RANK = (8, 6, 4), 3


def _problem_arrays(shape=SHAPE, rank=RANK, seed=0):
    x = random_tensor(jax.random.PRNGKey(seed), shape)
    factors = random_factors(jax.random.PRNGKey(seed + 1), shape, rank)
    return x, factors


@pytest.fixture(scope="module")
def tuned_cache(tmp_path_factory):
    """One disk-backed cache tuned on the module's local problem (tuning
    compiles dozens of kernels; share it across the tests that read it)."""
    path = tmp_path_factory.mktemp("tuning") / "cache.json"
    x, factors = _problem_arrays()
    cache = TuningCache(path)
    entry = tune(x, RANK, factors=factors, cache=cache, budget_ms=None, reps=1)
    return path, cache, entry


# ------------------------------------------------------------------- cache
def test_tuning_cache_disk_roundtrip(tmp_path):
    path = tmp_path / "cache.json"
    c = TuningCache(path)
    assert c.get("k") is None
    c.put("k", {"nodes": [], "tiles": {"fused_mttkrp": {"block_i": 64}}})
    # a fresh cache object sees what the first one persisted
    c2 = TuningCache(path)
    assert c2.get("k")["tiles"]["fused_mttkrp"]["block_i"] == 64
    assert c2.keys() == ["k"]
    # memory-only caches never touch disk
    mem = TuningCache()
    mem.put("m", {"x": 1})
    assert mem.path is None and mem.get("m") == {"x": 1}


def test_problem_key_separates_backend_shape_dtype_devices():
    base = Problem(shape=SHAPE, rank=RANK)
    keys = {
        problem_key(base),
        problem_key(Problem(shape=SHAPE, rank=RANK, dtype=jnp.bfloat16)),
        problem_key(Problem(shape=SHAPE, rank=RANK + 1)),
        problem_key(Problem(shape=(8, 6, 8), rank=RANK)),
        problem_key(
            Problem(
                shape=SHAPE, rank=RANK, mode_axes={0: "d"}, axis_sizes={"d": 2}
            )
        ),
        problem_key(base, backend="tpu"),
    }
    assert len(keys) == 6  # every dimension of the key separates entries
    # a cache entry under a different dtype must not leak into lookups
    cache = TuningCache()
    cache.put(
        problem_key(Problem(shape=SHAPE, rank=RANK, dtype=jnp.bfloat16)),
        {"nodes": [{"key": "x", "measured_s": 1.0}]},
    )
    assert lookup_measurements(base, cache) is None


def test_lookup_resolves_entry_fields(tuned_cache):
    path, cache, entry = tuned_cache
    problem = Problem(shape=SHAPE, rank=RANK)
    m = lookup_measurements(problem, cache)
    assert m is not None
    assert set(m.tiles) == {"fused_mttkrp", "matrix_free", "multi_ttv"}
    assert set(m.kernel_tiles("fused_mttkrp")) == {
        "block_i", "block_b", "block_batch",
    }
    assert set(m.kernel_tiles("matrix_free")) == {
        "block_i", "block_r", "block_batch",
    }
    # every stored node row resolves through the node_s map
    assert len(m.node_s) == len(entry["nodes"]) > 0
    # and the same measurements come back through a fresh disk read
    m2 = lookup_measurements(problem, TuningCache(path))
    assert dict(m2.node_s) == dict(m.node_s)


# ---------------------------------------------------------- tuned numerics
def test_tuned_tiles_numerics_identical_to_defaults(tuned_cache):
    """Tile sizes change only the blocking, never the math: tuned configs
    must reproduce the default-tile results at HIGHEST matmul precision."""
    _, cache, entry = tuned_cache
    x, factors = _problem_arrays()
    tiles = entry["tiles"]["fused_mttkrp"]
    for n in range(len(SHAPE)):
        tuned = np.asarray(
            ops.fused_mttkrp(
                x, factors, n, block_i=tiles["block_i"], block_b=tiles["block_b"]
            )
        )
        default = np.asarray(ops.fused_mttkrp(x, factors, n))
        np.testing.assert_allclose(tuned, default, rtol=1e-6, atol=1e-6)
        np.testing.assert_allclose(
            tuned, np.asarray(ref.fused_mttkrp_ref(x, factors, n)),
            rtol=1e-4, atol=1e-4,
        )
    # multi-TTV tile likewise
    bi = entry["tiles"]["multi_ttv"]["block_i"]
    t = jax.random.normal(jax.random.PRNGKey(5), (6, 32, 4))
    w = jax.random.normal(jax.random.PRNGKey(6), (6, 4))
    np.testing.assert_allclose(
        np.asarray(ops.multi_ttv(t, w, block_i=bi)),
        np.asarray(ops.multi_ttv(t, w)),
        rtol=1e-6, atol=1e-6,
    )


def test_planned_tiles_execute_through_the_engine(tuned_cache):
    """A tuned plan that picked the fused kernel carries its tiles and still
    produces reference ALS iterates through cp_als."""
    _, cache, _ = tuned_cache
    x, _ = _problem_arrays()
    plan = plan_sweep(
        Problem.from_tensor(x, RANK), strategy="autotune", tuning_cache=cache
    )
    st = cp_als(x, plan, n_iters=3, track_fit=False, seed=2)
    ref_plan = plan_sweep(Problem.from_tensor(x, RANK), schedule="flat")
    st_ref = cp_als(x, ref_plan, n_iters=3, track_fit=False, seed=2)
    for a, b in zip(st.factors, st_ref.factors):
        np.testing.assert_allclose(
            np.asarray(a), np.asarray(b), rtol=5e-3, atol=1e-3
        )


# ------------------------------------------------------- planner semantics
def test_autotune_falls_back_to_analytic_on_empty_cache():
    """CI default: no measurements -> autotune IS auto (plus the strategy
    stamp), with no measured_s anywhere."""
    problem = Problem(shape=(8, 6, 4, 4), rank=3)
    auto = plan_sweep(problem)
    cold = plan_sweep(problem, strategy="autotune", tuning_cache=TuningCache())
    assert cold.strategy == "autotune"
    assert cold.resolved_schedule.name == auto.resolved_schedule.name
    assert cold.executor == auto.executor
    assert [n.algorithm for n in cold.nodes] == [n.algorithm for n in auto.nodes]
    assert all(n.cost.measured_s is None for n in cold.nodes)
    assert all(n.tiles is None for n in cold.nodes)
    for got, want in zip(cold.nodes, auto.nodes):
        assert got.cost.predicted_s == want.cost.predicted_s
        assert got.cost.expected_s == got.cost.predicted_s


def test_autotune_stamps_measured_node_times(tuned_cache):
    """Acceptance: the autotune plan's describe() carries the hardware
    measurement of every node the tuner covered."""
    path, cache, entry = tuned_cache
    problem = Problem(shape=SHAPE, rank=RANK)
    plan = plan_sweep(problem, strategy="autotune", tuning_cache=cache)
    d = plan.describe()
    assert d["strategy"] == "autotune"
    stamped = [n for n in d["nodes"] if n["measured_s"] is not None]
    assert len(stamped) == len(d["nodes"]) > 0  # full coverage on this problem
    for n in stamped:
        assert n["expected_s"] == n["measured_s"] > 0.0
        assert n["predicted_s"] != n["measured_s"]  # analytic kept alongside
    # the argmin ran over the measurements: the chosen leaf algorithms are
    # the measured-fastest candidates recorded by the tuner
    by_key = {r["key"]: r["measured_s"] for r in entry["nodes"]}
    for np_ in plan.nodes:
        if not (np_.node.from_root and np_.node.is_leaf):
            continue
        mine = by_key[node_key(np_.node, np_.algorithm, plan.executor)]
        topo = node_key(np_.node, np_.algorithm, plan.executor).split("|", 2)[2]
        rivals = [
            s
            for k, s in by_key.items()
            if k.startswith(f"{plan.executor}|") and k.split("|", 2)[2] == topo
        ]
        assert mine == min(rivals)
    # tuned tiles ride the plan when the fused kernel won a leaf
    for np_ in plan.nodes:
        if np_.algorithm == "fused":
            assert np_.tiles == {
                k: entry["tiles"]["fused_mttkrp"][k]
                for k in ("block_i", "block_b", "block_batch")
                if k in entry["tiles"]["fused_mttkrp"]
            }


def test_autotune_recalibrates_serial_fractions_from_cache():
    """Cached serial_fractions flow into the plan (explicit ones win)."""
    problem = Problem(
        shape=(8, 16, 16), rank=5,
        mode_axes={0: "data"}, axis_sizes={"data": 2},
    )
    cache = TuningCache()
    cache.put(
        problem_key(problem),
        {"nodes": [], "tiles": {}, "serial_fractions": {"sharded": 1.0, "overlapping": 0.5}},
    )
    plan = plan_sweep(problem, strategy="autotune", tuning_cache=cache)
    assert dict(plan.serial_fractions) == {"sharded": 1.0, "overlapping": 0.5}
    forced = plan_sweep(
        problem, strategy="autotune", tuning_cache=cache,
        serial_fractions={"overlapping": 0.25},
    )
    assert dict(forced.serial_fractions) == {"overlapping": 0.25}


def test_cache_entries_of_a_removed_executor_are_ignored(tmp_path):
    """A cache file written while an int8 "compressed" executor existed
    still loads, and plans exactly as the same file without those rows:
    keys of an executor the planner no longer has are never looked up."""
    from repro.plan import EXECUTORS, enumerate_schedules

    problem = Problem(
        shape=(8, 16, 16), rank=5, mode_axes={0: "data"}, axis_sizes={"data": 2},
    )
    rows = []
    for kind in ("sharded", "overlapping"):
        for sched in enumerate_schedules(problem):
            plan = plan_sweep(problem, schedule=sched, executor=kind)
            for node in sched.walk():
                alg = plan.node_plan(node.id).algorithm
                rows.append({"key": node_key(node, alg, kind), "measured_s": 1e-3})
    stale = [
        dict(r, key="compressed|" + r["key"].split("|", 1)[1], measured_s=1e-6)
        for r in rows
    ]
    plans = []
    for name, nodes in (("exact", rows), ("stale", rows + stale)):
        path = tmp_path / f"{name}.json"
        TuningCache(path).put(problem_key(problem), {"nodes": nodes, "tiles": {}})
        cache = TuningCache(path)  # loaded back from disk
        assert len(cache.get(problem_key(problem))["nodes"]) == len(nodes)
        plans.append(plan_sweep(problem, strategy="autotune", tuning_cache=cache))
    assert plans[1].executor in EXECUTORS
    assert plans[1].describe() == plans[0].describe()


# ------------------------------------------------- sync-free chunked driver
def _planted(shape=(10, 8, 6), rank=2, seed=4):
    planted = random_factors(jax.random.PRNGKey(seed), shape, rank)
    return cp_full(None, planted), rank


def test_sweeps_per_sync_bitwise_identical_iterates():
    """Acceptance: k sweeps per dispatch reproduce the per-sweep iterates
    bitwise -- factors, weights and fit -- for even and ragged chunkings."""
    x, rank = _planted()
    plan = plan_sweep(Problem.from_tensor(x, rank))
    base = cp_als(x, plan, n_iters=6, track_fit=False, seed=7)
    for k in (2, 3, 4):  # 4 exercises the ragged 4+2 remainder chunk
        chunked = cp_als(
            x, plan, n_iters=6, track_fit=False, seed=7, sweeps_per_sync=k
        )
        assert chunked.it == base.it == 6
        for a, b in zip(base.factors, chunked.factors):
            np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
        np.testing.assert_array_equal(
            np.asarray(base.weights), np.asarray(chunked.weights)
        )
        assert float(base.fit) == float(chunked.fit)


def test_sweeps_per_sync_one_host_sync_per_chunk(monkeypatch):
    """Acceptance: the driver blocks on the host exactly once per chunk of
    k sweeps (counted at the module's single sync point)."""
    import repro.plan.sweep as sweeplib

    x, rank = _planted()
    plan = plan_sweep(Problem.from_tensor(x, rank))
    counts = {"n": 0}
    real = sweeplib._read_fits

    def counting(fits):
        counts["n"] += 1
        return real(fits)

    monkeypatch.setattr(sweeplib, "_read_fits", counting)
    cp_als(x, plan, n_iters=6, track_fit=False, seed=7)
    assert counts["n"] == 6  # k=1: one sync per sweep
    counts["n"] = 0
    cp_als(x, plan, n_iters=6, track_fit=False, seed=7, sweeps_per_sync=3)
    assert counts["n"] == 2  # two chunks of 3
    counts["n"] = 0
    cp_als(x, plan, n_iters=6, track_fit=False, seed=7, sweeps_per_sync=4)
    assert counts["n"] == 2  # ragged 4 + 2
    with pytest.raises(ValueError, match="sweeps_per_sync"):
        cp_als(x, plan, sweeps_per_sync=0)


def test_sweeps_per_sync_callback_and_convergence():
    """The callback fires once per executed sweep with in-order fits, and
    convergence still stops the loop (at a chunk boundary)."""
    x, rank = _planted()
    plan = plan_sweep(Problem.from_tensor(x, rank))
    fits1, fits3 = [], []
    st1 = cp_als(x, plan, n_iters=40, tol=1e-9, seed=5,
                 callback=lambda it, fit, dt: fits1.append((it, fit)))
    st3 = cp_als(x, plan, n_iters=40, tol=1e-9, seed=5, sweeps_per_sync=3,
                 callback=lambda it, fit, dt: fits3.append((it, fit)))
    assert len(fits1) == st1.it and len(fits3) == st3.it
    assert [it for it, _ in fits3] == list(range(st3.it))
    # chunked runs stop at the chunk containing the k=1 stopping sweep
    assert st1.it <= st3.it <= st1.it + 2
    assert float(st3.fit) > 0.99
    # identical per-sweep fits wherever both executed
    for (i1, f1), (i3, f3) in zip(fits1, fits3):
        assert i1 == i3 and f1 == f3


# ------------------------------------------------------------- gram carry
def test_grams_carried_across_sweeps_match_recompute():
    """SweepState.grams threading is exact: a sweep fed the previous sweep's
    Grams produces bitwise the state of one fed nothing (which recomputes),
    and the emitted Grams always equal grams(out.factors)."""
    from repro.plan.sweep import grams
    from repro.core import tensor_norm
    from repro.plan import LocalExecutor, SweepState, als_sweep

    x, factors = _problem_arrays(seed=9)
    problem = Problem.from_tensor(x, RANK)
    plan = plan_sweep(problem)
    w = jnp.ones((RANK,), x.dtype)
    state = SweepState(
        x=x, factors=list(factors), weights=w,
        norm_x=tensor_norm(x), it=jnp.asarray(0),
    )
    out1 = als_sweep(problem, plan, LocalExecutor(), state)
    assert out1.grams is not None
    for g, u in zip(out1.grams, out1.factors):
        np.testing.assert_array_equal(np.asarray(g), np.asarray(u.T @ u))
    # second sweep: carried grams vs. recompute-from-factors
    carried = als_sweep(problem, plan, LocalExecutor(), out1)
    recomputed = als_sweep(
        problem, plan, LocalExecutor(),
        SweepState(
            x=x, factors=list(out1.factors), weights=out1.weights,
            norm_x=state.norm_x, it=jnp.asarray(1),
        ),
    )
    for a, b in zip(carried.factors, recomputed.factors):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
    np.testing.assert_array_equal(
        np.asarray(carried.fit), np.asarray(recomputed.fit)
    )


# ------------------------------------------------------------ jitted 2step
def test_mttkrp_2step_kernel_jitted_and_tile_threaded():
    """The 2-step kernel entry point is jit'd (static mode/tile/interpret)
    and its multi-TTV tile is tunable without changing results."""
    assert hasattr(ops.mttkrp_2step_kernel, "lower")  # a jit-wrapped callable
    x, factors = _problem_arrays(shape=(9, 14, 11), seed=11, rank=5)
    for n in range(3):
        want = np.asarray(ref.fused_mttkrp_ref(x, factors, n))
        got = np.asarray(ops.mttkrp_2step_kernel(x, factors, n))
        np.testing.assert_allclose(got, want, rtol=2e-4, atol=2e-4)
        tiled = np.asarray(ops.mttkrp_2step_kernel(x, factors, n, block_i=64))
        np.testing.assert_allclose(tiled, got, rtol=1e-6, atol=1e-6)
