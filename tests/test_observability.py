"""What cp_als leaves for a profiler and a reader: named scopes on the
sweep's device ops, host spans around each step of the host loop, and
CPState.host_syncs."""

import glob
import re

import jax
import jax.numpy as jnp
import pytest

import repro.plan.sweep as sweeplib
from repro.core import cp_full, random_factors, random_tensor
from repro.plan import CPState
from repro.plan.sweep import grams
from repro.plan import Problem, cp_als, plan_sweep

SPANS = ("cp_als.init", "cp_als.dispatch", "cp_als.wait", "cp_als.check")


def _planted(shape=(8, 7, 6, 5), rank=3, seed=4):
    return cp_full(None, random_factors(jax.random.PRNGKey(seed), shape, rank)), rank


def _chunk_op_names(schedule):
    """The op names in the compiled chunk of a cp_als run on ``schedule``."""
    x, rank = _planted()
    plan = plan_sweep(Problem.from_tensor(x, rank), schedule=schedule)
    cache = {}
    st = cp_als(x, plan, n_iters=1, dispatch_cache=cache, dispatch_key=0)
    fs = list(st.factors)
    lowered = cache[0].lower(x, jnp.float32(1.0), jnp.asarray(0), fs, st.weights, grams(fs),
                             None, length=1)
    return plan, re.findall(r'op_name="([^"]+)"', lowered.compile().as_text())


@pytest.mark.parametrize("schedule", [None, "flat"])
def test_chunk_ops_carry_the_sweep_scopes(schedule):
    """Every schedule node's contraction runs under ``mttkrp.node<id>``,
    each mode's update under ``update.mode<n>`` and the fit under ``fit``;
    nothing of the sweep carries ``init``."""
    plan, names = _chunk_op_names(schedule)
    for node in plan.resolved_schedule.walk():
        assert any(re.search(rf"/mttkrp\.node{node.id}/(.*/)?dot_general", n) for n in names), node
    for n in range(plan.problem.ndim):
        assert any(f"/update.mode{n}/" in name for name in names), n
    assert any("/fit/" in name for name in names)
    assert not any("/init/" in name for name in names)
    # a scope is a whole path component: node ids never run together
    scopes = {p for n in names for p in n.split("/") if re.fullmatch(r"mttkrp\.node\d+", p)}
    assert scopes == {f"mttkrp.node{node.id}" for node in plan.resolved_schedule.walk()}


def test_cp_als_spans_in_the_host_trace(tmp_path):
    """Under jax.profiler the host plane holds one ``cp_als.init`` span per
    solve and one dispatch, wait and check span per chunk, in that order."""
    from jax.profiler import ProfileData

    x, rank = _planted()
    plan = plan_sweep(Problem.from_tensor(x, rank))
    cache = {}
    cp_als(x, plan, n_iters=2, dispatch_cache=cache, dispatch_key=0)
    with jax.profiler.trace(str(tmp_path)):
        st = cp_als(x, plan, n_iters=5, tol=0.0, sweeps_per_sync=2,
                    dispatch_cache=cache, dispatch_key=0)
    (path,) = glob.glob(str(tmp_path / "plugins" / "profile" / "*" / "*.xplane.pb"))
    events = sorted(
        (e.start_ns, e.end_ns, e.name)
        for p in ProfileData.from_file(path).planes if p.name == "/host:CPU"
        for line in p.lines for e in line.events if e.name in SPANS
    )
    names = [n for _, _, n in events]
    assert st.it == 5
    assert names == ["cp_als.init"] + ["cp_als.dispatch", "cp_als.wait", "cp_als.check"] * 3
    assert all(a[1] <= b[0] for a, b in zip(events, events[1:]))  # one after another


@pytest.fixture
def waits(monkeypatch):
    """Counts cp_als's waits at its one wait point."""
    counts = {"n": 0}
    real = sweeplib._read_fits

    def counting(fits):
        counts["n"] += 1
        return real(fits)

    monkeypatch.setattr(sweeplib, "_read_fits", counting)
    return counts


@pytest.mark.parametrize("k, n_waits", [(1, 6), (3, 2), (4, 2)])
def test_host_syncs_are_waits_plus_scalar_reads(waits, k, n_waits):
    """Unbatched: one wait per chunk, the read of its fits, and nothing
    else: the convergence test runs on that host copy."""
    x, rank = _planted()
    plan = plan_sweep(Problem.from_tensor(x, rank))
    st = cp_als(x, plan, n_iters=6, track_fit=False, seed=7, sweeps_per_sync=k)
    assert waits["n"] == n_waits
    assert st.host_syncs == n_waits


def test_host_syncs_batched_count_each_read_made(waits):
    """Batched: one read a chunk whether or not the callback's mean and
    the convergence test are asked for; both run on the host copy."""
    B, shape, rank = 4, (6, 5, 4), 2
    x = random_tensor(jax.random.PRNGKey(0), (B,) + shape)
    init = random_factors(jax.random.PRNGKey(1), shape, rank, batch=B)
    plan = plan_sweep(Problem.from_tensor(x, rank, batch=B))
    st = cp_als(x, plan, n_iters=6, track_fit=False, init_factors=init, sweeps_per_sync=3)
    assert (waits["n"], st.host_syncs) == (2, 2)
    waits["n"] = 0
    st = cp_als(x, plan, n_iters=6, tol=0.0, init_factors=init, sweeps_per_sync=3,
                callback=lambda it, fit, dt: None)
    assert (waits["n"], st.host_syncs) == (2, 2)


def test_host_syncs_count_the_pp_read(waits):
    """A pairwise-perturbation run reads its exact-sweep count once more,
    after its chunks' reads."""
    x, rank = _planted()
    plan = plan_sweep(Problem.from_tensor(x, rank, pp_tol=0.05))
    st = cp_als(x, plan, n_iters=4, track_fit=False, seed=3)
    assert st.pp_exact_sweeps is not None
    assert st.host_syncs == waits["n"] + 1


def test_cp_state_host_syncs_defaults_to_zero():
    st = CPState(factors=[], weights=jnp.ones(2), fit=jnp.float32(0.5))
    assert st.host_syncs == 0 and st.pp_exact_sweeps is None


SERVE_SPANS = ("serve.take", "serve.pack", "serve.dispatch", "serve.unpack")


def test_serve_spans_in_the_host_trace(tmp_path):
    """Under jax.profiler each CPService.step opens take, pack, dispatch
    and unpack one after another, with cp_als's own spans inside dispatch."""
    from jax.profiler import ProfileData

    from repro.serve import CPService

    svc = CPService(batch_size=2, n_iters=3)
    xs = [random_tensor(jax.random.PRNGKey(s), (6, 5, 4)) for s in range(2)]
    for x in xs:
        svc.submit(x, 2)
    svc.flush()  # compiles the signature's dispatch outside the trace
    for x in xs:
        svc.submit(x, 2)
    with jax.profiler.trace(str(tmp_path)):
        done = svc.step()
    assert len(done) == 2
    (path,) = glob.glob(str(tmp_path / "plugins" / "profile" / "*" / "*.xplane.pb"))
    events = sorted(
        (e.start_ns, e.end_ns, e.name)
        for p in ProfileData.from_file(path).planes if p.name == "/host:CPU"
        for line in p.lines for e in line.events if e.name in SERVE_SPANS + SPANS
    )
    serve = [ev for ev in events if ev[2] in SERVE_SPANS]
    assert [n for _, _, n in serve] == list(SERVE_SPANS)
    assert all(a[1] <= b[0] for a, b in zip(serve, serve[1:]))  # one after another
    (_, d0, d1), = [(n, s, e) for s, e, n in serve if n == "serve.dispatch"]
    inner = [n for s, e, n in events if n in SPANS]
    assert inner[0] == "cp_als.init" and "cp_als.wait" in inner
    assert all(d0 <= s and e <= d1 for s, e, n in events if n in SPANS)


def test_cp_als_takes_its_first_grams_at_the_sweep_precision(monkeypatch):
    """The Grams cp_als computes before its first sweep, which that sweep's
    updates solve with, are contracted at SWEEP_PRECISION like every
    contraction inside the sweep, not at the backend's default."""
    seen = []
    real = sweeplib.grams

    def recording(factors):
        seen.append(jax.config.jax_default_matmul_precision)
        return real(factors)

    monkeypatch.setattr(sweeplib, "grams", recording)
    x, rank = _planted()
    cp_als(x, plan_sweep(Problem.from_tensor(x, rank)), n_iters=2, seed=1)
    assert seen == [sweeplib.SWEEP_PRECISION]
