"""CP serving engine: signature buckets, padded batches, one compile each.

Covers the serving layer end-to-end: packed-batch results allclose to the
direct per-tensor ``cp_als`` with shared init (mixed-signature stream),
padded partial batches (masked dummies cannot perturb real results), the
one-compile-per-signature guarantee, FIFO + priority scheduling, bounded
queue backpressure, warm-plan (TuningCache) hit counting, and the shared
:mod:`repro.serve.queue` scheduler's ordering rules.
"""

import jax
import numpy as np
import pytest

from repro.core.tensor_ops import random_factors, random_tensor
from repro.launch.mesh import make_mesh
from repro.plan import Problem, cp_als, plan_sweep
from repro.plan.autotune import TuningCache, problem_key
from repro.serve import CPService, QueueFull, RequestQueue

N_DEV = jax.device_count()

RANK = 3
N_ITERS = 5


def _request(shape, seed):
    x = random_tensor(jax.random.PRNGKey(seed), shape)
    init = random_factors(jax.random.PRNGKey(1000 + seed), shape, RANK)
    return x, init


def _direct(x, init):
    """The per-tensor reference: same init, same sweep budget, tol=0."""
    plan = plan_sweep(Problem.from_tensor(x, RANK))
    return cp_als(x, plan, n_iters=N_ITERS, tol=0.0, init_factors=init)


def _assert_matches_direct(fut, x, init):
    res = fut.result()
    ref = _direct(x, init)
    for a, b in zip(res.factors, ref.factors):
        np.testing.assert_allclose(
            np.asarray(a), np.asarray(b), rtol=2e-4, atol=2e-5
        )
    np.testing.assert_allclose(
        np.asarray(res.weights), np.asarray(ref.weights), rtol=2e-4, atol=2e-5
    )
    np.testing.assert_allclose(res.fit, float(ref.fit), rtol=1e-4, atol=1e-5)
    assert res.sweeps == N_ITERS


# ------------------------------------------------------------ service numerics
def test_mixed_signature_stream_matches_per_tensor():
    """Acceptance: a mixed-signature request stream (two shapes interleaved,
    full + partial batches) returns decompositions allclose to the direct
    per-tensor cp_als with the same init, with exactly one compile per
    signature."""
    svc = CPService(batch_size=4, n_iters=N_ITERS)
    shapes = [(8, 9, 10), (6, 6, 6)]
    reqs = []
    for i in range(10):  # 5 per signature: one full batch + one padded each
        x, init = _request(shapes[i % 2], seed=i)
        reqs.append((x, init, svc.submit(x, RANK, init_factors=init)))
    done = svc.flush()
    assert len(done) == len(reqs) and all(f.done() for _, _, f in reqs)
    for x, init, fut in reqs:
        _assert_matches_direct(fut, x, init)
    stats = svc.stats()
    assert stats["signatures"] == 2
    assert stats["compiles"] == 2  # exactly one compiled dispatch per signature
    assert stats["batches"] == 4
    assert stats["completed"] == 10 and stats["queue_depth"] == 0


def test_padded_partial_batch_is_exact():
    """Masked dummy slots (real requests cycled into the padding) cannot
    perturb the real problems: a 3-request batch in an 8-slot dispatch
    matches the per-tensor reference exactly as a full batch would."""
    svc = CPService(batch_size=8, n_iters=N_ITERS)
    reqs = [_request((7, 6, 5), seed=20 + i) for i in range(3)]
    futs = [svc.submit(x, RANK, init_factors=init) for x, init in reqs]
    svc.flush()
    for (x, init), fut in zip(reqs, futs):
        _assert_matches_direct(fut, x, init)
    stats = svc.stats()
    assert stats["padded_slots"] == 5
    assert stats["batch_occupancy"] == pytest.approx(3 / 8)


def test_one_compile_per_signature_across_flushes():
    """Re-submitting a served signature reuses its compiled dispatch: the
    compile counter stays put across flushes and only a genuinely new
    signature bumps it."""
    svc = CPService(batch_size=2, n_iters=N_ITERS)
    for round_ in range(3):
        x, init = _request((6, 5, 4), seed=30 + round_)
        svc.submit(x, RANK, init_factors=init)
        svc.flush()
        assert svc.stats()["compiles"] == 1
    x, _ = _request((5, 5, 5), seed=40)  # new shape -> new signature
    svc.submit(x, RANK)
    svc.flush()
    assert svc.stats()["compiles"] == 2
    # update options are part of the signature: a different sweep budget
    # must NOT share the tuned dispatch (chunk length is compiled in)
    x, _ = _request((5, 5, 5), seed=41)
    svc.submit(x, RANK, n_iters=N_ITERS + 1)
    svc.flush()
    assert svc.stats()["signatures"] == 3 and svc.stats()["compiles"] == 3


def test_pp_requests_bucket_separately():
    """A pairwise-perturbation request cannot share a bucket (or a compiled
    dispatch) with the exact request for the same tensor: ``pp_tol`` is part
    of ``Problem.signature()``, so it is part of the batch key too."""
    svc = CPService(batch_size=2, n_iters=N_ITERS)
    x, init = _request((6, 5, 4), seed=77)
    sig_exact = svc.signature_of(x, RANK)
    sig_pp = svc.signature_of(x, RANK, pp_tol=0.25)
    assert "|pp" not in sig_exact and "|pp0.25" in sig_pp

    svc.submit(x, RANK, init_factors=init)
    svc.submit(x, RANK, init_factors=init, pp_tol=0.25)
    svc.flush()
    stats = svc.stats()
    assert stats["signatures"] == 2 and stats["compiles"] == 2

    # a repeat exact submit reuses the exact bucket's dispatch
    svc.submit(x, RANK, init_factors=init)
    svc.flush()
    assert svc.stats()["compiles"] == 2


# ---------------------------------------------------------------- scheduling
def test_fifo_within_signature_and_priority_across():
    """step() serves the bucket owning the most urgent request; within a
    bucket, higher priority first and FIFO (submission order) on ties."""
    svc = CPService(batch_size=2, n_iters=2)
    xa, _ = _request((6, 6, 6), seed=50)
    xb, _ = _request((7, 7, 7), seed=51)
    fa1 = svc.submit(xa, RANK)                  # bucket A, prio 0
    fb1 = svc.submit(xb, RANK, priority=5)      # bucket B, prio 5
    fa2 = svc.submit(xa, RANK, priority=3)      # bucket A, prio 3
    fa3 = svc.submit(xa, RANK)                  # bucket A, prio 0

    first = svc.step()  # B owns the globally most urgent request
    assert [f.rid for f in first] == [fb1.rid]
    second = svc.step()  # A: prio 3 first, then the oldest prio-0 request
    assert [f.rid for f in second] == [fa2.rid, fa1.rid]
    third = svc.step()
    assert [f.rid for f in third] == [fa3.rid]
    assert svc.step() == []


def test_request_queue_ordering_and_buckets():
    """The shared scheduler: priority-descending, FIFO within, per-key
    buckets, next_key() = bucket of the globally most urgent request."""
    q = RequestQueue()
    a0 = q.submit("a0", key="A")
    b0 = q.submit("b0", key="B", priority=2)
    a1 = q.submit("a1", key="A", priority=2)
    a2 = q.submit("a2", key="A")
    assert len(q) == q.depth == 4
    assert q.next_key() == "B"  # b0 is the oldest of the top-priority pair
    assert q.keys() == ["B", "A"]
    assert [r.payload for r in q] == ["b0", "a1", "a0", "a2"]
    assert q.take(10, "A") == [a1, a0, a2]
    assert q.take(10) == [b0]
    assert q.take(10) == [] and q.next_key() is None
    with pytest.raises(ValueError, match="batch_size"):
        q.take(0)


def test_bounded_queue_backpressure():
    """A full queue rejects submission with QueueFull (counted), and
    capacity frees up after a flush."""
    svc = CPService(batch_size=2, n_iters=2, max_pending=2)
    x, _ = _request((6, 6, 6), seed=60)
    svc.submit(x, RANK)
    svc.submit(x, RANK)
    with pytest.raises(QueueFull, match="max_pending=2"):
        svc.submit(x, RANK)
    assert svc.stats()["rejected"] == 1
    assert svc.stats()["queue_depth"] == 2
    svc.flush()
    svc.submit(x, RANK)  # drained: accepted again
    assert svc.stats()["queue_depth"] == 1
    with pytest.raises(ValueError, match="max_pending"):
        RequestQueue(0)


# ----------------------------------------------------------------- warm plans
def test_warm_plan_hits_from_tuning_cache(tmp_path):
    """The persistent TuningCache doubles as the warm-plan store keyed by
    the same signature: a signature tuned on disk counts a warm_plan_hit,
    an untuned one plans analytically (no hit)."""
    shape, B = (6, 5, 4), 2
    cache = TuningCache(tmp_path / "tuning.json")
    tuned = Problem(shape=shape, rank=RANK, batch=B)
    cache.put(
        problem_key(tuned),
        {"nodes": [], "tiles": {}, "serial_fractions": {}},
    )
    svc = CPService(batch_size=B, n_iters=2, strategy="autotune",
                    tuning_cache=TuningCache(tmp_path / "tuning.json"))
    x, _ = _request(shape, seed=70)
    svc.submit(x, RANK)
    svc.flush()
    assert svc.stats()["warm_plan_hits"] == 1
    y, _ = _request((8, 8, 8), seed=71)  # never tuned
    svc.submit(y, RANK)
    svc.flush()
    stats = svc.stats()
    assert stats["signatures"] == 2 and stats["warm_plan_hits"] == 1


def test_batched_tune_makes_the_service_signature_warm():
    """``tune(..., batch=B)`` measures the batched problem a
    ``CPService(batch_size=B)`` dispatches and stores it under that
    signature, so the service plans it warm; results still match the
    direct per-tensor run."""
    from repro.plan.autotune import tune

    shape, B = (6, 5, 4), 2
    reqs = [_request(shape, seed=80 + i) for i in range(B)]
    cache = TuningCache()
    tune(jax.numpy.stack([x for x, _ in reqs]), RANK, batch=B, cache=cache,
         budget_ms=1, reps=1)
    assert cache.get(problem_key(Problem(shape=shape, rank=RANK, batch=B)))
    svc = CPService(batch_size=B, n_iters=N_ITERS, tuning_cache=cache)
    futs = [svc.submit(x, RANK, init_factors=init) for x, init in reqs]
    svc.flush()
    assert svc.stats()["warm_plan_hits"] == 1
    for fut, (x, init) in zip(futs, reqs):
        _assert_matches_direct(fut, x, init)


def test_service_signature_is_the_canonical_problem_signature():
    """The batch bucket key extends Problem.signature()/problem_key (the
    tuning-cache key) with the update options -- one key construction."""
    svc = CPService(batch_size=4, n_iters=7, tol=0.0)
    x = random_tensor(jax.random.PRNGKey(0), (6, 5, 4))
    sig = svc.signature_of(x, RANK)
    base = problem_key(Problem(shape=(6, 5, 4), rank=RANK, batch=4))
    assert sig == f"{base}|i7|t0"
    assert svc.signature_of(x, RANK, n_iters=9) == f"{base}|i9|t0"


# ------------------------------------------------------------- sharded serving
@pytest.mark.skipif(N_DEV < 2, reason="needs a multi-device runtime")
def test_batch_parallel_service_matches_local():
    """A mesh-backed service (batch axis sharded over every device, zero
    collective traffic) returns the local service's results."""
    mesh = make_mesh((N_DEV,), ("b",))
    svc_sh = CPService(batch_size=N_DEV, n_iters=N_ITERS, mesh=mesh)
    svc_lo = CPService(batch_size=N_DEV, n_iters=N_ITERS)
    reqs = [_request((8, 8, 6), seed=80 + i) for i in range(N_DEV)]
    futs_sh = [svc_sh.submit(x, RANK, init_factors=init) for x, init in reqs]
    futs_lo = [svc_lo.submit(x, RANK, init_factors=init) for x, init in reqs]
    svc_sh.flush()
    svc_lo.flush()
    for fs, fl in zip(futs_sh, futs_lo):
        a, b = fs.result(), fl.result()
        for ua, ub in zip(a.factors, b.factors):
            np.testing.assert_allclose(
                np.asarray(ua), np.asarray(ub), rtol=2e-4, atol=2e-5
            )
        np.testing.assert_allclose(a.fit, b.fit, rtol=1e-4, atol=1e-5)
    assert svc_sh.stats()["compiles"] == 1


def test_submit_validation_and_future_protocol():
    """Bad submissions fail loudly; futures refuse to resolve early."""
    svc = CPService(batch_size=2, n_iters=2)
    with pytest.raises(ValueError, match="order"):
        svc.submit(np.zeros((4,)), RANK)
    x = random_tensor(jax.random.PRNGKey(0), (5, 4, 3))
    with pytest.raises(ValueError, match="init_factors"):
        svc.submit(x, RANK, init_factors=[np.zeros((5, RANK))] * 3)
    fut = svc.submit(x, RANK)
    assert not fut.done()
    with pytest.raises(RuntimeError, match="pending"):
        fut.result()
    svc.flush()
    assert fut.done() and fut.result().rid == fut.rid
    with pytest.raises(ValueError, match="batch_size"):
        CPService(batch_size=0)
    if N_DEV > 1:
        with pytest.raises(ValueError, match="divisible"):
            CPService(batch_size=N_DEV + 1, mesh=make_mesh((N_DEV,), ("b",)))
