"""cp_als's convergence check on the host copy of each chunk's fits.

Each chunk's per-sweep fits are read into host memory once, and the stop,
the callback and ``CPState.fit`` come from that copy and the chunk's own
outputs.  These tests replay the device-side check cp_als made before
(a slice program and a read per sweep) on the same chunk outputs and
require the same decisions, bitwise."""

import math

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import repro.plan.sweep as sweeplib
from repro.core import cp_full, random_factors, random_tensor
from repro.plan import LocalExecutor, Problem, cp_als, plan_sweep

N_ITERS = 12
B, BATCH_SHAPE, BATCH_RANK = 3, (6, 5, 4), 2


def _unbatched():
    x = cp_full(None, random_factors(jax.random.PRNGKey(4), (8, 7, 6, 5), 3))
    return x + 0.05 * random_tensor(jax.random.PRNGKey(9), x.shape), 3


def _batched():
    true = random_factors(jax.random.PRNGKey(15), BATCH_SHAPE, BATCH_RANK, batch=B)
    x = jnp.einsum("bic,bjc,bkc->bijk", *true)
    x = x + 0.3 * random_tensor(jax.random.PRNGKey(17), (B,) + BATCH_SHAPE)
    init = random_factors(jax.random.PRNGKey(16), BATCH_SHAPE, BATCH_RANK, batch=B)
    return x, init


# kind -> (tol whose first converged sweep is 8, 11 and 5 of N_ITERS: inside
# a chunk of 3, never at its end)
STOP_TOL = {"unbatched": 3e-4, "batched": 1e-3, "pp": 1e-3}
_CHUNKS = {}


def _setup(kind):
    """``(x, plan, run_kwargs)`` of one test problem, and its one jitted
    chunk program, shared by every case of the kind."""
    if kind == "batched":
        x, init = _batched()
        plan = plan_sweep(Problem.from_tensor(x, BATCH_RANK, batch=B))
        kw = dict(init_factors=init)
    else:
        x, rank = _unbatched()
        pp_tol = 0.05 if kind == "pp" else 0.0
        plan = plan_sweep(Problem.from_tensor(x, rank, pp_tol=pp_tol))
        kw = dict(init_factors=random_factors(jax.random.PRNGKey(2), x.shape, rank))
    if kind not in _CHUNKS:
        _CHUNKS[kind] = sweeplib.sweep_chunk(plan, LocalExecutor())
    return x, plan, kw, _CHUNKS[kind]


class _Recorder:
    """Stands in for the chunk program in cp_als's dispatch cache and keeps
    what each dispatch was given and gave back."""

    def __init__(self, chunk):
        self.chunk, self.calls = chunk, []

    def __call__(self, *args, length):
        out = self.chunk(*args, length=length)
        it0 = args[2]
        self.calls.append({
            "it0": (int(it0), it0.dtype, it0.weak_type),
            "length": length,
            "fits": np.asarray(out[6]),
        })
        return out


def _parent_check(calls, *, batched, tol, track_fit):
    """The convergence check as cp_als made it on the device, one slice
    and one read a sweep, replayed on the recorded chunks' fits.  Returns
    the sweeps run, whether it stopped, the last fit and the callback's
    ``(it, fit)`` values."""
    fit_prev, it, seen, done, fit = -math.inf, 0, [], False, None
    for c in calls:
        if done:
            raise AssertionError("a chunk was dispatched after the stop")
        fits, length = jnp.asarray(c["fits"]), c["length"]
        for j in range(length):
            if batched:
                f = fits[j]
                seen.append((it + j, float(jnp.mean(f))))
                if track_fit and bool(jnp.max(jnp.abs(f - fit_prev)) < tol):
                    done = True
                fit_prev = f
            else:
                f = float(fits[j])
                seen.append((it + j, f))
                if track_fit and abs(f - fit_prev) < tol:
                    done = True
                fit_prev = f
        it += length
        fit = fits[length - 1]
    return it, done, fit, seen


CASES = [
    pytest.param(kind, k, tol, track, id=f"{kind}-k{k}-{name}")
    for kind in ("unbatched", "batched")
    for k in (1, 3, N_ITERS)
    for name, tol, track in (("stop", None, True), ("tol0", 0.0, True),
                             ("untracked", None, False))
] + [
    pytest.param("pp", k, None, True, id=f"pp-k{k}-stop") for k in (1, 3)
]


@pytest.mark.parametrize("kind, k, tol, track_fit", CASES)
def test_host_check_decides_as_the_device_check(kind, k, tol, track_fit):
    """Same chunks dispatched, same stop sweep, same ``CPState.fit`` and
    factors, bitwise, and the callback sees every sweep with its fit."""
    tol = STOP_TOL[kind] if tol is None else tol
    batched = kind == "batched"
    x, plan, kw, chunk = _setup(kind)
    rec = _Recorder(chunk)
    seen = []
    st = cp_als(x, plan, n_iters=N_ITERS, tol=tol, track_fit=track_fit,
                sweeps_per_sync=k, dispatch_cache={0: rec}, dispatch_key=0,
                callback=lambda it, fit, dt: seen.append((it, fit)), **kw)

    it_ref, done_ref, fit_ref, seen_ref = _parent_check(
        rec.calls, batched=batched, tol=tol, track_fit=track_fit)
    # the parent would have dispatched exactly these chunks and no more
    assert done_ref or it_ref == N_ITERS
    assert st.it == it_ref
    if kind != "pp" and track_fit and tol > 0:
        stop = {"unbatched": 8, "batched": 11}[kind]  # the first converged sweep
        assert st.it == min(-(-stop // k) * k, N_ITERS)  # the end of its chunk
    # each chunk started at the parent's counter, with its dtype and weak type
    starts = [sum(c["length"] for c in rec.calls[:i]) for i in range(len(rec.calls))]
    ref0 = jnp.asarray(0)
    assert [c["it0"] for c in rec.calls] == [(s, ref0.dtype, True) for s in starts]
    # CPState.fit: the parent's eager fits[length - 1], bitwise
    got = np.asarray(st.fit)
    assert got.dtype == fit_ref.dtype and got.shape == fit_ref.shape
    np.testing.assert_array_equal(got, np.asarray(fit_ref))
    # the callback: every sweep, in order, with the parent's fit
    assert [i for i, _ in seen] == [i for i, _ in seen_ref] == list(range(st.it))
    if batched:
        # the batch mean is now summed on the host, in float32: another
        # order than the device's, and two orders of summing B positive
        # float32 terms differ by at most 2 (B - 1) eps relative
        host_means = [float(f.mean()) for c in rec.calls for f in c["fits"]]
        assert [v for _, v in seen] == host_means
        np.testing.assert_allclose([v for _, v in seen], [v for _, v in seen_ref],
                                   rtol=2 * (B - 1) * np.finfo(np.float32).eps)
    else:
        assert seen == seen_ref
    # the factors: a plain run of exactly st.it sweeps, bitwise
    plain = cp_als(x, plan, n_iters=st.it, track_fit=False,
                   dispatch_cache={0: chunk}, dispatch_key=0, **kw)
    for u, v in zip(st.factors, plain.factors):
        np.testing.assert_array_equal(np.asarray(u), np.asarray(v))
    np.testing.assert_array_equal(np.asarray(st.weights), np.asarray(plain.weights))
    if kind == "pp":
        assert st.pp_exact_sweeps == plain.pp_exact_sweeps


@pytest.mark.parametrize("kind", ["unbatched", "batched"])
def test_host_loop_takes_no_slice_and_sends_no_counter(kind, monkeypatch):
    """Between dispatches the loop slices no device array, sends one
    counter a solve however many chunks it runs, reads each chunk once,
    and feeds each chunk's returned counter back without a recompile."""
    x, plan, kw, _ = _setup(kind)
    cache = {0: sweeplib.sweep_chunk(plan, LocalExecutor())}  # compiles counted below
    cp_als(x, plan, n_iters=2, tol=0.0, dispatch_cache=cache, dispatch_key=0, **kw)

    counts = {"getitem": 0, "asarray": 0, "read": 0}

    def counting(name, real):
        def call(*args, **kwargs):
            counts[name] += 1
            return real(*args, **kwargs)
        return call

    array_type = type(jnp.zeros(()))
    monkeypatch.setattr(array_type, "__getitem__",
                        counting("getitem", array_type.__getitem__))
    monkeypatch.setattr(jnp, "asarray", counting("asarray", jnp.asarray))
    monkeypatch.setattr(sweeplib, "_read_fits", counting("read", sweeplib._read_fits))
    seen = {}
    for n in (2, 6):
        for c in counts:
            counts[c] = 0
        st = cp_als(x, plan, n_iters=n, tol=0.0, dispatch_cache=cache, dispatch_key=0,
                    callback=lambda it, fit, dt: None, **kw)
        assert st.it == n and st.host_syncs == n
        seen[n] = dict(counts)
    assert seen[2] == {"getitem": 0, "asarray": 1, "read": 2}
    assert seen[6] == {"getitem": 0, "asarray": 1, "read": 6}
    assert cache[0]._cache_size() == 1
