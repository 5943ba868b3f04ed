"""Property-based tests (hypothesis) on system invariants."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from conftest import given, settings, st  # shared optional-dep shim

from repro.configs import get_config
from repro.launch import mesh as meshlib
from repro.models import build_model, cross_entropy
from repro.models.common import mask_vocab_pad, rms_norm, vocab_padded


@pytest.fixture(scope="module")
def host_mesh():
    with meshlib.use_mesh(meshlib.make_host_mesh(1, 1)) as m:
        yield m


def test_causality_future_tokens_do_not_affect_past(host_mesh):
    """Perturbing token j must leave logits at positions < j unchanged."""
    cfg = get_config("qwen3-8b").reduced()
    model = build_model(cfg)
    params = model.init(jax.random.PRNGKey(0))
    from repro.models import transformer

    tokens = jax.random.randint(jax.random.PRNGKey(1), (1, 12), 0, cfg.vocab, jnp.int32)
    h1, _, _ = transformer.forward(params, cfg, tokens)
    l1 = np.asarray(transformer.lm_logits(params, cfg, h1), np.float32)
    j = 7
    tokens2 = tokens.at[0, j].set((tokens[0, j] + 1) % cfg.vocab)
    h2, _, _ = transformer.forward(params, cfg, tokens2)
    l2 = np.asarray(transformer.lm_logits(params, cfg, h2), np.float32)
    np.testing.assert_allclose(l1[:, :j], l2[:, :j], rtol=1e-5, atol=1e-5)
    assert np.abs(l1[:, j:] - l2[:, j:]).max() > 0  # and the future DID change


def test_causality_ssm(host_mesh):
    cfg = get_config("falcon-mamba-7b").reduced()
    model = build_model(cfg)
    params = model.init(jax.random.PRNGKey(0))
    from repro.models import transformer

    tokens = jax.random.randint(jax.random.PRNGKey(2), (1, 10), 0, cfg.vocab, jnp.int32)
    h1, _, _ = transformer.forward(params, cfg, tokens)
    tokens2 = tokens.at[0, 6].set((tokens[0, 6] + 3) % cfg.vocab)
    h2, _, _ = transformer.forward(params, cfg, tokens2)
    np.testing.assert_allclose(
        np.asarray(h1[:, :6], np.float32), np.asarray(h2[:, :6], np.float32),
        rtol=1e-5, atol=1e-5,
    )


@settings(max_examples=20, deadline=None)
@given(seed=st.integers(0, 2**31 - 1), scale=st.floats(0.1, 100.0))
def test_rmsnorm_scale_invariance(seed, scale):
    x = jax.random.normal(jax.random.PRNGKey(seed), (4, 16)) + 0.1
    w = jnp.ones((16,))
    a = np.asarray(rms_norm(x, w))
    b = np.asarray(rms_norm(x * scale, w))
    np.testing.assert_allclose(a, b, rtol=1e-3, atol=1e-4)


def test_cross_entropy_uniform_and_onehot():
    v = 64
    logits = jnp.zeros((2, 3, v))
    labels = jnp.asarray([[1, 2, 3], [4, 5, 6]], jnp.int32)
    loss, _ = cross_entropy(logits, labels)
    np.testing.assert_allclose(float(loss), np.log(v), rtol=1e-5)
    strong = jax.nn.one_hot(labels, v) * 100.0
    loss2, acc2 = cross_entropy(strong, labels)
    assert float(loss2) < 1e-3 and float(acc2) == 1.0


@settings(max_examples=30, deadline=None)
@given(v=st.integers(1, 200_000))
def test_vocab_padded_properties(v):
    p = vocab_padded(v)
    assert p >= v and p % 128 == 0 and p - v < 128


def test_mask_vocab_pad_blocks_pads():
    logits = jnp.ones((2, 2, 256))
    masked = mask_vocab_pad(logits, 200)
    assert float(masked[..., 199].min()) == 1.0
    assert float(masked[..., 200].max()) <= -1e8


@settings(max_examples=10, deadline=None)
@given(seed=st.integers(0, 2**31 - 1))
def test_mttkrp_scaling_in_factor(seed):
    """MTTKRP is linear in each non-target factor column-wise scale."""
    from repro.core import mttkrp, random_factors, random_tensor

    x = random_tensor(jax.random.PRNGKey(seed), (4, 5, 3))
    factors = random_factors(jax.random.PRNGKey(seed + 1), (4, 5, 3), 4)
    base = np.asarray(mttkrp(x, factors, 1))
    scaled = list(factors)
    scaled[0] = scaled[0] * 2.0
    out = np.asarray(mttkrp(x, scaled, 1))
    np.testing.assert_allclose(out, 2.0 * base, rtol=1e-4, atol=1e-5)


# ------------------------------------------------------- pairwise perturbation
@settings(max_examples=5, deadline=None)
@given(
    shape=st.lists(st.integers(3, 8), min_size=3, max_size=4).map(tuple),
    rank=st.integers(2, 5),
    seed=st.integers(0, 2**31 - 1),
)
def test_pp_tol_zero_bitwise_on_generated_problems(shape, rank, seed):
    """Hypothesis sweep of the exact-fallback invariant: for arbitrary
    shapes/ranks, ``pp_tol=0`` iterates are bitwise equal to exact ALS."""
    from repro.core import random_factors, random_tensor
    from repro.plan import Problem, cp_als, plan_sweep

    x = random_tensor(jax.random.PRNGKey(seed), shape)
    init = random_factors(jax.random.PRNGKey(seed + 1), shape, rank)
    a = cp_als(x, plan_sweep(Problem(shape=shape, rank=rank)),
               n_iters=4, tol=0.0, init_factors=list(init))
    b = cp_als(x, plan_sweep(Problem(shape=shape, rank=rank, pp_tol=0.0)),
               n_iters=4, tol=0.0, init_factors=list(init))
    assert np.array_equal(np.asarray(a.weights), np.asarray(b.weights))
    for fa, fb in zip(a.factors, b.factors):
        assert np.array_equal(np.asarray(fa), np.asarray(fb))


def test_pp_tol_zero_is_bitwise_exact():
    """``pp_tol=0`` is classic exact ALS, bit for bit: the PP state is never
    built, the sweep graph is untouched, and the signature does not change."""
    from repro.core import random_factors, random_tensor
    from repro.plan import Problem, cp_als, plan_sweep

    shape, rank = (8, 7, 6), 4
    x = random_tensor(jax.random.PRNGKey(10), shape)
    init = random_factors(jax.random.PRNGKey(11), shape, rank)

    p_exact = Problem(shape=shape, rank=rank)
    p_zero = Problem(shape=shape, rank=rank, pp_tol=0.0)
    assert p_zero.signature() == p_exact.signature()  # backward-compatible key

    a = cp_als(x, plan_sweep(p_exact), n_iters=8, tol=0.0, init_factors=list(init))
    b = cp_als(x, plan_sweep(p_zero), n_iters=8, tol=0.0, init_factors=list(init))
    assert a.pp_exact_sweeps is None and b.pp_exact_sweeps is None
    assert np.array_equal(np.asarray(a.weights), np.asarray(b.weights))
    for fa, fb in zip(a.factors, b.factors):
        assert np.array_equal(np.asarray(fa), np.asarray(fb))
    assert np.array_equal(np.asarray(a.fit), np.asarray(b.fit))


def test_pp_exact_sweep_cadence():
    """The drift gate controls the exact/approximate cadence at both extremes.
    The pair cache is only rebuilt once an exact sweep's own step settles
    under ``pp_tol``, so a vanishing tolerance never leaves the exact regime
    (every sweep is exact ALS); a huge tolerance materializes after the very
    first sweep and approximates everything after."""
    from repro.core import random_factors, random_tensor
    from repro.plan import Problem, cp_als, plan_sweep

    shape, rank, n_iters = (8, 7, 6), 4, 6
    x = random_tensor(jax.random.PRNGKey(10), shape)
    init = random_factors(jax.random.PRNGKey(11), shape, rank)

    tiny = cp_als(
        x, plan_sweep(Problem(shape=shape, rank=rank, pp_tol=1e-12), strategy="pp"),
        n_iters=n_iters, tol=0.0, init_factors=list(init),
    )
    assert tiny.pp_exact_sweeps == n_iters  # the cache is never rebuilt

    huge = cp_als(
        x, plan_sweep(Problem(shape=shape, rank=rank, pp_tol=1e9), strategy="pp"),
        n_iters=n_iters, tol=0.0, init_factors=list(init),
    )
    assert huge.pp_exact_sweeps == 1


def test_pp_correction_error_is_second_order():
    """The first-order PP approximation of MTTKRP has O(drift^2) error:
    halving the factor perturbation quarters the approximation error."""
    from repro.core import mttkrp, random_factors, random_tensor
    from repro.plan import LocalExecutor, Problem

    shape, rank = (6, 5, 4, 3), 3
    x = random_tensor(jax.random.PRNGKey(20), shape)
    ref = random_factors(jax.random.PRNGKey(21), shape, rank)
    direction = random_factors(jax.random.PRNGKey(22), shape, rank)
    problem = Problem(shape=shape, rank=rank, pp_tol=0.5)
    pairs = {
        k: np.asarray(v, np.float64)
        for k, v in LocalExecutor().pp_pairs(problem, x, ref).items()
    }

    # pairs are stored rank-major: M_{n,m}[c, i_n, i_m]
    def mean_rel_err(eps):
        cur = [r + eps * d for r, d in zip(ref, direction)]
        errs = []
        for n in range(len(shape)):
            m0 = 1 if n == 0 else 0
            if n < m0:
                approx = np.einsum("cab,bc->ac", pairs[(n, m0)], np.asarray(ref[m0]))
            else:
                approx = np.einsum("cab,ac->bc", pairs[(m0, n)], np.asarray(ref[m0]))
            for m in range(len(shape)):
                if m == n:
                    continue
                du = np.asarray(cur[m] - ref[m], np.float64)
                if n < m:
                    approx = approx + np.einsum("cab,bc->ac", pairs[(n, m)], du)
                else:
                    approx = approx + np.einsum("cab,ac->bc", pairs[(m, n)], du)
            exact = np.asarray(mttkrp(x, cur, n), np.float64)
            errs.append(np.linalg.norm(approx - exact) / np.linalg.norm(exact))
        return float(np.mean(errs))

    e1, e2 = mean_rel_err(0.1), mean_rel_err(0.05)
    assert e1 > 1e-6  # the approximation is genuinely approximate at eps=0.1
    assert 3.0 < e1 / e2 < 5.0  # quadratic: halving eps quarters the error


def test_pp_gate_restricted_carry_bitwise_iterates():
    """The PP gate carries only the sweep-mutable payload (the pair cache
    crosses a single rebuild cond instead of riding the per-sweep gate);
    this must not change a single bit: drive the same problem with the
    cond-gated ``als_sweep`` and with a reference loop that picks the
    exact/approximate/rebuild phases in host Python from the same drift
    quantities, and compare every sweep's iterates bitwise."""
    from dataclasses import replace as dc_replace

    from repro.core import cp_full, random_factors, random_tensor, tensor_norm
    from repro.plan import LocalExecutor, Problem, SweepState, als_sweep, plan_sweep
    from repro.plan import sweep as sweeplib

    shape, rank, n_sweeps = (10, 8, 6), 3, 16
    true = random_factors(jax.random.PRNGKey(40), shape, rank)
    x = cp_full(None, true) + 1e-3 * random_tensor(jax.random.PRNGKey(41), shape)
    init = random_factors(jax.random.PRNGKey(42), shape, rank)
    problem = Problem(shape=shape, rank=rank, pp_tol=0.08)
    plan = plan_sweep(problem, strategy="pp")
    ex = LocalExecutor()

    def initial_state():
        return SweepState(
            x=x, factors=list(init), weights=jnp.ones((rank,), x.dtype),
            norm_x=tensor_norm(x).astype(x.dtype), it=jnp.asarray(0),
            grams=sweeplib.grams(init), pp=sweeplib._pp_init(problem, x, init),
        )

    gated = initial_state()
    ref = initial_state()
    saw_pp = saw_exact = False
    for _ in range(n_sweeps):
        gated = als_sweep(problem, plan, ex, gated)

        # reference: the same phases, chosen by host control flow
        use_pp = bool(np.max(np.asarray(ref.pp.drift)) < problem.pp_tol)
        saw_pp |= use_pp
        saw_exact |= not use_pp
        if use_pp:
            ref = sweeplib._pp_sweep(problem, plan, ref)
        else:
            out = sweeplib._exact_sweep(problem, plan, ex, ref)
            step = sweeplib._pp_drift(out.factors, ref.factors)
            if float(jnp.max(step)) < problem.pp_tol:
                pp = sweeplib._pp_materialize(
                    problem, ex, out.x, out.factors, ref.pp.n_exact + 1
                )
            else:
                pp = sweeplib.PPState(
                    ref=ref.pp.ref, pairs=ref.pp.pairs, base=ref.pp.base,
                    drift=jnp.full_like(ref.pp.drift, jnp.inf),
                    n_exact=ref.pp.n_exact + 1,
                )
            ref = dc_replace(out, pp=pp)

        assert int(gated.pp.n_exact) == int(ref.pp.n_exact)
        assert np.array_equal(np.asarray(gated.weights), np.asarray(ref.weights))
        for fa, fb in zip(gated.factors, ref.factors):
            assert np.array_equal(np.asarray(fa), np.asarray(fb))
        assert np.array_equal(
            np.asarray(gated.pp.drift), np.asarray(ref.pp.drift)
        )
        gated = dc_replace(gated, it=gated.it + 1)
        ref = dc_replace(ref, it=ref.it + 1)
    # the run actually exercised both regimes, or the comparison is vacuous
    assert saw_pp and saw_exact


def test_pp_final_fit_matches_exact():
    """On a planted low-rank tensor a PP run (mostly approximated sweeps)
    converges to the same fit as exact ALS, while actually skipping exact
    re-materializations.  The planted factors have orthonormal columns, so
    ALS leaves its early large-step phase within a few sweeps instead of
    crawling through a swamp of nearly collinear components (where every
    sweep's drift exceeds pp_tol and PP never engages)."""
    import jax.numpy as jnp

    from repro.core import cp_full, random_factors, random_tensor
    from repro.plan import Problem, cp_als, plan_sweep

    shape, rank, n_iters = (12, 10, 8), 4, 40
    true = [
        jnp.linalg.qr(u)[0]
        for u in random_factors(jax.random.PRNGKey(30), shape, rank)
    ]
    x = cp_full(None, true)
    x = x + 1e-3 * random_tensor(jax.random.PRNGKey(31), shape)
    init = random_factors(jax.random.PRNGKey(32), shape, rank)

    exact = cp_als(
        x, plan_sweep(Problem(shape=shape, rank=rank)),
        n_iters=n_iters, tol=0.0, init_factors=list(init),
    )
    pp = cp_als(
        x, plan_sweep(Problem(shape=shape, rank=rank, pp_tol=0.003), strategy="pp"),
        n_iters=n_iters, tol=0.0, init_factors=list(init),
    )
    # a majority of sweeps were approximated, yet the fit agrees
    assert 0 < pp.pp_exact_sweeps < n_iters // 2
    assert abs(float(exact.fit) - float(pp.fit)) < 1e-3


def test_moe_combine_weights_are_convex(host_mesh):
    """Per-token routing weights are a softmax over the top-k: sum <= 1."""
    import jax.numpy as jnp

    from repro.models.moe import moe_apply

    cfg = get_config("qwen2-moe-a2.7b").reduced()
    model = build_model(cfg)
    params = model.init(jax.random.PRNGKey(0))
    # extract one layer's moe params
    moe_p = jax.tree.map(lambda x: x[0], params["layers"])["moe"]
    x = jax.random.normal(jax.random.PRNGKey(1), (2, 8, cfg.d_model))
    y, aux = moe_apply(moe_p, cfg, x)
    assert y.shape == x.shape
    assert np.isfinite(np.asarray(y, np.float32)).all()
    assert float(aux) >= 0.0
