"""Multi-device worker executed in a subprocess with 8 fake host devices.

Usage: XLA_FLAGS=--xla_force_host_platform_device_count=8 python dist_worker.py <case>
Exits nonzero on assertion failure; stdout carries diagnostics.
"""

import os
import sys

os.environ.setdefault("XLA_FLAGS", "--xla_force_host_platform_device_count=8")

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402
import numpy as np  # noqa: E402

from repro.core import CPConfig, cp_als, cp_full, mttkrp_einsum, random_factors, random_tensor  # noqa: E402
from repro.dist.collectives import (  # noqa: E402
    compressed_psum,
    init_error_state,
    make_compressed_dp_step,
)
from repro.dist.dist_mttkrp import dist_cp_als, dist_mttkrp, shard_problem  # noqa: E402
from repro.launch import mesh as meshlib  # noqa: E402
from repro.launch.mesh import make_mesh  # noqa: E402


def case_dist_mttkrp():
    assert jax.device_count() == 8, jax.device_count()
    mesh = make_mesh((2, 4), ("data", "model"))
    key = jax.random.PRNGKey(0)
    x = random_tensor(key, (8, 6, 4, 5))
    factors = random_factors(jax.random.PRNGKey(1), x.shape, 7)
    mode_axes = {0: "data", 2: "model"}
    xs, fs = shard_problem(x, factors, mode_axes, mesh)
    for n in range(4):
        out = dist_mttkrp(xs, fs, n, mode_axes, mesh)
        ref = mttkrp_einsum(x, factors, n)
        np.testing.assert_allclose(
            np.asarray(out), np.asarray(ref), rtol=5e-4, atol=5e-4
        )
    # also exercise a 3-axis-style mapping over both mesh axes + the paper's
    # 1-step method explicitly
    out = dist_mttkrp(xs, fs, 1, mode_axes, mesh, method="1step")
    np.testing.assert_allclose(
        np.asarray(out), np.asarray(mttkrp_einsum(x, factors, 1)), rtol=5e-4, atol=5e-4
    )
    print("dist_mttkrp OK")


def case_matrix_free_sharded():
    """Matrix-free kernel inside the shard_map local contraction == einsum
    oracle: each worker streams its natural-layout shard through the Pallas
    kernel (interpret mode on CPU) and the psum stitches the full MTTKRP."""
    assert jax.device_count() == 8, jax.device_count()
    mesh = make_mesh((2, 4), ("data", "model"))
    x = random_tensor(jax.random.PRNGKey(3), (8, 6, 4, 5))
    factors = random_factors(jax.random.PRNGKey(4), x.shape, 7)
    mode_axes = {0: "data", 2: "model"}
    xs, fs = shard_problem(x, factors, mode_axes, mesh)
    tiles = {"block_i": 4, "block_r": 2}
    for n in range(4):
        out = dist_mttkrp(xs, fs, n, mode_axes, mesh, method="matrix_free", tiles=tiles)
        ref = mttkrp_einsum(x, factors, n)
        np.testing.assert_allclose(
            np.asarray(out), np.asarray(ref), rtol=5e-4, atol=5e-4, err_msg=f"mode {n}"
        )
    print("matrix_free_sharded OK")


def case_dist_cpals():
    mesh = make_mesh((2, 4), ("data", "model"))
    key = jax.random.PRNGKey(2)
    planted = random_factors(key, (12, 8, 8), 3)
    x = cp_full(None, planted)
    mode_axes = {0: "data", 1: "model"}
    fs, w, fit = dist_cp_als(x, rank=3, mode_axes=mode_axes, mesh=mesh, n_iters=120, tol=1e-9)
    assert float(fit) > 0.99, float(fit)
    # cross-check against the single-device driver
    st = cp_als(x, CPConfig(rank=3, n_iters=120, tol=1e-9, seed=0))
    assert abs(float(fit) - float(st.fit)) < 5e-3, (float(fit), float(st.fit))
    print("dist_cpals OK fit=", float(fit))


def case_dist_dimtree():
    """Distributed dimension-tree sweep == single-device standard ALS sweep."""
    from repro.core.cpals import als_sweep
    from repro.core.tensor_ops import tensor_norm
    from repro.dist.dist_mttkrp import dist_dimtree_sweep, shard_problem

    mesh = make_mesh((2, 4), ("data", "model"))
    key = jax.random.PRNGKey(5)
    x = random_tensor(key, (8, 6, 8, 4))
    factors = random_factors(jax.random.PRNGKey(6), x.shape, 3)
    mode_axes = {0: "data", 2: "model"}
    xs, fs = shard_problem(x, factors, mode_axes, mesh)
    w = jnp.ones((3,), x.dtype)
    norm_x = tensor_norm(x)

    f_ref, w_ref = list(factors), w
    f_dist, w_dist = fs, w
    for it in range(3):
        f_ref, w_ref, fit_ref = als_sweep(
            x, f_ref, w_ref, norm_x, jnp.asarray(it), method="2step", normalize=True
        )
        f_dist, w_dist, fit_dist = dist_dimtree_sweep(
            xs, f_dist, w_dist, norm_x, jnp.asarray(it), mode_axes, mesh
        )
        for a, b in zip(f_ref, f_dist):
            np.testing.assert_allclose(
                np.asarray(a), np.asarray(b), rtol=5e-3, atol=5e-4
            )
        np.testing.assert_allclose(float(fit_ref), float(fit_dist), atol=1e-4)
    print("dist_dimtree OK fit=", float(fit_dist))


def case_elastic_restore():
    """Save sharded state from a (4,2) mesh, restore onto (2,4) -- the
    elastic-restart path (pod loss / mesh reshape) end to end."""
    import tempfile

    from jax.sharding import NamedSharding, PartitionSpec as P

    from repro.checkpoint.manager import CheckpointManager

    mesh_a = make_mesh((4, 2), ("data", "model"))
    mesh_b = make_mesh((2, 4), ("data", "model"))
    specs = {"w": P("data", "model"), "b": P("model")}
    tree = {
        "w": jax.device_put(
            jnp.arange(64.0).reshape(8, 8), NamedSharding(mesh_a, specs["w"])
        ),
        "b": jax.device_put(jnp.arange(8.0), NamedSharding(mesh_a, specs["b"])),
    }
    with tempfile.TemporaryDirectory() as d:
        mgr = CheckpointManager(d)
        mgr.save(3, tree)
        template = jax.tree.map(jnp.zeros_like, tree)
        restored, _ = mgr.restore(template, mesh=mesh_b, specs=specs)
    for k in tree:
        np.testing.assert_array_equal(np.asarray(restored[k]), np.asarray(tree[k]))
        assert restored[k].sharding.mesh.shape == dict(mesh_b.shape)
    print("elastic_restore OK")


def case_overlap_mttkrp():
    """OverlappingExecutor == ShardedExecutor: chunked per-slab psums cover
    disjoint output rows, so overlap changes the schedule, not the result --
    up to the rounding of the local GEMM, which XLA blocks by operand shape
    (a row slab need not reduce in the same order as the whole block)."""
    from repro.core.tensor_ops import tensor_norm
    from repro.dist.dist_mttkrp import dist_mttkrp, dist_mttkrp_overlapped
    from repro.plan import (
        OverlappingExecutor,
        Problem,
        ShardedExecutor,
        SweepState,
        als_sweep,
        plan_sweep,
    )

    assert jax.device_count() == 8, jax.device_count()
    mesh = make_mesh((2, 4), ("data", "model"))
    x = random_tensor(jax.random.PRNGKey(0), (8, 6, 4, 5))
    factors = random_factors(jax.random.PRNGKey(1), x.shape, 7)
    mode_axes = {0: "data", 2: "model"}
    xs, fs = shard_problem(x, factors, mode_axes, mesh)
    # per-mode MTTKRP: overlapped == plain for every mode and chunk count,
    # within the fp32 forward-error bound of the reduction: a few units of
    # roundoff times sum |x| |krp| per output element (elements that cancel
    # to ~0 from terms of ~40 differ in the last bits of the terms).
    eps = float(np.finfo(np.float32).eps)
    for n in range(4):
        ref = np.asarray(dist_mttkrp(xs, fs, n, mode_axes, mesh))
        scale = np.asarray(mttkrp_einsum(jnp.abs(x), [jnp.abs(u) for u in factors], n))
        for n_chunks in (1, 2, 3, 8):
            out = np.asarray(
                dist_mttkrp_overlapped(xs, fs, n, mode_axes, mesh, n_chunks=n_chunks)
            )
            assert np.all(np.abs(out - ref) <= 4 * eps * scale), (n, n_chunks)
    # full ALS sweeps: iterates stay matched across several sweeps.  The
    # rank stays <= every mode's extent: at rank 7 the (4, 7) and (5, 7)
    # factors make H singular, and pinv's minimum-norm solve then amplifies
    # the GEMM roundoff above without bound.
    rank = 4
    als_factors = random_factors(jax.random.PRNGKey(1), x.shape, rank)
    _, fs = shard_problem(x, als_factors, mode_axes, mesh)
    problem = Problem.from_tensor(x, rank, mode_axes=mode_axes, mesh=mesh)
    plan = plan_sweep(problem, executor="overlapping")
    assert plan.executor == "overlapping"
    assert any(m.cost.predicted_overlap_efficiency > 0 for m in plan.modes)
    w = jnp.ones((rank,), x.dtype)
    norm_x = tensor_norm(x)
    f_sh, f_ov = list(fs), list(fs)
    w_sh = w_ov = w
    for it in range(3):
        st_sh = SweepState(x=xs, factors=f_sh, weights=w_sh, norm_x=norm_x, it=jnp.asarray(it))
        st_ov = SweepState(x=xs, factors=f_ov, weights=w_ov, norm_x=norm_x, it=jnp.asarray(it))
        out_sh = als_sweep(problem, plan, ShardedExecutor(mesh, mode_axes), st_sh)
        out_ov = als_sweep(problem, plan, OverlappingExecutor(mesh, mode_axes, n_chunks=3), st_ov)
        f_sh, w_sh = out_sh.factors, out_sh.weights
        f_ov, w_ov = out_ov.factors, out_ov.weights
        for a, b in zip(f_sh, f_ov):
            np.testing.assert_allclose(np.asarray(a), np.asarray(b), rtol=1e-5, atol=1e-6)
        np.testing.assert_allclose(float(out_sh.fit), float(out_ov.fit), atol=1e-5)
    print("overlap_mttkrp OK")


def case_schedule_overlap():
    """Overlapped-dimtree == sharded-dimtree BITWISE: every node of the
    binary schedule is a partial contraction whose chunked per-slab psums
    cover disjoint rows of the same reduction, so overlap changes the
    schedule, never a bit of the result.  Also exercises the chain schedule
    and the compressed executor on tree partials (error-feedback carry)."""
    from repro.core.tensor_ops import tensor_norm
    from repro.plan import (
        CompressedShardedExecutor,
        OverlappingExecutor,
        Problem,
        ShardedExecutor,
        SweepState,
        als_sweep,
        enumerate_schedules,
        make_executor,
        plan_sweep,
        select_executor,
    )

    assert jax.device_count() == 8, jax.device_count()
    mesh = make_mesh((2, 4), ("data", "model"))
    x = random_tensor(jax.random.PRNGKey(0), (8, 6, 4, 4))
    factors = random_factors(jax.random.PRNGKey(1), x.shape, 5)
    mode_axes = {0: "data", 2: "model"}
    from repro.dist.dist_mttkrp import shard_problem as _shard

    xs, fs = _shard(x, factors, mode_axes, mesh)
    problem = Problem.from_tensor(x, 5, mode_axes=mode_axes, mesh=mesh)
    w = jnp.ones((5,), x.dtype)
    norm_x = tensor_norm(x)

    # the planner enumerates trees and may pair a dimtree schedule with any
    # executor; the restriction is gone
    assert sum(not s.is_flat for s in enumerate_schedules(problem)) >= 3
    assert select_executor(problem, "dimtree") in ("overlapping", "compressed")
    plan = plan_sweep(problem, strategy="dimtree", executor="overlapping")
    assert plan.executor == "overlapping" and plan.kind == "dimtree"

    # dimtree sweeps: overlapped == sharded bitwise, across several sweeps
    f_sh, f_ov = list(fs), list(fs)
    w_sh = w_ov = w
    for it in range(3):
        st_sh = SweepState(x=xs, factors=f_sh, weights=w_sh, norm_x=norm_x, it=jnp.asarray(it))
        st_ov = SweepState(x=xs, factors=f_ov, weights=w_ov, norm_x=norm_x, it=jnp.asarray(it))
        out_sh = als_sweep(problem, plan, ShardedExecutor(mesh, mode_axes), st_sh)
        out_ov = als_sweep(problem, plan, OverlappingExecutor(mesh, mode_axes, n_chunks=3), st_ov)
        f_sh, w_sh = out_sh.factors, out_sh.weights
        f_ov, w_ov = out_ov.factors, out_ov.weights
        for a, b in zip(f_sh, f_ov):
            np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
        np.testing.assert_array_equal(np.asarray(out_sh.fit), np.asarray(out_ov.fit))

    # chain schedule on the overlapping executor matches sharded numerics
    # (its root leaf is a chunked full MTTKRP: equal reductions, fp-tight)
    chain_plan = plan_sweep(problem, schedule="chain", executor="overlapping")
    st_a = SweepState(x=xs, factors=list(fs), weights=w, norm_x=norm_x, it=jnp.asarray(0))
    st_b = SweepState(x=xs, factors=list(fs), weights=w, norm_x=norm_x, it=jnp.asarray(0))
    out_a = als_sweep(problem, chain_plan, ShardedExecutor(mesh, mode_axes), st_a)
    out_b = als_sweep(problem, chain_plan, OverlappingExecutor(mesh, mode_axes, n_chunks=3), st_b)
    for a, b in zip(out_a.factors, out_b.factors):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b), rtol=1e-5, atol=1e-6)

    # compressed executor on the dimtree schedule: per-node residual carry
    # threads through the sweep and converges to the exact fit
    plan_c = plan_sweep(problem, strategy="dimtree", executor="compressed")
    ex_c = make_executor("compressed", mesh, mode_axes)
    assert isinstance(ex_c, CompressedShardedExecutor)
    carry = ex_c.init_carry(plan_c, xs, fs)
    assert carry  # at least one node needs a psum on this mapping
    st_c = SweepState(x=xs, factors=list(fs), weights=w, norm_x=norm_x, it=jnp.asarray(0), carry=carry)
    out_c = als_sweep(problem, plan_c, ex_c, st_c)
    assert out_c.carry is not carry  # residuals were updated
    for a, b in zip(out_c.factors, f_sh):
        assert np.all(np.isfinite(np.asarray(a)))
        assert np.asarray(a).shape == np.asarray(b).shape
    print("schedule_overlap OK")


def case_compressed_cpals():
    """Error-feedback convergence: CP-ALS with the compressed factor
    all-reduce reaches the uncompressed fit within tolerance on a fixed
    iteration budget (seeded planted problem)."""
    mesh = make_mesh((2, 4), ("data", "model"))
    planted = random_factors(jax.random.PRNGKey(7), (8, 8, 8), 3)
    x = cp_full(None, planted)
    mode_axes = {0: "data", 1: "model"}
    budget = 40
    f_e, w_e, fit_exact = dist_cp_als(
        x, rank=3, mode_axes=mode_axes, mesh=mesh, n_iters=budget, tol=1e-9,
        executor="sharded",
    )
    f_c, w_c, fit_comp = dist_cp_als(
        x, rank=3, mode_axes=mode_axes, mesh=mesh, n_iters=budget, tol=1e-9,
        executor="compressed",
    )
    assert float(fit_comp) > 0.75, float(fit_comp)
    assert abs(float(fit_comp) - float(fit_exact)) < 2e-2, (
        float(fit_comp), float(fit_exact),
    )
    # selection surface: a few-participant, collective-bound problem picks
    # compressed; this planted shape keeps an exact executor
    from repro.plan import Problem, select_executor

    p2 = Problem(shape=(2, 64, 2), rank=4096, mode_axes={0: "data"}, axis_sizes={"data": 2})
    assert select_executor(p2) == "compressed", select_executor(p2)
    print("compressed_cpals OK", float(fit_comp), float(fit_exact))


def case_compressed_psum():
    mesh = make_mesh((8,), ("data",))
    from jax import shard_map

    x = jnp.arange(64, dtype=jnp.float32).reshape(8, 8) / 7.0

    def f(x_blk, err):
        s, ne = compressed_psum(x_blk[0], "data", err[0])
        return s[None], ne[None]

    from jax.sharding import PartitionSpec as P

    err0 = jnp.zeros((8, 8), jnp.float32)
    s, ne = shard_map(
        f, mesh=mesh, in_specs=(P("data"), P("data")), out_specs=(P("data"), P("data")),
        check_vma=False,
    )(x, err0)
    exact = jnp.sum(x, 0)
    # every replica row should approximate the exact sum within int8 step
    scale = float(jnp.max(jnp.abs(x))) / 127.0 * 8
    for r in range(8):
        np.testing.assert_allclose(np.asarray(s[r]), np.asarray(exact), atol=scale + 1e-5)
    # error feedback: residuals bounded by one quantization step
    assert float(jnp.max(jnp.abs(ne))) <= float(jnp.max(jnp.abs(x))) / 127.0 + 1e-6
    print("compressed_psum OK")


def case_compressed_dp_trainer():
    from repro.configs import get_config
    from repro.data.pipeline import DataConfig, SyntheticLM
    from repro.models import build_model
    from repro.train.optimizer import OptConfig, init_opt_state

    mesh = make_mesh((8, 1), ("data", "model"))
    cfg = get_config("olmo-1b").reduced()
    model = build_model(cfg)
    with meshlib.use_mesh(mesh):
        params = model.init(jax.random.PRNGKey(0))
        data = SyntheticLM(DataConfig(vocab=32, seq_len=16, global_batch=8))
        opt_cfg = OptConfig(lr=3e-3, warmup_steps=0, total_steps=100)
        step_c = jax.jit(make_compressed_dp_step(model, opt_cfg, mesh, compress=True))
        step_e = jax.jit(make_compressed_dp_step(model, opt_cfg, mesh, compress=False))
        pc = pe = params
        oc = oe = init_opt_state(params)
        err = init_error_state(params)
        for i in range(8):
            batch = {k: jnp.asarray(v) for k, v in data.batch(i).items()}
            pc, oc, err, mc = step_c(pc, oc, err, batch)
            pe, oe, _, me = step_e(pe, oe, jax.tree.map(jnp.zeros_like, err), batch)
        lc, le = float(mc["loss"]), float(me["loss"])
        assert np.isfinite(lc) and np.isfinite(le)
        assert abs(lc - le) < 0.3, (lc, le)  # compressed tracks exact closely
    print("compressed_dp OK", lc, le)


def case_pp_sharded():
    """Sharded pairwise perturbation == local pairwise perturbation.

    Covers ``dist_pp_pairs`` (pair build inside shard_map with the minimal
    psum, rank-major layout) and the PP correction sweeps running through
    the sharded executor end to end: same pair tensors, same exact-sweep
    cadence, allclose factors."""
    from repro.plan import LocalExecutor, Problem, make_executor, plan_sweep
    from repro.plan import cp_als as plan_cp_als

    mesh = make_mesh((2, 4), ("data", "model"))
    mode_axes = {0: "data", 1: "model"}
    shape, rank = (12, 8, 8), 3
    planted = random_factors(jax.random.PRNGKey(7), shape, rank)
    x = cp_full(None, planted) + 1e-3 * random_tensor(jax.random.PRNGKey(8), shape)
    init = random_factors(jax.random.PRNGKey(9), shape, rank)

    prob_lo = Problem(shape=shape, rank=rank, pp_tol=0.05)
    prob_sh = Problem(
        shape=shape, rank=rank, pp_tol=0.05,
        mode_axes=mode_axes, axis_sizes={"data": 2, "model": 4},
    )
    ex = make_executor("sharded", mesh, mode_axes)

    # the pair cache itself: dist build == local build, pair by pair
    pairs_lo = LocalExecutor().pp_pairs(prob_lo, x, list(init))
    pairs_sh = ex.pp_pairs(prob_sh, x, list(init))
    assert set(pairs_lo) == set(pairs_sh), (set(pairs_lo), set(pairs_sh))
    for k in pairs_lo:
        np.testing.assert_allclose(
            np.asarray(pairs_sh[k]), np.asarray(pairs_lo[k]),
            rtol=5e-4, atol=5e-5, err_msg=f"pair {k}",
        )

    st_lo = plan_cp_als(
        x, plan_sweep(prob_lo, strategy="pp"),
        n_iters=10, tol=0.0, init_factors=list(init),
    )
    st_sh = plan_cp_als(
        x, plan_sweep(prob_sh, strategy="pp"), executor=ex,
        n_iters=10, tol=0.0, init_factors=list(init),
    )
    assert st_sh.pp_exact_sweeps == st_lo.pp_exact_sweeps, (
        st_sh.pp_exact_sweeps, st_lo.pp_exact_sweeps,
    )
    for a, b in zip(st_sh.factors, st_lo.factors):
        np.testing.assert_allclose(
            np.asarray(a), np.asarray(b), rtol=5e-3, atol=5e-4
        )
    np.testing.assert_allclose(float(st_sh.fit), float(st_lo.fit), atol=1e-4)
    print("pp_sharded OK exact_sweeps=", int(st_sh.pp_exact_sweeps))


def case_hierarchical_psum():
    """Hierarchical two-level collectives == flat psum on a 2x4 node mesh.

    ``hierarchical_psum`` (reduce-scatter within the node + cross-node psum
    of the shard + all-gather back) is an exact regrouping of the same sum,
    so every exact path -- the raw collective, ``dist_mttkrp``, and the
    overlapped variant -- must match its flat twin allclose; the compressed
    variant keeps its error-feedback carry semantics (residual shape and
    bound) while compressing only the cross-node stage.  Ends with the
    acceptance sweep: a ``plan_sweep(executor="auto")`` plan over the
    two-level problem executes hierarchical node collectives and matches
    the flat-psum plan's factors allclose.
    """
    from jax import shard_map
    from jax.sharding import PartitionSpec as P

    from repro.dist.collectives import hierarchical_psum
    from repro.dist.dist_mttkrp import (
        dist_mttkrp_compressed,
        dist_mttkrp_overlapped,
        init_mttkrp_error_state,
    )
    from repro.launch.mesh import make_node_mesh
    from repro.plan import Problem, SweepState, als_sweep, make_executor, plan_sweep

    assert jax.device_count() == 8, jax.device_count()
    mesh = make_node_mesh(2, 4)  # ("node", "device"): 2 nodes x 4 devices
    mode_axes = {0: "node", 2: "device"}

    # raw collective: hierarchical == flat psum, elementwise, every replica
    v = jnp.arange(8 * 12, dtype=jnp.float32).reshape(8, 12) / 7.0

    def f(blk):
        blk = blk[0]
        flat = jax.lax.psum(blk, ("node", "device"))
        hier = hierarchical_psum(blk, ("node", "device"), mesh, node_axis="device")
        return flat[None], hier[None]

    flat, hier = shard_map(
        f, mesh=mesh, in_specs=P(("node", "device")),
        out_specs=(P(("node", "device")), P(("node", "device"))),
        check_vma=False,
    )(v)
    np.testing.assert_allclose(np.asarray(hier), np.asarray(flat), rtol=1e-6, atol=1e-6)

    x = random_tensor(jax.random.PRNGKey(0), (8, 6, 4, 5))
    factors = random_factors(jax.random.PRNGKey(1), x.shape, 7)
    xs, fs = shard_problem(x, factors, mode_axes, mesh)
    for n in range(4):
        ref = dist_mttkrp(xs, fs, n, mode_axes, mesh)
        out = dist_mttkrp(
            xs, fs, n, mode_axes, mesh, collective="hierarchical", node_axis="device"
        )
        np.testing.assert_allclose(
            np.asarray(out), np.asarray(ref), rtol=1e-5, atol=1e-6, err_msg=f"mode {n}"
        )
        ov = dist_mttkrp_overlapped(
            xs, fs, n, mode_axes, mesh, n_chunks=2,
            collective="hierarchical", node_axis="device",
        )
        np.testing.assert_allclose(
            np.asarray(ov), np.asarray(ref), rtol=1e-5, atol=1e-6, err_msg=f"ov mode {n}"
        )

    # compressed + hierarchical: intra-node stage exact, cross-node stage
    # int8 error-feedback -- output within one quantization step of exact,
    # residual carry keeps its shape and stays bounded across a second call
    n = 1
    err = init_mttkrp_error_state(x.shape, 7, mode_axes, mesh)[n]
    exact = dist_mttkrp(xs, fs, n, mode_axes, mesh)
    out_c, err1 = dist_mttkrp_compressed(
        xs, fs, n, mode_axes, mesh, err,
        collective="hierarchical", node_axis="device",
    )
    assert err1.shape == err.shape, (err1.shape, err.shape)
    scale = float(jnp.max(jnp.abs(exact))) / 127.0 * 8
    np.testing.assert_allclose(np.asarray(out_c), np.asarray(exact), atol=scale + 1e-5)
    out_c2, err2 = dist_mttkrp_compressed(
        xs, fs, n, mode_axes, mesh, err1,
        collective="hierarchical", node_axis="device",
    )
    assert err2.shape == err.shape
    # error feedback: second pass corrects toward exact, residual bounded
    np.testing.assert_allclose(np.asarray(out_c2), np.asarray(exact), atol=scale + 1e-5)
    assert float(jnp.max(jnp.abs(err2))) <= 2.1 * float(jnp.max(jnp.abs(exact))) / 127.0 + 1e-6

    # acceptance sweep: auto plan on the two-level problem (hierarchical
    # node collectives) == the same plan forced flat, allclose factors
    problem = Problem.from_tensor(
        x, 7, mode_axes=mode_axes, mesh=mesh, intra_axes=("device",)
    )
    plan = plan_sweep(problem, executor="auto")
    assert any(np_.collective == "hierarchical" for np_ in plan.nodes), [
        np_.collective for np_ in plan.nodes
    ]
    assert plan.lower_bound_bytes is not None and plan.lower_bound_bytes > 0
    from repro.core.tensor_ops import tensor_norm

    flat_prob = Problem.from_tensor(x, 7, mode_axes=mode_axes, mesh=mesh)
    flat_plan = plan_sweep(
        flat_prob, executor=plan.executor, schedule=plan.resolved_schedule.name
    )
    assert all(np_.collective == "flat" for np_ in flat_plan.nodes)
    w = jnp.ones((7,), x.dtype)
    norm_x = tensor_norm(x)
    ex_h = make_executor(
        plan.executor, mesh, mode_axes, node_axis=problem.node_axis
    )
    ex_f = make_executor(flat_plan.executor, mesh, mode_axes)
    f_h, f_f = list(fs), list(fs)
    w_h = w_f = w
    for it in range(3):
        st_h = SweepState(x=xs, factors=f_h, weights=w_h, norm_x=norm_x, it=jnp.asarray(it))
        st_f = SweepState(x=xs, factors=f_f, weights=w_f, norm_x=norm_x, it=jnp.asarray(it))
        out_h = als_sweep(problem, plan, ex_h, st_h)
        out_f = als_sweep(flat_prob, flat_plan, ex_f, st_f)
        f_h, w_h = out_h.factors, out_h.weights
        f_f, w_f = out_f.factors, out_f.weights
        for a, b in zip(f_h, f_f):
            np.testing.assert_allclose(np.asarray(a), np.asarray(b), rtol=1e-4, atol=1e-5)
        np.testing.assert_allclose(float(out_h.fit), float(out_f.fit), atol=1e-5)
    print("hierarchical_psum OK")


if __name__ == "__main__":
    {
        "dist_mttkrp": case_dist_mttkrp,
        "matrix_free_sharded": case_matrix_free_sharded,
        "dist_cpals": case_dist_cpals,
        "dist_dimtree": case_dist_dimtree,
        "elastic_restore": case_elastic_restore,
        "overlap_mttkrp": case_overlap_mttkrp,
        "schedule_overlap": case_schedule_overlap,
        "compressed_cpals": case_compressed_cpals,
        "compressed_psum": case_compressed_psum,
        "compressed_dp": case_compressed_dp_trainer,
        "pp_sharded": case_pp_sharded,
        "hierarchical_psum": case_hierarchical_psum,
    }[sys.argv[1]]()
