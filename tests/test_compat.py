"""Tests for the one mesh constructor (every axis ``AxisType.Auto``) and
the single-device degenerate paths of the distributed MTTKRP subsystem
(runs on the default 1-device CPU backend -- the multi-device paths live
in test_dist.py)."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import AxisType, NamedSharding, PartitionSpec as P

from repro.core import mttkrp, mttkrp_einsum, random_factors, random_tensor
from repro.core.cpals import als_sweep
from repro.core.tensor_ops import tensor_norm
from repro.dist.collectives import compressed_psum, init_error_state
from repro.dist.dist_mttkrp import (
    dist_als_sweep,
    dist_dimtree_sweep,
    dist_mttkrp,
    shard_problem,
)
from repro.launch import mesh as meshlib


@pytest.fixture(scope="module")
def mesh1():
    return meshlib.make_host_mesh(1, 1)


# ------------------------------------------------------- mesh constructor
@pytest.mark.parametrize("names", [("data",), ("data", "model"), ("pod", "data", "model")])
def test_make_mesh_axes_are_auto(names):
    m = meshlib.make_mesh((1,) * len(names), names)
    assert m.axis_names == names
    assert m.axis_types == (AxisType.Auto,) * len(names)


def test_make_mesh_takes_the_given_devices():
    m = meshlib.make_mesh((1, 1), ("data", "model"), devices=jax.devices()[:1])
    assert dict(m.shape) == {"data": 1, "model": 1}
    assert list(m.devices.flat) == jax.devices()[:1]


def test_make_mesh_rejects_too_few_devices():
    with pytest.raises(RuntimeError, match="need"):
        meshlib.make_mesh((len(jax.devices()) + 1,), ("data",))


def test_contraction_over_a_sharded_dim_compiles_on_our_mesh():
    """The regression behind every ShardingTypeError: jax.make_mesh's
    default Explicit axes reject a Gram over a sharded row dim (what
    ``grams`` computes inside every sweep); the Auto mesh leaves it to
    GSPMD."""
    def gram(u):
        return u.T @ u

    for mesh, ok in ((jax.make_mesh((1,), ("data",)), False),
                     (meshlib.make_mesh((1,), ("data",)), True)):
        u = jax.device_put(jnp.ones((4, 3)), NamedSharding(mesh, P("data", None)))
        if ok:
            np.testing.assert_array_equal(np.asarray(jax.jit(gram)(u)), 4.0)
        else:
            with pytest.raises(Exception, match="Contracting dimensions are sharded"):
                jax.jit(gram)(u)


def test_shard_map_psum_on_auto_mesh(mesh1):
    out = jax.shard_map(
        lambda x: jax.lax.psum(x, "data"),
        mesh=mesh1, in_specs=P("data"), out_specs=P("data"), check_vma=False,
    )(jnp.arange(4.0))
    np.testing.assert_array_equal(np.asarray(out), np.arange(4.0))


def test_host_mesh_is_auto(mesh1):
    assert dict(mesh1.shape) == {"data": 1, "model": 1}
    assert mesh1.axis_types == (AxisType.Auto, AxisType.Auto)


# ------------------------------------- dist degenerate paths (1-device mesh)
def test_shard_problem_preserves_values_and_layout(mesh1):
    x = random_tensor(jax.random.PRNGKey(0), (4, 3, 2))
    fs = random_factors(jax.random.PRNGKey(1), x.shape, 5)
    xs, fss = shard_problem(x, fs, {0: "data", 1: "model"}, mesh1)
    np.testing.assert_array_equal(np.asarray(xs), np.asarray(x))
    for a, b in zip(fs, fss):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


def test_shard_problem_validates_mapping(mesh1):
    x = random_tensor(jax.random.PRNGKey(0), (4, 3, 2))
    fs = random_factors(jax.random.PRNGKey(1), x.shape, 2)
    with pytest.raises(ValueError):  # same mesh axis mapped twice
        shard_problem(x, fs, {0: "data", 1: "data"}, mesh1)
    with pytest.raises(ValueError):  # unknown mesh axis
        shard_problem(x, fs, {0: "pod"}, mesh1)
    with pytest.raises(ValueError):  # mode out of range
        shard_problem(x, fs, {7: "data"}, mesh1)


@pytest.mark.parametrize("mode_axes", [{}, {0: "data"}, {0: "data", 2: "model"}])
@pytest.mark.parametrize("method", ["auto", "1step", "2step"])
def test_dist_mttkrp_size1_mesh_reduces_to_core(mesh1, mode_axes, method):
    """Mesh of size 1: dist_mttkrp must equal repro.core.mttkrp exactly."""
    x = random_tensor(jax.random.PRNGKey(2), (4, 3, 2, 3))
    fs = random_factors(jax.random.PRNGKey(3), x.shape, 5)
    xs, fss = shard_problem(x, fs, mode_axes, mesh1)
    for n in range(x.ndim):
        out = dist_mttkrp(xs, fss, n, mode_axes, mesh1, method=method)
        ref = mttkrp(x, fs, n, method=method)
        np.testing.assert_array_equal(np.asarray(out), np.asarray(ref))
        np.testing.assert_allclose(
            np.asarray(out), np.asarray(mttkrp_einsum(x, fs, n)), rtol=1e-4, atol=1e-4
        )


def test_dist_sweeps_match_single_device_sweep(mesh1):
    """als + dimtree distributed sweeps == core als_sweep on a size-1 mesh."""
    mode_axes = {0: "data", 1: "model"}
    x = random_tensor(jax.random.PRNGKey(4), (6, 4, 4))
    fs = random_factors(jax.random.PRNGKey(5), x.shape, 3)
    xs, fss = shard_problem(x, fs, mode_axes, mesh1)
    w = jnp.ones((3,), x.dtype)
    norm_x = tensor_norm(x)

    f_ref, w_ref, fit_ref = als_sweep(
        x, list(fs), w, norm_x, jnp.asarray(0), method="2step", normalize=True
    )
    f_als, _, fit_als = dist_als_sweep(
        xs, fss, w, norm_x, jnp.asarray(0), mode_axes, mesh1, method="2step"
    )
    f_dt, _, fit_dt = dist_dimtree_sweep(
        xs, fss, w, norm_x, jnp.asarray(0), mode_axes, mesh1
    )
    for a, b, c in zip(f_ref, f_als, f_dt):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b), rtol=1e-5, atol=1e-6)
        np.testing.assert_allclose(np.asarray(a), np.asarray(c), rtol=1e-4, atol=1e-5)
    np.testing.assert_allclose(float(fit_ref), float(fit_als), atol=1e-5)
    np.testing.assert_allclose(float(fit_ref), float(fit_dt), atol=1e-5)


def test_compressed_psum_size1_axis_and_error_bound(mesh1):
    x = jnp.linspace(-2.0, 3.0, 16).reshape(4, 4)
    err0 = jnp.zeros_like(x)

    def f(x_blk, e_blk):
        return compressed_psum(x_blk, "data", e_blk)

    s, ne = jax.shard_map(
        f, mesh=mesh1, in_specs=(P(), P()), out_specs=(P(), P()), check_vma=False
    )(x, err0)
    step = float(jnp.max(jnp.abs(x))) / 127.0
    np.testing.assert_allclose(np.asarray(s), np.asarray(x), atol=step / 2 + 1e-6)
    assert float(jnp.max(jnp.abs(ne))) <= step / 2 + 1e-6
    # second round with carried residual stays bounded (error feedback)
    s2, ne2 = jax.shard_map(
        f, mesh=mesh1, in_specs=(P(), P()), out_specs=(P(), P()), check_vma=False
    )(x, ne)
    assert float(jnp.max(jnp.abs(ne2))) <= float(jnp.max(jnp.abs(x + ne))) / 254.0 + 1e-6


def test_init_error_state_shapes():
    params = {"a": jnp.zeros((3, 2)), "b": {"c": jnp.zeros((4,))}}
    err = init_error_state(params, n_shards=2)
    assert err["a"].shape == (2, 3, 2)
    assert err["b"]["c"].shape == (2, 4)
    assert all(e.dtype == jnp.float32 for e in jax.tree.leaves(err))
