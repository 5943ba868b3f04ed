"""CP-factorized layer tests: fit-from-dense + end-to-end training."""

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import get_config
from repro.models.cp_layers import (
    compress_ffn,
    factorize_expert_stack,
    factorize_linear,
    reconstruction_error,
)
from repro.launch import mesh as meshlib
from repro.models import build_model


@pytest.fixture(scope="module")
def host_mesh():
    with meshlib.use_mesh(meshlib.make_host_mesh(1, 1)) as m:
        yield m


def test_factorize_linear_recovers_lowrank():
    key = jax.random.PRNGKey(0)
    a0 = jax.random.normal(key, (24, 3))
    b0 = jax.random.normal(jax.random.PRNGKey(1), (3, 16))
    w = a0 @ b0
    a, b = factorize_linear(w, rank=3, n_iters=120)
    assert a.shape == (24, 3) and b.shape == (3, 16)
    assert reconstruction_error(w, a, b) < 1e-3


def test_factorize_expert_stack_3way():
    from repro.core import cp_full, random_factors

    planted = random_factors(jax.random.PRNGKey(2), (4, 12, 10), 2)
    w = cp_full(None, planted)
    e, a, b = factorize_expert_stack(w, rank=2, n_iters=150)
    approx = jnp.einsum("er,ir,or->eio", e, a, b)
    rel = float(jnp.linalg.norm((w - approx).ravel()) / jnp.linalg.norm(w.ravel()))
    assert rel < 1e-2, rel


def test_cp_rank_model_trains(host_mesh):
    """cfg.cp_rank switches the FFN to CP factors; training must work."""
    cfg = dataclasses.replace(get_config("olmo-1b").reduced(), cp_rank=8)
    model = build_model(cfg)
    params = model.init(jax.random.PRNGKey(3))
    layer0 = jax.tree.map(lambda x: x[0], params["layers"])
    assert "gate_a" in layer0["mlp"] and "gate" not in layer0["mlp"]

    tokens = jax.random.randint(jax.random.PRNGKey(4), (2, 12), 0, cfg.vocab, jnp.int32)
    loss, grads = jax.jit(
        lambda p: jax.value_and_grad(lambda q: model.loss_fn(q, {"tokens": tokens})[0])(p)
    )(params)
    assert np.isfinite(float(loss))
    assert all(bool(jnp.all(jnp.isfinite(g))) for g in jax.tree.leaves(grads))
    # factorized FFN params are smaller than dense for this rank
    from repro.analysis.flops import _defs_count

    dense = _defs_count(build_model(get_config("olmo-1b").reduced()).param_defs)
    fact = _defs_count(model.param_defs)
    assert fact < dense


def test_compress_ffn_roundtrip():
    key = jax.random.PRNGKey(5)
    d, f, r = 16, 32, 4
    a_g = jax.random.normal(key, (d, r)) @ jax.random.normal(jax.random.PRNGKey(6), (r, f))
    dense = {
        "gate": a_g,
        "up": jax.random.normal(jax.random.PRNGKey(7), (d, r))
        @ jax.random.normal(jax.random.PRNGKey(8), (r, f)),
        "down": jax.random.normal(jax.random.PRNGKey(9), (f, r))
        @ jax.random.normal(jax.random.PRNGKey(10), (r, d)),
    }
    comp = compress_ffn(dense, rank=r)
    assert set(comp) == {"gate_a", "gate_b", "up_a", "up_b", "down_a", "down_b"}
    assert reconstruction_error(dense["gate"], comp["gate_a"], comp["gate_b"]) < 1e-2
