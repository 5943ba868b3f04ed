"""Ahead-of-time compiles of the Pallas kernels for a described TPU v5e.

Interpret mode runs a kernel's body in Python and accepts any block shape;
the chip's compiler (Mosaic) does not: the last two dims of every block
must be whole (8, 128) tiles or full extents, and a kernel must fit the
VMEM it may use.  These tests compile each kernel, with ``interpret=False``
and the rank padded to 128 lanes as on the chip, at the paper's
neuroimaging widths (225 x 59 x 200 x 200, its linearized 225 x 59 x 19900,
and per-subject 225 x 200 x 200 slabs batched by 8; rank 25), and check
that the compiled program holds a ``tpu_custom_call``, i.e. that the kernel
was lowered for the chip and not interpreted.

Nothing runs: a described chip holds no arrays.  The topology is described
only inside the module fixture, never at import, so every pytest-xdist
worker collects the same tests and only the one that runs this file loads
the TPU compiler.
"""

from __future__ import annotations

import os

import jax
import jax.numpy as jnp
import pytest

from repro.kernels import ops
from repro.kernels.multi_ttv import multi_ttv, multi_ttv_batched

RANK = 25
FMRI = (225, 59, 200, 200)
FMRI_3WAY = (225, 59, 200 * 199 // 2)
SUBJECTS = (8, 225, 200, 200)  # CPService batch of per-subject tensors


@pytest.fixture(scope="module")
def one_chip():
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache
    from jax.sharding import SingleDeviceSharding

    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    # a compile for a described chip is written to the persistent cache but
    # cannot be read back without the chip: keep the cache out of it
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    try:
        topo = topologies.get_topology_desc(platform="tpu", topology_name="v5e:2x2")
    except Exception as e:  # no TPU compiler installed
        jax.config.update("jax_enable_compilation_cache", was)
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    yield SingleDeviceSharding(topo.devices[0])
    jax.config.update("jax_enable_compilation_cache", was)
    compilation_cache.reset_cache()


def _spec(shape, sharding):
    return jax.ShapeDtypeStruct(tuple(shape), jnp.float32, sharding=sharding)


def _compile_for_chip(fn, *args) -> None:
    compiled = jax.jit(fn).lower(*args).compile()
    assert "tpu_custom_call" in compiled.as_text()


def _factors(shape, sharding, batch=None):
    lead = () if batch is None else (batch,)
    return [_spec(lead + (d, RANK), sharding) for d in shape]


_CASES = [(FMRI, n) for n in range(4)] + [(FMRI_3WAY, n) for n in range(3)]


@pytest.mark.parametrize(
    "shape,n", _CASES, ids=[f"{len(s)}way-mode{n}" for s, n in _CASES]
)
@pytest.mark.parametrize("kernel", ["fused", "matrix_free"])
def test_mttkrp_kernel_compiles_for_v5e(one_chip, kernel, shape, n):
    wrapper = {"fused": ops.fused_mttkrp, "matrix_free": ops.matrix_free_mttkrp}[kernel]
    _compile_for_chip(
        lambda x, fs: wrapper(x, fs, n, interpret=False, pad_rank_to=128),
        _spec(shape, one_chip),
        _factors(shape, one_chip),
    )


@pytest.mark.parametrize("n", range(3))
@pytest.mark.parametrize("kernel", ["fused", "matrix_free"])
def test_batched_mttkrp_kernel_compiles_for_v5e(one_chip, kernel, n):
    wrapper = {
        "fused": ops.fused_mttkrp_batched,
        "matrix_free": ops.matrix_free_mttkrp_batched,
    }[kernel]
    batch, *shape = SUBJECTS
    _compile_for_chip(
        lambda x, fs: wrapper(x, fs, n, interpret=False, pad_rank_to=128),
        _spec(SUBJECTS, one_chip),
        _factors(shape, one_chip, batch),
    )


def test_multi_ttv_compiles_for_v5e(one_chip):
    # 2-step second stage of mode 1: T is (L, I_1, C), W is K_L (L, C)
    big_l, dim_i = FMRI[0], FMRI[1]
    _compile_for_chip(
        lambda t, w: multi_ttv(t, w, interpret=False),
        _spec((big_l, dim_i, RANK), one_chip),
        _spec((big_l, RANK), one_chip),
    )


def test_multi_ttv_batched_compiles_for_v5e(one_chip):
    batch, big_l, dim_i = SUBJECTS[0], SUBJECTS[1], SUBJECTS[2]
    _compile_for_chip(
        lambda t, w: multi_ttv_batched(t, w, interpret=False),
        _spec((batch, big_l, dim_i, RANK), one_chip),
        _spec((batch, big_l, RANK), one_chip),
    )


def test_krp_compiles_for_v5e(one_chip):
    _compile_for_chip(
        lambda a, b: ops.krp_materialize([a, b], interpret=False),
        _spec((FMRI[2], RANK), one_chip),
        _spec((FMRI[3], RANK), one_chip),
    )
