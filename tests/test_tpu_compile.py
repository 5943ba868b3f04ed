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

import math
import os
import re

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


def _tensor_sized_relayouts(hlo: str, size: int) -> list[str]:
    """Every copy, transpose or (non-bitcast) reshape in an optimized HLO
    text, fused computations included, whose output holds at least
    ``size`` elements."""
    found = []
    for m in re.finditer(r"%(\S+) = f32\[([0-9,]*)\]\S* (copy|transpose|reshape)\(", hlo):
        dims = [int(d) for d in m.group(2).split(",") if d]
        if math.prod(dims) >= size:
            found.append(f"{m.group(3)} {m.group(1)} f32[{m.group(2)}]")
    return found


def test_cp_als_sweep_reads_its_view_without_a_relayout(one_chip):
    """At the paper's fMRI shape, the chunk cp_als dispatches (binary@2,
    the tree the planner picks) reads the matrix view X_(2) as laid out
    by the set-up program: its optimized HLO holds no tensor-sized copy,
    transpose or reshape, and no tensor-sized temporary.  The set-up
    program holds the view's reshape and the norm as one fused
    multiply-reduce over the tensor."""
    from repro.plan import LocalExecutor, Problem, plan_sweep
    from repro.plan.sweep import prepare_operands, sweep_chunk, view_splits

    size = math.prod(FMRI)
    x = _spec(FMRI, one_chip)
    plan = plan_sweep(Problem.from_tensor(x, RANK))
    ex = LocalExecutor()
    splits = view_splits(plan, ex)
    assert plan.resolved_schedule.name == "binary@2" and splits == (2,)

    prep = prepare_operands.lower(x, splits=splits, batched=False).compile().as_text()
    assert re.search(r"= f32\[13275,40000\]\S* (reshape|copy|fusion)\(.*prepare/", prep)
    assert re.search(r"= f32\[\]\S* fusion\(%x[.\d]*\).*prepare/reduce_sum", prep)
    entry = prep[prep.index("\nENTRY"):]
    entry = entry[:entry.index("\n}\n")]
    assert not re.search(r"= f32\[225,59,200,200\]\S* (multiply|square)\(", entry)

    views = {2: _spec((225 * 59, 200 * 200), one_chip)}
    chunk = sweep_chunk(plan, ex, donate=(3, 4, 5, 6))
    compiled = chunk.lower(
        None, _spec((), one_chip), jax.ShapeDtypeStruct((), jnp.int32, sharding=one_chip),
        _factors(FMRI, one_chip), _spec((RANK,), one_chip),
        [_spec((RANK, RANK), one_chip)] * len(FMRI), None, views, length=1,
    ).compile()
    assert _tensor_sized_relayouts(compiled.as_text(), size) == []
    assert compiled.memory_analysis().temp_size_in_bytes < 4 * size / 100
