"""jit'd wrappers around the Pallas kernels: padding, tiling, mode dispatch.

Public entry points:

* :func:`fused_mttkrp`   -- MTTKRP for any mode without materializing the full
                            KRP in HBM (beyond-paper; see fused_mttkrp.py).
* :func:`fused_mttkrp_batched` -- same, with a leading batch axis mapped to
                            the kernel's batch grid dimension (one launch
                            for S stacked problems).
* :func:`matrix_free_mttkrp` -- streaming matrix-free MTTKRP (no KRP at
                            all, not even partial; see matrix_free.py).
* :func:`matrix_free_mttkrp_batched` -- same, leading batch axis.
* :func:`krp_materialize`-- explicit KRP via the tiled kernel (Alg. 1).
* :func:`multi_ttv`      -- kernelized 2nd step of the 2-step algorithm.
* :func:`multi_ttv_batched` -- batched variant over a leading batch axis.
* :func:`mttkrp_2step_kernel` -- Alg. 4 with the multi-TTV step kernelized.

``multi_ttv`` / ``multi_ttv_batched`` and the matrix-free pair are frozen
aliases of the single implementations in ``multi_ttv.py`` / ``matrix_free.py``
(re-exported here so callers keep one import surface).

On non-TPU backends the kernels run in ``interpret=True`` mode (the kernel
body executes in Python on CPU) -- this container's validation path.  Real-TPU
runs additionally pad the rank axis to the 128-lane boundary.
"""

from __future__ import annotations

import math
from functools import partial
from typing import Sequence

import jax
import jax.numpy as jnp

from repro.core.krp import krp_or_ones, krp_or_ones_batched
from repro.core.tensor_ops import dims_split

from ._tiling import LANE, SUBLANE, TILE_ELEM_BUDGET
from ._tiling import block as _block
from ._tiling import interpret_default as _interpret
from ._tiling import on_tpu as _on_tpu
from ._tiling import pad_axis as _pad_axis
from .fused_mttkrp import fused_mttkrp_bilinear, fused_mttkrp_bilinear_batched
from .krp_kernel import krp_pair
from .matrix_free import matrix_free_mttkrp, matrix_free_mttkrp_batched
from .multi_ttv import multi_ttv, multi_ttv_batched

Array = jax.Array


def balanced_split(dims: Sequence[int]) -> int:
    """Split index minimizing |log prod(left) - log prod(right)| (>=1 each side).

    Public because the ``repro.plan`` cost model mirrors the fused kernel's
    partial-KRP split when predicting its HBM traffic.

    ``dims`` must be *mode* extents only -- never a raw ``x.shape`` that
    still carries a leading batch axis, which would skew the split (and
    hence the tile sizes) toward the batch extent.  The batched wrappers
    split on ``x.shape[1:]`` / per-factor row counts for exactly this
    reason.
    """
    best, best_val = 1, float("inf")
    total = math.prod(dims)
    acc = 1
    for i in range(1, len(dims)):
        acc *= dims[i - 1]
        val = abs(math.log(acc) - math.log(total / acc))
        if val < best_val:
            best, best_val = i, val
    return best


_balanced_split = balanced_split


def _fused_blocks(
    dim_i: int, dim_b: int, pos: int, block_i: int, block_b: int
) -> tuple[int, int]:
    """Legal (block_i, block_b) for the fused kernel's tensor view: the
    i axis is the block's minor dim at pos 2 and its second-minor dim
    otherwise (the output block keeps it second-minor too); the b axis
    takes the other of the two."""
    if pos == 2:
        return _block(dim_i, block_i, LANE), _block(dim_b, block_b, SUBLANE)
    return _block(dim_i, block_i, SUBLANE), _block(dim_b, block_b, LANE)


@partial(jax.jit, static_argnames=("n", "block_i", "block_b", "interpret", "pad_rank_to"))
def fused_mttkrp(
    x: Array,
    factors: Sequence[Array],
    n: int,
    *,
    block_i: int = 128,
    block_b: int = 256,
    interpret: bool | None = None,
    pad_rank_to: int | None = None,
) -> Array:
    """MTTKRP via the fused kernel.  ``M = X_(n) . KRP(factors != n)``.

    The two partial KRPs fed to the kernel are built with the reuse fold
    (Alg. 1); the full ``L*R x C`` KRP never exists.  External modes split
    their single factor list at the log-balanced point so both kernel inputs
    stay near ``sqrt`` of the full KRP size.
    """
    factors = list(factors)
    big_n = len(factors)
    if x.ndim != big_n:
        # a batched tensor here would silently treat the batch axis as mode 0
        # and derive tiles from it; route batched inputs explicitly instead
        raise ValueError(
            f"x.ndim {x.ndim} != {big_n} factors -- for a leading batch axis "
            "use fused_mttkrp_batched"
        )
    c = factors[0].shape[1]
    interp = _interpret(interpret)
    if pad_rank_to is None and _on_tpu():
        pad_rank_to = 128

    left = factors[:n]
    right = factors[n + 1 :]
    in_dim = x.shape[n]

    if 0 < n < big_n - 1:
        pos = 1
        a_mats, b_mats = left, right
        big_l, _, big_r = dims_split(x.shape, n)
        t = x.reshape(big_l, in_dim, big_r)
    elif n == 0:
        pos = 0
        split = _balanced_split([f.shape[0] for f in right]) if len(right) > 1 else 0
        a_mats, b_mats = right[:split], right[split:]
        da = math.prod(f.shape[0] for f in a_mats) if a_mats else 1
        db = math.prod(f.shape[0] for f in b_mats)
        t = x.reshape(in_dim, da, db)
    else:  # n == N-1
        pos = 2
        split = _balanced_split([f.shape[0] for f in left]) if len(left) > 1 else 1
        a_mats, b_mats = left[:split], left[split:]
        da = math.prod(f.shape[0] for f in a_mats)
        db = math.prod(f.shape[0] for f in b_mats) if b_mats else 1
        t = x.reshape(da, db, in_dim)

    a = krp_or_ones(a_mats, c, x.dtype)
    b = krp_or_ones(b_mats, c, x.dtype)
    if pad_rank_to:
        a = _pad_axis(a, 1, pad_rank_to)
        b = _pad_axis(b, 1, pad_rank_to)

    bi, bb = _fused_blocks(in_dim, b.shape[0], pos, block_i, block_b)
    b_axis = 1 if pos == 2 else 2  # t layout: pos0 (i,a,b), pos1 (a,i,b), pos2 (a,b,i)
    t = _pad_axis(_pad_axis(t, pos, bi), b_axis, bb)
    b = _pad_axis(b, 0, bb)
    if pos == 0 and a.shape[0] > SUBLANE:  # a is the block's second-minor dim
        t = _pad_axis(t, 1, SUBLANE)
        a = _pad_axis(a, 0, SUBLANE)
    out = fused_mttkrp_bilinear(
        t, a, b, pos=pos, block_i=bi, block_b=bb, interpret=interp
    )
    return out[:in_dim, :c].astype(x.dtype)


@partial(
    jax.jit,
    static_argnames=(
        "n", "block_i", "block_b", "block_batch", "interpret", "pad_rank_to"
    ),
)
def fused_mttkrp_batched(
    x: Array,
    factors: Sequence[Array],
    n: int,
    *,
    block_i: int = 128,
    block_b: int = 256,
    block_batch: int = 8,
    interpret: bool | None = None,
    pad_rank_to: int | None = None,
) -> Array:
    """Batched fused MTTKRP: ``x`` is ``(S, *shape)``, factors ``(S, I_k, C)``.

    One kernel launch covers all S stacked problems via the kernel's leading
    batch grid axis (``block_batch`` slabs); each slab forms its own KRP
    tiles in VMEM, so the per-problem KRP still never exists in HBM.  All
    reshape/split/tile arithmetic runs on the *mode* dims ``x.shape[1:]`` --
    the batch axis never participates in tile selection -- and every pad
    axis is shifted by one for the leading batch axis.
    """
    factors = list(factors)
    big_n = len(factors)
    if x.ndim != big_n + 1:
        raise ValueError(
            f"x.ndim {x.ndim} != {big_n} factors + batch axis -- for an "
            "unbatched tensor use fused_mttkrp"
        )
    s_batch = x.shape[0]
    mode_shape = x.shape[1:]  # tile choice keys on mode dims, never the batch
    c = factors[0].shape[2]
    interp = _interpret(interpret)
    if pad_rank_to is None and _on_tpu():
        pad_rank_to = 128

    left = factors[:n]
    right = factors[n + 1 :]
    in_dim = mode_shape[n]

    if 0 < n < big_n - 1:
        pos = 1
        a_mats, b_mats = left, right
        big_l, _, big_r = dims_split(mode_shape, n)
        t = x.reshape(s_batch, big_l, in_dim, big_r)
    elif n == 0:
        pos = 0
        split = balanced_split([f.shape[1] for f in right]) if len(right) > 1 else 0
        a_mats, b_mats = right[:split], right[split:]
        da = math.prod(f.shape[1] for f in a_mats) if a_mats else 1
        db = math.prod(f.shape[1] for f in b_mats)
        t = x.reshape(s_batch, in_dim, da, db)
    else:  # n == N-1
        pos = 2
        split = balanced_split([f.shape[1] for f in left]) if len(left) > 1 else 1
        a_mats, b_mats = left[:split], left[split:]
        da = math.prod(f.shape[1] for f in a_mats)
        db = math.prod(f.shape[1] for f in b_mats) if b_mats else 1
        t = x.reshape(s_batch, da, db, in_dim)

    a = krp_or_ones_batched(a_mats, s_batch, c, x.dtype)
    b = krp_or_ones_batched(b_mats, s_batch, c, x.dtype)
    if pad_rank_to:
        a = _pad_axis(a, 2, pad_rank_to)
        b = _pad_axis(b, 2, pad_rank_to)

    bi, bb = _fused_blocks(in_dim, b.shape[1], pos, block_i, block_b)
    bs = _block(s_batch, block_batch)
    while bs > 1 and bs * bi * SUBLANE * bb > TILE_ELEM_BUDGET:
        bs //= 2  # a grid step walks up to SUBLANE rows of A per slab
    b_axis = 2 if pos == 2 else 3  # unbatched layout axes, shifted by one
    t = _pad_axis(_pad_axis(_pad_axis(t, pos + 1, bi), b_axis, bb), 0, bs)
    a = _pad_axis(a, 0, bs)
    b = _pad_axis(_pad_axis(b, 1, bb), 0, bs)
    if pos == 0 and a.shape[1] > SUBLANE:  # a is the block's second-minor dim
        t = _pad_axis(t, 2, SUBLANE)
        a = _pad_axis(a, 1, SUBLANE)
    out = fused_mttkrp_bilinear_batched(
        t, a, b, pos=pos, block_i=bi, block_b=bb, block_batch=bs,
        interpret=interp,
    )
    return out[:s_batch, :in_dim, :c].astype(x.dtype)


@partial(jax.jit, static_argnames=("block_b", "interpret"))
def krp_materialize(
    mats: Sequence[Array], *, block_b: int = 512, interpret: bool | None = None
) -> Array:
    """Explicit KRP via the tiled kernel, left-folded for Z > 2 (Alg. 1 reuse:
    each fold intermediate is a cached partial Hadamard product)."""
    mats = list(mats)
    interp = _interpret(interpret)
    out = mats[0]
    for u in mats[1:]:
        jb = u.shape[0]
        bb = _block(jb, block_b, SUBLANE)
        u_pad = _pad_axis(u, 0, bb)
        ja = out.shape[0]
        prod = krp_pair(out, u_pad, block_b=bb, interpret=interp)
        prod = prod.reshape(ja, u_pad.shape[0], -1)[:, :jb, :]
        out = prod.reshape(ja * jb, -1)
    return out


@partial(jax.jit, static_argnames=("n", "block_i", "interpret"))
def mttkrp_2step_kernel(
    x: Array,
    factors: Sequence[Array],
    n: int,
    *,
    block_i: int = 256,
    interpret: bool | None = None,
) -> Array:
    """Alg. 4 with the partial MTTKRP on the MXU (plain dot) and the 2nd-step
    multi-TTV in the Pallas kernel.  Right-first ordering shown; the left
    variant transposes into the same kernel form.  jit'd with the mode /
    tile / interpret flags static so repeated calls on the same shapes reuse
    the trace instead of re-running the reshape + padding logic; ``block_i``
    is the multi-TTV kernel's (autotunable) row tile."""
    factors = list(factors)
    c = factors[0].shape[1]
    big_l, in_dim, big_r = dims_split(x.shape, n)
    left, right = factors[:n], factors[n + 1 :]
    if big_l == 1 or big_r == 1:
        return fused_mttkrp(x, factors, n, interpret=interpret)
    if big_l <= big_r:  # right-first: 2nd step contracts the smaller L
        k_r = krp_or_ones(right, c, x.dtype)
        r_t = (x.reshape(big_l * in_dim, big_r) @ k_r).reshape(big_l, in_dim, c)
        k_l = krp_or_ones(left, c, x.dtype)
        return multi_ttv(r_t, k_l, block_i=block_i, interpret=interpret)
    k_l = krp_or_ones(left, c, x.dtype)
    l_t = (k_l.T @ x.reshape(big_l, in_dim * big_r)).reshape(c, in_dim, big_r)
    k_r = krp_or_ones(right, c, x.dtype)
    # transpose (C, I, R) -> (R, I, C): same multi-TTV form over r.
    return multi_ttv(jnp.transpose(l_t, (2, 1, 0)), k_r, block_i=block_i, interpret=interpret)
