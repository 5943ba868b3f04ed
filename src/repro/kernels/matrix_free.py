"""Matrix-free Pallas MTTKRP: stream tensor blocks once, no KRP anywhere.

The fused kernel (fused_mttkrp.py) already avoids the *full* KRP, but it
still materializes two partial KRPs in HBM and reads the tensor through a
matricized 3-D view.  This kernel goes the rest of the way, following the
source paper's closing lesson (avoid tensor reordering AND large KRP
intermediates) and GenTen's performance-portable dense formulation
(Kosmacher-Phipps-Rajamanickam, arXiv:2510.14891): the tensor is passed to
the kernel in its natural N-D layout (no matricization, no reshape), the
raw factor matrices ride along untouched, and each grid step folds one
tensor block against the non-target factor rows entirely in VMEM:

* one MXU contraction over the innermost non-target mode produces a
  trailing rank axis (``dot_general`` at HIGHEST precision), then
* one VPU broadcast-multiply-reduce per remaining non-target mode peels
  the block down to an ``(I-block, C)`` contribution.

The output factor block stays resident in VMEM across all reduction grid
steps (revisited-output accumulation, zero-initialized on the first visit),
so each tensor element is read exactly once from HBM and nothing of KRP
shape -- full or partial -- is ever written.

Supported: every mode of order-3..6 tensors, plus a leading batch axis
(``matrix_free_mttkrp_batched``).  Tile knobs: ``block_i`` (target-mode
rows kept in VMEM), ``block_r`` (cap on each reduction-mode block; the
wrapper shrinks caps further if the tensor tile would blow the VMEM
budget), ``block_batch`` (batch slab).
"""

from __future__ import annotations

import math
from functools import partial, reduce
from typing import Sequence

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from ._tiling import LANE, SUBLANE, TILE_ELEM_BUDGET
from ._tiling import block as _block
from ._tiling import interpret_default as _interpret
from ._tiling import on_tpu as _on_tpu
from ._tiling import pad_axis as _pad_axis

Array = jax.Array

def _fold_tile(t, us_by_mode, live, n, batched):
    """Contract every non-target mode out of one VMEM tile.

    ``live`` is the list of original mode ids for ``t``'s spatial axes (in
    order); ``n`` is the target mode.  Descending-order processing keeps
    axis bookkeeping local: removing an axis only shifts larger ids, which
    are already gone.
    """
    off = 1 if batched else 0
    hi = jax.lax.Precision.HIGHEST
    live = list(live)
    desc = sorted((k for k in live if k != n), reverse=True)
    first = desc[0]
    u = us_by_mode[first][...].astype(jnp.float32)
    pos = live.index(first) + off
    if batched:
        t = jax.lax.dot_general(t, u, (((pos,), (1,)), ((0,), (0,))), precision=hi)
    else:
        t = jax.lax.dot_general(t, u, (((pos,), (0,)), ((), ())), precision=hi)
    live.remove(first)
    for a in desc[1:]:
        u = us_by_mode[a][...].astype(jnp.float32)
        pos = live.index(a) + off
        shape = [1] * t.ndim
        if batched:
            shape[0] = u.shape[0]
        shape[pos] = u.shape[-2]
        shape[-1] = u.shape[-1]
        t = _sum_axis(t * u.reshape(shape), pos)
        live.remove(a)
    return t


def _sum_axis(t, axis: int):
    """``t.sum(axis)``; over a leading (untiled) axis as a chain of
    elementwise adds, since Mosaic cannot lower a leading-axis reduction
    of an intermediate that an earlier sublane reduction left behind."""
    if axis >= t.ndim - 2:
        return t.sum(axis=axis)
    return reduce(
        jnp.add,
        [jax.lax.index_in_dim(t, j, axis, keepdims=False) for j in range(t.shape[axis])],
    )


def matrix_free_kernel(
    x: Array,
    us: Sequence[Array],
    n: int,
    *,
    block_i: int,
    blocks: Sequence[int],
    interpret: bool = False,
) -> Array:
    """Raw matrix-free MTTKRP grid: ``M = X_(n) . KRP(us)`` with no KRP.

    ``x`` is the natural N-D tensor, every axis pre-padded to its block
    multiple; ``us`` the non-target factors in ascending mode order (rows
    padded likewise); ``blocks`` the per-non-target-mode block sizes in the
    same order.  Grid: target-mode blocks outermost, one reduction axis per
    non-target mode inner, so the ``(block_i, C)`` output block is revisited
    in place across every reduction step.
    """
    big_n = x.ndim
    others = [k for k in range(big_n) if k != n]
    c = us[0].shape[1]
    if len(us) != len(others) or len(blocks) != len(others):
        raise ValueError("need one factor and one block per non-target mode")
    if x.shape[n] % block_i:
        raise ValueError("target mode must be padded to block_i")
    for k, u, b in zip(others, us, blocks):
        if x.shape[k] % b or u.shape[0] != x.shape[k] or u.shape[1] != c:
            raise ValueError(f"mode {k}: factor/block mismatch")

    grid = (x.shape[n] // block_i,) + tuple(
        x.shape[k] // b for k, b in zip(others, blocks)
    )
    x_block = [0] * big_n
    x_block[n] = block_i
    for k, b in zip(others, blocks):
        x_block[k] = b

    def x_index(i, *rs):
        out = [0] * big_n
        out[n] = i
        for j, k in enumerate(others):
            out[k] = rs[j]
        return tuple(out)

    in_specs = [pl.BlockSpec(tuple(x_block), x_index)]
    for j, (k, b) in enumerate(zip(others, blocks)):
        in_specs.append(
            pl.BlockSpec((b, c), lambda i, *rs, j=j: (rs[j], 0))
        )

    def kernel(x_ref, *refs):
        o_ref = refs[-1]
        u_refs = refs[:-1]
        red = [pl.program_id(j + 1) for j in range(len(others))]

        @pl.when(reduce(jnp.logical_and, [r == 0 for r in red]))
        def _init():
            o_ref[...] = jnp.zeros_like(o_ref)

        t = x_ref[...].astype(jnp.float32)
        us_by_mode = dict(zip(others, u_refs))
        o_ref[...] += _fold_tile(t, us_by_mode, list(range(big_n)), n, False)

    return pl.pallas_call(
        kernel,
        grid=grid,
        in_specs=in_specs,
        out_specs=pl.BlockSpec((block_i, c), lambda i, *rs: (i, 0)),
        out_shape=jax.ShapeDtypeStruct((x.shape[n], c), jnp.float32),
        interpret=interpret,
    )(x, *us)


def matrix_free_batched_kernel(
    x: Array,
    us: Sequence[Array],
    n: int,
    *,
    block_i: int,
    blocks: Sequence[int],
    block_batch: int,
    interpret: bool = False,
) -> Array:
    """Batched raw grid: ``x`` is ``(S, *shape)``, ``us`` are ``(S, I_k, C)``.

    Leading batch grid axis outermost; each batch slab folds its own factor
    rows, so per-problem intermediates still never leave VMEM.
    """
    big_n = x.ndim - 1
    s_batch = x.shape[0]
    others = [k for k in range(big_n) if k != n]
    c = us[0].shape[2]
    if s_batch % block_batch or x.shape[1 + n] % block_i:
        raise ValueError("batch and target mode must be padded to their blocks")
    for k, u, b in zip(others, us, blocks):
        if x.shape[1 + k] % b or u.shape[:2] != (s_batch, x.shape[1 + k]):
            raise ValueError(f"mode {k}: factor/block mismatch")

    grid = (
        s_batch // block_batch,
        x.shape[1 + n] // block_i,
    ) + tuple(x.shape[1 + k] // b for k, b in zip(others, blocks))
    x_block = [0] * (big_n + 1)
    x_block[0] = block_batch
    x_block[1 + n] = block_i
    for k, b in zip(others, blocks):
        x_block[1 + k] = b

    def x_index(s, i, *rs):
        out = [0] * (big_n + 1)
        out[0] = s
        out[1 + n] = i
        for j, k in enumerate(others):
            out[1 + k] = rs[j]
        return tuple(out)

    in_specs = [pl.BlockSpec(tuple(x_block), x_index)]
    for j, (k, b) in enumerate(zip(others, blocks)):
        in_specs.append(
            pl.BlockSpec((block_batch, b, c), lambda s, i, *rs, j=j: (s, rs[j], 0))
        )

    def kernel(x_ref, *refs):
        o_ref = refs[-1]
        u_refs = refs[:-1]
        red = [pl.program_id(j + 2) for j in range(len(others))]

        @pl.when(reduce(jnp.logical_and, [r == 0 for r in red]))
        def _init():
            o_ref[...] = jnp.zeros_like(o_ref)

        t = x_ref[...].astype(jnp.float32)
        us_by_mode = dict(zip(others, u_refs))
        o_ref[...] += _fold_tile(t, us_by_mode, list(range(big_n)), n, True)

    return pl.pallas_call(
        kernel,
        grid=grid,
        in_specs=in_specs,
        out_specs=pl.BlockSpec(
            (block_batch, block_i, c), lambda s, i, *rs: (s, i, 0)
        ),
        out_shape=jax.ShapeDtypeStruct((s_batch, x.shape[1 + n], c), jnp.float32),
        interpret=interpret,
    )(x, *us)


def _align(k: int, big_n: int) -> int:
    """Mosaic alignment of mode ``k``'s block: the tensor's minor dim takes
    whole 128-lane tiles; every other mode's block is also the
    second-minor dim of its factor's ``(rows, C)`` block, so 8 sublanes."""
    return LANE if k == big_n - 1 else SUBLANE


def _sublanes(b: int) -> int:
    """Round a block up to whole 8-row sublane tiles: each mode's axis
    becomes the sublane dim of some fold intermediate, where Mosaic cannot
    reduce over a ragged tile (the wrapper zero-pads the extra rows)."""
    return -(-b // SUBLANE) * SUBLANE


def _padded(c: int, mult: int | None) -> int:
    return -(-c // mult) * mult if mult else c


def _tile_blocks(
    mode_shape: Sequence[int], n: int, lead_elems: int, c: int,
    block_i: int, block_r: int,
) -> tuple[int, dict[int, int]]:
    """Target-mode block and per-non-target-mode blocks for one VMEM tile.

    Blocks start at ``block_i`` / ``block_r`` (aligned, whole sublane
    tiles) and the largest is halved, staying aligned, until both the
    tensor tile and the first fold contraction's ``(..., C)`` result fit
    the budget.  ``lead_elems`` is the batch-slab extent (1 unbatched).
    """
    big_n = len(mode_shape)
    blocks = {
        k: _sublanes(_block(d, block_i if k == n else block_r, _align(k, big_n)))
        for k, d in enumerate(mode_shape)
    }
    first = max(k for k in range(big_n) if k != n)  # see _fold_tile

    def footprint() -> int:
        tile = lead_elems * math.prod(blocks.values())
        return max(tile, tile // blocks[first] * c)

    while footprint() > TILE_ELEM_BUDGET:
        half = {
            k: b // 2 // _align(k, big_n) * _align(k, big_n)
            for k, b in blocks.items()
        }
        cands = [k for k, b in blocks.items() if 0 < half[k] < b]
        if not cands:
            break
        k = max(cands, key=lambda kk: blocks[kk])
        blocks[k] = half[k]
    bi = blocks.pop(n)
    return bi, blocks


@partial(
    jax.jit,
    static_argnames=("n", "block_i", "block_r", "interpret", "pad_rank_to"),
)
def matrix_free_mttkrp(
    x: Array,
    factors: Sequence[Array],
    n: int,
    *,
    block_i: int = 128,
    block_r: int = 8,
    interpret: bool | None = None,
    pad_rank_to: int | None = None,
) -> Array:
    """Matrix-free MTTKRP for any mode of an order-3..6 tensor.

    The tensor is zero-padded to block multiples (zero entries nullify any
    padded factor rows) and handed to the kernel in its natural layout --
    no reshape, no matricization, no KRP of any size.
    """
    factors = list(factors)
    big_n = len(factors)
    if x.ndim != big_n:
        raise ValueError(
            f"x.ndim {x.ndim} != {big_n} factors -- for a leading batch axis "
            "use matrix_free_mttkrp_batched"
        )
    if not 3 <= big_n <= 6:
        raise ValueError(f"matrix-free kernel covers order-3..6, got {big_n}")
    c = factors[0].shape[1]
    interp = _interpret(interpret)
    if pad_rank_to is None and _on_tpu():
        pad_rank_to = 128

    in_dim = x.shape[n]
    others = [k for k in range(big_n) if k != n]
    bi, rb = _tile_blocks(x.shape, n, 1, _padded(c, pad_rank_to), block_i, block_r)

    x_pad = _pad_axis(x, n, bi)
    us = []
    for k in others:
        x_pad = _pad_axis(x_pad, k, rb[k])
        u = _pad_axis(factors[k], 0, x_pad.shape[k])
        if pad_rank_to:
            u = _pad_axis(u, 1, pad_rank_to)
        us.append(u)
    out = matrix_free_kernel(
        x_pad, us, n,
        block_i=bi, blocks=[rb[k] for k in others], interpret=interp,
    )
    return out[:in_dim, :c].astype(x.dtype)


@partial(
    jax.jit,
    static_argnames=(
        "n", "block_i", "block_r", "block_batch", "interpret", "pad_rank_to"
    ),
)
def matrix_free_mttkrp_batched(
    x: Array,
    factors: Sequence[Array],
    n: int,
    *,
    block_i: int = 128,
    block_r: int = 8,
    block_batch: int = 8,
    interpret: bool | None = None,
    pad_rank_to: int | None = None,
) -> Array:
    """Batched matrix-free MTTKRP: ``x`` is ``(S, *shape)``, factors
    ``(S, I_k, C)``.  Tile choice keys on the mode dims only; every pad
    axis is shifted by one for the leading batch axis."""
    factors = list(factors)
    big_n = len(factors)
    if x.ndim != big_n + 1:
        raise ValueError(
            f"x.ndim {x.ndim} != {big_n} factors + batch axis -- for an "
            "unbatched tensor use matrix_free_mttkrp"
        )
    if not 3 <= big_n <= 6:
        raise ValueError(f"matrix-free kernel covers order-3..6, got {big_n}")
    s_batch = x.shape[0]
    mode_shape = x.shape[1:]
    c = factors[0].shape[2]
    interp = _interpret(interpret)
    if pad_rank_to is None and _on_tpu():
        pad_rank_to = 128

    in_dim = mode_shape[n]
    others = [k for k in range(big_n) if k != n]
    bs = _block(s_batch, block_batch)
    bi, rb = _tile_blocks(
        mode_shape, n, bs, _padded(c, pad_rank_to), block_i, block_r
    )

    x_pad = _pad_axis(_pad_axis(x, 1 + n, bi), 0, bs)
    us = []
    for k in others:
        x_pad = _pad_axis(x_pad, 1 + k, rb[k])
        u = _pad_axis(_pad_axis(factors[k], 1, x_pad.shape[1 + k]), 0, bs)
        if pad_rank_to:
            u = _pad_axis(u, 2, pad_rank_to)
        us.append(u)
    out = matrix_free_batched_kernel(
        x_pad, us, n,
        block_i=bi, blocks=[rb[k] for k in others], block_batch=bs,
        interpret=interp,
    )
    return out[:s_batch, :in_dim, :c].astype(x.dtype)
