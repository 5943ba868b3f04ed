"""Fused MTTKRP Pallas TPU kernel -- the beyond-paper contribution.

The paper's closing lesson (Sec. 6): *"Just as tensor reordering should be
avoided, future optimization of MTTKRP should avoid computing large KRPs."*
On TPU we can do exactly that: the full KRP ``K = K_L (.) K_R`` (size
``L*R x C``, the dominant memory-bound object of the 1-step algorithm) is
never written to HBM.  Instead each grid step forms a *KRP tile* in VMEM from
one row of the small partial ``A`` and a block of rows of the small partial
``B`` (one broadcast VPU multiply -- the row-wise Hadamard definition of the
KRP) and immediately feeds it to the MXU.

Computation (unified bilinear form; `pos` places the uncontracted mode):

    pos=1 (internal modes):  M[i,c] = sum_{a,b} T[a,i,b] * A[a,c] * B[b,c]
    pos=0 (mode 0):          M[i,c] = sum_{a,b} T[i,a,b] * A[a,c] * B[b,c]
    pos=2 (mode N-1):        M[i,c] = sum_{a,b} T[a,b,i] * A[a,c] * B[b,c]

where for an internal mode ``n``: ``T = x.view(L, I_n, R)``, ``A = K_L``,
``B = K_R`` (both geometrically smaller than ``K``); for external modes the
right (resp. left) factor list is split in two and ``T`` is the corresponding
free 3-D view -- so even external modes avoid the full-KRP write that the
paper's Alg. 3 pays for (their Fig. 6 shows KRP costing up to half the time).

Grid layout: ``(I_blocks, A_dim, B_blocks)`` with the two reduction dims
innermost, so each output block stays resident in VMEM across its whole
reduction (revisited-output accumulation pattern).  The output is zeroed at
the first reduction step via ``pl.when``.

TPU tiling notes (the BlockSpec shapes define the VMEM working set; Mosaic
requires the last two dims of every block to be multiples of (8, 128) or
the array's full extent):
  * block_i x block_b is the MXU matmul tile; C (CP rank, typically 10-50)
    is padded to the 128-lane boundary by the wrapper on TPU.
  * ``A`` rides as an ``(A_dim, 1, C)`` array, so its ``(block_a, 1, C)``
    block keeps a full ``(1, C)`` minor tile whatever ``block_a`` is.
  * Each grid step walks ``block_a`` rows of ``A`` in an unrolled loop:
    one (1, C) * (bb, C) VPU multiply forms the KRP tile, one MXU dot
    consumes it.  At pos=0 the ``a`` axis is the tensor block's
    second-minor dim (multiple of 8 or full); elsewhere it is a leading
    dim and any size.
  * VMEM per step = T-tile (ba*bi*bb) + A rows (ba*C) + B-tile (bb*C)
    + out (bi*C) floats, double-buffered for the streamed tiles.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from ._tiling import SUBLANE

Array = jax.Array


def _x_tile(t_ref, j: int, pos: int, batched: bool):
    """Row ``j`` of the block's ``a`` axis as the (..., rows, cols) matmul
    operand: (bi, bb) at pos 0/1, (bb, bi) at pos 2 (contracted on dim 0)."""
    if batched:
        return t_ref[:, :, j, :] if pos == 0 else t_ref[:, j]
    return t_ref[:, j, :] if pos == 0 else t_ref[j]


def _kernel(t_ref, a_ref, b_ref, o_ref, *, pos: int, batched: bool):
    """One grid step: o += sum_j T[.., a_j, ..] @ (A[a_j] * B_tile)."""
    lead = 1 if batched else 0
    a_idx = pl.program_id(lead + 1)
    b_idx = pl.program_id(lead + 2)

    @pl.when(jnp.logical_and(a_idx == 0, b_idx == 0))
    def _init():
        o_ref[...] = jnp.zeros_like(o_ref)

    hi = jax.lax.Precision.HIGHEST
    # contract the tile's bb axis: dim 1 of (bi, bb) at pos 0/1, dim 0 of
    # (bb, bi) at pos 2 -- shifted by one under a leading batch axis
    x_dim = lead + (0 if pos == 2 else 1)
    batch_dims = ((0,), (0,)) if batched else ((), ())
    acc = jnp.zeros(o_ref.shape, jnp.float32)
    for j in range(a_ref.shape[lead]):
        # KRP tile, formed on the fly in VMEM (row-wise Hadamard
        # definition): (.., 1, C) * (.., bb, C) -> (.., bb, C).  This is the
        # object the paper materializes in HBM (Alg. 2 line 2 / Alg. 3
        # line 15); here it lives only in VMEM.
        a_row = a_ref[:, j] if batched else a_ref[j]
        k_tile = a_row * b_ref[...]
        x_tile = _x_tile(t_ref, j, pos, batched).astype(k_tile.dtype)
        acc += jax.lax.dot_general(
            x_tile, k_tile, (((x_dim,), (lead,)), batch_dims), precision=hi,
            preferred_element_type=jnp.float32,
        )
    o_ref[...] += acc.astype(o_ref.dtype)


def _t_spec(pos: int, block_i: int, block_a: int, block_b: int, block_batch=None):
    """Tensor BlockSpec for the (i, a, b) grid at each ``pos`` layout:
    pos0 (I, A, B), pos1 (A, I, B), pos2 (A, B, I), plus a leading batch
    block when ``block_batch`` is given."""
    shape = {
        0: (block_i, block_a, block_b),
        1: (block_a, block_i, block_b),
        2: (block_a, block_b, block_i),
    }[pos]
    order = {0: (0, 1, 2), 1: (1, 0, 2), 2: (1, 2, 0)}[pos]  # (i, a, b) slots

    def index(*g):
        ids = g[-3:]
        idx = tuple(ids[o] for o in order)
        return idx if block_batch is None else (g[0],) + idx

    if block_batch is not None:
        shape = (block_batch,) + shape
    return pl.BlockSpec(shape, index)


def fused_mttkrp_bilinear_batched(
    t: Array,
    a: Array,
    b: Array,
    *,
    pos: int,
    block_i: int,
    block_b: int,
    block_batch: int,
    interpret: bool = False,
) -> Array:
    """Batched bilinear MTTKRP: ``M[s,i,c] = sum_{a,b} T[s,...] A[s,a,c] B[s,b,c]``.

    ``t`` is ``(S, *3-D view)`` with the i-axis of the per-slab view at
    ``pos``; ``a``/``b`` are per-batch partial KRPs ``(S, dim, C)``.  The
    grid gains a leading batch axis ``S // block_batch`` (outermost, so each
    output block still stays VMEM-resident across its whole reduction).
    Dims (including S) must be padded to block multiples by the wrapper.
    """
    if t.ndim != 4:
        raise ValueError("t must be a batched (4-D) view")
    n_batch = t.shape[0]
    if a.shape[0] != n_batch or b.shape[0] != n_batch:
        raise ValueError(
            f"batch mismatch: t {t.shape}, a {a.shape}, b {b.shape}"
        )
    dim_a, dim_b = a.shape[1], b.shape[1]
    c = a.shape[2]
    shape = list(t.shape[1:])
    dim_i = shape.pop(pos)
    if shape != [dim_a, dim_b]:
        raise ValueError(f"t shape {t.shape} inconsistent with A/B {a.shape}/{b.shape}")
    if dim_i % block_i or dim_b % block_b or n_batch % block_batch:
        raise ValueError("dims must be padded to block multiples")

    block_a = _block_a(dim_a, pos)
    grid = (
        n_batch // block_batch, dim_i // block_i, dim_a // block_a,
        dim_b // block_b,
    )
    return pl.pallas_call(
        functools.partial(_kernel, pos=pos, batched=True),
        grid=grid,
        in_specs=[
            _t_spec(pos, block_i, block_a, block_b, block_batch),
            pl.BlockSpec(
                (block_batch, block_a, 1, c), lambda s, i, al, bl: (s, al, 0, 0)
            ),
            pl.BlockSpec(
                (block_batch, block_b, c), lambda s, i, al, bl: (s, bl, 0)
            ),
        ],
        out_specs=pl.BlockSpec(
            (block_batch, block_i, c), lambda s, i, al, bl: (s, i, 0)
        ),
        out_shape=jax.ShapeDtypeStruct((n_batch, dim_i, c), jnp.float32),
        interpret=interpret,
    )(t, a.reshape(n_batch, dim_a, 1, c), b)


def _block_a(dim_a: int, pos: int) -> int:
    """Rows of ``A`` walked per grid step.  At pos 0 the ``a`` axis is the
    tensor block's second-minor dim: 8 (the wrapper pads ``dim_a`` to a
    multiple) or the whole dim.  Elsewhere it is a leading dim: the largest
    divisor of ``dim_a`` up to 8, so no padding is needed."""
    if pos == 0:
        return dim_a if dim_a <= SUBLANE else SUBLANE
    return max(d for d in range(1, SUBLANE + 1) if dim_a % d == 0)


def fused_mttkrp_bilinear(
    t: Array,
    a: Array,
    b: Array,
    *,
    pos: int,
    block_i: int,
    block_b: int,
    interpret: bool = False,
) -> Array:
    """``M[i,c] = sum_{a,b} T * A[a,c] * B[b,c]`` with T's i-axis at ``pos``.

    Dims must already be padded to multiples of the block sizes (the ops.py
    wrapper does this); C should be lane-aligned (128) for real TPUs.
    """
    if t.ndim != 3:
        raise ValueError("t must be a 3-D view")
    dim_a, dim_b = a.shape[0], b.shape[0]
    c = a.shape[1]
    shape = list(t.shape)
    dim_i = shape.pop(pos)
    if shape != [dim_a, dim_b]:
        raise ValueError(f"t shape {t.shape} inconsistent with A/B {a.shape}/{b.shape}")
    if dim_i % block_i or dim_b % block_b:
        raise ValueError("dims must be padded to block multiples")

    block_a = _block_a(dim_a, pos)
    grid = (dim_i // block_i, dim_a // block_a, dim_b // block_b)
    return pl.pallas_call(
        functools.partial(_kernel, pos=pos, batched=False),
        grid=grid,
        in_specs=[
            _t_spec(pos, block_i, block_a, block_b),
            pl.BlockSpec((block_a, 1, c), lambda i, al, bl: (al, 0, 0)),
            pl.BlockSpec((block_b, c), lambda i, al, bl: (bl, 0)),
        ],
        out_specs=pl.BlockSpec((block_i, c), lambda i, al, bl: (i, 0)),
        out_shape=jax.ShapeDtypeStruct((dim_i, c), jnp.float32),
        interpret=interpret,
    )(t, a.reshape(dim_a, 1, c), b)
