"""Tiled Khatri-Rao product Pallas kernel (paper Alg. 1, parallel variant).

Materializes ``K = A (.) B`` (``(J_A*J_B, C)``) tile by tile.  The grid maps
directly onto the paper's parallel decomposition (Sec. 4.1.2): each grid step
owns a contiguous row block of the output; the "re-initialize the multi-index
from the start row" step becomes the BlockSpec index map ``(a, b)``, and the
cached partial Hadamard product is the ``(1, C)`` A-row held in VMEM while the
fast index sweeps a ``(block_b, C)`` tile -- one VPU broadcast-multiply per
output tile, i.e. ~one Hadamard multiply per output row, the same flop count
as Alg. 1's reuse scheme.

Z > 2 factors are handled in ops.py by left-folding (the fold intermediates
are exactly Alg. 1's reused partials).  Used by the 1-step MTTKRP path when an
explicit KRP is requested; the fused kernel (fused_mttkrp.py) skips the HBM
round-trip entirely.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

Array = jax.Array


def _kernel(a_ref, b_ref, o_ref):
    # (1, C) * (bb, C) -> (bb, C): the row-wise KRP definition on the VPU.
    o_ref[0, :, :] = (a_ref[0] * b_ref[...]).astype(o_ref.dtype)


def krp_pair(
    a: Array, b: Array, *, block_b: int, interpret: bool = False
) -> Array:
    """KRP of two matrices: out[(ja, jb), c] = a[ja, c] * b[jb, c]."""
    ja, c = a.shape
    jb, cb = b.shape
    if c != cb:
        raise ValueError("factor column counts differ")
    if jb % block_b:
        raise ValueError("J_B must be padded to the block size")
    grid = (ja, jb // block_b)
    out = pl.pallas_call(
        _kernel,
        grid=grid,
        in_specs=[
            # a rides as (J_A, 1, C): a full (1, C) minor tile per block
            pl.BlockSpec((1, 1, c), lambda al, bl: (al, 0, 0)),
            pl.BlockSpec((block_b, c), lambda al, bl: (bl, 0)),
        ],
        out_specs=pl.BlockSpec((1, block_b, c), lambda al, bl: (al, bl, 0)),
        out_shape=jax.ShapeDtypeStruct((ja, jb, c), a.dtype),
        interpret=interpret,
    )(a.reshape(ja, 1, c), b)
    return out.reshape(ja * jb, c)
