"""Multi-TTV Pallas kernel -- the 2nd step of the 2-step MTTKRP (Alg. 4).

Computes  ``M[i, c] = sum_l T[l, i, c] * W[l, c]``  where ``T`` is the partial
MTTKRP output (``(L, I_n, C)``, the paper's R-tensor reshaped) and ``W`` is the
complementary partial KRP (``(L, C)``).  The paper implements this as ``C``
independent DGEMV calls (Alg. 4 lines 7-9 / 13-15); on TPU a batched GEMV is
lane-hostile, so the idiomatic form is a broadcast multiply-accumulate on the
VPU with the rank axis on lanes: each grid step does
``o[i-block, :] += T[l, i-block, :] * W[l, :]``.

Grid ``(I_blocks, L)`` with the reduction dim innermost (revisited-output
accumulation, zero-initialized at l == 0).  VMEM per step: T-tile (bi*C) +
W row (C) + out (bi*C) -> a few hundred KB at bi=512, C=128.

This module is the single multi-TTV implementation: the raw grid kernels
(``multi_ttv_kernel`` / ``multi_ttv_batched_kernel``) plus the jit'd
padding wrappers (``multi_ttv`` / ``multi_ttv_batched``).  ``ops.multi_ttv``
is a frozen alias of the wrapper here, so tile threading has one seam.
"""

from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from ._tiling import SUBLANE
from ._tiling import block as _block
from ._tiling import interpret_default as _interpret
from ._tiling import pad_axis as _pad_axis

Array = jax.Array


def _kernel(t_ref, w_ref, o_ref):
    l_idx = pl.program_id(1)

    @pl.when(l_idx == 0)
    def _init():
        o_ref[...] = jnp.zeros_like(o_ref)

    o_ref[...] += (t_ref[0, :, :] * w_ref[0]).astype(o_ref.dtype)


def multi_ttv_kernel(
    t: Array, w: Array, *, block_i: int, interpret: bool = False
) -> Array:
    """``M[i,c] = sum_l t[l,i,c] * w[l,c]`` (t: (L, I, C), w: (L, C))."""
    big_l, dim_i, c = t.shape
    if w.shape != (big_l, c):
        raise ValueError(f"w shape {w.shape} != ({big_l}, {c})")
    if dim_i % block_i:
        raise ValueError("I must be padded to the block size")
    grid = (dim_i // block_i, big_l)
    return pl.pallas_call(
        _kernel,
        grid=grid,
        in_specs=[
            pl.BlockSpec((1, block_i, c), lambda i, l: (l, i, 0)),
            # w rides as (L, 1, C): a full (1, C) minor tile per block
            pl.BlockSpec((1, 1, c), lambda i, l: (l, 0, 0)),
        ],
        out_specs=pl.BlockSpec((block_i, c), lambda i, l: (i, 0)),
        out_shape=jax.ShapeDtypeStruct((dim_i, c), jnp.float32),
        interpret=interpret,
    )(t, w.reshape(big_l, 1, c))


def _kernel_batched(t_ref, w_ref, o_ref):
    l_idx = pl.program_id(2)

    @pl.when(l_idx == 0)
    def _init():
        o_ref[...] = jnp.zeros_like(o_ref)

    # per-batch broadcast MAC: (bt, bi, C) * (bt, 1, C)
    o_ref[...] += (t_ref[:, 0, :, :] * w_ref[:, 0]).astype(o_ref.dtype)


def multi_ttv_batched_kernel(
    t: Array,
    w: Array,
    *,
    block_i: int,
    block_batch: int,
    interpret: bool = False,
) -> Array:
    """Batched multi-TTV: ``M[s,i,c] = sum_l t[s,l,i,c] * w[s,l,c]``.

    Same VPU accumulation as :func:`multi_ttv_kernel` with a leading batch
    grid axis (outermost; the L reduction stays innermost so each output
    block is revisited in place).  S and I must be padded to block multiples.
    """
    n_batch, big_l, dim_i, c = t.shape
    if w.shape != (n_batch, big_l, c):
        raise ValueError(f"w shape {w.shape} != ({n_batch}, {big_l}, {c})")
    if dim_i % block_i or n_batch % block_batch:
        raise ValueError("S and I must be padded to the block sizes")
    grid = (n_batch // block_batch, dim_i // block_i, big_l)
    return pl.pallas_call(
        _kernel_batched,
        grid=grid,
        in_specs=[
            pl.BlockSpec(
                (block_batch, 1, block_i, c), lambda s, i, l: (s, l, i, 0)
            ),
            pl.BlockSpec(
                (block_batch, 1, 1, c), lambda s, i, l: (s, l, 0, 0)
            ),
        ],
        out_specs=pl.BlockSpec(
            (block_batch, block_i, c), lambda s, i, l: (s, i, 0)
        ),
        out_shape=jax.ShapeDtypeStruct((n_batch, dim_i, c), jnp.float32),
        interpret=interpret,
    )(t, w.reshape(n_batch, big_l, 1, c))


@partial(jax.jit, static_argnames=("block_i", "interpret"))
def multi_ttv(
    t: Array, w: Array, *, block_i: int = 256, interpret: bool | None = None
) -> Array:
    """Kernelized multi-TTV:  M[i,c] = sum_l t[l,i,c] * w[l,c]."""
    interp = _interpret(interpret)
    dim_i = t.shape[1]
    bi = _block(dim_i, block_i, SUBLANE)
    t_pad = _pad_axis(t, 1, bi)
    out = multi_ttv_kernel(t_pad, w, block_i=bi, interpret=interp)
    return out[:dim_i].astype(t.dtype)


@partial(jax.jit, static_argnames=("block_i", "block_batch", "interpret"))
def multi_ttv_batched(
    t: Array,
    w: Array,
    *,
    block_i: int = 256,
    block_batch: int = 8,
    interpret: bool | None = None,
) -> Array:
    """Batched multi-TTV: ``M[s,i,c] = sum_l t[s,l,i,c] * w[s,l,c]``.

    One launch over the kernel's batch grid axis; the I tile is chosen from
    the mode extent ``t.shape[2]`` (pad axes shifted for the batch axis).
    """
    interp = _interpret(interpret)
    s_batch, dim_i = t.shape[0], t.shape[2]
    bi = _block(dim_i, block_i, SUBLANE)
    bs = _block(s_batch, block_batch)
    t_pad = _pad_axis(_pad_axis(t, 2, bi), 0, bs)
    w_pad = _pad_axis(w, 0, bs)
    out = multi_ttv_batched_kernel(
        t_pad, w_pad, block_i=bi, block_batch=bs, interpret=interp
    )
    return out[:s_batch, :dim_i].astype(t.dtype)
