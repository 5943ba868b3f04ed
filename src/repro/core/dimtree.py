"""Dimension-tree contraction primitives -- the paper's Sec. 6 "next step".

Phan et al. [19, Sec. III.C] avoid recomputing partial MTTKRPs across modes:
split the modes into halves L = {0..m-1}, R = {m..N-1} and compute two
X-sized partial contractions per sweep instead of N:

    T_L[i_0..i_{m-1}, c] = sum_R X * K_R[r, c]      (one GEMM, free reshape)
    T_R[i_m..i_{N-1}, c] = sum_L X * K_L[l, c]      (one GEMM, free reshape)

Every mode-n MTTKRP then reads only the small T tensor of its half (a
multi-TTV over the sibling modes).  Updating the left modes first (from T_L,
which depends only on the *right* factors) and then recomputing T_R from the
fresh left factors reproduces the EXACT standard-ALS iterates -- verified in
tests against cpals.als_sweep -- while reading X twice per sweep instead of
N times.  The paper predicts ~2x per-iteration gain for 4-way tensors.

This module holds the *numeric primitives* of that idea, generalized so the
binary two-partial split is just one point in a family: any tree over
contiguous mode ranges (Ma & Solomonik's multi-level dimension trees) is
expressible with two operations --

* :func:`partial_mttkrp_range` -- contract every mode outside ``[lo, hi)``
  of the raw tensor away (the root-level GEMM of a tree node);
* :func:`contract_from_partial` -- contract a subset of a partial tensor's
  surviving modes with their factors (an inner tree edge, or a leaf's
  multi-TTV when a single mode survives).

The tree *shapes* themselves live in :mod:`repro.plan.schedule` (the
contraction-schedule IR); :func:`dimtree_sweep` stays as the frozen
back-compat wrapper for the original binary-split sweep.
"""

from __future__ import annotations

import math
from typing import Mapping, Sequence

import jax
import jax.numpy as jnp

from .krp import krp_or_ones
from .tensor_ops import mode_letters

Array = jax.Array


def view_split(lo: int, hi: int, n: int) -> int:
    """The split point of the matrix view a root partial over ``[lo, hi)``
    of an order-``n`` tensor reads: ``hi``, unless the range runs to the
    last mode, then ``lo`` (see :func:`partial_from_view`)."""
    return lo if hi == n else hi


def matrix_view(x: Array, m: int, *, batched: bool = False) -> Array:
    """``X_(m)``: the tensor as a ``prod(shape[:m]) x prod(shape[m:])``
    matrix, in the tensor's own row-major order (no entry moves).

    The paper's "free reshape": free in a row-major layout, a relayout
    under a TPU's (8, 128) tiles whenever a minor dim is not a whole tile.
    ``batched`` keeps a leading batch axis in front (``m`` counts the
    tensor's modes, not the batch).
    """
    lead = 1 if batched else 0
    left = math.prod(x.shape[lead:lead + m])
    return x.reshape(x.shape[:lead] + (left, -1))


def view_times_krp(xm: Array, right_factors: Sequence[Array]) -> Array:
    """``X_(m) @ K_R``: the view contracted with the KRP of the trailing
    modes' factors, shape ``(rows, C)``."""
    c = right_factors[0].shape[1]
    return xm @ krp_or_ones(list(right_factors), c, xm.dtype)


def krp_times_view(left_factors: Sequence[Array], xm: Array) -> Array:
    """``K_L^T @ X_(m)``: the KRP of the leading modes' factors contracted
    with the view, shape ``(C, cols)``."""
    c = left_factors[0].shape[1]
    return krp_or_ones(list(left_factors), c, xm.dtype).T @ xm


def _root_partial(
    xm: Array, left: Sequence[Array], right: Sequence[Array],
    kept: Sequence[int], *, fence: bool,
) -> Array:
    """The partial of shape ``kept + (C,)`` left when the leading modes'
    factors ``left`` and the trailing modes' ``right`` are contracted out
    of the view ``xm``.  The trailing modes go first through ``X_(m) @
    K_R``; with no trailing modes it is ``K_L^T @ X_(m)`` instead; a middle
    range then contracts its leading modes against their KRP along the
    shared rank axis.  ``fence`` ends the GEMM in an optimization barrier
    on its small output (see :func:`partial_from_view`)."""
    done = jax.lax.optimization_barrier if fence else (lambda t: t)
    kept = tuple(kept)
    if not right:
        t = done(krp_times_view(left, xm))  # (C, R)
        return jnp.moveaxis(t.reshape((t.shape[0],) + kept), 0, -1)
    t = done(view_times_krp(xm, right))  # (L, C)
    c = t.shape[-1]
    if not left:
        return t.reshape(kept + (c,))
    k_l = krp_or_ones(list(left), c, xm.dtype)  # (L', C)
    t3 = t.reshape(k_l.shape[0], -1, c)
    return jnp.einsum("lmc,lc->mc", t3, k_l).reshape(kept + (c,))


def partial_from_view(
    xm: Array, factors: Sequence[Array], lo: int, hi: int, kept: Sequence[int]
) -> Array:
    """The root partial over ``[lo, hi)`` from the view ``X_(m)``, ``m =
    view_split(lo, hi, len(factors))``, built once for many sweeps;
    ``kept`` are the dims of the kept modes.  Returns the partial tensor
    of shape ``kept + (C,)``, by the GEMMs of :func:`partial_mttkrp_range`.
    ``factors`` is the full mode-ordered list; entries inside ``[lo, hi)``
    are ignored.

    The GEMM ends in an optimization barrier on its small output: without
    it XLA folds the partial's reshape (and the leaves that read it) into
    the GEMM and reshapes or transposes the view instead, which on a TPU
    relays out the whole view in every sweep.
    """
    return _root_partial(xm, factors[:lo], factors[hi:], kept, fence=True)


def partial_mttkrp_right(x: Array, right_factors: Sequence[Array]) -> Array:
    """T_L = X contracted with the KRP of the trailing ``len(right)`` modes.

    Returns a tensor of shape  x.shape[:m] + (C,).
    """
    m = x.ndim - len(right_factors)
    return _root_partial(matrix_view(x, m), (), right_factors, x.shape[:m], fence=False)


def partial_mttkrp_left(x: Array, left_factors: Sequence[Array]) -> Array:
    """T_R = X contracted with the KRP of the leading ``len(left)`` modes.

    Returns a tensor of shape  x.shape[m:] + (C,).
    """
    m = len(left_factors)
    return _root_partial(matrix_view(x, m), left_factors, (), x.shape[m:], fence=False)


def partial_mttkrp_range(x: Array, factors: Sequence[Array], lo: int, hi: int) -> Array:
    """Contract every mode of ``x`` outside ``[lo, hi)`` with its factor.

    Returns the partial tensor of shape ``x.shape[lo:hi] + (C,)`` -- the
    root-level contraction of a general dimension-tree node, through the
    GEMMs on the view ``X_(m)``, ``m = view_split(lo, hi, N)``, taken
    inside the call (so ``lo == 0`` reproduces :func:`partial_mttkrp_right`
    exactly, and ``hi == N`` reproduces :func:`partial_mttkrp_left`).
    ``factors`` is the full mode-ordered list; entries inside ``[lo, hi)``
    are ignored.
    """
    n = x.ndim
    if not 0 <= lo < hi <= n:
        raise ValueError(f"range [{lo}, {hi}) invalid for order-{n} tensor")
    if lo == 0 and hi == n:
        raise ValueError("range [0, N) contracts nothing")
    xm = matrix_view(x, view_split(lo, hi, n))
    return _root_partial(xm, factors[:lo], factors[hi:], x.shape[lo:hi], fence=False)


def contract_from_partial(
    t: Array, factors: Mapping[int, Array], lo: int, hi: int, parent_lo: int
) -> Array:
    """Contract modes of a partial tensor ``t`` down to the range ``[lo, hi)``.

    ``t`` carries the parent node's surviving modes (starting at tensor mode
    ``parent_lo``) plus the trailing rank axis; ``factors`` maps each
    *tensor* mode being contracted here to its ``(I_m, C)`` factor.  The
    rank axis is shared by every term (Hadamard semantics, exactly as in the
    binary tree's multi-TTV).  With a single surviving mode this is the
    leaf-level MTTKRP of :func:`mttkrp_from_partial`.
    """
    order = t.ndim - 1
    letters = mode_letters(order)
    terms = [letters + "c"]
    args: list[Array] = [t]
    for m in sorted(factors):
        terms.append(letters[m - parent_lo] + "c")
        args.append(factors[m])
    out = "".join(letters[k - parent_lo] for k in range(lo, hi)) + "c"
    return jnp.einsum(",".join(terms) + f"->{out}", *args)


def mttkrp_from_partial(t: Array, siblings: Sequence[Array], pos: int) -> Array:
    """MTTKRP for one mode of a half from its partial tensor ``t``.

    ``t``: (I_s0, ..., I_sk, C) -- the half's modes plus the rank axis;
    ``siblings``: factors of the half's other modes (in order, skipping pos).
    """
    order = t.ndim - 1
    letters = mode_letters(order)
    terms = [letters + "c"]
    args: list[Array] = [t]
    si = 0
    for k in range(order):
        if k == pos:
            continue
        terms.append(letters[k] + "c")
        args.append(siblings[si])
        si += 1
    return jnp.einsum(",".join(terms) + f"->{letters[pos]}c", *args)


def dimtree_sweep(
    x: Array,
    factors: list[Array],
    weights: Array,
    norm_x: Array,
    it: Array,
    *,
    normalize: bool = True,
    split: int | None = None,
):
    """One full ALS sweep via the dimension tree; same signature contract as
    cpals.als_sweep (returns (factors, weights, fit)) and identical iterates.

    Back-compat wrapper: builds the ``strategy='dimtree'`` plan and runs the
    single shared sweep engine on a LocalExecutor.
    """
    from repro import plan as planlib

    return planlib.legacy_sweep(
        x, factors, weights, norm_x, it,
        strategy="dimtree", normalize=normalize, split=split,
    )
