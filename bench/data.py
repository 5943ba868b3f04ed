"""The benchmark's inputs, made on the device from the run's seed.

Kept here, apart from the program, so that no change to the program can
move them: the planted fMRI-shaped tensor and the
'nvecs' start of a solve.
"""

from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp

HI = jax.lax.Precision.HIGHEST


def seed_key(seed: int) -> jax.Array:
    """A PRNG key from any whole number the driver passes: ``--seed`` may
    exceed 32 signed bits, so the high and low words are folded in apart."""
    seed = int(seed)
    key = jax.random.PRNGKey(seed & 0xFFFFFFFF)
    return jax.random.fold_in(key, (seed >> 32) & 0xFFFFFFFF)


@partial(jax.jit, static_argnames=("shape", "rank", "shared", "noise", "permute"))
def _planted(key, perm_key, *, shape, rank, shared, noise, permute):
    keys = jax.random.split(key, len(shape) + 1)
    pkeys = jax.random.split(perm_key, len(shape))
    shared = dict(shared)
    factors, perms = [], []
    for n, dim in enumerate(shape):
        src = shared.get(n, n)
        if src < n:
            factors.append(factors[src])
            perms.append(perms[src])
            continue
        factors.append(jax.random.normal(keys[n], (dim, rank), jnp.float32))
        perms.append(jax.random.permutation(pkeys[n], dim) if permute else jnp.arange(dim))
    letters = "abcdefgh"[: len(shape)]
    spec = ",".join(f"{c}z" for c in letters) + f"->{letters}"
    clean = jnp.einsum(spec, *(u[p] for u, p in zip(factors, perms)), precision=HI)
    scale = noise * jnp.linalg.norm(clean) / jnp.sqrt(float(clean.size))
    eps = jax.random.normal(keys[-1], shape, jnp.float32)
    if permute:
        eps = eps[jnp.ix_(*perms)]
    return clean + scale * eps


def planted(seed: int, shape, rank: int, noise: float, shared=None,
            perm_seed: int | None = None) -> jax.Array:
    """A planted rank-``rank`` CP tensor plus ``noise`` relative Gaussian
    noise, in one jitted call.  ``shared`` maps a mode to an earlier mode
    whose factor it reuses (the paper's region x region modes are one
    symmetric pair).  With ``perm_seed`` the indices of every mode are
    permuted (shared modes alike) by permutations drawn from it: the same
    tensor in another order, so that every ``perm_seed`` poses the same
    problem and an iterative solve does the same work."""
    shared = tuple(sorted((int(k), int(v)) for k, v in (shared or {}).items()))
    permute = perm_seed is not None
    return jax.block_until_ready(
        _planted(
            seed_key(seed), seed_key(perm_seed if permute else 0),
            shape=tuple(shape), rank=int(rank), shared=shared, noise=float(noise),
            permute=permute,
        )
    )


def nvecs(x, rank: int) -> list[jax.Array]:
    """The 'nvecs' CP-ALS start: per mode, the leading ``rank`` eigenvectors
    of the unfolding's Gram ``X_(n) X_(n)^T``, at HIGHEST.  Deterministic,
    and the same start up to signs for the same tensor in another order.
    From random starts ALS on the planted fMRI tensor reaches different
    stationary points under rounding (seen on a TPU v5e, PR 11), so
    answers are compared from here."""
    return list(_nvecs(x, rank=int(rank)))


@partial(jax.jit, static_argnames=("rank",))
def _nvecs(x, *, rank):
    out = []
    for n in range(x.ndim):
        other = [k for k in range(x.ndim) if k != n]
        g = jnp.tensordot(x, x, axes=(other, other), precision=HI)
        _, v = jnp.linalg.eigh(g)  # ascending
        out.append(v[:, ::-1][:, :rank])
    return out
