"""Shared tiling/padding helpers for the Pallas kernel wrappers.

Every jit'd wrapper (ops.py, multi_ttv.py, matrix_free.py) uses the same
three decisions: interpret off-TPU, zero-pad each tiled axis to its block
multiple, and clamp requested blocks to the actual extent.  Keeping them in
one module means the kernels never import each other's wrapper modules
(no ops <-> multi_ttv <-> matrix_free cycles).
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

Array = jax.Array


def on_tpu() -> bool:
    return jax.default_backend() == "tpu"


def interpret_default(flag: bool | None) -> bool:
    """Interpret mode resolves to "not on TPU" unless explicitly forced."""
    return (not on_tpu()) if flag is None else flag


def pad_axis(x: Array, axis: int, mult: int) -> Array:
    """Zero-pad ``axis`` up to a multiple of ``mult``.

    ``axis`` is a raw array axis, NOT a tensor mode: batched wrappers must
    shift mode positions by one for the leading batch axis (the unbatched
    wrappers pass modes through unchanged).
    """
    size = x.shape[axis]
    pad = (-size) % mult
    if pad == 0:
        return x
    widths = [(0, 0)] * x.ndim
    widths[axis] = (0, pad)
    return jnp.pad(x, widths)


# Mosaic (the TPU Pallas compiler) tiles the last two dims of every block
# in (8, 128) vreg tiles: each must be a multiple of its tile or the
# array's full extent.  Leading dims are free.
SUBLANE = 8
LANE = 128

# Cap on the elements of one kernel's largest VMEM tile (2 MB at f32):
# double-buffered input tiles plus in-kernel temporaries of the same size
# stay well inside the 16 MB of VMEM a kernel may use by default.
TILE_ELEM_BUDGET = 512 * 1024


def block(dim: int, target: int, align: int = 1) -> int:
    """Block size for one array axis: the whole ``dim`` when it is at most
    ``target``, else ``target`` rounded down to a multiple of ``align``
    (never below ``align``; the whole dim again if that covers it).

    ``align`` is :data:`LANE` for a block's minor dim, :data:`SUBLANE` for
    its second-minor dim and 1 for leading dims, so interpret mode tiles
    exactly as the chip must.
    """
    if dim <= target:
        return dim
    b = max(align, target // align * align)
    return dim if b >= dim else b
