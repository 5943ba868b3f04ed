"""Core library: the paper's contribution (KRP, MTTKRP and the tensor
operations they need) in JAX.  CP-ALS itself is :mod:`repro.plan`, which
builds on this package; nothing here imports it."""

from .krp import (
    krp,
    krp_batched,
    krp_naive,
    krp_or_ones,
    krp_or_ones_batched,
    krp_row_block,
    krp_rowwise_scan,
)
from .mttkrp import (
    mttkrp,
    mttkrp_1step,
    mttkrp_2step,
    mttkrp_baseline,
    mttkrp_batched,
    mttkrp_einsum,
    mttkrp_flops,
)
from .tensor_ops import (
    EINSUM_LETTERS,
    as_lir,
    cp_full,
    dims_split,
    matricize,
    matricize_multi,
    mode_letters,
    multi_ttv,
    random_factors,
    random_tensor,
    tensor_norm,
    ttm,
    ttv,
)

__all__ = [
    "EINSUM_LETTERS",
    "mode_letters",
    "krp",
    "krp_batched",
    "krp_naive",
    "krp_or_ones",
    "krp_or_ones_batched",
    "krp_row_block",
    "krp_rowwise_scan",
    "mttkrp",
    "mttkrp_1step",
    "mttkrp_2step",
    "mttkrp_baseline",
    "mttkrp_batched",
    "mttkrp_einsum",
    "mttkrp_flops",
    "as_lir",
    "cp_full",
    "dims_split",
    "matricize",
    "matricize_multi",
    "multi_ttv",
    "random_factors",
    "random_tensor",
    "tensor_norm",
    "ttm",
    "ttv",
]
