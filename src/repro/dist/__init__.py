"""Distributed-memory extension of the paper's shared-memory MTTKRP.

``dist_mttkrp``: block-distributed MTTKRP over a device mesh -- the
device-for-thread port of the paper's parallelization, with the
communication structure of Ballard/Knight/Rouse (comm lower bounds for
MTTKRP) and Ballard/Hayashi/Kannan (parallel dense CP).  The lower bounds
say the per-mode reduction volume cannot shrink, so the communication-
hiding variant attacks latency instead: ``dist_mttkrp_overlapped`` chunks
the local kernel so each slab's psum runs under the next slab's GEMM
(exact).  The per-node contractions of a general dimension-tree schedule
(``repro.plan.schedule``) get the same treatment: ``dist_contract_range``
/ ``dist_contract_partial`` place one minimal psum per schedule node
(chunked for overlap via ``n_chunks``).  Every path here is exact.

``collectives``: hierarchical two-level collectives (``hierarchical_psum``
= ``reduce_scatter`` within the node + cross-node psum of the shard +
``all_gather`` back, so only ``1/k`` of each block crosses the slow
inter-node level).

The sharded CP-ALS solve is :mod:`repro.plan` with its sharded executors;
this package imports nothing from it.
"""

from .collectives import (
    all_gather,
    hierarchical_psum,
    reduce_scatter,
)
from .dist_mttkrp import (
    dist_contract_partial,
    dist_contract_range,
    dist_mttkrp,
    dist_mttkrp_overlapped,
    shard_problem,
)

__all__ = [
    "all_gather",
    "hierarchical_psum",
    "reduce_scatter",
    "dist_contract_partial",
    "dist_contract_range",
    "dist_mttkrp",
    "dist_mttkrp_overlapped",
    "shard_problem",
]
