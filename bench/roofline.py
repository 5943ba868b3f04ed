"""Peaks by device, and the least work of one exact CP-ALS sweep.

Any exact ALS sweep, whichever leaf algorithm, tree or kernel implements
it, reads every tensor entry at least once and multiplies it into at least
one rank-``C`` row.  So the least bytes and operations below charge one
read of the tensor and ``2 C`` operations per entry, and the least time
bounds every implementation alike: no implementation can honestly read
above 100% of it.
"""

from __future__ import annotations

import json
import math
from pathlib import Path

PEAKS = Path(__file__).resolve().parent / "peaks.json"

ITEMSIZE = {"float32": 4, "bfloat16": 2, "float16": 2, "float64": 8}


def peaks(device_kind: str, path: Path = PEAKS) -> dict:
    """The published peaks of ``device_kind``; a device not in the table is
    an error, never a default."""
    table = json.loads(Path(path).read_text())
    if device_kind not in table:
        raise KeyError(f"no peaks for device kind {device_kind!r} in {path}; known: {sorted(table)}")
    return table[device_kind]


def sweep_least_bytes(shape, dtype: str = "float32") -> int:
    """Bytes one exact sweep must read: the tensor, once."""
    return math.prod(int(d) for d in shape) * ITEMSIZE[dtype]


def sweep_least_flops(shape, rank: int) -> int:
    """Operations one exact sweep must do: a multiply and an add of every
    entry into one rank-``rank`` row."""
    return 2 * math.prod(int(d) for d in shape) * int(rank)


def sweep_least_seconds(shape, rank: int, dtype: str, peak: dict) -> tuple[float, str]:
    """The least time of one exact sweep on a chip with ``peak``, and which
    bound sets it (``"hbm"`` or ``"flops"``).  The operations are priced at
    the bf16 peak, which no fp32 contraction exceeds."""
    t_mem = sweep_least_bytes(shape, dtype) / peak["hbm_bytes_per_s"]
    t_ops = sweep_least_flops(shape, rank) / peak["bf16_flops_per_s"]
    return (t_mem, "hbm") if t_mem >= t_ops else (t_ops, "flops")
