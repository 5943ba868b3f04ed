"""mttkrp_roofline: one exact sweep's least time (one read of the tensor at
the chip's peak HBM bandwidth, bench/roofline.py) over the device seconds
per sweep inside the program's ``mttkrp.*`` scopes (the dimension tree's
contractions), on the clock aligned with the host's; in %."""

from bench import program_trace
from bench.harness import sweeps_of
from bench.roofline import sweep_least_seconds


def read(run):
    cfg = run.config
    sweeps = sweeps_of(run.units)
    if run.program is None or not sweeps or "shape" not in cfg:
        return None
    least, _ = sweep_least_seconds(cfg["shape"], cfg["rank"], cfg["dtype"], run.peak)
    return program_trace.mttkrp_roofline(run.program, sweeps, least)
