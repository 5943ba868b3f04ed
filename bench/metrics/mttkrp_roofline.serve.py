"""mttkrp_roofline.serve: one exact sweep's least time on one subject's
tensor (one read of it at the chip's peak HBM bandwidth, bench/roofline.py)
over the device seconds per request-sweep inside the program's
``mttkrp.*`` scopes (the batched dimension tree's contractions), on the
clock aligned with the host's; in %.  The sweeps are the window's
requests' sweeps summed, so a batch of B requests reads B tensors a sweep:
the same ratio as the batch's tensors over the batch's sweeps."""

from bench import program_trace
from bench.harness import sweeps_of
from bench.roofline import sweep_least_seconds


def read(run):
    cfg = run.config
    sweeps = sweeps_of(run.units)
    if run.program is None or not sweeps or "subject_mode" not in cfg:
        return None
    shape = [d for n, d in enumerate(cfg["shape"]) if n != int(cfg["subject_mode"])]
    least, _ = sweep_least_seconds(shape, cfg["rank"], cfg["dtype"], run.peak)
    return program_trace.mttkrp_roofline(run.program, sweeps, least)
