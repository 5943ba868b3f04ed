"""sweep_roofline: one exact sweep's least time (one read of the tensor at
the chip's peak HBM bandwidth, bench/roofline.py) over the device's busy
seconds per sweep: the profiler trace's device time inside the window's
``solve`` spans over the sweeps those solves ran; in %.  Host time, the
sync between sweeps among it, is not in it."""

from bench.harness import sweeps_of
from bench.roofline import sweep_least_seconds


def read(run):
    cfg = run.config
    if run.trace is None or "shape" not in cfg:
        return None
    sweeps = sweeps_of(run.units)
    busy = run.trace.span_busy.get("solve", 0.0)
    if not sweeps or busy <= 0:
        return None
    least, _ = sweep_least_seconds(cfg["shape"], cfg["rank"], cfg["dtype"], run.peak)
    return 100.0 * least / (busy / sweeps)
