"""host_syncs_per_sweep: the times cp_als blocked the host on the device
(CPState.host_syncs: each wait for a chunk and each device value read into
Python) over the sweeps, summed over the window's solves.  None where the
program does not count them."""

from bench.harness import sweeps_of


def read(run):
    sweeps = sweeps_of(run.units)
    syncs = [getattr(u.get("state"), "host_syncs", None) for u in run.units]
    if not sweeps or None in syncs:
        return None
    return sum(syncs) / sweeps
