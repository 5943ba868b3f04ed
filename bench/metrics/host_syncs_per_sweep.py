"""host_syncs_per_sweep: the times cp_als blocked the host on the device
(CPState.host_syncs: each wait for a chunk and each device value read into
Python) over the sweeps, summed over the window's solves.  None where the
program does not count them."""


def read(run):
    if run.config["kind"] != "solve" or not run.units:
        return None
    syncs = [getattr(u.get("state"), "host_syncs", None) for u in run.units]
    sweeps = sum(u["sweeps"] for u in run.units)
    if None in syncs or not sweeps:
        return None
    return sum(syncs) / sweeps
