"""sweeps_per_solve: sweeps to converge (CPState.it), averaged over the
window's solves."""


def read(run):
    if run.config["kind"] != "solve" or not run.units:
        return None
    return sum(u["sweeps"] for u in run.units) / len(run.units)
