"""sweeps_per_solve: sweeps to converge (CPState.it), averaged over the
window's solves."""

from bench.harness import sweeps_of


def read(run):
    sweeps = sweeps_of(run.units)
    return None if sweeps is None else sweeps / len(run.units)
