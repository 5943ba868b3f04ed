"""update_ms_per_sweep: the device ms per sweep inside the program's
``update.mode<n>`` and ``fit`` scopes (each mode's Gram solve and
normalisation, and the fit), on the clock aligned with the host's."""

from bench import program_trace
from bench.harness import sweeps_of


def read(run):
    if run.program is None:
        return None
    return program_trace.per_sweep_ms(program_trace.update_s(run.program), sweeps_of(run.units))
