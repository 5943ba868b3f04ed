"""sync_idle_ms_per_sweep: the device's idle ms per sweep while the host
was inside cp_als's ``cp_als.dispatch``, ``cp_als.wait`` and
``cp_als.check`` spans, on the clock aligned with the host's."""

from bench import program_trace
from bench.harness import sweeps_of


def read(run):
    if run.program is None:
        return None
    return program_trace.per_sweep_ms(program_trace.sync_idle_s(run.program), sweeps_of(run.units))
