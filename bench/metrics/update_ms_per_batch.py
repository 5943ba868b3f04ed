"""update_ms_per_batch: the device ms per batch inside the program's
``update.mode<n>`` and ``fit`` scopes (each mode's batched Gram solve and
normalisation, and the fit, over all the batch's sweeps), on the clock
aligned with the host's; over the window's batches."""

from bench import program_trace


def read(run):
    batches = run.counters.get("batches")
    if run.program is None or not batches:
        return None
    seconds = program_trace.update_s(run.program)
    return None if seconds is None else 1e3 * seconds / batches
