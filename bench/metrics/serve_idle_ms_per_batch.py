"""serve_idle_ms_per_batch: the device's idle ms per batch while the host
was inside CPService.step's ``serve.take``, ``serve.pack`` and
``serve.unpack`` spans (the service's host path around each dispatch), on
the clock aligned with the host's; over the window's batches."""

HOST_PATH = ("serve.take", "serve.pack", "serve.unpack")


def read(run):
    batches = run.counters.get("batches")
    if run.program is None or not batches:
        return None
    parts = [run.program.idle_s_by_span[n] for n in HOST_PATH if n in run.program.idle_s_by_span]
    return 1e3 * sum(parts) / batches if parts else None
