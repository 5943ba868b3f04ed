"""host_reads_per_batch: the device values CPService.step read into Python
(its ``host_reads`` counter) over the batches it dispatched, both counted
over the window.  None where the service does not count them."""


def read(run):
    reads, batches = run.counters.get("host_reads"), run.counters.get("batches")
    if reads is None or not batches:
        return None
    return reads / batches
