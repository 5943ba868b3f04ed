"""idle_share.serve: the device's idle share of the traced window of a
service cell, in %: one minus the union of its operations' intervals over
the window."""


def read(run):
    if run.trace is None or not run.trace.devices:
        return None
    return 100.0 * run.trace.idle_share
