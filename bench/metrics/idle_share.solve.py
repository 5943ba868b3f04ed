"""idle_share.solve: the device's idle share of the traced window, in %:
one minus the union of its operations' intervals over the window."""


def read(run):
    if run.trace is None or not run.trace.devices:
        return None
    return 100.0 * run.trace.idle_share
