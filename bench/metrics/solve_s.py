"""solve_s: the window's seconds over the solves completed in it (host clock)."""


def read(run):
    if not run.units:
        return None
    return run.window_s / len(run.units)
