"""Device: share of the profiled slice in which no operation ran on the
chip (1 - union of XLA op intervals / slice length)."""


def read(run):
    if run.trace is None:
        return None
    return 100 * (1 - run.trace.busy_s / run.trace.window_s)
