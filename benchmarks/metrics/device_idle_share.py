"""device layer: share of the traced steps in which no op ran on a chip."""


def read(run):
    return None if run.trace is None else 100.0 * run.trace.idle_share
