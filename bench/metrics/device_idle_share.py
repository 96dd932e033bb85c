"""Share of the traced window in which the device ran no operation, in
percent, averaged over the cell's chips."""
from harness import trace


def read(run):
    if run.trace is None:
        return None
    share = trace.idle_share(run.trace)
    return None if share is None else 100.0 * share
