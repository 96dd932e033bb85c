"""95th percentile over every classification completed in the window of
the time from handing its input to the program's entry to its logits
being on the host, in milliseconds."""
import numpy as np


def read(run):
    return float(np.percentile(run.window.latencies_s, 95)) * 1e3
