"""The whole step's share of the chips' peak, in percent: required
operations per classification (the configuration's count) times
classifications per second in this run, over the chips' int8 peak."""
from harness import counts


def read(run):
    return counts.step_mfu(run)
