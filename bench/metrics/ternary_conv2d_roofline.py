"""Share of its roofline that the ternary conv kernel reaches, in percent:
over every launch of the kernel in the traced window, the sum of the
least times the launches could take (the larger of their required
operations at the chip's int8 peak and their bytes at HBM bandwidth, from
the configuration's layer table) over the sum of their device times."""
from harness import counts

# The HLO names the Pallas conv kernel's launches carry in the device trace.
PATTERN = r"^ternary_conv2d_pallas(\.\d+)?$"


def read(run):
    return counts.roofline_share(run, PATTERN)
