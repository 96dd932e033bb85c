"""Share of its roofline that the residual conv kernel reaches, in percent:
over every launch of ``ternary_conv2d_residual_pallas`` in the traced
window, mapped onto the configuration's convs that take a shortcut, the
sum of the least times the launches could take (the larger of their
required operations at the chip's int8 peak and their bytes, the int8
shortcut read included, at HBM bandwidth) over the sum of their device
times.  None where the program launches no residual kernel."""
from harness import shortcuts

# The HLO names the residual conv kernel's launches carry in the device trace.
PATTERN = r"^ternary_conv2d_residual_pallas(\.\d+)?$"


def read(run):
    return shortcuts.roofline_share(run, PATTERN, residual=True)
