"""Share of its roofline that the plain ternary conv kernel reaches in a
residual network, in percent: its launches mapped onto the convs that
take no shortcut (the residual convs launch under their own name, which
the generic reader would spread these launches over), the sum of their
least times over the sum of their device times."""
from harness import shortcuts

# The HLO names the plain Pallas conv kernel's launches carry in the device trace.
PATTERN = r"^ternary_conv2d_pallas(\.\d+)?$"


def read(run):
    return shortcuts.roofline_share(run, PATTERN, residual=False)
