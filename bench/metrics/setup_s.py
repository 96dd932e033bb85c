"""Seconds from the start of the process to the start of the window:
imports, the chip, the seeded weights, the deployed program, the inputs
and the warm-up of every shape the window uses (compiles included)."""


def read(run):
    return run.setup_s
